"""Fused Lloyd assignment kernels: nearest-centroid assignment + per-cluster
partial sums/counts in ONE pass over the points (the clustering-phase hot spot).

Centroids are VMEM-resident (constant-memory analogue); the per-cluster
accumulators (k, d) and (k,) live in VMEM for the whole grid (output blocks
with a constant index_map), initialized at grid step 0 — the TPU version of a
privatized-then-reduced histogram, with the one-hot matmul on the MXU instead
of atomics (TPU has no global atomics; this is the idiomatic replacement).

Like the seeding-round kernels, the assignment kernels stream a cached fp32
``||x||^2`` input (norm caching: computed once per fit, not once per
iteration) and keep the point/centroid tiles in their input dtype into the
MXU (bf16 streams at half the HBM bytes; accumulators stay fp32). Raw
kernels take ``interpret`` explicitly — ``kernels.ops`` owns the default.

Two kernel families:

* ``lloyd_assign_pallas`` (+ batched) — the historical accumulated form: one
  (k, d)/(k,) VMEM accumulator pair for the whole grid. Used by the legacy
  weighted / mini-batch paths.
* ``lloyd_assign_tiled_pallas`` / ``lloyd_assign_gated_pallas`` (+ batched)
  — the BOUNDED-LLOYD form: per-tile inertia partials and second-best gaps,
  per-point labels/D², and HIERARCHICAL per-cluster accumulators: every
  ``tps = tiles_per_super(n_tiles)`` consecutive tiles accumulate into ONE
  per-super-tile (k, d)/(k,) slot (sequential, ascending tile order inside
  the kernel; the engine reduces the small (n_super, k, d) array outside),
  capping accumulator HBM at O(n_super·k·d) instead of the flat
  O(n_tiles·k·d). The gated variant reuses PR 3's scalar-prefetched
  compacted index map + ``input_output_aliases``: a tile whose movement
  bound proves no label can change is neither computed nor fetched, its
  per-tile/per-point output blocks keep the previous iteration's
  (bitwise-identical) values, and the accumulator aliasing happens at the
  SUPER level — a super-tile's slot is carried only when ALL its tiles
  skip, so callers must pass super-aligned active sets
  (``core.bounds.expand_active_supers``; the ops wrapper enforces it).
  Inside an active tile the FINE level fires: per-point Hamerly bounds
  (carried ``point_lb`` + exact ``min_d2``) short-circuit the k-way
  distance recomputation for every point whose label and D² provably
  cannot change (``core.bounds.assign_point_prune``) — the selects are
  value-noops, pinned bitwise, and the ``pruned`` output counts them. The
  reduction tree is shared by the gated and ungated tiled kernels, which
  is what makes bounded-vs-unbounded fits bitwise comparable end to end.

The layout is ``kmeans_distance``'s: per-point labels/D²/bounds are
``(1, block_n)`` rows, a tile's distances ``(k, block_n)``, per-tile
scalars lane-broadcast blocks, and cluster counts ``(1, k)`` rows (the
one-hot column sums, taken on the MXU so they land on lanes). As there, the
wrappers take the valid row count from ``norms``: the points may arrive as
the prologue's once-padded rows (``core.bounds.RoundCache.rows``), which
stream as they are; plain ``(n, d)`` points are padded on each call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the one shared definition of the cached-norm matmul-form D^2 and the
# nearest-centroid fold — the fused==pallas bitwise-parity claims hang off
# every kernel using them
from repro.core.distance import HIGHEST, nearest, tile_d2
from repro.kernels.kmeans_distance import (LANES, as_lanes, as_row,
                                           compiler_params, from_lanes,
                                           lane_fill, lane_scalar, lane_spec,
                                           pad_rows, pallas_call, row_ids)
# the ONE definition of the fine-level per-point prune test (the pure-JAX
# gate model evaluates the same function — single source of truth)
from repro.core.bounds import assign_point_prune as _assign_point_prune


def _onehot_sums(won, valid, x):
    """Per-cluster sums (k, d) and counts (1, k) of one tile: the one-hot
    matmuls on the MXU instead of atomics (exact for the 0/1 counts)."""
    onehot = jnp.where(valid, won.astype(jnp.float32), 0.0)    # (k, block_n)
    sums = jax.lax.dot_general(onehot, x, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=HIGHEST)
    counts = jax.lax.dot_general(jnp.ones((1, onehot.shape[1]), jnp.float32),
                                 onehot, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=HIGHEST)
    return sums, counts


def _won(a, k):
    """(k, block_n) winner mask of labels ``a`` (1, block_n)."""
    return jax.lax.broadcasted_iota(jnp.int32, (k, a.shape[1]), 0) == a


def assign_tile(x_raw, xn, c_raw, valid):
    """Plain per-tile assignment: (labels, masked min_d2, sums (k, d),
    counts (1, k)) — shared by the accumulated kernels and the fused
    backend's blocked assignment."""
    d2 = tile_d2(x_raw, c_raw, xn)
    a, m = nearest(d2)
    sums, counts = _onehot_sums(_won(a, d2.shape[0]), valid,
                                x_raw.astype(jnp.float32))
    return a, jnp.where(valid, m, 0.0), sums, counts


def _assign_kernel(nv_ref, pts_ref, norms_ref, cents_ref, assign_ref,
                   md_ref, sums_ref, counts_ref, *, block_n: int):
    i = pl.program_id(0)
    valid = row_ids(i, block_n) < nv_ref[0]
    a, m, tile_sums, tile_counts = assign_tile(pts_ref[...], norms_ref[...],
                                               cents_ref[...], valid)
    assign_ref[...] = a
    md_ref[...] = m

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = tile_sums
        counts_ref[...] = tile_counts

    @pl.when(i > 0)
    def _accum():
        sums_ref[...] += tile_sums
        counts_ref[...] += tile_counts


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def lloyd_assign_pallas(points: jax.Array, norms: jax.Array,
                        centroids: jax.Array, *, block_n: int,
                        interpret: bool):
    """Returns (assignment (n,) int32, min_d2 (n,), sums (k, d), counts (k,)).
    ``norms`` is the cached fp32 ``||x||^2`` (n,)."""
    n, d = norms.shape[0], points.shape[1]
    k = centroids.shape[0]
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    pts = pad_rows(points, n + pad)
    nrm = as_row(norms.astype(jnp.float32), pad)
    row = pl.BlockSpec((1, block_n), lambda i, nv: (0, i))

    a, md, sums, counts = pallas_call(
        functools.partial(_assign_kernel, block_n=block_n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                  # n_valid
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((block_n, d), lambda i, nv: (i, 0)),
                row,                                # cached ||x||^2
                pl.BlockSpec((k, d), lambda i, nv: (0, 0)),     # resident
            ],
            out_specs=[
                row,
                row,
                pl.BlockSpec((k, d), lambda i, nv: (0, 0)),  # accumulator
                pl.BlockSpec((1, k), lambda i, nv: (0, 0)),  # accumulator
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((1, n + pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n + pad), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(jnp.array([n], jnp.int32), pts, nrm, centroids)
    return a[0, :n], md[0, :n], sums, counts[0]


# ---------------------------------------------------------------------------
# batch-grid variant (multi-tenant clustering: B independent problems)
# ---------------------------------------------------------------------------


def _assign_kernel_batched(nv_ref, pts_ref, norms_ref, cents_ref,
                           assign_ref, md_ref, sums_ref, counts_ref, *,
                           block_n: int):
    """Grid step (b, i): same math as `_assign_kernel` for problem b's tile i.

    The (1, k, d)/(1, 1, k) accumulators map to problem b's slot; the grid
    iterates i fastest, so `i == 0` re-initializes them exactly once per
    problem."""
    i = pl.program_id(1)
    valid = row_ids(i, block_n) < nv_ref[0]
    a, m, tile_sums, tile_counts = assign_tile(pts_ref[0], norms_ref[0],
                                               cents_ref[0], valid)
    assign_ref[0] = a
    md_ref[0] = m

    @pl.when(i == 0)
    def _init():
        sums_ref[0] = tile_sums
        counts_ref[0] = tile_counts

    @pl.when(i > 0)
    def _accum():
        sums_ref[0] += tile_sums
        counts_ref[0] += tile_counts


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def lloyd_assign_batched_pallas(points: jax.Array, norms: jax.Array,
                                centroids: jax.Array, *, block_n: int,
                                interpret: bool):
    """Batched Lloyd half-step over B independent problems in ONE launch.

    points (B, n, d), norms (B, n), centroids (B, k, d) -> (assignment (B, n)
    int32, min_d2 (B, n), sums (B, k, d), counts (B, k)). Row b matches
    `lloyd_assign_pallas` on problem b; the grid gains a leading batch
    dimension and the per-cluster accumulators gain a per-problem slot."""
    B, n, d = points.shape[0], norms.shape[1], points.shape[2]
    k = centroids.shape[1]
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    pts = pad_rows(points, n + pad)
    nrm = as_row(norms.astype(jnp.float32), pad)
    row = pl.BlockSpec((1, 1, block_n), lambda b, i, nv: (b, 0, i))

    a, md, sums, counts = pallas_call(
        functools.partial(_assign_kernel_batched, block_n=block_n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                  # n_valid
            grid=(B, grid),
            in_specs=[
                pl.BlockSpec((1, block_n, d), lambda b, i, nv: (b, i, 0)),
                row,
                pl.BlockSpec((1, k, d), lambda b, i, nv: (b, 0, 0)),
            ],
            out_specs=[
                row,
                row,
                pl.BlockSpec((1, k, d), lambda b, i, nv: (b, 0, 0)),
                pl.BlockSpec((1, 1, k), lambda b, i, nv: (b, 0, 0)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, n + pad), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, n + pad), jnp.float32),
            jax.ShapeDtypeStruct((B, k, d), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, k), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(jnp.array([n], jnp.int32), pts, nrm, centroids)
    return a[:, 0, :n], md[:, 0, :n], sums, counts[:, 0]


# ---------------------------------------------------------------------------
# tiled variant (bounded Lloyd): per-tile partial/gap/sums/counts outputs
# ---------------------------------------------------------------------------


def _tile_assign(x_raw, xn, c_raw, valid):
    """Shared per-tile assignment math for the tiled/gated kernels:
    (labels, masked min_d2, tile inertia partial (1, 1), tile second-best
    gap (1, 1), per-point second-best lower bound, tile per-cluster sums
    (k, d), tile per-cluster counts (1, k)). The gap/lb are in DISTANCE
    units (the movement bound compares them against centroid movement); a
    k=1 tile has no runner-up, so its gap/lb are +inf."""
    d2 = tile_d2(x_raw, c_raw, xn)                      # (k, block_n)
    a, m = nearest(d2)
    won = _won(a, d2.shape[0])
    second = jnp.min(jnp.where(won, jnp.inf, d2), axis=0, keepdims=True)
    gap_pt = jnp.sqrt(second) - jnp.sqrt(m)
    gap = jnp.min(jnp.where(valid, gap_pt, jnp.inf), axis=1, keepdims=True)
    m = jnp.where(valid, m, 0.0)
    tile_sums, tile_counts = _onehot_sums(won, valid,
                                          x_raw.astype(jnp.float32))
    return (a, m, jnp.sum(m, axis=1, keepdims=True), gap, jnp.sqrt(second),
            tile_sums, tile_counts)


def _tile_assign_pruned(x_raw, xn, c_raw, valid, prev_a, prev_md, prev_lb,
                        delta, thresh, absorb):
    """Fine-level twin of `_tile_assign`: per-point Hamerly pruning inside
    one ACTIVE tile. Points whose own centroid is bitwise unmoved and whose
    carried second-best lower bound clears the movement threshold
    (`core.bounds.assign_point_prune`) short-circuit the k-way distance
    recomputation: label, min_d2 come from the carry (bitwise what a fresh
    compute would produce — the exactness argument in ``core.bounds``), and
    their lb decays by ``absorb`` instead of being re-derived. ``delta`` is
    the (k, 1) movement column, ``thresh``/``absorb`` the tile's (1, 1)
    scalars. Returns (labels, masked min_d2, tile partial, tile gap,
    per-point lb, pruned-point count, tile sums, tile counts)."""
    prune = _assign_point_prune(prev_a, prev_md, prev_lb, delta, thresh,
                                valid)
    d2 = tile_d2(x_raw, c_raw, xn)                      # (k, block_n)
    a_f, m_f = nearest(d2)
    a = jnp.where(prune, prev_a, a_f)
    m = jnp.where(prune, prev_md, m_f)
    won = _won(a, d2.shape[0])
    second = jnp.min(jnp.where(won, jnp.inf, d2), axis=0, keepdims=True)
    # pruned rows carry the decayed bound — their fresh second-best was
    # never (conceptually) computed; fresh rows re-derive it exactly
    lb = jnp.where(prune, prev_lb - absorb, jnp.sqrt(second))
    gap_pt = lb - jnp.sqrt(m)
    gap = jnp.min(jnp.where(valid, gap_pt, jnp.inf), axis=1, keepdims=True)
    m = jnp.where(valid, m, 0.0)
    tile_sums, tile_counts = _onehot_sums(won, valid,
                                          x_raw.astype(jnp.float32))
    return (a, m, jnp.sum(m, axis=1, keepdims=True), gap, lb,
            jnp.sum(prune.astype(jnp.float32), axis=1, keepdims=True),
            tile_sums, tile_counts)


def _super_accum(cond_first, ssums_ref, scounts_ref, tsums, tcounts, idx):
    """Accumulate one tile's contribution into its super-tile's resident
    accumulator slot at ``ssums_ref[idx]``: re-initialize on the super's
    first visited tile (the freshly-mapped output block is undefined VMEM —
    the where never USES it then), sequential adds after. One shared
    definition keeps the gated and ungated kernels on the same tree."""
    prev_s = jnp.where(cond_first, jnp.zeros_like(tsums), ssums_ref[idx])
    prev_c = jnp.where(cond_first, jnp.zeros_like(tcounts),
                       scounts_ref[idx])
    ssums_ref[idx] = prev_s + tsums
    scounts_ref[idx] = prev_c + tcounts


def _assign_tiled_kernel(nv_ref, pts_ref, norms_ref, cents_ref,
                         assign_ref, md_ref, partial_ref, gap_ref, ssums_ref,
                         scounts_ref, *, block_n: int, tps: int):
    i = pl.program_id(0)
    valid = row_ids(i, block_n) < nv_ref[0]
    a, m, part, gap, _, tsums, tcounts = _tile_assign(
        pts_ref[...], norms_ref[...], cents_ref[...], valid)
    assign_ref[...] = a
    md_ref[...] = m
    partial_ref[0] = lane_fill(part)
    gap_ref[0] = lane_fill(gap)
    _super_accum(i % tps == 0, ssums_ref, scounts_ref, tsums, tcounts, 0)


@functools.partial(jax.jit, static_argnames=("block_n", "tps", "interpret"))
def lloyd_assign_tiled_pallas(points: jax.Array, norms: jax.Array,
                              centroids: jax.Array, *, block_n: int,
                              tps: int, interpret: bool):
    """Bounded-Lloyd assignment half-step with per-tile scalars and
    HIERARCHICAL per-cluster accumulators.

    Returns (assignment (n,) int32, min_d2 (n,), partials (n_tiles,),
    gaps (n_tiles,), super_sums (n_super, k, d), super_counts (n_super, k))
    where every ``tps`` consecutive tiles share one accumulator slot
    (n_super = ceil(n_tiles / tps)). ``sum(partials)`` is the iteration's
    inertia; ``super_sums.sum(0)`` / ``super_counts.sum(0)`` are the
    centroid-update accumulators — the SAME two-level reduction tree the
    gated kernel produces, so bounded and unbounded fits compare bitwise."""
    n, d = norms.shape[0], points.shape[1]
    k = centroids.shape[0]
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    n_super = -(-grid // tps)
    pts = pad_rows(points, n + pad)
    nrm = as_row(norms.astype(jnp.float32), pad)
    row = pl.BlockSpec((1, block_n), lambda i, nv: (0, i))
    one = lane_spec(lambda i, nv: (i, 0, 0))

    a, md, partials, gaps, ssums, scounts = pallas_call(
        functools.partial(_assign_tiled_kernel, block_n=block_n, tps=tps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                  # n_valid
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((block_n, d), lambda i, nv: (i, 0)),
                row,                                # cached ||x||^2
                pl.BlockSpec((k, d), lambda i, nv: (0, 0)),     # resident
            ],
            out_specs=[
                row,
                row,
                one,
                one,
                pl.BlockSpec((1, k, d), lambda i, nv: (i // tps, 0, 0)),
                pl.BlockSpec((1, 1, k), lambda i, nv: (i // tps, 0, 0)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((1, n + pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n + pad), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_super, k, d), jnp.float32),
            jax.ShapeDtypeStruct((n_super, 1, k), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(jnp.array([n], jnp.int32), pts, nrm, centroids)
    return (a[0, :n], md[0, :n], from_lanes(partials), from_lanes(gaps),
            ssums, scounts[:, 0])


def _assign_gated_kernel(ids_ref, meta_ref, pts_ref, norms_ref, cents_ref,
                         delta_ref, thresh_ref, absorb_ref, pa_ref, pmd_ref,
                         plb_ref, pp_ref, pg_ref, pss_ref, psc_ref, pz_ref,
                         assign_ref, md_ref, lb_ref, partial_ref, gap_ref,
                         ssums_ref, scounts_ref, pruned_ref, *, block_n: int,
                         tps: int):
    """Grid step i streams tile ``ids[i]``; steps >= n_active revisit the
    last active tile (VMEM-resident, no HBM fetch) gated off by pl.when.
    ``pa``/``pmd``/``plb`` (the per-point carries) are READ — they feed the
    fine-level per-point prune — and their buffers are donated to the
    matching outputs; the pp/pg/pss/psc/pz refs are never read and live in
    ANY memory space (zero DMA), existing only to carry the aliased buffers
    the skipped tiles'/supers' outputs fall back to. The super-tile
    accumulator re-initializes on each super's first tile (``ids[i] % tps
    == 0`` — the caller guarantees super-aligned active sets, so a super's
    tiles are visited completely and in ascending order)."""
    del pp_ref, pg_ref, pss_ref, psc_ref, pz_ref
    i = pl.program_id(0)

    @pl.when(i < meta_ref[1])
    def _compute():
        t = ids_ref[i]                                 # the REAL tile id
        valid = row_ids(t, block_n) < meta_ref[0]
        a, m, part, gap, lb, pruned, tsums, tcounts = _tile_assign_pruned(
            pts_ref[...], norms_ref[...], cents_ref[...], valid, pa_ref[...],
            pmd_ref[...].astype(jnp.float32),
            plb_ref[...].astype(jnp.float32), delta_ref[...],
            lane_scalar(thresh_ref[0]), lane_scalar(absorb_ref[0]))
        assign_ref[...] = a
        md_ref[...] = m
        lb_ref[...] = lb
        partial_ref[0] = lane_fill(part)
        gap_ref[0] = lane_fill(gap)
        pruned_ref[0] = lane_fill(pruned)
        _super_accum(t % tps == 0, ssums_ref, scounts_ref, tsums, tcounts, 0)


@functools.partial(jax.jit, static_argnames=("block_n", "tps", "interpret"))
def lloyd_assign_gated_pallas(points: jax.Array, norms: jax.Array,
                              centroids: jax.Array, delta: jax.Array,
                              thresh: jax.Array, absorb: jax.Array,
                              prev_assign: jax.Array,
                              prev_min_d2: jax.Array, prev_lb: jax.Array,
                              prev_partials: jax.Array, prev_gaps: jax.Array,
                              prev_super_sums: jax.Array,
                              prev_super_counts: jax.Array, ids: jax.Array,
                              meta: jax.Array, *, block_n: int, tps: int,
                              interpret: bool):
    """Bound-gated assignment half-step (two-level exact pruning for Lloyd).

    ``ids``/``meta=[n_valid, n_active]`` come from `core.bounds.compact_ids`
    over a SUPER-ALIGNED active mask (`core.bounds.expand_active_supers` of
    `assign_active_tiles` — the ops wrapper enforces it): only the first
    n_active grid steps fetch + compute; every output block of a skipped
    tile keeps the aliased previous-iteration value, which the movement
    bound proves is bitwise what a recompute would write (labels cannot
    change AND the tile's assigned centroids did not move). The per-cluster
    accumulators are per-SUPER-tile (aliased at super granularity — carried
    iff the whole super skipped). Inside computed tiles the per-point
    Hamerly bound short-circuits stable points (``delta``/``thresh``/
    ``absorb`` from `core.bounds.assign_point_scalars`). Same returns as
    `lloyd_assign_tiled_pallas` plus (lb (n,), pruned (n_tiles,)).
    ``points`` may be the prologue's once-padded rows, which stream as they
    are (the engine's prologue owns that pad); n is ``norms``' length."""
    n, d = norms.shape[0], points.shape[1]
    k = centroids.shape[0]
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    n_super = -(-grid // tps)
    pts = pad_rows(points, n + pad)
    nrm = as_row(norms.astype(jnp.float32), pad)
    pa = as_row(prev_assign.astype(jnp.int32), pad)
    pmd = as_row(prev_min_d2.astype(jnp.float32), pad)
    plb = as_row(prev_lb.astype(jnp.float32), pad)

    # the five pp/pg/pss/psc/pz operands exist ONLY to donate their buffers
    # via input_output_aliases (the kernel never reads them): ANY memory
    # space keeps them in HBM with no per-step VMEM DMA, so active tiles pay
    # zero traffic for those carries and skipped tiles still inherit them
    carry_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    blk = pl.BlockSpec((1, block_n), lambda i, ids, meta: (0, ids[i]))
    one = lane_spec(lambda i, ids, meta: (ids[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                          # ids, meta
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, ids, meta: (ids[i], 0)),
            blk,                                            # norms
            pl.BlockSpec((k, d), lambda i, ids, meta: (0, 0)),   # resident
            pl.BlockSpec((k, 1), lambda i, ids, meta: (0, 0)),   # delta
            one,                                            # thresh
            one,                                            # absorb
            blk,                                            # prev assign
            blk,                                            # prev min_d2
            blk,                                            # prev lb
        ] + [carry_spec] * 5,
        out_specs=[
            blk,                                            # assignment
            blk,                                            # min_d2
            blk,                                            # lb
            one,                                            # partial
            one,                                            # gap
            pl.BlockSpec((1, k, d),
                         lambda i, ids, meta: (ids[i] // tps, 0, 0)),
            pl.BlockSpec((1, 1, k),
                         lambda i, ids, meta: (ids[i] // tps, 0, 0)),
            one,                                            # pruned
        ],
    )
    a, md, lb, partials, gaps, ssums, scounts, pruned = pallas_call(
        functools.partial(_assign_gated_kernel, block_n=block_n, tps=tps),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, n + pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n + pad), jnp.float32),
            jax.ShapeDtypeStruct((1, n + pad), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_super, k, d), jnp.float32),
            jax.ShapeDtypeStruct((n_super, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, LANES), jnp.float32),
        ],
        # skipped tiles/supers reuse all of their prior output blocks;
        # skipped tiles report zero pruned points (the donated zeros)
        input_output_aliases={8: 0, 9: 1, 10: 2, 11: 3, 12: 4, 13: 5,
                              14: 6, 15: 7},
        compiler_params=compiler_params(),
        interpret=interpret,
    )(ids.astype(jnp.int32), meta.astype(jnp.int32), pts, nrm, centroids,
      delta.astype(jnp.float32)[:, None], as_lanes(thresh), as_lanes(absorb),
      pa, pmd, plb, as_lanes(prev_partials), as_lanes(prev_gaps),
      prev_super_sums.astype(jnp.float32),
      prev_super_counts.astype(jnp.float32)[:, None, :],
      jnp.zeros((grid, 1, LANES), jnp.float32))
    return (a[0, :n], md[0, :n], lb[0, :n], from_lanes(partials),
            from_lanes(gaps), ssums, scounts[:, 0], from_lanes(pruned))


def _assign_tiled_kernel_batched(nv_ref, pts_ref, norms_ref, cents_ref,
                                 assign_ref, md_ref, partial_ref, gap_ref,
                                 ssums_ref, scounts_ref, *, block_n: int,
                                 tps: int):
    i = pl.program_id(1)
    valid = row_ids(i, block_n) < nv_ref[0]
    a, m, part, gap, _, tsums, tcounts = _tile_assign(
        pts_ref[0], norms_ref[0], cents_ref[0], valid)
    assign_ref[0] = a
    md_ref[0] = m
    partial_ref[0, 0] = lane_fill(part)
    gap_ref[0, 0] = lane_fill(gap)
    _super_accum(i % tps == 0, ssums_ref, scounts_ref, tsums, tcounts,
                 (0, 0))


@functools.partial(jax.jit, static_argnames=("block_n", "tps", "interpret"))
def lloyd_assign_tiled_batched_pallas(points: jax.Array, norms: jax.Array,
                                      centroids: jax.Array, *, block_n: int,
                                      tps: int, interpret: bool):
    """Batch-grid tiled assignment over B independent problems in ONE launch;
    row b is bitwise `lloyd_assign_tiled_pallas` on problem b."""
    B, n, d = points.shape[0], norms.shape[1], points.shape[2]
    k = centroids.shape[1]
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    n_super = -(-grid // tps)
    pts = pad_rows(points, n + pad)
    nrm = as_row(norms.astype(jnp.float32), pad)
    row = pl.BlockSpec((1, 1, block_n), lambda b, i, nv: (b, 0, i))
    one = pl.BlockSpec((1, 1, 1, LANES), lambda b, i, nv: (b, i, 0, 0))

    a, md, partials, gaps, ssums, scounts = pallas_call(
        functools.partial(_assign_tiled_kernel_batched, block_n=block_n,
                          tps=tps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                  # n_valid
            grid=(B, grid),
            in_specs=[
                pl.BlockSpec((1, block_n, d), lambda b, i, nv: (b, i, 0)),
                row,
                pl.BlockSpec((1, k, d), lambda b, i, nv: (b, 0, 0)),
            ],
            out_specs=[
                row,
                row,
                one,
                one,
                pl.BlockSpec((1, 1, k, d),
                             lambda b, i, nv: (b, i // tps, 0, 0)),
                pl.BlockSpec((1, 1, 1, k),
                             lambda b, i, nv: (b, i // tps, 0, 0)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, n + pad), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, n + pad), jnp.float32),
            jax.ShapeDtypeStruct((B, grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, n_super, k, d), jnp.float32),
            jax.ShapeDtypeStruct((B, n_super, 1, k), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(jnp.array([n], jnp.int32), pts, nrm, centroids)
    return (a[:, 0, :n], md[:, 0, :n], from_lanes(partials),
            from_lanes(gaps), ssums, scounts[:, :, 0])


def _assign_gated_kernel_batched(ids_ref, nact_ref, nv_ref, pts_ref,
                                 norms_ref, cents_ref, delta_ref, thresh_ref,
                                 absorb_ref, pa_ref, pmd_ref, plb_ref,
                                 pp_ref, pg_ref, pss_ref, psc_ref, pz_ref,
                                 assign_ref, md_ref, lb_ref, partial_ref,
                                 gap_ref, ssums_ref, scounts_ref, pruned_ref,
                                 *, block_n: int, tps: int):
    """Grid step (b, i) streams tile ids[b, i] of problem b; steps past
    problem b's n_active are no-ops (per-problem compaction)."""
    del pp_ref, pg_ref, pss_ref, psc_ref, pz_ref
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i < nact_ref[b])
    def _compute():
        t = ids_ref[b, i]
        valid = row_ids(t, block_n) < nv_ref[0]
        a, m, part, gap, lb, pruned, tsums, tcounts = _tile_assign_pruned(
            pts_ref[0], norms_ref[0], cents_ref[0], valid, pa_ref[0],
            pmd_ref[0].astype(jnp.float32), plb_ref[0].astype(jnp.float32),
            delta_ref[0], lane_scalar(thresh_ref[0, 0]),
            lane_scalar(absorb_ref[0, 0]))
        assign_ref[0] = a
        md_ref[0] = m
        lb_ref[0] = lb
        partial_ref[0, 0] = lane_fill(part)
        gap_ref[0, 0] = lane_fill(gap)
        pruned_ref[0, 0] = lane_fill(pruned)
        _super_accum(t % tps == 0, ssums_ref, scounts_ref, tsums, tcounts,
                     (0, 0))


@functools.partial(jax.jit, static_argnames=("block_n", "tps", "interpret"))
def lloyd_assign_gated_batched_pallas(
        points: jax.Array, norms: jax.Array, centroids: jax.Array,
        delta: jax.Array, thresh: jax.Array, absorb: jax.Array,
        prev_assign: jax.Array, prev_min_d2: jax.Array, prev_lb: jax.Array,
        prev_partials: jax.Array, prev_gaps: jax.Array,
        prev_super_sums: jax.Array, prev_super_counts: jax.Array,
        ids: jax.Array, n_active: jax.Array, *, block_n: int, tps: int,
        interpret: bool):
    """Batch-grid bound-gated assignment: per-problem compacted
    (super-aligned) active-tile maps ids (B, n_tiles) / n_active (B,).
    Row b is bitwise `lloyd_assign_gated_pallas` on problem b."""
    B, n, d = points.shape[0], norms.shape[1], points.shape[2]
    k = centroids.shape[1]
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    n_super = -(-grid // tps)
    pts = pad_rows(points, n + pad)
    nrm = as_row(norms.astype(jnp.float32), pad)
    pa = as_row(prev_assign.astype(jnp.int32), pad)
    pmd = as_row(prev_min_d2.astype(jnp.float32), pad)
    plb = as_row(prev_lb.astype(jnp.float32), pad)
    nv = jnp.array([n], jnp.int32)

    # never-read aliased carries: ANY memory space, no per-step DMA
    carry_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    blk = pl.BlockSpec((1, 1, block_n),
                       lambda b, i, ids, na, nv: (b, 0, ids[b, i]))
    one = pl.BlockSpec((1, 1, 1, LANES),
                       lambda b, i, ids, na, nv: (b, ids[b, i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                      # ids, n_active, n_valid
        grid=(B, grid),
        in_specs=[
            pl.BlockSpec((1, block_n, d),
                         lambda b, i, ids, na, nv: (b, ids[b, i], 0)),
            blk,                                        # norms
            pl.BlockSpec((1, k, d), lambda b, i, ids, na, nv: (b, 0, 0)),
            pl.BlockSpec((1, k, 1),
                         lambda b, i, ids, na, nv: (b, 0, 0)),  # delta
            one,                                        # thresh
            one,                                        # absorb
            blk,                                        # prev assign
            blk,                                        # prev min_d2
            blk,                                        # prev lb
        ] + [carry_spec] * 5,
        out_specs=[
            blk,                                        # assignment
            blk,                                        # min_d2
            blk,                                        # lb
            one,                                        # partial
            one,                                        # gap
            pl.BlockSpec((1, 1, k, d),
                         lambda b, i, ids, na, nv: (b, ids[b, i] // tps,
                                                    0, 0)),
            pl.BlockSpec((1, 1, 1, k),
                         lambda b, i, ids, na, nv: (b, ids[b, i] // tps,
                                                    0, 0)),
            one,                                        # pruned
        ],
    )
    a, md, lb, partials, gaps, ssums, scounts, pruned = pallas_call(
        functools.partial(_assign_gated_kernel_batched, block_n=block_n,
                          tps=tps),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, n + pad), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, n + pad), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, n + pad), jnp.float32),
            jax.ShapeDtypeStruct((B, grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, n_super, k, d), jnp.float32),
            jax.ShapeDtypeStruct((B, n_super, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((B, grid, 1, LANES), jnp.float32),
        ],
        input_output_aliases={9: 0, 10: 1, 11: 2, 12: 3, 13: 4, 14: 5,
                              15: 6, 16: 7},
        compiler_params=compiler_params(),
        interpret=interpret,
    )(ids.astype(jnp.int32), n_active.astype(jnp.int32), nv, pts, nrm,
      centroids, delta.astype(jnp.float32)[..., None], as_lanes(thresh),
      as_lanes(absorb), pa, pmd, plb, as_lanes(prev_partials),
      as_lanes(prev_gaps), prev_super_sums.astype(jnp.float32),
      prev_super_counts.astype(jnp.float32)[:, :, None, :],
      jnp.zeros((B, grid, 1, LANES), jnp.float32))
    return (a[:, 0, :n], md[:, 0, :n], lb[:, 0, :n], from_lanes(partials),
            from_lanes(gaps), ssums, scounts[:, :, 0], from_lanes(pruned))
