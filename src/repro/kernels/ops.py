"""jit'd public wrappers around the Pallas kernels.

On a TPU backend the kernels compile to Mosaic; everywhere else they run in
interpret mode (Python evaluation of the kernel body — bit-correct, slow),
which is how this CPU container validates them. THIS module is the single
place that default is chosen (`default_interpret`): the raw kernels in
``kmeans_distance`` / ``lloyd_assign`` require ``interpret`` explicitly, so
bypassing these wrappers can never silently run interpreted on real TPU.

Block sizes are chosen so the working set (points tile + resident centroids
+ cached-norms block + accumulators + per-tile partials + bound-state
blocks) fits `_VMEM_BUDGET` with double buffering, and every kernel is
compiled with the scoped-VMEM limit `vmem_limit_bytes` derived from that
same budget (a v5e core has 128 MiB of VMEM; Mosaic's default scoped limit
is far below the budget, so without the limit the picked tiles would not
compile).

The wrappers carry a `custom_vmap` rule: `jax.vmap` over them dispatches to
the batch-grid kernel variants (one launch with a leading batch grid
dimension) instead of relying on the generic pallas batching rule — this is
what lets the engine's `seed_batched`/`fit_batched` vmap hit real batched
kernels with the VMEM budget accounted for. The bound-gated wrapper does the
same for the gated batch-grid kernel (per-problem compacted tile maps).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.kernels.kmeans_distance import (
    LANES, distance_min_update_batched_pallas,
    distance_min_update_gated_pallas,
    distance_min_update_gated_batched_pallas, distance_min_update_pallas,
    row_min_d2_pallas, seed_prologue_pallas, tile_cap_pallas)
from repro.core.bounds import point_norms  # noqa: F401  (re-exported: the
#   cached-norm input the kernels stream; wrappers compute it on the fly
#   when the caller has no prologue cache)
from repro.core.guards import KernelFailureError
from repro.kernels.lloyd_assign import (lloyd_assign_batched_pallas,
                                        lloyd_assign_gated_batched_pallas,
                                        lloyd_assign_gated_pallas,
                                        lloyd_assign_pallas,
                                        lloyd_assign_tiled_batched_pallas,
                                        lloyd_assign_tiled_pallas)

_VMEM_BUDGET = 48 * 1024 * 1024  # what pick_block_n sizes tiles for
# Mosaic's own scratch (matmul staging, relayouts) comes on top of the
# buffers the budget counts; the limit leaves that headroom below a v5e
# core's 128 MiB
_VMEM_HEADROOM = 2
# (k, block_n) fp32 values a round kernel keeps live at once: distances,
# the winner mask / one-hot, and the masked selects built from them
_D2_LIVE = 4
_SUBLANES = 8                     # f32 VMEM tiles are (8, LANES)


def vmem_limit_bytes() -> int:
    """The scoped-VMEM limit every kernel compiles with — the one owner of
    both this limit and the budget `pick_block_n` sizes tiles against."""
    return _VMEM_HEADROOM * _VMEM_BUDGET


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m

# The graceful-degradation order when a Pallas kernel fails to compile or
# launch: each pallas-flavoured local backend degrades to the fused XLA
# backend (same math, no Mosaic), which itself degrades to the looped
# reference. `None` terminates the chain — an exhausted chain re-raises the
# KernelFailureError to the caller. ClusterEngine._dispatch walks this map;
# the mesh backend substitutes its LOCAL backend through the same chain.
FALLBACK_CHAIN: dict = {
    "pallas": "fused",
    "pallas_constant": "fused",
    "pallas_fused": "fused",
    "fused": "reference",
    "global": "reference",
    "reference": None,
    "serial": None,
}

# Fault-injection hook (see repro.testing.faults.force_kernel_failure): when
# set to a reason string, EVERY public kernel wrapper raises
# KernelFailureError at trace time — on this CPU container the kernels run
# in interpret mode, so a forced trace-time raise is exactly where a real
# Mosaic compile/launch failure would surface from under jit.
_FORCED_FAILURE: str | None = None


def _check_forced() -> None:
    if _FORCED_FAILURE is not None:
        raise KernelFailureError(
            f"pallas kernel launch failed (forced: {_FORCED_FAILURE})")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """THE kernel-execution default: compiled on TPU, interpreted elsewhere.
    Every entry point whose ``interpret`` is None resolves it here."""
    return not _on_tpu()


def pick_block_n(d: int, k: int, *, dtype_bytes: int = 4,
                 max_block: int = 4096, batched: bool = False) -> int:
    """Largest power-of-two point-tile height whose double-buffered working set
    fits the VMEM budget. Accounted per grid step, in VMEM's (8, 128) tiles
    (a (bn, d) block pads d to 128 lanes; a (1, bn) row pads to 8 sublanes):

      2 x (bn, d) point tile           (double-buffered HBM->VMEM stream;
                                        dtype_bytes=2 budgets the half-width
                                        bf16 streaming blocks)
      2 x (1, bn) fp32 cached-norms row (double-buffered alongside the points)
      2 x (k, d) centroid block
      (k, bn) distance tile and the selects built from it + the per-point
        rows of the round
      fp32 accumulators: (k, d) sums + (1, k) counts + the per-tile partial
        (the seeding kernel's thrust::reduce analogue)
      bound-state blocks: previous-partial/tile-max in + partial/tile-max out
        lane blocks per step, double-buffered (the gated kernel's skip state)
      per-point bound blocks: the fine-level gates stream the prologue's
        fp32 ``center_d`` row (seeding, 2 buffers) and the assignment
        carries' int32 label + fp32 min_d2 + fp32 point_lb aliased in/out
        row pairs, plus the (k, 1) movement column and the per-tile
        dc/margin/thresh/absorb lane blocks
      hierarchical accumulator: the tiled/gated Lloyd kernels keep ONE
        per-SUPER-tile (k, d)+(1, k) cluster sums/counts block resident (plus
        the gated kernel's aliased prev block in flight) — per-tile sums no
        longer stream through VMEM per step

    `batched=True` budgets the batch-grid kernels, whose centroid block is
    re-fetched per problem and therefore double-buffered like the point
    stream (one extra (k, d) operand block in flight)."""
    bn = max_block
    while bn > 128:
        working = sum(vmem_working_set(d, k, bn, dtype_bytes=dtype_bytes,
                                       batched=batched).values())
        if working <= _VMEM_BUDGET:
            return bn
        bn //= 2
    return 128


def vmem_working_set(d: int, k: int, bn: int, *, dtype_bytes: int = 4,
                     batched: bool = False) -> dict[str, int]:
    """THE itemized per-grid-step VMEM accounting `pick_block_n` budgets —
    one shared table so tests (and the autotuner's candidate filter) assert
    against the implementation instead of hand-copied constants. Keys name
    the resident buffers; the budget is ``sum(values()) <= _VMEM_BUDGET``."""
    dp = _pad_to(d, LANES)             # lanes a (., d) block occupies
    kp = _pad_to(k, _SUBLANES)          # sublanes a (k, .) block occupies
    kl = _pad_to(k, LANES)             # lanes a (1, k) row occupies
    row = _SUBLANES * bn                # elements a (1, bn) row occupies
    lane_blk = _SUBLANES * LANES       # elements a per-tile lane block takes
    ws = {
        # double-buffered point tile + centroid block + the live (k, bn)
        # distance values + ~4 per-point rows, all at the stream dtype
        "stream": dtype_bytes * (2 * bn * dp + 2 * kp * dp
                                 + _D2_LIVE * kp * bn + 4 * row),
        "norms": 4 * 2 * row,               # cached ||x||^2 (fp32, 2 buffers)
        "accumulators": 4 * (kp * dp + _SUBLANES * kl + lane_blk),
        "bound_scalars": 4 * 2 * 4 * lane_blk,  # bound-state lane blocks
        "super_accumulators": 4 * 2 * (kp * dp + _SUBLANES * kl),
        "point_carries": 4 * 6 * row,       # assignment/min_d2/point_lb
                                            #   aliased i/o row pairs
        "center_d": 4 * 2 * row,            # center_d row (fp32, 2 bufs)
        "movement": 4 * kp * LANES,        # movement column (k, 1)
        "gate_scalars": 4 * 2 * 8 * lane_blk,   # dc/margin/thresh/absorb +
                                            #   gap/partial/pruned blocks
    }
    if batched:
        ws["batched_centroids"] = dtype_bytes * k * d  # second centroid buf
    return ws


def choose_block_n(n: int, d: int, k: int, *, batched: bool = False) -> int:
    """Point-tile height for an (n, d) x (k, d) launch: the VMEM-fitted block,
    clamped DOWN to the largest power of two <= n (never past the point count;
    the old round-up overshot n and launched oversized tiles), floored at the
    128-lane minimum. Non-multiple-of-block n is handled by padding + masking
    in the kernel wrappers, so any returned size is legal. The pick always
    uses the fp32 accounting even for bf16 streams, so a run's tile height —
    and with it the partials/bound-state shapes — is precision-independent."""
    cap = pick_block_n(d, k, batched=batched)
    if n >= cap:
        return cap
    return max(128, 1 << (max(n, 1).bit_length() - 1))


def _ensure_batched(x, is_batched: bool, axis_size: int):
    return x if is_batched else jnp.broadcast_to(x[None], (axis_size,) + x.shape)


def _align(points: jax.Array, centroids: jax.Array, norms):
    """Centroids follow the point stream dtype (bf16 streaming streams both);
    norms default to an on-the-fly fp32 computation."""
    cents = centroids.astype(points.dtype)
    if norms is None:
        norms = point_norms(points)
    return cents, norms.astype(jnp.float32)


def seed_prologue(points: jax.Array, *, block_n: int | None = None,
                  interpret: bool | None = None):
    """One streaming pass over the dataset: (norms, tile centers, tile radii)
    at the seed-tile height — everything the gated round kernels cache."""
    _check_forced()
    n, d = points.shape
    if block_n is None:
        block_n = choose_block_n(n, d, 1, batched=True)
    if interpret is None:
        interpret = default_interpret()
    return seed_prologue_pallas(points, block_n=block_n, interpret=interpret)


def distance_min_update(points: jax.Array, centroids: jax.Array,
                        min_d2: jax.Array, *, norms: jax.Array | None = None,
                        resident_centroids: bool = True,
                        block_n: int | None = None,
                        interpret: bool | None = None):
    """One k-means++ seeding round: fused D^2 min-update + per-tile partials.

    Returns (new_min_d2 (n,), partials (n_tiles,)) with the tile height
    `choose_block_n(n, d, k)` — the same tile the two-level `tiled` sampler
    draws from. Under `jax.vmap` this dispatches to the batch-grid kernel
    (`distance_min_update_batched`), not a per-problem loop."""
    _check_forced()
    n, d = points.shape
    k = centroids.shape[0]
    user_block = block_n
    if block_n is None:
        block_n = choose_block_n(n, d, k)
    if interpret is None:
        interpret = default_interpret()
    centroids, norms = _align(points, centroids, norms)

    @custom_vmap
    def call(pts, cents, md, nrm):
        return distance_min_update_pallas(pts, nrm, cents, md,
                                          block_n=block_n,
                                          resident=resident_centroids,
                                          interpret=interpret)

    @call.def_vmap
    def _rule(axis_size, in_batched, pts, cents, md, nrm):
        pts = _ensure_batched(pts, in_batched[0], axis_size)
        cents = _ensure_batched(cents, in_batched[1], axis_size)
        md = _ensure_batched(md, in_batched[2], axis_size)
        nrm = _ensure_batched(nrm, in_batched[3], axis_size)
        # block_n=None re-picks the tile with the batch-grid VMEM accounting
        out = distance_min_update_batched(pts, cents, md, norms=nrm,
                                          block_n=user_block,
                                          interpret=interpret)
        return out, (True, True)

    return call(points, centroids, min_d2, norms)


def distance_min_update_batched(points: jax.Array, centroids: jax.Array,
                                min_d2: jax.Array, *,
                                norms: jax.Array | None = None,
                                block_n: int | None = None,
                                interpret: bool | None = None):
    """Batched seeding round: (B, n, d) x (B, k, d) -> ((B, n), (B, n_tiles))
    in one batch-grid kernel launch."""
    _check_forced()
    _, n, d = points.shape
    k = centroids.shape[1]
    if block_n is None:
        block_n = choose_block_n(n, d, k, batched=True)
    if interpret is None:
        interpret = default_interpret()
    centroids, norms = _align(points, centroids, norms)
    return distance_min_update_batched_pallas(points, norms, centroids,
                                              min_d2, block_n=block_n,
                                              interpret=interpret)


def distance_min_update_gated(points: jax.Array, centroids: jax.Array,
                              min_d2: jax.Array, norms: jax.Array,
                              center_d: jax.Array, dc: jax.Array,
                              margin: jax.Array, prev_partials: jax.Array,
                              prev_tile_max: jax.Array, active: jax.Array, *,
                              block_n: int,
                              resident_centroids: bool = True,
                              interpret: bool | None = None):
    """Bound-gated seeding round (two-level exact pruning).

    ``active``/``dc``/``margin`` come from `core.bounds.seed_gate` and
    ``center_d`` from the prologue; the mask is compacted here into the
    scalar-prefetched index map the gated kernel consumes, so inactive tiles
    are neither fetched nor computed and their outputs keep the previous
    round's (bitwise-identical) values, while inside active tiles the
    per-point bound short-circuits rows whose ``min_d2`` provably cannot
    improve. Returns (new_min_d2, partials, tile_max, pruned (n_tiles,),
    skipped). ``block_n`` is required: it must match the tile height of the
    carried bound state. Under `jax.vmap` this dispatches to the gated
    batch-grid kernel with per-problem compaction."""
    from repro.core import bounds as bnd

    _check_forced()
    n, d = points.shape
    if interpret is None:
        interpret = default_interpret()
    centroids = centroids.astype(points.dtype)
    norms = norms.astype(jnp.float32)
    grid = -(-n // block_n)
    ids, n_active = bnd.compact_ids(active)
    skipped = (grid - n_active).astype(jnp.int32)

    @custom_vmap
    def call(pts, cents, md, nrm, cd, dc_, mg, pp, ptm, ids_, nact):
        meta = jnp.stack([jnp.full((), n, jnp.int32), nact.astype(jnp.int32)])
        return distance_min_update_gated_pallas(
            pts, nrm, cents, md, cd, dc_, mg, pp, ptm, ids_, meta,
            block_n=block_n, resident=resident_centroids,
            interpret=interpret)

    @call.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [_ensure_batched(a, b, axis_size)
                for a, b in zip(args, in_batched)]
        pts, cents, md, nrm, cd, dc_, mg, pp, ptm, ids_, nact = args
        out = distance_min_update_gated_batched_pallas(
            pts, nrm, cents, md, cd, dc_, mg, pp, ptm, ids_, nact,
            block_n=block_n, interpret=interpret)
        return out, (True, True, True, True)

    new_md, partials, tile_max, pruned = call(
        points, centroids, min_d2, norms, center_d.astype(jnp.float32), dc,
        margin, prev_partials, prev_tile_max, ids, n_active)
    return new_md, partials, tile_max, pruned, skipped


def row_min_d2(points: jax.Array, idx: jax.Array, centroids: jax.Array,
               count: jax.Array, *, interpret: bool | None = None):
    """Scalar D^2 of row ``idx`` to the nearest of ``centroids[:count]`` —
    the rejection sampler's exact-p gather (O(d) bytes of the dataset per
    proposal, DMA-steered by the scalar-prefetched row index). count == 0
    returns +inf. Under `jax.vmap` (the engine's batched seeding) this
    dispatches to the pure-jnp twin — a (B,)-batch of single-row gathers has
    no kernel to win."""
    _check_forced()
    if interpret is None:
        interpret = default_interpret()

    @custom_vmap
    def call(pts, i, cents, cnt):
        return row_min_d2_pallas(pts, i, cents, cnt, interpret=interpret)

    @call.def_vmap
    def _rule(axis_size, in_batched, pts, i, cents, cnt):
        from repro.kernels.ref import row_min_d2_ref
        pts = _ensure_batched(pts, in_batched[0], axis_size)
        i = _ensure_batched(i, in_batched[1], axis_size)
        cents = _ensure_batched(cents, in_batched[2], axis_size)
        cnt = _ensure_batched(cnt, in_batched[3], axis_size)
        return jax.vmap(row_min_d2_ref)(pts, i, cents, cnt), True

    return call(points, jnp.asarray(idx, jnp.int32), centroids,
                jnp.asarray(count, jnp.int32))


def tile_cap(centers: jax.Array, radii: jax.Array, pending: jax.Array,
             count: jax.Array, *, interpret: bool | None = None):
    """(n_tiles,) per-tile rejection-envelope caps ``(dc_t + r_t)^2`` against
    the first ``count`` pending centroids — the movement-tightened envelope's
    one (n_tiles, pending) pass over the prologue's tile summaries (never
    rows). count == 0 returns +inf everywhere (no tightening). Under
    `jax.vmap` (the engine's batched seeding) this dispatches to the
    pure-jnp twin — the per-problem summary pass is accumulator-bound, not
    kernel-bound."""
    _check_forced()
    if interpret is None:
        interpret = default_interpret()

    @custom_vmap
    def call(cent, rad, pend, cnt):
        return tile_cap_pallas(cent, rad, pend, cnt, interpret=interpret)

    @call.def_vmap
    def _rule(axis_size, in_batched, cent, rad, pend, cnt):
        from repro.kernels.ref import tile_cap_ref
        cent = _ensure_batched(cent, in_batched[0], axis_size)
        rad = _ensure_batched(rad, in_batched[1], axis_size)
        pend = _ensure_batched(pend, in_batched[2], axis_size)
        cnt = _ensure_batched(cnt, in_batched[3], axis_size)
        return jax.vmap(tile_cap_ref)(cent, rad, pend, cnt), True

    return call(centers.astype(jnp.float32), radii.astype(jnp.float32),
                pending.astype(jnp.float32), jnp.asarray(count, jnp.int32))


def lloyd_assign(points: jax.Array, centroids: jax.Array, *,
                 norms: jax.Array | None = None, block_n: int | None = None,
                 interpret: bool | None = None):
    """Fused assignment + per-cluster partial sums/counts. Under `jax.vmap`
    this dispatches to the batch-grid kernel (`lloyd_assign_batched`)."""
    _check_forced()
    n, d = points.shape
    k = centroids.shape[0]
    user_block = block_n
    if block_n is None:
        block_n = choose_block_n(n, d, k)
    if interpret is None:
        interpret = default_interpret()
    centroids, norms = _align(points, centroids, norms)

    @custom_vmap
    def call(pts, cents, nrm):
        return lloyd_assign_pallas(pts, nrm, cents, block_n=block_n,
                                   interpret=interpret)

    @call.def_vmap
    def _rule(axis_size, in_batched, pts, cents, nrm):
        pts = _ensure_batched(pts, in_batched[0], axis_size)
        cents = _ensure_batched(cents, in_batched[1], axis_size)
        nrm = _ensure_batched(nrm, in_batched[2], axis_size)
        # block_n=None re-picks the tile with the batch-grid VMEM accounting
        out = lloyd_assign_batched(pts, cents, norms=nrm, block_n=user_block,
                                   interpret=interpret)
        return out, (True, True, True, True)

    return call(points, centroids, norms)


def lloyd_assign_batched(points: jax.Array, centroids: jax.Array, *,
                         norms: jax.Array | None = None,
                         block_n: int | None = None,
                         interpret: bool | None = None):
    """Batched Lloyd half-step: (B, n, d) x (B, k, d) -> per-problem
    (assignment, min_d2, sums, counts) in one batch-grid kernel launch."""
    _check_forced()
    _, n, d = points.shape
    k = centroids.shape[1]
    if block_n is None:
        block_n = choose_block_n(n, d, k, batched=True)
    if interpret is None:
        interpret = default_interpret()
    centroids, norms = _align(points, centroids, norms)
    return lloyd_assign_batched_pallas(points, norms, centroids,
                                       block_n=block_n, interpret=interpret)


def lloyd_assign_tiled(points: jax.Array, centroids: jax.Array, *,
                       norms: jax.Array | None = None,
                       block_n: int | None = None,
                       tps: int | None = None,
                       interpret: bool | None = None):
    """Bounded-Lloyd assignment half-step with per-tile scalars and
    hierarchical accumulators.

    Returns (assignment, min_d2, partials (n_tiles,), gaps (n_tiles,),
    super_sums (n_super, k, d), super_counts (n_super, k)) with
    ``n_super = ceil(n_tiles / core.bounds.tiles_per_super(n_tiles, tps))``
    — the ungated twin of `lloyd_assign_gated`, sharing its two-level
    reduction tree so bounded and unbounded fits compare bitwise. ``tps``
    overrides the super-tile fan-in heuristic (the autotuner's knob); the
    gated twin must be called with the SAME value so the carried super
    accumulator shapes agree. Under `jax.vmap` this dispatches to the
    batch-grid kernel."""
    from repro.core import bounds as bnd

    _check_forced()
    n, d = points.shape
    k = centroids.shape[0]
    if block_n is None:
        block_n = choose_block_n(n, d, k)
    bn = block_n
    tps = bnd.tiles_per_super(-(-n // bn), tps)
    if interpret is None:
        interpret = default_interpret()
    centroids, norms = _align(points, centroids, norms)

    @custom_vmap
    def call(pts, cents, nrm):
        return lloyd_assign_tiled_pallas(pts, nrm, cents, block_n=bn,
                                         tps=tps, interpret=interpret)

    @call.def_vmap
    def _rule(axis_size, in_batched, pts, cents, nrm):
        pts = _ensure_batched(pts, in_batched[0], axis_size)
        cents = _ensure_batched(cents, in_batched[1], axis_size)
        nrm = _ensure_batched(nrm, in_batched[2], axis_size)
        out = lloyd_assign_tiled_batched_pallas(pts, nrm, cents, block_n=bn,
                                                tps=tps, interpret=interpret)
        return out, (True,) * 6

    return call(points, centroids, norms)


def lloyd_assign_gated(points: jax.Array, centroids: jax.Array,
                       norms: jax.Array, delta: jax.Array,
                       thresh: jax.Array, absorb: jax.Array,
                       prev_assign: jax.Array, prev_min_d2: jax.Array,
                       prev_lb: jax.Array, prev_partials: jax.Array,
                       prev_gaps: jax.Array, prev_super_sums: jax.Array,
                       prev_super_counts: jax.Array, active: jax.Array, *,
                       block_n: int, tps: int | None = None,
                       interpret: bool | None = None):
    """Bound-gated assignment half-step (two-level exact Lloyd pruning).

    ``active`` is the (n_tiles,) bool mask from
    `core.bounds.assign_active_tiles`; it is EXPANDED to whole super-tiles
    here (the hierarchical accumulators alias at super granularity — see
    `core.bounds.expand_active_supers`) and compacted into the
    scalar-prefetched index map, so skipped tiles are neither fetched nor
    computed and all of their outputs keep the previous iteration's
    (bitwise-identical) values. ``delta``/``thresh``/``absorb`` (from
    `core.bounds.assign_point_scalars`) drive the per-point Hamerly prune
    inside computed tiles. Returns the `lloyd_assign_tiled` tuple plus
    (lb (n,), pruned (n_tiles,), skipped ()). ``block_n`` is required: it
    must match the tile height of the carried bound state. Under `jax.vmap`
    this dispatches to the gated batch-grid kernel with per-problem
    expansion + compaction."""
    from repro.core import bounds as bnd

    _check_forced()
    n, d = points.shape
    if interpret is None:
        interpret = default_interpret()
    centroids = centroids.astype(points.dtype)
    norms = norms.astype(jnp.float32)
    grid = -(-n // block_n)
    tps = bnd.tiles_per_super(grid, tps)
    active = bnd.expand_active_supers(active, tps)
    ids, n_active = bnd.compact_ids(active)
    skipped = (grid - n_active).astype(jnp.int32)

    @custom_vmap
    def call(pts, cents, nrm, dl, th, ab, pa, pmd, plb, pp, pg, pss, psc,
             ids_, nact):
        meta = jnp.stack([jnp.full((), n, jnp.int32), nact.astype(jnp.int32)])
        return lloyd_assign_gated_pallas(
            pts, nrm, cents, dl, th, ab, pa, pmd, plb, pp, pg, pss, psc,
            ids_, meta, block_n=block_n, tps=tps, interpret=interpret)

    @call.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [_ensure_batched(a, b, axis_size)
                for a, b in zip(args, in_batched)]
        (pts, cents, nrm, dl, th, ab, pa, pmd, plb, pp, pg, pss, psc,
         ids_, nact) = args
        out = lloyd_assign_gated_batched_pallas(
            pts, nrm, cents, dl, th, ab, pa, pmd, plb, pp, pg, pss, psc,
            ids_, nact, block_n=block_n, tps=tps, interpret=interpret)
        return out, (True,) * 8

    out = call(points, centroids, norms, delta.astype(jnp.float32),
               thresh.astype(jnp.float32), absorb.astype(jnp.float32),
               prev_assign, prev_min_d2, prev_lb, prev_partials, prev_gaps,
               prev_super_sums, prev_super_counts, ids, n_active)
    return out + (skipped,)


def ivf_scan(queries: jax.Array, points: jax.Array, norms: jax.Array,
             centers: jax.Array, radii: jax.Array, ids: jax.Array,
             n_active: jax.Array, *, k: int, block_n: int,
             gate: bool = True, interpret: bool | None = None):
    """Batched gated cluster-local exact scan (IVF serving's inner loop).

    ``ids``/``n_active`` are the per-query compacted probed-tile maps
    (`core.bounds.compact_ids` over the probed-list tile coverage);
    ``centers``/``radii`` the prologue's ball summaries at the SAME
    ``block_n``. Already batched over queries by its grid, so no
    custom_vmap rule is needed. Returns (dists (Q, k) fp32, rows (Q, k)
    int32 into the sorted layout, gate_skipped (Q,) int32)."""
    from repro.kernels.ivf_scan import ivf_scan_pallas

    _check_forced()
    if interpret is None:
        interpret = default_interpret()
    return ivf_scan_pallas(queries, points, norms.astype(jnp.float32),
                           centers, radii, ids, n_active, k=k,
                           block_n=block_n, gate=gate, interpret=interpret)


def ivf_adc_scan(queries: jax.Array, lut: jax.Array, qdots: jax.Array,
                 codes: jax.Array, labels: jax.Array, u: jax.Array,
                 centers: jax.Array, radii: jax.Array, ids: jax.Array,
                 n_active: jax.Array, *, k: int, block_n: int,
                 gate: bool = True, interpret: bool | None = None):
    """Batched gated PQ/ADC scan: per-query LUT + routing dots against
    streamed uint8 codes (n_sub bytes/row instead of 4d). ``centers``/
    ``radii`` must be the balls over the RECONSTRUCTED rows so the gate is
    exact for ADC scores. Same return triple as :func:`ivf_scan`."""
    from repro.kernels.ivf_scan import ivf_adc_scan_pallas

    _check_forced()
    if interpret is None:
        interpret = default_interpret()
    return ivf_adc_scan_pallas(queries, lut, qdots, codes, labels,
                               u.astype(jnp.float32), centers, radii, ids,
                               n_active, k=k, block_n=block_n, gate=gate,
                               interpret=interpret)
