"""Fused k-means++ seeding-round kernels (the paper's hot spot, TPU-native).

One seeding round updates every point's D^2 against the newest centroid(s) and
produces the normalization term sum(D^2).

CUDA (paper)                         ->  TPU (this kernel)
---------------------------------------------------------------------------
1 thread per point, 1024/block       ->  grid over (block_n, d) point tiles;
                                         the 8x128 VPU lanes are the threads
centroids in CONSTANT memory         ->  centroid block VMEM-RESIDENT across
(broadcast cache)                        all grid steps (index_map -> (0, 0))
points in TEXTURE memory             ->  points streamed HBM->VMEM by the
(read-only, cached, spatial)             Pallas pipeline (double-buffered),
                                         read exactly ONCE (fused pass)
thrust::reduce for sum(D^2)          ->  per-tile partial sums accumulated
                                         on-chip; final tiny jnp.sum outside

Three bandwidth/FLOP optimizations compose on top of that mapping:

* **norm caching** — ``||x||^2`` is computed ONCE per dataset (the prologue)
  and streamed as an extra fp32 input, dropping d FLOPs/point/round from
  every round kernel.
* **mixed-precision streaming** — the point tiles and centroid block keep
  their input dtype all the way into the MXU (`dot_general` with
  ``preferred_element_type=f32``), so bf16 inputs stream at half the HBM
  bytes with fp32 accumulation and fp32 cached norms.
* **exact tile skipping** — the gated variants take a scalar-prefetched
  compacted active-tile index map (`core.bounds.compact_ids`): grid step i
  streams tile ``ids[i]``; steps past ``n_active`` revisit the last active
  tile (already VMEM-resident, no HBM fetch) and are compute-gated off by
  ``pl.when``. Skipped tiles are neither computed nor fetched — their
  ``min_d2`` / partial / tile-max outputs keep the previous round's values
  via ``input_output_aliases``, which is exact (see ``core.bounds``).

The matmul form ``||x||^2 - 2 x.c + ||c||^2`` puts the inner product on the
MXU (d up to 4096 in our integrations vs d=2 in the paper's figures); it
lives in ``core.distance``, shared with the fused backend, and its dots run
at ``Precision.HIGHEST``: the TPU's default fp32 matmul rounds its operands
to bf16, whose error the cancellation in the matmul form would amplify into
wrong assignments.

Layout (what Mosaic, the TPU kernel compiler, accepts): a block's last two
dims must be multiples of (8, 128) or span the array, and vectors inside a
kernel are 2-D. So inside the kernels

* per-point vectors (norms, min_d2, center_d, labels) are lane-dense rows:
  ``(1, n_pad)`` arrays streamed in ``(1, block_n)`` blocks;
* a tile's distances are ``(k, block_n)`` — centroids on sublanes, points
  on lanes — so every per-point reduction over centroids yields a row;
* per-tile scalars (partials, tile max, gaps, pruned counts, gate inputs)
  are ``(n_tiles, 1, LANES)`` lane-broadcast blocks, one per tile;
* scalars the whole launch shares (the valid row count, the compacted tile
  map) ride the scalar-prefetch channel into SMEM.

The jitted wrappers keep the callers' ``(n,)``/``(n_tiles,)`` shapes and do
the padding and reshaping outside the kernels. The round wrappers take the
valid row count ``n`` from their per-point vectors (``min_d2`` / ``norms``),
not from the points: the engine's prologue pads the ``(n, d)`` points to a
tile multiple ONCE per job (``core.bounds.RoundCache.rows``, built by
`pad_rows_blocked`) and every round streams those rows as they are
(`pad_rows` is then a no-op); callers with plain ``(n, d)`` points get the
pad on each call.

Small d: a ``(block_n, d)`` row tile pads d to 128 lanes (2 of 128 lanes
are points at d=2), so the plain and gated round kernels have
coordinate-major twins (``distance_min_update_cols*_pallas``) that stream
``(d, block_n)`` tiles of the ``(d, n_pad)`` points (``RoundCache.cols``),
the points on lanes and d padded only to 8 sublanes. The math, the tile
height and every other operand are the row kernels'; ``kernels.ops`` picks
the layout from d (`ops.streams_cols`).

Raw kernels take ``interpret`` EXPLICITLY: ``kernels.ops`` is the single
place the on-TPU/off-TPU default is chosen — calling a raw kernel without it
is a TypeError, not a silent interpreted run on real hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the ONE definition of the fine-level (per-point) seeding prune test — the
# pure-JAX gate model in core.engine evaluates the same function, so model
# and kernel prune decisions share a single source of truth
from repro.core.bounds import seed_point_prune as _seed_point_prune
# the cached-norm matmul-form D^2 the fused backend evaluates too (the
# fused==pallas bitwise parity hangs off it)
from repro.core.distance import tile_d2_min, tile_d2_min_cols

LANES = 128                      # one lane-row per per-tile scalar block


# ---------------------------------------------------------------------------
# layout helpers shared by every kernel module
# ---------------------------------------------------------------------------


def compiler_params():
    """Mosaic compiler parameters: the scoped-VMEM limit that goes with the
    tile budget `ops.pick_block_n` sizes for (``kernels.ops`` owns both)."""
    from repro.kernels.ops import vmem_limit_bytes
    return pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes())


def pallas_call(kernel, *, out_shape, **kwargs):
    """``pl.pallas_call`` whose outputs vary over the mesh axes its operands
    vary over: inside ``shard_map`` (``check_vma``) the output types must
    say so, and a kernel's outputs are per-shard wherever its inputs are."""
    def call(*operands):
        vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
        shapes = [jax.ShapeDtypeStruct(o.shape, o.dtype, vma=vma)
                  for o in jax.tree.leaves(out_shape)]
        if isinstance(out_shape, jax.ShapeDtypeStruct):
            shapes = shapes[0]
        return pl.pallas_call(kernel, out_shape=shapes, **kwargs)(*operands)
    return call


def pad_rows(points: jax.Array, n_pad: int) -> jax.Array:
    """(..., r, d) point rows -> at least ``n_pad`` rows, zero-filled.

    Rows that already reach ``n_pad`` (the prologue's once-padded
    ``RoundCache.rows``) pass through untouched: rows past ``n_pad`` are
    whole tiles the grid never visits, and pad rows are masked by the
    kernels' valid-row count either way."""
    extra = n_pad - points.shape[-2]
    if extra <= 0:
        return points
    return jnp.pad(points, [(0, 0)] * (points.ndim - 2) + [(0, extra), (0, 0)])


@functools.partial(jax.jit, static_argnames=("n_pad", "block"))
def pad_rows_blocked(points: jax.Array, n_pad: int, block: int) -> jax.Array:
    """`pad_rows` of (n, d) points, copied ``block`` rows at a time into
    the zero-filled (n_pad, d) rows: the points are read as they lie. The
    kernels stream row-major rows, while a chip's default layout for some
    (n, d) shapes is column-major ((2,025,000, 784) on a v5e); a plain pad
    of such points makes XLA relayout all of them first, and the relayout
    copy, the padded rows and the points do not fit one chip together. Here
    each block is relaid out on its own. The last block ends at row n."""
    n, d = points.shape
    if n >= n_pad or n < block:
        return pad_rows(points, n_pad)
    out = jnp.zeros((n_pad, d), points.dtype)
    vma = tuple(jax.typeof(points).vma)
    if vma:     # inside shard_map: the rows vary over the mesh as the points do
        out = jax.lax.pcast(out, vma, to="varying")

    def copy(i, rows):
        start = jnp.minimum(i * block, n - block)
        return jax.lax.dynamic_update_slice(
            rows, jax.lax.dynamic_slice(points, (start, 0), (block, d)),
            (start, 0))

    return jax.lax.fori_loop(0, -(-n // block), copy, out)


def as_row(v: jax.Array, pad: int, fill=0.0) -> jax.Array:
    """(..., n) per-point vector -> (..., 1, n + pad) lane-dense row."""
    v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)],
                constant_values=fill)
    return v[..., None, :]


def as_lanes(v: jax.Array) -> jax.Array:
    """(..., n_tiles) per-tile scalars -> (..., n_tiles, 1, LANES)."""
    v = v.astype(jnp.float32)
    return jnp.broadcast_to(v[..., None, None], v.shape + (1, LANES))


def from_lanes(a: jax.Array) -> jax.Array:
    """Inverse of `as_lanes`: (..., n_tiles, 1, LANES) -> (..., n_tiles)."""
    return a[..., 0, 0]


def lane_spec(index_map) -> pl.BlockSpec:
    """One tile's (1, 1, LANES) per-tile-scalar block."""
    return pl.BlockSpec((1, 1, LANES), index_map)


def lane_scalar(v: jax.Array) -> jax.Array:
    """A (1, LANES) lane-broadcast block -> its (1, 1) value."""
    return jnp.max(v, axis=1, keepdims=True)


def lane_fill(v: jax.Array, dtype=jnp.float32) -> jax.Array:
    """A (1, 1) value (or scalar) -> the (1, LANES) block that stores it."""
    return jnp.broadcast_to(jnp.asarray(v, dtype), (1, LANES))


def row_ids(t, block_n: int) -> jax.Array:
    """(1, block_n) global row ids of tile ``t``."""
    return t * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)


def pad_cols(cols: jax.Array, n_pad: int) -> jax.Array:
    """(d, r) coordinate-major points -> at least ``n_pad`` columns,
    zero-filled: `pad_rows` of the (d, n) layout the small-d rounds stream
    (``RoundCache.cols``, built once per call from ``points.T``)."""
    extra = n_pad - cols.shape[-1]
    if extra <= 0:
        return cols
    return jnp.pad(cols, ((0, 0), (0, extra)))


def _point_stream(points, n_pad: int, block_n: int, tile, cols: bool):
    """The points a round streams, and the BlockSpec of grid step i's tile
    ``tile(i, *prefetch)``: (block_n, d) tiles of (n_pad, d) rows, or with
    ``cols`` (d, block_n) tiles of the coordinate-major (d, n_pad) points,
    whose d pads to 8 sublanes where a row tile pads it to 128 lanes."""
    if cols:
        return pad_cols(points, n_pad), pl.BlockSpec(
            (points.shape[0], block_n), lambda i, *s: (0, tile(i, *s)))
    return pad_rows(points, n_pad), pl.BlockSpec(
        (block_n, points.shape[1]), lambda i, *s: (tile(i, *s), 0))


def _round_kernel(nv_ref, pts_ref, norms_ref, cents_ref, md_ref,
                  out_md_ref, partial_ref, *, block_n: int, d2_min):
    """Grid step i processes point rows [i*block_n, (i+1)*block_n);
    ``d2_min`` is `tile_d2_min` or, on coordinate-major tiles, its twin."""
    i = pl.program_id(0)
    md = md_ref[...].astype(jnp.float32)           # (1, block_n)
    new_md = jnp.minimum(md, d2_min(pts_ref[...], cents_ref[...],
                                    norms_ref[...]))
    # mask padded tail rows (they must not contribute to the reduction)
    new_md = jnp.where(row_ids(i, block_n) < nv_ref[0], new_md, 0.0)
    out_md_ref[...] = new_md
    partial_ref[0] = lane_fill(jnp.sum(new_md, axis=1, keepdims=True))


def _round_call(points, norms, centroids, min_d2, *, block_n: int,
                resident: bool, interpret: bool, cols: bool):
    """The plain round over row tiles or, with ``cols``, coordinate-major
    tiles (see `distance_min_update_pallas` / `_cols_pallas`)."""
    n, (k_new, d) = min_d2.shape[0], centroids.shape
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    pts, pts_spec = _point_stream(points, n + pad, block_n,
                                  lambda i, nv: i, cols)
    nrm = as_row(norms.astype(jnp.float32), pad)
    md = as_row(min_d2.astype(jnp.float32), pad, jnp.inf)

    if resident:
        cent_spec = pl.BlockSpec((k_new, d), lambda i, nv: (0, 0))
    else:
        # index_map depends on i mod 1 == 0 block but non-constant lambda forces
        # a refetch each grid step (two-pass global-memory behaviour).
        cent_spec = pl.BlockSpec((k_new, d), lambda i, nv: (0, i * 0))
    row = pl.BlockSpec((1, block_n), lambda i, nv: (0, i))

    out_md, partials = pallas_call(
        functools.partial(_round_kernel, block_n=block_n,
                          d2_min=tile_d2_min_cols if cols else tile_d2_min),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                  # n_valid
            grid=(grid,),
            in_specs=[
                pts_spec,                           # points
                row,                                # cached ||x||^2
                cent_spec,                          # centroids
                row,                                # min_d2 in
            ],
            out_specs=[
                row,                                # min_d2 out
                lane_spec(lambda i, nv: (i, 0, 0)),  # per-tile partial
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((1, n + pad), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, LANES), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(jnp.array([n], jnp.int32), pts, nrm, centroids, md)
    return out_md[0, :n], from_lanes(partials)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "resident", "interpret"))
def distance_min_update_pallas(points: jax.Array, norms: jax.Array,
                               centroids: jax.Array, min_d2: jax.Array, *,
                               block_n: int, resident: bool, interpret: bool):
    """Returns (new_min_d2 (n,), partials (grid,)). sum(partials) == sum(D^2).

    ``norms`` is the cached fp32 ``||x||^2`` (n,) from the prologue.
    ``points`` are the (n, d) points or rows already padded past n (see
    `pad_rows`); n is ``min_d2``'s length.
    resident=True keeps the centroid block pinned in VMEM across grid steps
    (constant-memory analogue). resident=False re-indexes the centroid block
    every step, modelling the global-memory variant's repeated fetch.
    """
    return _round_call(points, norms, centroids, min_d2, block_n=block_n,
                       resident=resident, interpret=interpret, cols=False)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "resident", "interpret"))
def distance_min_update_cols_pallas(cols: jax.Array, norms: jax.Array,
                                    centroids: jax.Array, min_d2: jax.Array,
                                    *, block_n: int, resident: bool,
                                    interpret: bool):
    """`distance_min_update_pallas` on coordinate-major points: ``cols`` is
    (d, n) or already padded past n (`pad_cols`), streamed in (d, block_n)
    tiles with the points on lanes. Same math, tile and outputs; at small d
    a tile's bytes are the points', not 128 lanes of padding."""
    return _round_call(cols, norms, centroids, min_d2, block_n=block_n,
                       resident=resident, interpret=interpret, cols=True)


# ---------------------------------------------------------------------------
# bound-gated variant (exact tile skipping via scalar-prefetched index map)
# ---------------------------------------------------------------------------


def _round_kernel_gated(ids_ref, meta_ref, pts_ref, norms_ref, cents_ref,
                        md_ref, cdist_ref, dc_ref, margin_ref, pp_ref,
                        ptm_ref, pz_ref, out_md_ref, partial_ref,
                        tmax_ref, pruned_ref, *, block_n: int, d2_min):
    """Grid step i streams tile ``ids[i]``; steps >= n_active are no-ops.

    ``meta`` = [n_valid, n_active]. ``pp_ref``/``ptm_ref``/``pz_ref``
    (previous partials / tile-max / a zeros buffer) are never read — they
    exist to carry the aliased buffers the skipped tiles' outputs fall back
    to. Inside an active tile the FINE level of the bound fires per point:
    rows whose carried ``min_d2`` provably cannot improve (``(dc −
    center_d)² >= md`` with margin — see ``core.bounds.seed_point_prune``)
    keep it verbatim, a value-noop by construction that the ``pruned``
    output counts (the modelled per-point FLOP saving). ``d2_min`` is
    `tile_d2_min` or, on coordinate-major tiles, its twin.
    """
    del pp_ref, ptm_ref, pz_ref
    i = pl.program_id(0)

    @pl.when(i < meta_ref[1])
    def _compute():
        t = ids_ref[i]                             # the REAL tile id
        md = md_ref[...].astype(jnp.float32)
        valid = row_ids(t, block_n) < meta_ref[0]
        prune = valid & _seed_point_prune(md, cdist_ref[...],
                                          lane_scalar(dc_ref[0]),
                                          lane_scalar(margin_ref[0]))
        upd = jnp.minimum(md, d2_min(pts_ref[...], cents_ref[...],
                                     norms_ref[...]))
        new_md = jnp.where(prune, md, upd)
        new_md = jnp.where(valid, new_md, 0.0)

        out_md_ref[...] = new_md
        partial_ref[0] = lane_fill(jnp.sum(new_md, axis=1, keepdims=True))
        # bound state for next round
        tmax_ref[0] = lane_fill(jnp.max(new_md, axis=1, keepdims=True))
        pruned_ref[0] = lane_fill(jnp.sum(prune.astype(jnp.float32), axis=1,
                                          keepdims=True))


def _gated_call(points, norms, centroids, min_d2, center_d, dc, margin,
                prev_partials, prev_tile_max, ids, meta, *, block_n: int,
                resident: bool, interpret: bool, cols: bool):
    """The gated round over row tiles or, with ``cols``, coordinate-major
    tiles (see `distance_min_update_gated_pallas` / `_cols_gated_pallas`)."""
    n, (k_new, d) = min_d2.shape[0], centroids.shape
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    pts, pts_spec = _point_stream(points, n + pad, block_n,
                                  lambda i, ids, meta: ids[i], cols)
    nrm = as_row(norms.astype(jnp.float32), pad)
    md = as_row(min_d2.astype(jnp.float32), pad, jnp.inf)
    cd = as_row(center_d.astype(jnp.float32), pad)

    if resident:
        cent_spec = pl.BlockSpec((k_new, d), lambda i, ids, meta: (0, 0))
    else:
        cent_spec = pl.BlockSpec((k_new, d), lambda i, ids, meta: (0, i * 0))
    row = pl.BlockSpec((1, block_n), lambda i, ids, meta: (0, ids[i]))
    one = lane_spec(lambda i, ids, meta: (ids[i], 0, 0))
    # never-read aliased carries: ANY memory space, no per-step DMA
    carry = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                      # ids, meta
        grid=(grid,),
        in_specs=[
            pts_spec,
            row,                                    # norms
            cent_spec,
            row,                                    # min_d2
            row,                                    # center_d
            one,                                    # dc
            one,                                    # margin
            carry, carry, carry,                    # prev part/tmax, zeros
        ],
        out_specs=[row, one, one, one],
    )
    out_md, partials, tile_max, pruned = pallas_call(
        functools.partial(_round_kernel_gated, block_n=block_n,
                          d2_min=tile_d2_min_cols if cols else tile_d2_min),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, n + pad), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, LANES), jnp.float32),
        ],
        # skipped tiles reuse their prior min_d2 / partials / tile-max and
        # report zero pruned points (the donated zeros buffer)
        input_output_aliases={5: 0, 9: 1, 10: 2, 11: 3},
        compiler_params=compiler_params(),
        interpret=interpret,
    )(ids.astype(jnp.int32), meta.astype(jnp.int32), pts, nrm, centroids,
      md, cd, as_lanes(dc), as_lanes(margin), as_lanes(prev_partials),
      as_lanes(prev_tile_max), jnp.zeros((grid, 1, LANES), jnp.float32))
    return (out_md[0, :n], from_lanes(partials), from_lanes(tile_max),
            from_lanes(pruned))


@functools.partial(jax.jit,
                   static_argnames=("block_n", "resident", "interpret"))
def distance_min_update_gated_pallas(points: jax.Array, norms: jax.Array,
                                     centroids: jax.Array, min_d2: jax.Array,
                                     center_d: jax.Array, dc: jax.Array,
                                     margin: jax.Array,
                                     prev_partials: jax.Array,
                                     prev_tile_max: jax.Array,
                                     ids: jax.Array, meta: jax.Array, *,
                                     block_n: int, resident: bool,
                                     interpret: bool):
    """Bound-gated seeding round. Returns (new_min_d2 (n,), partials (grid,),
    tile_max (grid,), pruned (grid,)).

    ``ids``/``meta=[n_valid, n_active]`` come from `core.bounds.compact_ids`:
    only the first n_active grid steps fetch + compute (each visiting active
    tile ids[i]); every output block of a skipped tile keeps the aliased
    previous-round value, which the bound proves is bitwise what a full
    recompute would write. ``center_d``/``dc``/``margin`` are the fine-level
    inputs from the prologue and `core.bounds.seed_gate`; ``pruned`` counts
    per-point short-circuits per tile (zero for skipped tiles via a donated
    zeros buffer). ``points`` may be the prologue's once-padded rows, which
    stream as they are; n is ``min_d2``'s length.
    """
    return _gated_call(points, norms, centroids, min_d2, center_d, dc, margin,
                       prev_partials, prev_tile_max, ids, meta,
                       block_n=block_n, resident=resident,
                       interpret=interpret, cols=False)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "resident", "interpret"))
def distance_min_update_cols_gated_pallas(
        cols: jax.Array, norms: jax.Array, centroids: jax.Array,
        min_d2: jax.Array, center_d: jax.Array, dc: jax.Array,
        margin: jax.Array, prev_partials: jax.Array,
        prev_tile_max: jax.Array, ids: jax.Array, meta: jax.Array, *,
        block_n: int, resident: bool, interpret: bool):
    """`distance_min_update_gated_pallas` on coordinate-major points
    (``cols`` (d, n) or padded past n, see `pad_cols`): active tile ids[i]
    streams as a (d, block_n) block. Same gate, prune, aliases and outputs."""
    return _gated_call(cols, norms, centroids, min_d2, center_d, dc, margin,
                       prev_partials, prev_tile_max, ids, meta,
                       block_n=block_n, resident=resident,
                       interpret=interpret, cols=True)


# ---------------------------------------------------------------------------
# single-row gather + distance: the rejection sampler's exact-p evaluation
# ---------------------------------------------------------------------------

_ROW_BLOCK = 8                   # the row is fetched inside its 8-row tile


def _row_min_d2_kernel(meta_ref, rows_ref, cents_ref, out_ref):
    """One grid step: D^2 of the prefetched row to the nearest of the first
    ``meta[1]`` centroid slots (the rejection loop's pending buffer; slots
    past the count are +inf-masked, so an empty pending block yields +inf and
    ``min(q, +inf) == q`` keeps the accept ratio bitwise at 1).

    ``meta = [row_idx, count]`` rides the scalar-prefetch channel: the row
    index steers the DMA of the aligned 8-row block holding the row — the
    kernel touches O(d) bytes of the dataset, not a tile — which is the
    whole point of the rejection sampler (per-proposal work independent of
    n). The row is picked out of its block by a one-hot select (exact: the
    other rows contribute zeros)."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (_ROW_BLOCK, 1), 0)
    pick = sub == meta_ref[0] % _ROW_BLOCK
    x = jnp.sum(jnp.where(pick, rows_ref[...].astype(jnp.float32), 0.0),
                axis=0, keepdims=True)             # (1, d)
    c = cents_ref[...].astype(jnp.float32)         # (m, d)
    diff = x - c                                   # broadcast over slots
    d2 = jnp.sum(diff * diff, axis=1, keepdims=True)     # (m, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    best = jnp.min(jnp.where(slot < meta_ref[1], d2, jnp.inf), axis=0,
                   keepdims=True)
    out_ref[...] = lane_fill(best)


@functools.partial(jax.jit, static_argnames=("interpret",))
def row_min_d2_pallas(points: jax.Array, idx: jax.Array,
                      centroids: jax.Array, count: jax.Array, *,
                      interpret: bool) -> jax.Array:
    """Scalar fp32 D^2 of row ``idx`` to the nearest of ``centroids[:count]``.

    The diff-square form (not the matmul/cached-norm form): a single row has
    no MXU tile to win back, and the rejection sampler's exactness needs only
    p <= q — which ``min`` with the stale weight enforces regardless of the
    fp form (see kernels.ref.row_min_d2_ref, the bitwise oracle)."""
    n, d = points.shape
    m = centroids.shape[0]
    meta = jnp.stack([idx.astype(jnp.int32), count.astype(jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                      # meta = [row, count]
        grid=(1,),
        in_specs=[
            pl.BlockSpec((_ROW_BLOCK, d),
                         lambda i, meta: (meta[0] // _ROW_BLOCK, 0)),
            pl.BlockSpec((m, d), lambda i, meta: (0, 0)),        # pending
        ],
        out_specs=pl.BlockSpec((1, LANES), lambda i, meta: (0, 0)),
    )
    pad = (-n) % _ROW_BLOCK
    out = pallas_call(
        _row_min_d2_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, LANES), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(meta, jnp.pad(points, ((0, pad), (0, 0))),
      centroids.astype(points.dtype))
    return out[0, 0]


# ---------------------------------------------------------------------------
# per-tile envelope cap: the movement-tightened rejection envelope's
# (n_tiles, pending) pass over tile summaries — never rows
# ---------------------------------------------------------------------------


def _tile_cap_kernel(meta_ref, cents_ref, radii_ref, pend_ref, out_ref):
    """One grid step: per-tile envelope caps from the tile BALLS only.

    ``meta = [count]`` rides the scalar-prefetch channel. For every tile ball
    (center_t, r_t) the triangle inequality gives ``d(x_i, c) <= d(center_t,
    c) + r_t`` for each of its rows, so ``(min_c d(center_t, c) + r_t)^2``
    over the first ``count`` pending slots dominates every row's CURRENT
    min_d2 — the Raff bound the rejection sampler shrinks its stale envelope
    with between refreshes. Slots >= count are +inf-masked; count == 0
    yields +inf everywhere (a tightening no-op, which is what keeps
    refresh_block=1 bitwise on the flat path).

    Tiles ride the lanes: the (m, n_tiles) squared distances accumulate one
    coordinate at a time from the transposed centers."""
    ct = cents_ref[...].astype(jnp.float32).T      # (d, n_tiles)
    p = pend_ref[...].astype(jnp.float32)          # (m, d)
    d2 = jnp.zeros((p.shape[0], ct.shape[1]), jnp.float32)
    for j in range(p.shape[1]):
        diff = ct[j:j + 1, :] - p[:, j:j + 1]      # (m, n_tiles)
        d2 = d2 + diff * diff
    slot = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    dc2 = jnp.min(jnp.where(slot < meta_ref[0], d2, jnp.inf), axis=0,
                  keepdims=True)                   # (1, n_tiles)
    cap = (jnp.sqrt(dc2) + radii_ref[...].astype(jnp.float32)) ** 2
    out_ref[...] = jnp.where(meta_ref[0] > 0, cap, jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tile_cap_pallas(centers: jax.Array, radii: jax.Array,
                    pending: jax.Array, count: jax.Array, *,
                    interpret: bool) -> jax.Array:
    """(n_tiles,) fp32 per-tile envelope caps ``(dc_t + r_t)^2`` against
    ``pending[:count]`` — O(n_tiles * count * d) over tile summaries (the
    whole point: no row is touched; see kernels.ref.tile_cap_ref)."""
    t, d = centers.shape
    m = pending.shape[0]
    meta = count.astype(jnp.int32)[None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                      # meta = [count]
        grid=(1,),
        in_specs=[
            pl.BlockSpec((t, d), lambda i, meta: (0, 0)),  # tile centers
            pl.BlockSpec((1, t), lambda i, meta: (0, 0)),  # tile radii
            pl.BlockSpec((m, d), lambda i, meta: (0, 0)),  # pending block
        ],
        out_specs=pl.BlockSpec((1, t), lambda i, meta: (0, 0)),
    )
    out = pallas_call(
        _tile_cap_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, t), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(meta, centers, radii.astype(jnp.float32)[None, :], pending)
    return out[0]


# ---------------------------------------------------------------------------
# prologue kernel: tile centroid-balls, ONE pass over the data
# ---------------------------------------------------------------------------


def _prologue_kernel(nv_ref, pts_ref, center_ref, radius_ref, cdist_ref, *,
                     block_n: int):
    i = pl.program_id(0)
    x = pts_ref[...].astype(jnp.float32)           # (block_n, d)
    valid = row_ids(i, block_n) < nv_ref[0]        # (1, block_n)
    valid_col = (i * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, 1), 0)) < nv_ref[0]   # (block_n, 1)

    cnt = jnp.sum(valid.astype(jnp.float32))
    xm = jnp.where(valid_col, x, 0.0)
    ctr = jnp.sum(xm, axis=0, keepdims=True) / jnp.maximum(cnt, 1.0)
    center_ref[0] = ctr                            # (1, d)
    dt = (x - ctr).T                               # (d, block_n)
    d2c = jnp.sum(dt * dt, axis=0, keepdims=True)  # (1, block_n)
    radius_ref[0] = lane_fill(jnp.sqrt(jnp.max(jnp.where(valid, d2c, 0.0),
                                               axis=1, keepdims=True)))
    # per-point distance to the ball center — the fine-level seeding bound
    cdist_ref[...] = jnp.where(valid, jnp.sqrt(d2c), 0.0)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def seed_prologue_pallas(points: jax.Array, *, block_n: int, interpret: bool,
                         rows: jax.Array | None = None):
    """Everything the round kernels cache: (norms (n,) fp32, tile centers
    (grid, d) fp32, tile radii (grid,) fp32, center_d (n,) fp32 — each
    point's distance to its tile ball center, the per-point seeding bound).

    The norms come from `core.bounds.point_norms`, the one definition every
    backend caches (they must agree bitwise across backends); the kernel
    makes the single streaming pass for the tile balls. ``rows`` are the
    same points already padded to a tile multiple (the engine's prologue
    pads once and hands the rows on to every round); without them the pad
    happens here."""
    from repro.core.bounds import point_norms

    n, d = points.shape
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    pts = pad_rows(points if rows is None else rows, n + pad)

    centers, radii, center_d = pallas_call(
        functools.partial(_prologue_kernel, block_n=block_n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                  # n_valid
            grid=(grid,),
            in_specs=[pl.BlockSpec((block_n, d), lambda i, nv: (i, 0))],
            out_specs=[
                pl.BlockSpec((1, 1, d), lambda i, nv: (i, 0, 0)),
                lane_spec(lambda i, nv: (i, 0, 0)),
                pl.BlockSpec((1, block_n), lambda i, nv: (0, i)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((grid, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, n + pad), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(jnp.array([n], jnp.int32), pts)
    return (point_norms(points), centers[:, 0, :], from_lanes(radii),
            center_d[0, :n])


# ---------------------------------------------------------------------------
# batch-grid variants (multi-tenant clustering: B independent problems)
# ---------------------------------------------------------------------------


def _round_kernel_batched(nv_ref, pts_ref, norms_ref, cents_ref, md_ref,
                          out_md_ref, partial_ref, *, block_n: int):
    """Grid step (b, i) processes rows [i*block_n, (i+1)*block_n) of problem b.

    Same math as `_round_kernel`; the leading singleton axis is problem b's
    block. The centroid block is re-fetched per problem (it differs per b) but
    stays resident across the inner i steps."""
    i = pl.program_id(1)
    md = md_ref[0].astype(jnp.float32)             # (1, block_n)
    new_md = jnp.minimum(md, tile_d2_min(pts_ref[0], cents_ref[0],
                                         norms_ref[0]))
    new_md = jnp.where(row_ids(i, block_n) < nv_ref[0], new_md, 0.0)
    out_md_ref[0] = new_md
    partial_ref[0, 0] = lane_fill(jnp.sum(new_md, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def distance_min_update_batched_pallas(points: jax.Array, norms: jax.Array,
                                       centroids: jax.Array,
                                       min_d2: jax.Array, *,
                                       block_n: int, interpret: bool):
    """Batched seeding round over B independent problems in ONE launch.

    points (B, n, d), norms (B, n), centroids (B, k_new, d), min_d2 (B, n) ->
    (new_min_d2 (B, n), partials (B, n_tiles)). Row b of the outputs is
    bitwise what `distance_min_update_pallas` computes for problem b — the
    grid just gains a leading batch dimension, so the many-tenant path pays
    one kernel launch instead of B. n is ``min_d2``'s length (the points may
    be once-padded rows)."""
    B, n, d = points.shape[0], min_d2.shape[1], points.shape[2]
    k_new = centroids.shape[1]
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    pts = pad_rows(points, n + pad)
    nrm = as_row(norms.astype(jnp.float32), pad)
    md = as_row(min_d2.astype(jnp.float32), pad, jnp.inf)
    row = pl.BlockSpec((1, 1, block_n), lambda b, i, nv: (b, 0, i))

    out_md, partials = pallas_call(
        functools.partial(_round_kernel_batched, block_n=block_n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                  # n_valid
            grid=(B, grid),
            in_specs=[
                pl.BlockSpec((1, block_n, d), lambda b, i, nv: (b, i, 0)),
                row,
                pl.BlockSpec((1, k_new, d), lambda b, i, nv: (b, 0, 0)),
                row,
            ],
            out_specs=[
                row,
                pl.BlockSpec((1, 1, 1, LANES), lambda b, i, nv: (b, i, 0, 0)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, n + pad), jnp.float32),
            jax.ShapeDtypeStruct((B, grid, 1, LANES), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(jnp.array([n], jnp.int32), pts, nrm, centroids, md)
    return out_md[:, 0, :n], from_lanes(partials)


def _round_kernel_gated_batched(ids_ref, nact_ref, nv_ref, pts_ref, norms_ref,
                                cents_ref, md_ref, cdist_ref, dc_ref,
                                margin_ref, pp_ref, ptm_ref, pz_ref,
                                out_md_ref, partial_ref, tmax_ref,
                                pruned_ref, *, block_n: int):
    """Grid step (b, i) streams tile ids[b, i] of problem b; steps past
    problem b's n_active are no-ops (per-problem compaction)."""
    del pp_ref, ptm_ref, pz_ref
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i < nact_ref[b])
    def _compute():
        t = ids_ref[b, i]
        md = md_ref[0].astype(jnp.float32)
        valid = row_ids(t, block_n) < nv_ref[0]
        prune = valid & _seed_point_prune(md, cdist_ref[0],
                                          lane_scalar(dc_ref[0, 0]),
                                          lane_scalar(margin_ref[0, 0]))
        upd = jnp.minimum(md, tile_d2_min(pts_ref[0], cents_ref[0],
                                          norms_ref[0]))
        new_md = jnp.where(prune, md, upd)
        new_md = jnp.where(valid, new_md, 0.0)

        out_md_ref[0] = new_md
        partial_ref[0, 0] = lane_fill(jnp.sum(new_md, axis=1, keepdims=True))
        tmax_ref[0, 0] = lane_fill(jnp.max(new_md, axis=1, keepdims=True))
        pruned_ref[0, 0] = lane_fill(jnp.sum(prune.astype(jnp.float32),
                                             axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def distance_min_update_gated_batched_pallas(
        points: jax.Array, norms: jax.Array, centroids: jax.Array,
        min_d2: jax.Array, center_d: jax.Array, dc: jax.Array,
        margin: jax.Array, prev_partials: jax.Array,
        prev_tile_max: jax.Array, ids: jax.Array, n_active: jax.Array, *,
        block_n: int, interpret: bool):
    """Batch-grid bound-gated round: (B, n, d) problems, per-problem compacted
    active-tile maps ids (B, n_tiles) / n_active (B,). Row b is bitwise
    `distance_min_update_gated_pallas` on problem b (n is ``min_d2``'s
    length: the points may be once-padded rows)."""
    B, n, d = points.shape[0], min_d2.shape[1], points.shape[2]
    k_new = centroids.shape[1]
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    pts = pad_rows(points, n + pad)
    nrm = as_row(norms.astype(jnp.float32), pad)
    md = as_row(min_d2.astype(jnp.float32), pad, jnp.inf)
    cd = as_row(center_d.astype(jnp.float32), pad)
    nv = jnp.array([n], jnp.int32)

    row = pl.BlockSpec((1, 1, block_n),
                       lambda b, i, ids, na, nv: (b, 0, ids[b, i]))
    one = pl.BlockSpec((1, 1, 1, LANES),
                       lambda b, i, ids, na, nv: (b, ids[b, i], 0, 0))
    carry = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                      # ids, n_active, n_valid
        grid=(B, grid),
        in_specs=[
            pl.BlockSpec((1, block_n, d),
                         lambda b, i, ids, na, nv: (b, ids[b, i], 0)),
            row,
            pl.BlockSpec((1, k_new, d), lambda b, i, ids, na, nv: (b, 0, 0)),
            row,
            row,                                    # c_d
            one, one,                               # dc, margin
            carry, carry, carry,                    # prev part/tmax, zeros
        ],
        out_specs=[row, one, one, one],
    )
    out_md, partials, tile_max, pruned = pallas_call(
        functools.partial(_round_kernel_gated_batched, block_n=block_n),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, n + pad), jnp.float32),
            jax.ShapeDtypeStruct((B, grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, grid, 1, LANES), jnp.float32),
        ],
        input_output_aliases={6: 0, 10: 1, 11: 2, 12: 3},
        compiler_params=compiler_params(),
        interpret=interpret,
    )(ids.astype(jnp.int32), n_active.astype(jnp.int32), nv, pts, nrm,
      centroids, md, cd, as_lanes(dc), as_lanes(margin),
      as_lanes(prev_partials), as_lanes(prev_tile_max),
      jnp.zeros((B, grid, 1, LANES), jnp.float32))
    return (out_md[:, 0, :n], from_lanes(partials), from_lanes(tile_max),
            from_lanes(pruned))
