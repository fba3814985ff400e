"""Gated cluster-local IVF scan kernels (batched top-k query serving).

A trained k-means model IS an inverted-file index: ``serve.ivf`` routes each
query to its top-``nprobe`` centroids and scans only those clusters' tiles.
The two kernels here are the scan: the grid streams a compacted
probed-tile id list through the scalar-prefetch channel — the same trick
as ``kmeans_distance``'s gated round kernel (`core.bounds.compact_ids`), so
tiles outside the probed lists are neither fetched nor computed; trailing
steps revisit the last probed tile (already VMEM-resident) and are
compute-gated off by ``pl.when``.

Two scoring paths share the scan skeleton:

* **exact** (`ivf_scan_pallas`) — matmul-form fp32 D^2 against the raw rows
  (cached ``||x||^2`` streamed like every round kernel);
* **PQ/ADC** (`ivf_adc_scan_pallas`) — distances to the PQ-RECONSTRUCTED
  rows ``x̂ = c_list + decode(code)``, assembled without ever
  materializing ``x̂``: ``‖q − x̂‖² = ‖q‖² − 2(q·c_list + q·r̂) + ‖x̂‖²``
  where ``q·r̂`` is a per-query inner-product LUT contracted against the
  uint8 codes via the one-hot-matmul MXU pattern of ``pq_decode``, and
  ``q·c_list`` reuses the routing dots through a one-hot over the streamed
  row labels. ``‖x̂‖²`` is a per-row build-time constant.

Layered on top, the per-tile triangle-inequality gate
(`core.bounds.ivf_gate_skip`): a probed tile whose ball provably cannot
beat the carried kth-best distance is skipped as a bitwise value-noop (the
ADC path gates against balls computed over the RECONSTRUCTED rows, so its
scores — true distances to x̂ — satisfy the same triangle bound). The fp32
blocked top-k ``(d2, row)``-lexicographic merge (`core.topk.merge_topk`)
is carried across tiles in VMEM scratch, making the scan bitwise equal to
a global brute-force top-k at ``nprobe == nlist``.

Layout follows ``kmeans_distance``: a tile's scores are a (1, block_n) row
(the query on the left of the MXU dot), tile balls are ``(1, 1, d)``
centers and lane-broadcast radii. One launch scans a chunk of
`QUERIES_PER_LAUNCH` queries over the ascending union of their probed
tiles, tile-major: each union tile is fetched once for the chunk (not once
per query — at full probe that is the whole index per query), and a
per-tile bitmask says which of the chunk's queries score it. Those maps sit
in SMEM (1 MiB on a v5e core) at two words per tile, whatever the number
of queries; the per-chunk launches run under ``lax.map``.

Raw kernels take ``interpret`` EXPLICITLY — ``kernels.ops`` chooses the
on-TPU/off-TPU default, as everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the ONE definition of the kth-distance ball gate and of the lexicographic
# blocked merge — the pure-jnp twins in kernels.ref evaluate the same
# functions, so kernel and model share a single source of truth
from repro.core.bounds import compact_ids, ivf_gate_skip as _gate_skip
from repro.core.distance import HIGHEST
from repro.core.topk import IDX_SENTINEL, merge_topk
from repro.kernels.kmeans_distance import (LANES, as_lanes, as_row,
                                           compiler_params, from_lanes,
                                           lane_fill, lane_scalar, lane_spec,
                                           pallas_call, row_ids)

# queries one launch scans: the chunk's probe sets ride one int32 bitmask
# per tile, so the scalar-prefetched maps take 2 words per tile of SMEM
# whatever the number of queries
QUERIES_PER_LAUNCH = 32


def exact_scores(q, pts, xn, qn):
    """(1, N) matmul-form fp32 D^2 of query row ``q`` (1, d) against rows
    ``pts`` (N, d) with cached norms ``xn`` (1, N) and ``qn`` = ||q||^2.
    Per-row results do not depend on N, so the kernel's per-tile rows and
    the whole-array twins agree bitwise."""
    dots = jax.lax.dot_general(q.astype(pts.dtype), pts,
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=HIGHEST)
    return jnp.maximum(xn - 2.0 * dots + qn, 0.0)


def adc_scores(lut, codes_t, qdot, labels, u, qn):
    """(1, N) ADC D^2 against PQ-reconstructed rows: ``lut`` (n_sub,
    n_codes) the query's inner-product table, ``codes_t`` (n_sub, N) the
    codes (rows on lanes), ``qdot`` (1, nlist) the routing dots, ``labels``
    (1, N) the rows' lists and ``u`` (1, N) their ``||x̂||^2``. Every dot is
    a one-hot gather (one nonzero term per output), so the scores are exact
    table lookups whatever N is."""
    n_sub, n_codes = lut.shape
    code_iota = jax.lax.broadcasted_iota(jnp.int32, (n_codes, 1), 0)
    qr = jnp.zeros(u.shape, jnp.float32)
    for s in range(n_sub):
        oh = (code_iota == codes_t[s:s + 1, :].astype(jnp.int32))
        qr = qr + jax.lax.dot_general(
            lut[s:s + 1, :], oh.astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=HIGHEST)
    list_iota = jax.lax.broadcasted_iota(jnp.int32, (qdot.shape[1], 1), 0)
    onl = (list_iota == labels).astype(jnp.float32)        # (nlist, N)
    qc = jax.lax.dot_general(qdot, onl, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=HIGHEST)
    return jnp.maximum(qn - 2.0 * (qr + qc) + u, 0.0)


def _scan_step(uids_ref, bits_ref, nu_ref, nv_ref, q_ref, ctr_ref, rad_ref,
               dist_ref, idx_ref, skip_ref, tv_scr, ti_scr, ns_scr, score, *,
               block_n: int, gate: bool):
    """The scan skeleton both kernels share. Grid step (i, j) is union tile
    ``uids[i]`` for query j of the launch's chunk: the tile is fetched once
    for the whole chunk (its block index does not change across the inner
    query steps), and query j scores it (``score(j, q, qn) -> (1,
    block_n)``) only when bit j of ``bits[i]`` says it probes that tile. A
    query thus visits its own probed tiles in ascending order, exactly as
    a per-query scan would. Steps past the union's length are no-ops. The
    per-query top-k scratch carries across the sequential outer dimension;
    outputs are written at the final step."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        tv_scr[j] = jnp.full(tv_scr.shape[1:], jnp.inf, jnp.float32)
        ti_scr[j] = jnp.full(ti_scr.shape[1:], IDX_SENTINEL, jnp.int32)
        ns_scr[j] = 0

    probes = jnp.logical_and(i < nu_ref[0],
                             jnp.right_shift(bits_ref[i], j) & 1 == 1)

    @pl.when(probes)
    def _visit():
        t = uids_ref[i]
        q = q_ref[j].astype(jnp.float32)                # (1, d)
        qn = jnp.sum(q * q, axis=1, keepdims=True)
        tv, ti = tv_scr[j], ti_scr[j]                   # (1, k) carries
        if gate:
            ctr = ctr_ref[0].astype(jnp.float32)        # (1, d) ball center
            diff = ctr - q
            dc = jnp.sqrt(jnp.sum(diff * diff, axis=1, keepdims=True))
            cn = jnp.sqrt(jnp.sum(ctr * ctr, axis=1, keepdims=True))
            # the carried top-k is ascending: its max is the kth best
            tau = jnp.max(tv, axis=1, keepdims=True)
            skip_v = _gate_skip(dc, lane_scalar(rad_ref[0]), cn, qn, tau)
            skip = jnp.max(skip_v.astype(jnp.int32)) > 0
        else:
            skip = jnp.zeros((), jnp.bool_)
        ns_scr[j] += skip.astype(jnp.int32)

        @pl.when(jnp.logical_not(skip))
        def _score():
            d2 = score(j, q, qn)
            row = row_ids(t, block_n)
            valid = row < nv_ref[0]
            nv, ni = merge_topk(tv, ti, jnp.where(valid, d2, jnp.inf),
                                jnp.where(valid, row, IDX_SENTINEL),
                                tv.shape[1])
            tv_scr[j] = nv
            ti_scr[j] = ni

    @pl.when(i == pl.num_programs(0) - 1)
    def _finalize():
        dist_ref[j] = tv_scr[j]
        idx_ref[j] = ti_scr[j]
        skip_ref[j] = lane_fill(ns_scr[j], jnp.int32)


def _ivf_scan_kernel(uids_ref, bits_ref, nu_ref, nv_ref, q_ref, pts_ref,
                     xn_ref, ctr_ref, rad_ref, dist_ref, idx_ref, skip_ref,
                     tv_scr, ti_scr, ns_scr, *, block_n: int, gate: bool):
    def score(j, q, qn):
        return exact_scores(q, pts_ref[...], xn_ref[...], qn)

    _scan_step(uids_ref, bits_ref, nu_ref, nv_ref, q_ref, ctr_ref, rad_ref,
               dist_ref, idx_ref, skip_ref, tv_scr, ti_scr, ns_scr, score,
               block_n=block_n, gate=gate)


def _chunk_maps(ids, n_active, n_chunks):
    """Per-query compacted tile maps -> per-chunk union maps: for each chunk
    of `QUERIES_PER_LAUNCH` queries, the ascending union of their probed
    tiles (compacted as `core.bounds.compact_ids` does), its length, and
    per union tile an int32 bitmask of the chunk's queries that probe it."""
    Q, n_tiles = ids.shape
    step = jnp.arange(n_tiles, dtype=jnp.int32)[None, :]
    probed = jnp.zeros((Q, n_tiles), bool).at[
        jnp.arange(Q)[:, None], ids].max(step < n_active[:, None])
    probed = jnp.pad(probed, ((0, n_chunks * QUERIES_PER_LAUNCH - Q), (0, 0)))
    probed = probed.reshape(n_chunks, QUERIES_PER_LAUNCH, n_tiles)
    shift = jnp.arange(QUERIES_PER_LAUNCH, dtype=jnp.int32)[None, :, None]
    bits = jnp.sum(jnp.left_shift(probed.astype(jnp.int32), shift), axis=1,
                   dtype=jnp.int32)                    # (n_chunks, n_tiles)
    uids, n_union = jax.vmap(compact_ids)(jnp.any(probed, axis=1))
    return uids, jnp.take_along_axis(bits, uids, axis=1), n_union


def _chunked(launch, per_query, ids, n_active, k):
    """Run ``launch(uids_c, bits_c, n_union_c, *per_query_c)`` over chunks
    of `QUERIES_PER_LAUNCH` queries under ``lax.map``; padded queries probe
    nothing. Returns (dists (Q, k), rows (Q, k), gate_skipped (Q,))."""
    Q = ids.shape[0]
    qc = QUERIES_PER_LAUNCH
    n_chunks = -(-Q // qc)
    uids, bits, n_union = _chunk_maps(ids.astype(jnp.int32),
                                      n_active.astype(jnp.int32), n_chunks)

    def split(a):
        a = jnp.pad(a, ((0, n_chunks * qc - Q),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((n_chunks, qc) + a.shape[1:])

    dists, rows, skipped = jax.lax.map(
        lambda args: launch(*args),
        (uids, bits, n_union[:, None]) + tuple(split(a) for a in per_query))
    return (dists.reshape(-1, k)[:Q], rows.reshape(-1, k)[:Q],
            from_lanes(skipped.reshape(-1, 1, LANES))[:Q])


def _scan_specs(n_tiles, d, k, per_query_specs, tile_specs):
    """Grid spec of a scan launch over one chunk of queries: the chunk's
    per-query blocks (resident for the whole launch, ``(chunk,) + shape``),
    then the streamed per-tile blocks (``tile_specs`` build them from
    ``tile(i, uids)``, the union tile of step i), then the tile balls."""
    qc = QUERIES_PER_LAUNCH

    def resident(*shape):
        zeros = (0,) * (len(shape) + 1)
        return pl.BlockSpec((qc,) + shape, lambda i, j, *_: zeros)

    def at_tile(spec_fn):
        return spec_fn(lambda i, uids: uids[i])

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,                   # uids, bits, n_union, n_valid
        grid=(n_tiles, qc),
        in_specs=[resident(*s) for s in per_query_specs]
        + [at_tile(f) for f in tile_specs]
        + [pl.BlockSpec((1, 1, d), lambda i, j, u, *_: (u[i], 0, 0)),
           lane_spec(lambda i, j, u, *_: (u[i], 0, 0))],
        out_specs=[resident(1, k), resident(1, k), resident(1, LANES)],
        scratch_shapes=[
            pltpu.VMEM((qc, 1, k), jnp.float32),
            pltpu.VMEM((qc, 1, k), jnp.int32),
            pltpu.SMEM((qc,), jnp.int32),
        ],
    )


def _scan_out(k):
    qc = QUERIES_PER_LAUNCH
    return [jax.ShapeDtypeStruct((qc, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((qc, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((qc, 1, LANES), jnp.int32)]


@functools.partial(jax.jit,
                   static_argnames=("k", "block_n", "gate", "interpret"))
def ivf_scan_pallas(queries: jax.Array, points: jax.Array, norms: jax.Array,
                    centers: jax.Array, radii: jax.Array, ids: jax.Array,
                    n_active: jax.Array, *, k: int, block_n: int, gate: bool,
                    interpret: bool):
    """Exact gated cluster-local scan.

    queries (Q, d); points (n, d) label-sorted rows; norms (n,) cached fp32
    ``||x||^2``; centers/radii the tile ball summaries; ids (Q, n_tiles) /
    n_active (Q,) the per-query compacted probed-tile maps
    (`core.bounds.compact_ids` over the probed-list coverage). Returns
    ``(dists (Q, k) fp32, rows (Q, k) int32, gate_skipped (Q,) int32)`` —
    rows index the SORTED layout (the caller maps through its permutation);
    unfilled slots hold the (+inf, INT32_MAX) sentinel."""
    d = queries.shape[1]
    n = points.shape[0]
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    pts = jnp.pad(points, ((0, pad), (0, 0)))
    nrm = as_row(norms.astype(jnp.float32), pad)
    ctr = centers.astype(jnp.float32)[:, None, :]
    rad = as_lanes(radii)
    nv = jnp.array([n], jnp.int32)

    grid_spec = _scan_specs(
        grid, d, k, [(1, d)],
        [lambda t: pl.BlockSpec((block_n, d),
                                lambda i, j, u, *_: (t(i, u), 0)),
         lambda t: pl.BlockSpec((1, block_n),
                                lambda i, j, u, *_: (0, t(i, u)))])
    call = pallas_call(
        functools.partial(_ivf_scan_kernel, block_n=block_n, gate=gate),
        grid_spec=grid_spec, out_shape=_scan_out(k),
        compiler_params=compiler_params(), interpret=interpret)

    def launch(uids, bits, n_union, q_c):
        return call(uids, bits, n_union, nv, q_c, pts, nrm, ctr, rad)

    return _chunked(launch, (queries[:, None, :],), ids, n_active, k)


def _ivf_adc_kernel(uids_ref, bits_ref, nu_ref, nv_ref, q_ref, lut_ref,
                    qdot_ref, codes_ref, lab_ref, u_ref, ctr_ref, rad_ref,
                    dist_ref, idx_ref, skip_ref, tv_scr, ti_scr, ns_scr, *,
                    block_n: int, gate: bool):
    """ADC twin of `_ivf_scan_kernel`: scores are exact distances to the
    PQ-reconstructed rows, assembled from the per-query LUT + routing dots +
    per-row ``||x̂||^2`` — codes stream at n_sub bytes/row instead of the
    raw 4d. The gate compares against balls over the RECONSTRUCTED rows, so
    it is a value-noop for ADC scores too."""
    def score(j, q, qn):
        return adc_scores(lut_ref[j], codes_ref[...], qdot_ref[j],
                          lab_ref[...], u_ref[...], qn)

    _scan_step(uids_ref, bits_ref, nu_ref, nv_ref, q_ref, ctr_ref, rad_ref,
               dist_ref, idx_ref, skip_ref, tv_scr, ti_scr, ns_scr, score,
               block_n=block_n, gate=gate)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_n", "gate", "interpret"))
def ivf_adc_scan_pallas(queries: jax.Array, lut: jax.Array, qdots: jax.Array,
                        codes: jax.Array, labels: jax.Array, u: jax.Array,
                        centers: jax.Array, radii: jax.Array, ids: jax.Array,
                        n_active: jax.Array, *, k: int, block_n: int,
                        gate: bool, interpret: bool):
    """PQ/ADC gated cluster-local scan.

    queries (Q, d); lut (Q, n_sub, n_codes) per-query inner-product LUT
    ``lut[s, c] = q_s · codebook[s, c]`` over the RESIDUAL codebook; qdots
    (Q, nlist) routing dots ``q · centroid_l``; codes (n, n_sub) uint8;
    labels (n,) int32 per-row list ids; u (n,) fp32 ``||x̂||^2``;
    centers/radii the tile balls over the reconstructed rows. Returns the
    `ivf_scan_pallas` triple with ADC distances."""
    d = queries.shape[1]
    n, n_sub = codes.shape
    n_codes = lut.shape[2]
    nlist = qdots.shape[1]
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    cds = jnp.pad(codes, ((0, pad), (0, 0))).T           # (n_sub, n_pad)
    lab = as_row(labels.astype(jnp.int32), pad)
    up = as_row(u.astype(jnp.float32), pad)
    ctr = centers.astype(jnp.float32)[:, None, :]
    rad = as_lanes(radii)
    nv = jnp.array([n], jnp.int32)

    def lanes_at(t):
        return pl.BlockSpec((1, block_n), lambda i, j, u, *_: (0, t(i, u)))

    grid_spec = _scan_specs(
        grid, d, k, [(1, d), (n_sub, n_codes), (1, nlist)],
        [lambda t: pl.BlockSpec((n_sub, block_n),
                                lambda i, j, u, *_: (0, t(i, u))),
         lanes_at, lanes_at])
    call = pallas_call(
        functools.partial(_ivf_adc_kernel, block_n=block_n, gate=gate),
        grid_spec=grid_spec, out_shape=_scan_out(k),
        compiler_params=compiler_params(), interpret=interpret)

    def launch(uids, bits, n_union, q_c, lut_c, qd_c):
        return call(uids, bits, n_union, nv, q_c, lut_c, qd_c, cds, lab, up,
                    ctr, rad)

    return _chunked(launch, (queries[:, None, :], lut.astype(jnp.float32),
                             qdots.astype(jnp.float32)[:, None, :]),
                    ids, n_active, k)
