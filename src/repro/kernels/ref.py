"""Pure-jnp oracles for the Pallas kernels. Every kernel test sweeps shapes and
dtypes and asserts allclose against these.

The IVF twins (`ivf_scan_ref`, `ivf_adc_scan_ref`) are BITWISE mirrors, not
merely allclose oracles: they replay the op sequence of the Pallas scan
kernels — ``lax.map`` over queries (NOT vmap, so no batched 3-D contraction
changes the arithmetic), the same shared gate predicate and lexicographic
merge, ``jnp.where``-selected carries standing in for ``pl.when``. Their
scores are their own code, not the kernels' scoring functions: the exact
twin writes the same matmul-form formula in the kernel's (1, N) row layout,
and the ADC twin looks its LUT and routing terms up by gather where the
kernel contracts one-hot matrices. One deliberate deviation: scores are
computed for the WHOLE padded array in one dot per query and sliced per tile
from the materialized result, instead of dotting each (block_n, d) tile
inside the loop. Per-row dot results are invariant to the operand's row
count (the fused==pallas precedent), so the full-array rows equal the
kernel's tile-dot rows bitwise — whereas a dot fed by a ``dynamic_slice``
inside the same jit gets the slice fused into it with a DIFFERENT
accumulation order (observed 1-ulp drift on CPU), which would break the
mirror. `ivf_bruteforce_topk` is the ground truth the exactness tests pin
both against at ``nprobe == nlist``."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.bounds import ivf_gate_skip
from repro.core.topk import IDX_SENTINEL, init_topk, lex_topk, merge_topk


# the oracles' fp32 dots (the TPU's default matmul precision rounds to bf16)
_HIGHEST = jax.lax.Precision.HIGHEST


def _d2(x: jax.Array, c: jax.Array) -> jax.Array:
    """(n, d) x (k, d) -> (n, k) squared distances in fp32."""
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    xn = jnp.sum(x * x, axis=-1, keepdims=True)
    cn = jnp.sum(c * c, axis=-1)
    dots = jnp.matmul(x, c.T, precision=_HIGHEST)
    return jnp.maximum(xn - 2.0 * dots + cn[None, :], 0.0)


def distance_min_update_ref(points: jax.Array, centroids: jax.Array,
                            min_d2: jax.Array):
    """Oracle for kernels.kmeans_distance: one k-means++ seeding round.

    Returns (new_min_d2 (n,), total (,)): the min-distance array updated against
    the new centroid(s) and the sum of the updated array (the paper's
    thrust::reduce term).
    """
    d2 = jnp.min(_d2(points, centroids), axis=1)
    new = jnp.minimum(min_d2.astype(jnp.float32), d2)
    return new, jnp.sum(new)


def row_min_d2_ref(points: jax.Array, idx: jax.Array, centroids: jax.Array,
                   count: jax.Array) -> jax.Array:
    """Oracle for kernels.row_min_d2: D^2 of the single row ``idx`` to its
    nearest among the first ``count`` rows of ``centroids`` (slots >= count
    are masked to +inf, so count == 0 returns +inf — the rejection sampler's
    empty-pending case, where min(q, +inf) == q keeps the accept ratio
    bitwise at 1). Scalar fp32; O(count * d) reads."""
    x = points[idx].astype(jnp.float32)
    c = centroids.astype(jnp.float32)
    d2 = jnp.sum((x[None, :] - c) ** 2, axis=1)
    slot = jnp.arange(c.shape[0])
    return jnp.min(jnp.where(slot < count, d2, jnp.inf))


def tile_cap_ref(centers: jax.Array, radii: jax.Array, pending: jax.Array,
                 count: jax.Array) -> jax.Array:
    """Oracle for kernels.tile_cap: per-tile rejection-envelope cap from tile
    summaries only. For tile t with ball (center_t, r_t) every row satisfies
    ``d(x_i, c) <= d(center_t, c) + r_t`` (triangle inequality), so

        cap_t = (min_{j < count} d(center_t, pending_j) + r_t)^2

    dominates every row's CURRENT min_d2 against the pending block — a valid
    per-tile upper bound the rejection sampler may shrink its stale envelope
    with (Raff-style, applied to sampling). Slots >= count are masked to
    +inf, so count == 0 returns +inf everywhere (no tightening). (n_tiles,)
    fp32; O(n_tiles * count * d) — tile summaries, never rows."""
    d2 = _d2(centers, pending)
    slot = jnp.arange(pending.shape[0])
    dc2 = jnp.min(jnp.where(slot[None, :] < count, d2, jnp.inf), axis=1)
    cap = (jnp.sqrt(dc2) + radii.astype(jnp.float32)) ** 2
    return jnp.where(count > 0, cap, jnp.inf)


def flash_attention_ref(q, k, v, *, causal=True, window=0, cap=0.0,
                        q_offset=0):
    """Oracle for kernels.flash_attention: exact softmax attention in fp32.
    q (B, Sq, H, hd); k/v (B, Skv, KH, hd) with H = KH * G."""
    B, Sq, H, hd = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    G = H // KH
    qf = q.astype(jnp.float32).reshape(B, Sq, KH, G, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qf, kf) * (hd ** -0.5)
    if cap > 0:
        s = cap * jnp.tanh(s / cap)
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, vf)
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def lloyd_assign_ref(points: jax.Array, centroids: jax.Array):
    """Oracle for kernels.lloyd_assign: fused assignment + per-cluster partials.

    Returns (assignment (n,) int32, min_d2 (n,), sums (k, d) fp32, counts (k,)).
    """
    d2 = _d2(points, centroids)
    a = jnp.argmin(d2, axis=1).astype(jnp.int32)
    m = jnp.min(d2, axis=1)
    k = centroids.shape[0]
    onehot = jax.nn.one_hot(a, k, dtype=jnp.float32)
    sums = jnp.matmul(onehot.T, points.astype(jnp.float32),
                      precision=_HIGHEST)
    counts = jnp.sum(onehot, axis=0)
    return a, m, sums, counts


def lloyd_assign_tiled_ref(points: jax.Array, centroids: jax.Array,
                           block_n: int, tps: int = 1):
    """Oracle for kernels.lloyd_assign_tiled: per-tile assignment outputs
    with hierarchical (super-tile) accumulators.

    Returns (assignment (n,) int32, min_d2 (n,), partials (n_tiles,),
    gaps (n_tiles,), super_sums (n_super, k, d), super_counts (n_super, k))
    where every ``tps`` consecutive tiles share one accumulator slot.
    ``gaps`` is the per-tile min of the second-best margin in distance units
    (+inf for k == 1 — no runner-up exists)."""
    n, d = points.shape
    k = centroids.shape[0]
    d2 = _d2(points, centroids)
    a = jnp.argmin(d2, axis=1).astype(jnp.int32)
    m = jnp.min(d2, axis=1)
    won = jax.nn.one_hot(a, k, dtype=bool)
    second = jnp.min(jnp.where(won, jnp.inf, d2), axis=1)
    gap_pt = jnp.sqrt(second) - jnp.sqrt(m)

    pad = (-n) % block_n
    n_tiles = (n + pad) // block_n
    valid = jnp.arange(n + pad) < n
    mt = jnp.pad(m, (0, pad)).reshape(n_tiles, block_n)
    partials = jnp.sum(mt, axis=1)
    gaps = jnp.min(jnp.pad(gap_pt, (0, pad), constant_values=jnp.inf)
                   .reshape(n_tiles, block_n), axis=1)
    onehot = jnp.where(valid[:, None],
                       jnp.pad(won.astype(jnp.float32), ((0, pad), (0, 0))),
                       0.0).reshape(n_tiles, block_n, k)
    xt = jnp.pad(points.astype(jnp.float32),
                 ((0, pad), (0, 0))).reshape(n_tiles, block_n, d)
    tile_sums = jnp.einsum("tbk,tbd->tkd", onehot, xt, precision=_HIGHEST)
    tile_counts = jnp.sum(onehot, axis=1)
    spad = (-n_tiles) % tps
    super_sums = jnp.pad(tile_sums, ((0, spad), (0, 0), (0, 0))) \
        .reshape(-1, tps, k, d).sum(axis=1)
    super_counts = jnp.pad(tile_counts, ((0, spad), (0, 0))) \
        .reshape(-1, tps, k).sum(axis=1)
    return a, m, partials, gaps, super_sums, super_counts


@functools.partial(jax.jit, static_argnames=("k",))
def ivf_bruteforce_topk(queries: jax.Array, points: jax.Array,
                        norms: jax.Array, *, k: int):
    """Ground-truth batched top-k: every query against EVERY row, one
    lexicographic sort. Written to the scan kernels' formula, not with their
    code — cached ``||x||^2``, a (1, d) x (n, d) fp32 dot per query (per-row
    results are invariant to row-block height, the fused==pallas
    precedent), the same ``max(xn - 2 dots + qn, 0)`` op order, and
    `core.topk`'s (value, index) tie-break — so the gated scan at
    ``nprobe == nlist`` must match it BITWISE on one compiler, which is
    exactly what the exactness tests assert.

    Returns (dists (Q, k) fp32, rows (Q, k) int32)."""
    n = points.shape[0]
    nrm = norms.astype(jnp.float32)
    rows = jnp.arange(n, dtype=jnp.int32)

    def one(q_row):
        q = q_row[None, :].astype(jnp.float32)
        qn = jnp.sum(q * q)
        dots = jax.lax.dot_general(q.astype(points.dtype), points,
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32,
                                   precision=_HIGHEST)[0]
        d2 = jnp.maximum(nrm - 2.0 * dots + qn, 0.0)
        return lex_topk(d2, rows, k)

    return jax.lax.map(one, queries)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_n", "gate"))
def ivf_scan_ref(queries: jax.Array, points: jax.Array, norms: jax.Array,
                 centers: jax.Array, radii: jax.Array, ids: jax.Array,
                 n_active: jax.Array, *, k: int, block_n: int, gate: bool):
    """Bitwise twin of kernels.ivf_scan.ivf_scan_pallas: the gated
    cluster-local exact scan replayed in pure jnp — ``lax.map`` over queries,
    ``fori_loop`` over the compacted tile stream, ``jnp.where``-selected
    carries mirroring ``pl.when``. Same signature and returns."""
    n, d = points.shape
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    pts = jnp.pad(points, ((0, pad), (0, 0)))
    nrm = jnp.pad(norms.astype(jnp.float32), (0, pad))
    ctr = centers.astype(jnp.float32)
    rad = radii.astype(jnp.float32)
    iota = jnp.arange(block_n, dtype=jnp.int32)

    def one(args):
        q_row, tile_ids, nact = args
        q = q_row[None, :].astype(jnp.float32)
        qn = jnp.sum(q * q)
        # whole-array scores once, sliced per tile below (see module note:
        # bitwise equal to the kernel's per-tile dots, unlike a sliced-
        # operand dot inside the loop)
        dots = jax.lax.dot_general(q.astype(pts.dtype), pts,
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32,
                                   precision=_HIGHEST)[0]
        d2_all = jnp.maximum(nrm - 2.0 * dots + qn, 0.0)

        def step(i, carry):
            tv, ti, ns = carry
            t = tile_ids[i]
            visit = i < nact
            if gate:
                c = jax.lax.dynamic_slice(ctr, (t, 0), (1, d))
                diff = c - q
                dc = jnp.sqrt(jnp.sum(diff * diff))
                cn = jnp.sqrt(jnp.sum(c * c))
                skip = ivf_gate_skip(dc, rad[t], cn, qn, tv[k - 1])
            else:
                skip = jnp.full((), False)
            ns = ns + jnp.where(visit, skip.astype(jnp.int32), 0)
            d2 = jax.lax.dynamic_slice(d2_all, (t * block_n,), (block_n,))
            row = t * block_n + iota
            valid = row < n
            cv = jnp.where(valid, d2, jnp.inf)
            ci = jnp.where(valid, row, IDX_SENTINEL)
            nv_, ni_ = merge_topk(tv, ti, cv, ci, k)
            take = visit & jnp.logical_not(skip)
            return (jnp.where(take, nv_, tv), jnp.where(take, ni_, ti), ns)

        tv0, ti0 = init_topk(k)
        return jax.lax.fori_loop(0, grid, step,
                                 (tv0, ti0, jnp.zeros((), jnp.int32)))

    return jax.lax.map(one, (queries, ids.astype(jnp.int32),
                             n_active.astype(jnp.int32)))


@functools.partial(jax.jit,
                   static_argnames=("k", "block_n", "gate"))
def ivf_adc_scan_ref(queries: jax.Array, lut: jax.Array, qdots: jax.Array,
                     codes: jax.Array, labels: jax.Array, u: jax.Array,
                     centers: jax.Array, radii: jax.Array, ids: jax.Array,
                     n_active: jax.Array, *, k: int, block_n: int,
                     gate: bool):
    """Bitwise twin of kernels.ivf_scan.ivf_adc_scan_pallas: the PQ/ADC
    gated scan — per-query LUT contraction against one-hot codes, routing
    dots gathered through one-hot labels — replayed in pure jnp. Same
    signature and returns."""
    n, n_sub = codes.shape
    pad = (-n) % block_n
    grid = (n + pad) // block_n
    cds = jnp.pad(codes, ((0, pad), (0, 0)))
    lab = jnp.pad(labels.astype(jnp.int32), (0, pad))
    up = jnp.pad(u.astype(jnp.float32), (0, pad))
    d = queries.shape[1]
    ctr = centers.astype(jnp.float32)
    rad = radii.astype(jnp.float32)
    iota = jnp.arange(block_n, dtype=jnp.int32)

    def one(args):
        q_row, q_lut, q_dot, tile_ids, nact = args
        q = q_row[None, :].astype(jnp.float32)
        qn = jnp.sum(q * q)
        # whole-array ADC scores once, sliced per tile below: the LUT and
        # routing-dot terms are table lookups (the kernel's one-hot dots
        # have one nonzero term each, so they are exact lookups too),
        # summed over sub-spaces in order
        lut_f = q_lut.astype(jnp.float32)
        qr = jnp.zeros((cds.shape[0],), jnp.float32)
        for s in range(n_sub):
            qr = qr + lut_f[s][cds[:, s].astype(jnp.int32)]
        qc = q_dot.astype(jnp.float32)[lab]
        d2_all = jnp.maximum(qn - 2.0 * (qr + qc) + up, 0.0)

        def step(i, carry):
            tv, ti, ns = carry
            t = tile_ids[i]
            visit = i < nact
            if gate:
                c = jax.lax.dynamic_slice(ctr, (t, 0), (1, d))
                diff = c - q
                dc = jnp.sqrt(jnp.sum(diff * diff))
                cn = jnp.sqrt(jnp.sum(c * c))
                skip = ivf_gate_skip(dc, rad[t], cn, qn, tv[k - 1])
            else:
                skip = jnp.full((), False)
            ns = ns + jnp.where(visit, skip.astype(jnp.int32), 0)
            d2 = jax.lax.dynamic_slice(d2_all, (t * block_n,), (block_n,))
            row = t * block_n + iota
            valid = row < n
            cv = jnp.where(valid, d2, jnp.inf)
            ci = jnp.where(valid, row, IDX_SENTINEL)
            nv_, ni_ = merge_topk(tv, ti, cv, ci, k)
            take = visit & jnp.logical_not(skip)
            return (jnp.where(take, nv_, tv), jnp.where(take, ni_, ti), ns)

        tv0, ti0 = init_topk(k)
        return jax.lax.fori_loop(0, grid, step,
                                 (tv0, ti0, jnp.zeros((), jnp.int32)))

    return jax.lax.map(one, (queries, lut, qdots, ids.astype(jnp.int32),
                             n_active.astype(jnp.int32)))
