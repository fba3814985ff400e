"""Blocked lexicographic top-k: THE merge the IVF scan carries across tiles.

A streaming top-k over tile-blocked candidates is only bitwise equal to a
global top-k when the per-merge order is a strict TOTAL order — plain
``lax.top_k`` on distances leaves ties ordered by visit order, which differs
between a brute-force pass and a tile-blocked scan. Every merge here orders
by the lexicographic key ``(value, index)`` (``jax.lax.sort`` with
``num_keys=2`` for the brute force, k rounds of lexicographic selection for
the blocked merge): indices are unique, so the order is total, every merge is
associative over candidate batches, and the scan's carried top-k equals the
global sort's first k rows bitwise no matter how the candidates were
blocked — the exactness anchor ``serve.ivf`` pins at ``nprobe == nlist``.

Sentinels: empty slots hold ``(+inf, INT32_MAX)``, which lexicographically
trails every real candidate (a finite d2 beats +inf; a real index beats the
sentinel on a +inf tie), so partially-filled merges need no masking. Shared
verbatim by the Pallas scan kernels, their pure-jnp twins, and the
brute-force oracle, so all three tie-break identically.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

IDX_SENTINEL = jnp.iinfo(jnp.int32).max


def init_topk(k: int) -> tuple[jax.Array, jax.Array]:
    """Empty carried top-k: (+inf values, INT32_MAX indices)."""
    return (jnp.full((k,), jnp.inf, jnp.float32),
            jnp.full((k,), IDX_SENTINEL, jnp.int32))


def lex_topk(vals: jax.Array, idxs: jax.Array,
             k: int) -> tuple[jax.Array, jax.Array]:
    """Smallest k of (vals, idxs) under the lexicographic (value, index)
    order — ascending sort with num_keys=2, first k rows."""
    sv, si = jax.lax.sort((vals.astype(jnp.float32), idxs.astype(jnp.int32)),
                          num_keys=2)
    return sv[:k], si[:k]


def merge_topk(top_vals: jax.Array, top_idxs: jax.Array, cand_vals: jax.Array,
               cand_idxs: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """One blocked-merge step: carried top-k + a candidate block -> new
    top-k. Associative over blocks (total order), so any tiling of the
    candidate stream yields the global :func:`lex_topk` bitwise.

    Written as k rounds of lexicographic selection — each round takes the
    smallest value left and, among its holders, the smallest index — out of
    full reductions and selects, which is what the TPU kernel compiler
    lowers (it has no sort). The result is :func:`lex_topk`'s first k rows
    of the concatenation: the same entries, in the same order. Shapes are
    rank-agnostic: 1-D carries (pure-jnp twins) or (1, k) rows (kernels)."""
    big = jnp.int32(IDX_SENTINEL)
    tv = top_vals.astype(jnp.float32)
    cv = cand_vals.astype(jnp.float32)
    ti = top_idxs.astype(jnp.int32)
    ci = cand_idxs.astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, tv.shape, tv.ndim - 1)
    out_v = jnp.full(tv.shape, jnp.inf, jnp.float32)
    out_i = jnp.full(tv.shape, big, jnp.int32)
    for s in range(k):
        m = jnp.minimum(jnp.min(tv), jnp.min(cv))
        j = jnp.minimum(jnp.min(jnp.where(tv == m, ti, big)),
                        jnp.min(jnp.where(cv == m, ci, big)))
        out_v = jnp.where(lane == s, m, out_v)
        out_i = jnp.where(lane == s, j, out_i)
        # retire the taken entry as a sentinel (real indices are unique;
        # sentinels taken together with it trail every real entry anyway)
        hit_t = (tv == m) & (ti == j)
        hit_c = (cv == m) & (ci == j)
        tv, ti = jnp.where(hit_t, jnp.inf, tv), jnp.where(hit_t, big, ti)
        cv, ci = jnp.where(hit_c, jnp.inf, cv), jnp.where(hit_c, big, ci)
    return out_v, out_i
