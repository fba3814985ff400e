"""Mesh collective primitives shared by the distributed clustering paths.

These are the building blocks the ``mesh`` ClusterEngine backend (see
``repro.core.engine``) composes into pod-scale seeding/Lloyd rounds: points are
sharded over the data axes, centroids replicated, and every round costs
O(devices) scalars + O(d) for the winner broadcast — independent of N.

Extracted from ``repro.core.distributed`` so the engine can depend on them
without a circular import; ``distributed`` re-exports for back-compat.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import sampling


def axis_size(axes):
    return jax.lax.psum(1, axes)


def axis_index(axes) -> jax.Array:
    """Linearized index over (possibly multiple) mesh axes."""
    if isinstance(axes, str):
        return jax.lax.axis_index(axes)
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


def shard_argmax(score: jax.Array, global_idx: jax.Array, axes) -> jax.Array:
    """Global index whose shard-local score wins the pmax, pmin tie-broken —
    the two O(1)-byte collectives every distributed sampler combine uses."""
    best = jax.lax.pmax(score, axes)
    cand = jnp.where(score == best, global_idx, jnp.iinfo(jnp.int32).max)
    return jax.lax.pmin(cand, axes)


def dist_gumbel_choice(key: jax.Array, log_w: jax.Array, axes) -> jax.Array:
    """Exact distributed categorical sample via Gumbel-max.

    Each shard computes its local (best_score, best_local_idx); a pmax over the
    scores plus a pmin tie-break over indices picks the global winner with two
    O(1)-byte collectives (no gather of D^2 to any single device). Returns the
    GLOBAL index (shard_offset + local idx), replicated on every shard.
    """
    me = axis_index(axes)
    n_local = log_w.shape[0]
    shard_key = jax.random.fold_in(key, me)
    score, local_idx = sampling.gumbel_max_local(shard_key, log_w)
    return shard_argmax(score, me * n_local + local_idx, axes)


def dist_tiled_choice(key: jax.Array, weights: jax.Array,
                      partials: jax.Array, block_n: int, axes) -> jax.Array:
    """Exact distributed categorical sample from per-tile partial sums.

    Three-level hierarchical composition of the seeding kernel's partials
    with the distributed Gumbel-max:

      1. tile:  each shard draws Gumbel scores over log(partials) — the max
         over tiles is Gumbel(log local_total) by max-stability, and the
         argmax picks a tile with prob partials[t]/local_total;
      2. point: the winning tile's (block_n,) weight slice is sampled by
         inverse-CDF — prob w_i/partials[t];
      3. shard: pmax of the per-shard max scores picks a shard with prob
         local_total/global_total (the same combining rule as
         `dist_gumbel_choice`), with a pmin tie-break on indices.

    The product telescopes to w_i/global_total — an exact global draw that
    reads O(n_local/block_n + block_n) elements per shard after the round
    kernel instead of O(n_local). Returns the GLOBAL index, replicated."""
    me = axis_index(axes)
    n_local = weights.shape[0]
    shard_key = jax.random.fold_in(key, me)
    kt, kp = jax.random.split(shard_key)

    score, t = sampling.gumbel_max_local(kt, sampling.safe_log(partials))

    within = sampling.categorical_cdf(kp, sampling.tile_window(weights, t,
                                                               block_n))
    local_idx = jnp.minimum(t * block_n + within, n_local - 1)
    return shard_argmax(score, me * n_local + local_idx, axes)


def dist_hier_choice(key: jax.Array, weights: jax.Array,
                     partials: jax.Array, block_n: int, tps: int, axes,
                     cap: jax.Array = None, tight: jax.Array = None
                     ) -> jax.Array:
    """Coarse-to-fine distributed categorical sample: the four-level
    composition super-tile -> tile -> point -> shard.

      1. super: each shard draws Gumbel scores over log(super masses) —
         the gathered-boundary differences of its tile CDF (see
         `sampling.super_cdf`) — picking super s with prob mass_s/local_total
         and carrying a Gumbel(log local_total) max score by max-stability;
      2. tile:  inverse-CDF over only the chosen super's (tps,) partials
         slice — prob partials[t]/mass_s;
      3. point: inverse-CDF over the winning tile's (block_n,) weight slice,
         switched to the capped window ``min(weights, cap_t)`` where the
         per-tile Raff cap tightens the stale envelope (``tight[t]``);
      4. shard: the same pmax + pmin-tie-break combine as
         `dist_gumbel_choice` — max-stability makes the per-shard max score
         Gumbel(log local_total) regardless of the partition granularity,
         so the combine is unchanged from the flat tiled draw.

    Reads O(n_local/(block_n*tps) + tps + block_n) elements per shard
    post-kernel instead of the flat draw's O(n_local/block_n + block_n).
    Returns the GLOBAL index, replicated. NOTE: a different key schedule
    than `dist_tiled_choice` (three splits, not two) — callers that need
    the refresh_block=1 bitwise pin route fresh-envelope rounds through
    the flat draw instead (see engine._seed_mesh)."""
    me = axis_index(axes)
    n_local = weights.shape[0]
    n_tiles = partials.shape[0]
    shard_key = jax.random.fold_in(key, me)
    ks, kt, kp = jax.random.split(shard_key, 3)

    tcdf = jnp.cumsum(partials)
    scdf = sampling.super_cdf(tcdf, tps)
    sup = scdf - jnp.concatenate([jnp.zeros((1,), scdf.dtype), scdf[:-1]])
    score, s = sampling.gumbel_max_local(ks, sampling.safe_log(sup))

    ppad = jnp.concatenate([partials, jnp.zeros((tps,), partials.dtype)])
    pwin = jax.lax.dynamic_slice(ppad, (s * tps,), (tps,))
    t = jnp.minimum(s * tps + sampling.categorical_cdf(kt, pwin),
                    n_tiles - 1)

    win = sampling.tile_window(weights, t, block_n)
    if cap is not None:
        # where-form, not minimum(): inf-cap * zero-pad NaNs must lose
        cwin = jnp.where(cap[t] < win, cap[t], win)
        win = jnp.where(tight[t], cwin, win)
    within = sampling.categorical_cdf(kp, win)
    local_idx = jnp.minimum(t * block_n + within, n_local - 1)
    return shard_argmax(score, me * n_local + local_idx, axes)


def dist_gumbel_topl(key: jax.Array, log_w: jax.Array, l: int, axes):
    """Exact distributed Gumbel top-l: sample l indices WITHOUT replacement
    from the sharded categorical exp(log_w) — the k-means|| oversampling draw.

    Each shard takes its local top-l Gumbel scores (candidates for the global
    top-l must be a shard-local top-l), all-gathers the (l,) score/global-index
    pairs (O(l * n_shards) scalars, independent of N), and every shard reduces
    the l*S candidates to the same global top-l. Returns (global_idx (l,),
    scores (l,)), replicated on every shard."""
    me = axis_index(axes)
    n_local = log_w.shape[0]
    shard_key = jax.random.fold_in(key, me)
    g = log_w.astype(jnp.float32) + jax.random.gumbel(
        shard_key, log_w.shape, jnp.float32)
    score, local_idx = jax.lax.top_k(g, l)
    gidx = me * n_local + local_idx.astype(jnp.int32)
    # all-gather as a psum of one-hot rows: adding zeros is exact, and a
    # psum's result is replicated by type, so the returned top-l can leave
    # shard_map under a replicated out_spec (an all_gather's is varying)
    slot = (jnp.arange(axis_size(axes)) == me)[:, None]
    all_scores = jax.lax.psum(jnp.where(slot, score[None, :], 0.0),
                              axes).reshape(-1)
    all_gidx = jax.lax.psum(jnp.where(slot, gidx[None, :], 0),
                            axes).reshape(-1)
    best, pos = jax.lax.top_k(all_scores, l)
    return all_gidx[pos], best


def take_global_rows(points_local: jax.Array, global_idx: jax.Array,
                     axes) -> jax.Array:
    """Vector form of `take_global`: fetch the (l,) rows `global_idx` of the
    axis-0-sharded array with a single (l, d) psum — each row contributed by
    its owning shard, zeros elsewhere."""
    me = axis_index(axes)
    n_local = points_local.shape[0]
    owner = global_idx // n_local
    local = jnp.clip(global_idx - me * n_local, 0, n_local - 1)
    rows = jnp.where((me == owner)[:, None], points_local[local],
                     jnp.zeros_like(points_local[0])[None, :])
    return jax.lax.psum(rows, axes)


def take_global(points_local: jax.Array, global_idx: jax.Array, axes) -> jax.Array:
    """Fetch the row `global_idx` of the sharded (axis-0) array: the owning shard
    contributes the row, everyone else zeros, and one psum broadcasts it."""
    me = axis_index(axes)
    n_local = points_local.shape[0]
    owner = global_idx // n_local
    local = jnp.clip(global_idx - me * n_local, 0, n_local - 1)
    row = jnp.where(me == owner, points_local[local],
                    jnp.zeros_like(points_local[0]))
    return jax.lax.psum(row, axes)


def ring_psum(x: jax.Array, axis: str) -> jax.Array:
    """Ring all-reduce built from ppermute — demonstrates the collective the
    compiler emits for psum and lets the k-means|| round overlap its candidate
    broadcast with local compute (each hop's add overlaps the next permute)."""
    n = jax.lax.psum(1, axis)
    if isinstance(n, jax.Array):  # abstract axis size — fall back
        return jax.lax.psum(x, axis)

    def body(i, acc_cur):
        acc, cur = acc_cur
        nxt = jax.lax.ppermute(
            cur, axis, [(j, (j + 1) % n) for j in range(n)])
        return acc + nxt, nxt

    acc, _ = jax.lax.fori_loop(0, n - 1, body, (x, x))
    return acc
