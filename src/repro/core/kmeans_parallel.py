"""k-means|| (Bahmani et al., VLDB 2012) — the *scalable* k-means++ the paper
cites as related work. Instead of k strictly-sequential rounds, it runs
O(log N) rounds that each oversample ~l candidates in parallel, then reduces
the ~l*rounds candidates to k seeds with a *weighted* k-means++.

Fixed-shape TPU adaptation (recorded in DESIGN.md §9): the original samples a
Binomial(n, l*d2/phi) number of candidates per round; we draw exactly `l` per
round with Gumbel top-l (weighted, without replacement). Shapes stay static for
jit/pjit, the expected distribution matches, and the (1+eps) potential bound
argument is unaffected in practice (verified empirically by the quality bench).

Both the per-round D^2 fold (against all l new candidates at once — the
multi-centroid form of the paper's round) and the final weighted reduce now go
through the engine's Backend protocol, so k-means|| gets Pallas/XLA dispatch
from the same seam as everything else.
"""
from __future__ import annotations

import functools
from typing import Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import bounds, collectives, engine, sampling
from repro.core.engine import (Backend, KmeansppResult, make_backend,
                               pairwise_d2, point_d2)


@functools.partial(jax.jit, static_argnames=("k", "rounds", "oversample",
                                             "backend"))
def kmeans_parallel_init(key: jax.Array, points: jax.Array, k: int, *,
                         rounds: int = 5, oversample: int = 0,
                         backend: Union[str, Backend] = "fused"
                         ) -> KmeansppResult:
    """Returns k seeds. `oversample` (l) defaults to 2*k per round.

    On a mesh backend the oversampling draw is the distributed Gumbel top-l
    (`collectives.dist_gumbel_topl`): each round moves O(l * n_shards)
    scalars + one (l, d) candidate psum instead of gathering D^2 anywhere —
    the k-means|| scaling story at pod size."""
    n, d = points.shape
    l = oversample or 2 * k
    be = make_backend(backend)
    if be.distributed:
        return _kmeans_parallel_mesh(key, points, k, rounds, l, be)
    pts = points.astype(jnp.float32)
    # once-per-call prologue (cached norms + tile balls) at the l-candidate
    # round's tile height; each round carries the bound state so tiles the
    # triangle inequality proves unchanged are skipped exactly.
    cache = be.prologue(pts, m=l)
    tile = be.seed_tile(n, d, l)

    key, k0 = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n, dtype=jnp.int32)
    n_cand = rounds * l + 1
    cands = jnp.zeros((n_cand, d), jnp.float32).at[0].set(pts[first])
    cand_idx = jnp.zeros((n_cand,), jnp.int32).at[0].set(first)
    min_d2 = point_d2(pts, pts[first])
    state = bounds.BoundState(sampling.tile_partials(min_d2, tile),
                              bounds.tile_reduce_max(min_d2, tile))

    def body(r, carry):
        key, cands, cand_idx, min_d2, state = carry
        key, ks = jax.random.split(key)
        # sample l candidates with prob ∝ D² (Gumbel top-l, no replacement)
        idx = sampling.gumbel_topk(ks, sampling.safe_log(min_d2), l)
        new_pts = pts[idx]
        cands = jax.lax.dynamic_update_slice(cands, new_pts, (1 + r * l, 0))
        cand_idx = jax.lax.dynamic_update_slice(cand_idx, idx, (1 + r * l,))
        # fold D² against all l new candidates in one multi-centroid round
        rnd = be.seed_round(pts, new_pts, min_d2, None, cache=cache,
                            state=state)
        state = bounds.BoundState(rnd.partials, rnd.tile_max)
        return key, cands, cand_idx, rnd.min_d2, state

    key, cands, cand_idx, min_d2, _ = jax.lax.fori_loop(
        0, rounds, body, (key, cands, cand_idx, min_d2, state))

    # weight each candidate by how many points it is closest to, then reduce
    # the small weighted candidate set to k seeds with weighted k-means++.
    # The reduce draws with the TILED two-level sampler, so it stays
    # O(candidates/bn + bn) per seed as l*rounds grows instead of re-scanning
    # the full candidate set's cumsum every round.
    a = jnp.argmin(pairwise_d2(pts, cands), axis=1)
    w = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), a, num_segments=n_cand)
    key, kr = jax.random.split(key)
    red = engine.seed_points(kr, cands, k, w, be, "tiled")
    final_idx = cand_idx[red.indices]
    final_min_d2 = jnp.min(pairwise_d2(pts, red.centroids), axis=1)
    return KmeansppResult(red.centroids.astype(points.dtype), final_idx,
                          final_min_d2)


def _kmeans_parallel_mesh(key, points, k, rounds, l, be) -> KmeansppResult:
    """Distributed k-means|| rounds inside shard_map.

    Per round: `dist_gumbel_topl` picks the global weighted top-l without
    replacement (local top-l + an all-gather of (l,) score/index pairs),
    `take_global_rows` broadcasts the l chosen rows with one psum, and the
    shard-local multi-centroid `seed_round` folds them into the local D^2
    with the usual bound gating. Candidate weights are one psum'd
    segment_sum; the small weighted reduce to k seeds then runs REPLICATED
    on the mesh's local backend (candidates are O(rounds*l), not O(n))."""
    axes = be.axes
    n, d = points.shape
    n_cand = rounds * l + 1
    key, kin, kr = jax.random.split(key, 3)

    def local_fn(kk, pp):
        pts = pp.astype(jnp.float32)
        n_local = pts.shape[0]
        cache = be.prologue(pts, m=l)
        tile = be.seed_tile(n_local, d, l)
        kk, k0 = jax.random.split(kk)
        first = collectives.dist_gumbel_choice(
            k0, jnp.zeros((n_local,), jnp.float32), axes)
        c0 = collectives.take_global(pts, first, axes)
        cands = jnp.zeros((n_cand, d), jnp.float32).at[0].set(c0)
        cand_idx = jnp.zeros((n_cand,), jnp.int32).at[0].set(first)
        min_d2 = point_d2(pts, c0)
        state = bounds.BoundState(sampling.tile_partials(min_d2, tile),
                                  bounds.tile_reduce_max(min_d2, tile))

        def body(r, carry):
            kk, cands, cand_idx, min_d2, state = carry
            kk, ks = jax.random.split(kk)
            gidx, _ = collectives.dist_gumbel_topl(
                ks, sampling.safe_log(min_d2), l, axes)
            new_pts = collectives.take_global_rows(pts, gidx, axes)
            cands = jax.lax.dynamic_update_slice(cands, new_pts,
                                                 (1 + r * l, 0))
            cand_idx = jax.lax.dynamic_update_slice(cand_idx, gidx,
                                                    (1 + r * l,))
            rnd = be.seed_round(pts, new_pts, min_d2, None, cache=cache,
                                state=state)
            state = bounds.BoundState(rnd.partials, rnd.tile_max)
            return kk, cands, cand_idx, rnd.min_d2, state

        kk, cands, cand_idx, min_d2, _ = jax.lax.fori_loop(
            0, rounds, body, (kk, cands, cand_idx, min_d2, state))
        a = jnp.argmin(pairwise_d2(pts, cands), axis=1)
        w = jax.lax.psum(
            jax.ops.segment_sum(jnp.ones((n_local,), jnp.float32), a,
                                num_segments=n_cand), axes)
        return cands, cand_idx, w

    mapped = jax.shard_map(local_fn, mesh=be.mesh,
                           in_specs=(P(), P(axes)),
                           out_specs=(P(), P(), P()))
    cands, cand_idx, w = mapped(kin, points)
    red = engine.seed_points(kr, cands, k, w, be.local, "tiled")
    final_idx = cand_idx[red.indices]

    def d2_fn(pp, cc):
        return jnp.min(pairwise_d2(pp.astype(jnp.float32), cc), axis=1)

    final_min_d2 = jax.shard_map(
        d2_fn, mesh=be.mesh, in_specs=(P(axes), P()),
        out_specs=P(axes))(points, red.centroids)
    return KmeansppResult(red.centroids.astype(points.dtype), final_idx,
                          final_min_d2)
