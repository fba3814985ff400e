"""ClusterEngine — one backend-dispatched engine for every clustering path.

The paper's contribution is a single primitive: a parallel D^2 min-update +
reduction round. This module makes that primitive the ONLY seam between the
algorithms (k-means++ seeding, Lloyd, mini-batch Lloyd, k-means||, batched
multi-problem clustering) and the hardware mappings (serial reference, XLA
fusion, Pallas kernels, shard_map meshes).

A ``Backend`` provides exactly two round primitives:

  seed_round(points, c_new, min_d2, weights, cache=, state=)
      -> SeedRound(min_d2', total, partials, tile_max, skipped)
      One seeding round: fold the distances to the new centroid block
      ``c_new`` (m, d) into ``min_d2`` and return the (weighted) sum of the
      result — the paper's min-update kernel + thrust::reduce — plus the
      per-tile partial sums the reduction tree already produced
      (shape (ceil(n / seed_tile),)). The ``tiled`` sampler draws the next
      seed from those partials in two exact inverse-CDF levels, reading
      O(n/tile + tile) elements instead of re-scanning all n.
      ``cache`` is the per-call prologue (`core.bounds.RoundCache`: fp32
      ``||x||^2`` norms so no round recomputes them, plus tile
      centroid-balls); ``state`` is the loop-carried bound state
      (`BoundState(partials, tile_max)`). With both present the round SKIPS
      every tile the triangle-inequality bound proves unchanged — exactly
      (fp32 results are bitwise identical, skipped tiles reuse their prior
      partials) — and reports the skipped-tile count.

  assign_update(points, centroids, weights, norms=, cache=, state=, delta=)
      -> AssignRound(assignment, min_d2, sums, counts, state, skipped)
      One Lloyd half-step: nearest-centroid assignment plus per-cluster
      (weighted) partial sums and counts — everything the centroid update
      needs, in one pass. ``norms`` is the cached fp32 ``||x||^2`` (computed
      once per fit, not once per iteration). The fit loop threads ``cache``
      and ``state`` exactly like ``seed_round`` does: with ``cache`` the
      round runs the TILED form (per-tile inertia partials, second-best
      gaps, per-cluster sums/counts — one shared reduction tree across the
      gated and ungated paths), and with ``state`` + ``delta`` (the
      per-centroid movement ``‖c_j^{t+1} − c_j^t‖``) it additionally SKIPS
      every tile the movement bound proves cannot change — exactly (fp32
      results are bitwise identical to the ungated path; see
      ``core.bounds``). ``AssignRound.state`` is the fully-updated
      ``BoundState`` for the next iteration (stale gaps already decayed).

plus ``prologue(points, m=, with_bounds=)`` — the once-per-call pass that
builds the RoundCache (the Pallas backend fuses it into one streaming
kernel, and pads the points to a tile multiple there, once, for every
round: ``RoundCache.rows``). Mixed precision: the engine streams
points/centroids as bf16 when ``precision='bf16'`` while norms,
accumulators, min_d2 and the bound state stay fp32.

plus two trivial hooks (``allreduce``, ``pvary``) that are identity on a
single device and psum/pcast on a mesh. Every algorithm above is written once
against this protocol; picking ``reference``/``fused``/``pallas``/``mesh``
swaps the hardware mapping without touching the algorithm.

Public shims (``repro.core.kmeanspp.kmeanspp``, ``lloyd``, ``kmeans``,
``kmeans_parallel_init``, ``dist_*``) route here and keep their historical
signatures; the seed-parity tests pin the routing to be bitwise-identical.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, ClassVar, Iterable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import bounds, collectives, distance, guards, sampling
from repro.core.bounds import BoundState, RoundCache
from repro.core.guards import (CheckpointError, KernelFailureError,
                               PipelineError)

# ---------------------------------------------------------------------------
# result contracts + distance helpers
# ---------------------------------------------------------------------------


class KmeansppResult(NamedTuple):
    centroids: jax.Array   # (k, d) — (B, k, d) for batched problems
    indices: jax.Array     # (k,) int32 — which data points were chosen
    min_d2: jax.Array      # (n,) final D^2 to nearest seed (useful for k-means||)
    skipped: Optional[jax.Array] = None  # (k,) int32 tiles skipped per round
                                         # (None when bound gating is off)
    pruned: Optional[jax.Array] = None   # (k,) int32 points whose min-update
                                         # the per-point bound short-circuited
                                         # inside ACTIVE tiles, per round
    proposals: Optional[jax.Array] = None  # (k,) int32 envelope draws per
                                           # round (sampler='rejection' only;
                                           # slot 0 is zero — the first seed
                                           # is uniform, not proposed)
    accepts: Optional[jax.Array] = None    # (k,) int32 0/1 ratio-test accepts
                                           # per round (0 also when the round
                                           # fell back to an exact full draw)
    recovered: Optional[jax.Array] = None  # (k,) int32 0/1 corruption-
                                           # recovery flags per round (None
                                           # when the in-flight guard is off;
                                           # see core.telemetry)
    tune: Optional[object] = None          # repro.tune.TuneRecord provenance
                                           # (attached POST-jit by the
                                           # engine; None when tune='off')
    tightened: Optional[jax.Array] = None  # (k,) int32 tiles whose envelope
                                           # the per-tile Raff cap shrank
                                           # below the stale partial, per
                                           # round (sampler='rejection' only;
                                           # zero under proposal='flat')
    supers: Optional[jax.Array] = None     # (k,) int32 super-tile windows
                                           # the coarse-to-fine draw visited
                                           # per round (proposal='hier' only
                                           # — one per attempt plus one for
                                           # the exact fallback when taken)
    # counter contract (shared with LloydResult; pinned by
    # tests/test_telemetry_contract.py): fixed length (k,), one slot per
    # round, slots of rounds that did not run the counted event are ZERO —
    # never truncated, never NaN-filled.


class SeedRound(NamedTuple):
    """One seeding round's outputs (the extended seed_round contract)."""
    min_d2: jax.Array      # (n,) updated D^2 to the nearest centroid
    total: jax.Array       # () (weighted) sum of min_d2 — the paper's phi
    partials: jax.Array    # (n_tiles,) per-tile (weighted) partial sums
    tile_max: Optional[jax.Array] = None  # (n_tiles,) per-tile max of min_d2
                                          # (bound state; None when gating off)
    skipped: Union[jax.Array, int] = 0    # () tiles skipped this round
    pruned: Union[jax.Array, int] = 0     # () points short-circuited inside
                                          # active tiles this round


class LloydResult(NamedTuple):
    centroids: jax.Array      # (k, d) — (B, k, d) for batched problems
    assignment: jax.Array     # (n,) int32 — ALWAYS in the caller's row order
                              # (reordered fits invert the permutation)
    inertia: jax.Array        # () sum of squared distances to assigned centroid
    n_iters: jax.Array        # () int32
    skipped: Optional[jax.Array] = None  # (max_iters,) int32 assignment tiles
                                         # skipped per iteration (None when
                                         # bound gating is off / weighted)
    pruned: Optional[jax.Array] = None   # (max_iters,) int32 points the
                                         # per-point Hamerly bound short-
                                         # circuited inside active tiles
    reorder: Optional[jax.Array] = None  # (n,) int32 row permutation the
                                         # kernels saw (None = natural order)
                                         # — provenance for pruning audits
    recovered: Optional[jax.Array] = None  # (max_iters,) int32 0/1
                                           # corruption-recovery flags per
                                           # iteration (None when the guard
                                           # is off; see core.telemetry)
    tune: Optional[object] = None          # repro.tune.TuneRecord provenance
                                           # (attached POST-jit by the
                                           # engine; None when tune='off')


class AssignRound(NamedTuple):
    """One Lloyd half-step's outputs (the extended assign_update contract)."""
    assignment: jax.Array     # (n,) int32
    min_d2: jax.Array         # (n,) D^2 to the assigned centroid
    sums: jax.Array           # (k, d) per-cluster (weighted) sums
    counts: jax.Array         # (k,) per-cluster (weighted) counts
    state: Optional[BoundState] = None   # next iteration's bound state
                                         # (None on the legacy/weighted path)
    skipped: Union[jax.Array, int] = 0   # () tiles skipped this iteration
    pruned: Union[jax.Array, int] = 0    # () points short-circuited inside
                                         # active tiles this iteration


def pairwise_d2(x: jax.Array, c: jax.Array) -> jax.Array:
    """Squared euclidean distances (n, d) x (k, d) -> (n, k); MXU-friendly form
    (fp32 dots: the TPU's default matmul precision rounds to bf16)."""
    xn = jnp.sum(x * x, axis=-1, keepdims=True)
    cn = jnp.sum(c * c, axis=-1)
    d2 = xn - 2.0 * jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST) \
        + cn[None, :]
    return jnp.maximum(d2, 0.0)


def point_d2(x: jax.Array, c: jax.Array) -> jax.Array:
    """Squared euclidean distance of every point in x (n, d) to one centroid (d,)."""
    diff = x - c[None, :]
    return jnp.sum(diff * diff, axis=-1)


def _min_d2_to(points: jax.Array, c_new: jax.Array) -> jax.Array:
    """D^2 of every point to its nearest centroid among c_new (m, d).

    m == 1 keeps the diff-square-sum form: the seeding loop feeds one centroid
    per round and the serial/reference bitwise-parity claim is pinned to it.
    """
    if c_new.shape[0] == 1:
        return point_d2(points, c_new[0])
    return jnp.min(pairwise_d2(points, c_new), axis=1)


def _matmul_min_d2(points: jax.Array, c_new: jax.Array,
                   norms: Optional[jax.Array]) -> jax.Array:
    """min over c_new of the matmul-form D^2 with cached fp32 norms — the
    fused/Pallas round math (points/centroids keep their stream dtype into
    the dot, accumulation is fp32; bitwise what the Pallas kernels compute
    per tile — the same `core.distance.tile_d2_min`)."""
    if norms is None:
        norms = bounds.point_norms(points)
    return distance.tile_d2_min(points, c_new.astype(points.dtype),
                                norms.astype(jnp.float32)[None, :])[0]


def assign_blocked(points: jax.Array, centroids: jax.Array,
                   *, block: int = 4096,
                   norms: Optional[jax.Array] = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Nearest centroid per point, blocked so the (n, k) distance matrix never
    materializes whole. Returns (assignment, min_d2). ``norms`` is the cached
    fp32 ``||x||^2`` — computed on the fly when absent, hoisted out of the
    Lloyd loop by the engine. Each block runs the Pallas assignment
    kernel's math (`core.distance.nearest` over `tile_d2`)."""
    n, d = points.shape
    pad = (-n) % block
    pts = jnp.pad(points, ((0, pad), (0, 0)))
    if norms is None:
        norms = bounds.point_norms(points)
    nrm = jnp.pad(norms.astype(jnp.float32), (0, pad))
    cents = centroids.astype(points.dtype)

    def blk(args):
        x, xn = args
        return distance.nearest(distance.tile_d2(x, cents, xn))

    a, m = jax.lax.map(blk, (pts.reshape(-1, block, d),
                             nrm.reshape(-1, 1, block)))
    return a.reshape(-1)[:n], m.reshape(-1)[:n]


def segment_update(points: jax.Array, assignment: jax.Array, k: int,
                   weights: Optional[jax.Array]) -> tuple[jax.Array, jax.Array]:
    """Per-cluster (weighted) sums and counts via segment-sum."""
    pts = points.astype(jnp.float32)
    w = (jnp.ones((points.shape[0],), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    sums = jax.ops.segment_sum(pts * w[:, None], assignment, num_segments=k)
    counts = jax.ops.segment_sum(w, assignment, num_segments=k)
    return sums, counts


def centroid_means(sums: jax.Array, counts: jax.Array,
                   prev_centroids: Optional[jax.Array]) -> jax.Array:
    """Means from per-cluster sums/counts; empty clusters keep their previous
    centroid (the standard production fallback)."""
    means = sums / jnp.maximum(counts, 1e-12)[:, None]
    if prev_centroids is not None:
        means = jnp.where((counts > 0)[:, None], means,
                          prev_centroids.astype(jnp.float32))
    return means


def reseed_split_largest(means: jax.Array, counts: jax.Array, *,
                         rel: float = 1e-3) -> jax.Array:
    """Empty-cluster *reseeding*: each empty cluster jumps to a nudged copy of
    the largest cluster's centroid, so the next assignment splits the donor's
    points between the donor and the copies (vs the keep-previous fallback,
    which can leave a dead centroid forever). The nudge is deterministic and
    rank-scaled — the r-th empty cluster lands at a distinct offset — so the
    fit stays key-free and mesh-replicable (counts arrive psum'd)."""
    empty = counts <= 0
    donor = jnp.argmax(counts)
    target = means[donor]
    rank = jnp.cumsum(empty.astype(means.dtype)) * empty.astype(means.dtype)
    off = rel * rank[:, None]
    nudged = target[None, :] * (1.0 + off) + off
    return jnp.where(empty[:, None], nudged, means)


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------


def _gate_model(new_md_full, min_d2, weights, c_new, cache: RoundCache,
                state: BoundState, tile: int) -> SeedRound:
    """Pure-JAX model of the gated kernel, shared by the reference and fused
    backends: tiles the bound proves unchanged take their ``min_d2`` slice
    and partial/tile-max entries from the CARRIED state instead of the fresh
    compute, and inside ACTIVE tiles the per-point bound keeps every row
    whose min-update provably cannot fire — exactly what the Pallas
    kernel's aliased outputs and in-kernel prune do, so the distribution/
    parity tests cover both levels of the skip logic. (Skipping is exact, so
    in fp32 the selects are value-noops unless the bound were wrong; under
    bf16 streams they additionally suppress bf16-noise updates the bound
    proves spurious — see docs/engine.md "Precision & bounds".)"""
    n = min_d2.shape[0]
    active, dc, margin = bounds.seed_gate(c_new, cache, state.tile_max)
    act_pt = bounds.expand_mask(active, tile, n)
    prune = bounds.seed_point_prune(min_d2, cache.center_d,
                                    bounds.expand_mask(dc, tile, n),
                                    bounds.expand_mask(margin, tile, n))
    md = jnp.where(act_pt & jnp.logical_not(prune), new_md_full, min_d2)
    wmd = md if weights is None else md * weights
    partials = jnp.where(active, sampling.tile_partials(wmd, tile),
                         state.partials)
    tile_max = jnp.where(active, bounds.tile_reduce_max(md, tile),
                         state.tile_max)
    # floor at one computed tile, mirroring compact_ids' write-back guard in
    # the gated kernel, so fused/pallas skip counters agree (up to ulp-level
    # differences in the two prologues' tile geometry at bound boundaries)
    skipped = jnp.minimum(jnp.sum(jnp.logical_not(active)),
                          active.shape[0] - 1).astype(jnp.int32)
    pruned = jnp.sum((act_pt & prune).astype(jnp.int32))
    return SeedRound(md, jnp.sum(partials), partials, tile_max, skipped,
                     pruned)


def _assign_tiled_model(points, centroids, norms, tile, tps=None):
    """Pure-JAX twin of `lloyd_assign_tiled_pallas`, shared by the reference
    and fused backends: `jax.lax.map` over point tiles of the SAME per-tile
    assignment math the kernel runs (`kernels.lloyd_assign._tile_assign`),
    so the per-tile partial/gap trees and the hierarchical super-tile
    sums/counts agree and the gate model's selects are value-noops in fp32.
    ``tps`` must match the caller's backend fan-in (``None`` keeps the
    heuristic). Returns (assignment, min_d2, partials, gaps, lb, super_sums,
    super_counts)."""
    from repro.kernels.lloyd_assign import _tile_assign

    n, d = points.shape
    pad = (-n) % tile
    tps = bounds.tiles_per_super((n + pad) // tile, tps)
    pts = jnp.pad(points, ((0, pad), (0, 0)))
    nrm = jnp.pad(norms.astype(jnp.float32), (0, pad))
    valid = jnp.arange(n + pad) < n
    cents = centroids.astype(points.dtype)

    def blk(args):
        x, xn, vld = args
        return _tile_assign(x, xn, cents, vld)

    a, m, part, gap, lb, tsums, tcounts = jax.lax.map(
        blk, (pts.reshape(-1, tile, d), nrm.reshape(-1, 1, tile),
              valid.reshape(-1, 1, tile)))
    n_tiles = part.shape[0]
    return (a.reshape(-1)[:n], m.reshape(-1)[:n], part.reshape(n_tiles),
            gap.reshape(n_tiles), lb.reshape(-1)[:n],
            bounds.super_reduce(tsums, tps),
            bounds.super_reduce(tcounts[:, 0], tps))


def _assign_pruned_model(points, centroids, norms, tile, state: BoundState,
                         delta, thresh, absorb):
    """Pure-JAX twin of the GATED kernel's in-tile math: the per-point
    Hamerly prune (`kernels.lloyd_assign._tile_assign_pruned`) over every
    tile. Returns per-tile trees BEFORE the coarse tile-level selects
    (assignment, min_d2, partials, gaps, lb, pruned (n_tiles,), tile_sums,
    tile_counts — the last two still per-tile so the caller can select at
    super granularity)."""
    from repro.kernels.lloyd_assign import _tile_assign_pruned

    n, d = points.shape
    pad = (-n) % tile
    pts = jnp.pad(points, ((0, pad), (0, 0)))
    nrm = jnp.pad(norms.astype(jnp.float32), (0, pad))
    valid = jnp.arange(n + pad) < n
    cents = centroids.astype(points.dtype)
    pa = jnp.pad(state.assignment.astype(jnp.int32), (0, pad))
    pmd = jnp.pad(state.min_d2.astype(jnp.float32), (0, pad))
    plb = jnp.pad(state.point_lb.astype(jnp.float32), (0, pad))

    dcol = delta.astype(jnp.float32)[:, None]

    def blk(args):
        x, xn, vld, a0, m0, l0, th, ab = args
        return _tile_assign_pruned(x, xn, cents, vld, a0, m0, l0, dcol,
                                   th, ab)

    rows = lambda v: v.reshape(-1, 1, tile)  # noqa: E731
    scal = lambda v: v.reshape(-1, 1, 1)  # noqa: E731
    a, m, part, gap, lb, pruned, tsums, tcounts = jax.lax.map(
        blk, (pts.reshape(-1, tile, d), rows(nrm), rows(valid), rows(pa),
              rows(pmd), rows(plb), scal(thresh), scal(absorb)))
    n_tiles = part.shape[0]
    return (a.reshape(-1)[:n], m.reshape(-1)[:n], part.reshape(n_tiles),
            gap.reshape(n_tiles), lb.reshape(-1)[:n],
            pruned.reshape(n_tiles), tsums, tcounts[:, 0])


@dataclasses.dataclass(frozen=True)
class Backend:
    """Round-primitive provider. Frozen/hashable: instances are jit-static."""

    name: ClassVar[str] = "base"
    distributed: ClassVar[bool] = False

    # floor on the centroid-count the seed_tile VMEM pick budgets for.
    # ``kmeans_points`` sets this to k (dataclasses.replace) so the seeding
    # AND fit phases agree on one tile geometry and can share one prologue;
    # 0 leaves the per-call m untouched (the historical behavior).
    tile_m: int = 0
    # autotuner overrides (repro.tune): a tuned point-tile height and
    # super-tile fan-in. 0 keeps the heuristics (``choose_block_n`` /
    # ``bounds.tiles_per_super``) — the default, so a backend constructed
    # without the tuner is bitwise the pre-tuner backend. A tuned block_n
    # can only SHRINK the heuristic pick (min with the VMEM-fitted cap), so
    # any cached value — even one recorded for a different shape via the
    # nearest-shape fallback — stays within the VMEM budget; tps is clamped
    # and pow2-floored by ``bounds.tiles_per_super``.
    block_n: int = 0
    tps: int = 0

    def seed_round(self, points, c_new, min_d2, weights, *,
                   cache: Optional[RoundCache] = None,
                   state: Optional[BoundState] = None) -> "SeedRound":
        raise NotImplementedError

    def assign_update(self, points, centroids, weights, norms=None, *,
                      cache: Optional[RoundCache] = None,
                      state: Optional[BoundState] = None,
                      delta: Optional[jax.Array] = None) -> "AssignRound":
        """One Lloyd half-step. Without ``cache`` this is the legacy path
        (global accumulators, no bound machinery). With ``cache`` the round
        runs the TILED form; with ``state`` + ``delta`` it additionally
        gates on the movement bound (exact tile skipping)."""
        if cache is None:
            a, md, sums, counts = self._assign_plain(points, centroids,
                                                     weights, norms)
            return AssignRound(a, md, sums, counts)
        return self._assign_tiled(points, centroids,
                                  cache.norms if norms is None else norms,
                                  cache, state, delta)

    def _assign_plain(self, points, centroids, weights, norms=None):
        raise NotImplementedError

    def _assign_tiled(self, points, centroids, norms, cache, state,
                      delta) -> "AssignRound":
        """Shared pure-JAX tiled/gated assignment round (Pallas overrides
        with its kernels). Tiles the movement bound proves unchanged take
        ALL their outputs from the carried state — exactly what the gated
        kernel's aliased outputs do — which is a value-noop in fp32 because
        skipping additionally requires the tile's assigned centroids to be
        bitwise unmoved (see core.bounds.assign_active_tiles). The skip mask
        is expanded to whole SUPER-tiles (the hierarchical accumulators
        alias at super granularity), and inside active tiles the per-point
        Hamerly bound short-circuits provably-stable points — also a
        value-noop, counted in ``pruned``."""
        n, d = points.shape
        k = centroids.shape[0]
        tile = self.seed_tile(n, d, k)
        tps = self.tiles_per_super(-(-n // tile))
        if (state is not None and delta is not None
                and cache.centers is not None):
            dmax = jnp.max(delta)
            cand = bounds.assign_active_tiles(delta, centroids, state, cache,
                                              tps=tps)
            active = bounds.expand_active_supers(cand, tps)
            thresh, absorb = bounds.assign_point_scalars(delta, centroids,
                                                         state, cache)
            a, md, part, gap, lb, pruned_t, tsums, tcounts = \
                _assign_pruned_model(points, centroids, norms, tile, state,
                                     delta, thresh, absorb)
            act_pt = bounds.expand_mask(active, tile, n)
            a = jnp.where(act_pt, a, state.assignment)
            md = jnp.where(act_pt, md, state.min_d2)
            lb = jnp.where(act_pt, lb, state.point_lb)
            part = jnp.where(active, part, state.partials)
            gap = bounds.decay_gap(state.tile_gap, active, gap, dmax)
            sup_act = bounds.super_any(active, tps)
            ssums = jnp.where(sup_act[:, None, None],
                              bounds.super_reduce(tsums, tps),
                              state.tile_sums)
            scounts = jnp.where(sup_act[:, None],
                                bounds.super_reduce(tcounts, tps),
                                state.tile_counts)
            # same tree-pinning barrier as the ungated branch (the where
            # usually blocks XLA's reduce merging already; the barrier makes
            # the two-level tree unconditional)
            ssums, scounts = jax.lax.optimization_barrier((ssums, scounts))
            debt = jnp.where(active, 0.0, state.lb_debt + dmax)
            skipped = jnp.sum(jnp.logical_not(active)).astype(jnp.int32)
            # cast the fp32 per-tile counts BEFORE reducing (exact > 2^24)
            pruned = jnp.sum(jnp.where(active, pruned_t,
                                       0.0).astype(jnp.int32))
            new_state = BoundState(part, tile_gap=gap, tile_sums=ssums,
                                   tile_counts=scounts, assignment=a,
                                   min_d2=md, point_lb=lb, lb_debt=debt)
            return AssignRound(a, md, jnp.sum(ssums, axis=0),
                               jnp.sum(scounts, axis=0), new_state, skipped,
                               pruned)
        a, md, part, gap, lb, ssums, scounts = _assign_tiled_model(
            points, centroids, norms, tile, tps=tps)
        del lb  # the ungated state carries no per-point bound fields (same
        #         pytree as the Pallas ungated branch — the gated loop
        #         builds its own init state)
        # pin the two-level tree: without the barrier XLA merges the
        # super-level reshape-sum into the outer cluster sum (one flat
        # reduce over all tiles), which would make the ungated reduction
        # order differ from the gated branch's where-blocked tree and break
        # the bitwise gated==ungated claim
        ssums, scounts = jax.lax.optimization_barrier((ssums, scounts))
        new_state = BoundState(part, tile_gap=gap, tile_sums=ssums,
                               tile_counts=scounts, assignment=a, min_d2=md)
        return AssignRound(a, md, jnp.sum(ssums, axis=0),
                           jnp.sum(scounts, axis=0), new_state,
                           jnp.zeros((), jnp.int32))

    def prologue(self, points, m: int = 1,
                 with_bounds: bool = True) -> RoundCache:
        """Once-per-call pass: cached fp32 norms (+ tile centroid-balls when
        bound gating is on). The Pallas backend overrides this with its
        single-kernel streaming prologue."""
        n, d = points.shape
        return bounds.prologue(points, self.seed_tile(n, d, m),
                               with_bounds=with_bounds)

    def seed_tile(self, n: int, d: int, m: int = 1) -> int:
        """Static tile height of seed_round's partials: every backend uses the
        Pallas kernel's VMEM-fitted block (batch-grid accounting — slightly
        conservative for the single-problem launch) so partial shapes agree
        across backends and the tiled sampler slices the right window.
        ``tile_m`` (see the field) floors m so a kmeans call's two phases
        share one geometry. A tuned ``block_n`` (repro.tune) caps the pick
        from below the heuristic — never above it, so the VMEM accounting
        of ``pick_block_n`` still holds."""
        from repro.kernels.ops import choose_block_n
        pick = choose_block_n(n, d, max(m, self.tile_m, 1), batched=True)
        if self.block_n > 0:
            return max(128, min(pick, self.block_n))
        return pick

    def tiles_per_super(self, n_tiles: int) -> int:
        """Super-tile fan-in for this backend: the tuned ``tps`` when set
        (clamped/pow2-floored), else the ~sqrt(n_tiles) heuristic. ALL
        call sites — the engine's init-state shapes, the pure-JAX model
        and the Pallas wrappers — route through here so the jnp and pallas
        accumulator paths can never silently disagree."""
        return bounds.tiles_per_super(n_tiles, self.tps or None)

    def _partials(self, min_d2, weights, n: int, d: int, m: int):
        w_md = min_d2 if weights is None else min_d2 * weights
        return sampling.tile_partials(w_md, self.seed_tile(n, d, m))

    def row_min_d2(self, points, idx, pending, count):
        """Scalar D^2 of row ``idx`` to the nearest of ``pending[:count]`` —
        the rejection sampler's exact-p evaluation (O(count * d) work,
        independent of n). count == 0 returns +inf, so an empty pending
        block leaves the accept ratio bitwise at 1. The Pallas backend
        overrides this with the scalar-prefetched single-row gather kernel;
        this pure-jnp form is its bitwise oracle."""
        from repro.kernels.ref import row_min_d2_ref
        return row_min_d2_ref(points, idx, pending, count)

    def tile_cap(self, centers, radii, pending, count):
        """(n_tiles,) per-tile rejection-envelope caps ``(dc_t + r_t)^2``
        against ``pending[:count]`` — the movement-tightened envelope's one
        (n_tiles, pending) pass over the prologue's tile summaries (Raff
        triangle bound applied to SAMPLING; never touches a row). count == 0
        returns +inf everywhere, a tightening no-op. The Pallas backend
        overrides this with the scalar-prefetched summary kernel; this
        pure-jnp form (XLA-fused) is its oracle."""
        from repro.kernels.ref import tile_cap_ref
        return tile_cap_ref(centers, radii, pending, count)

    # mesh hooks — identity on a single device
    def allreduce(self, x):
        return x

    def pvary(self, x):
        return x


@dataclasses.dataclass(frozen=True)
class ReferenceBackend(Backend):
    """Serial (paper's CPU baseline) or global-memory (two-pass) semantics.

    ``mode='serial'`` loops one point at a time with a second serial reduction
    pass; ``mode='global'`` vectorizes the min-update but materializes it and
    re-reads it for the reduction (the paper's global-memory variant).
    """

    name: ClassVar[str] = "reference"
    mode: str = "global"

    def seed_round(self, points, c_new, min_d2, weights, *, cache=None,
                   state=None):
        n, d = points.shape
        m = c_new.shape[0]
        tile = self.seed_tile(n, d, m)
        if self.mode == "serial":
            def body(i, md):
                d2 = jnp.min(jnp.sum((points[i] - c_new) ** 2, axis=1))
                return md.at[i].set(jnp.minimum(md[i], d2))

            min_d2 = jax.lax.fori_loop(0, n, body, min_d2)

            def sum_body(i, acc):
                w = min_d2[i] if weights is None else min_d2[i] * weights[i]
                return acc + w

            total = jax.lax.fori_loop(0, n, sum_body,
                                      jnp.zeros((), min_d2.dtype))
            # the partials/bound state are contract-only here (the paper's
            # serial baseline has no tiles and never skips); computed
            # vectorized, outside the timed loop shape
            tmax = (None if state is None
                    else bounds.tile_reduce_max(min_d2, tile))
            return SeedRound(min_d2, total,
                             self._partials(min_d2, weights, n, d, m), tmax)

        new_md = jnp.minimum(min_d2, _min_d2_to(points, c_new))
        # optimization_barrier forces the reduction to be a second pass over
        # the materialized array instead of fusing — mirrors the two-kernel
        # CUDA structure.
        new_md = jax.lax.optimization_barrier(new_md)
        if state is not None and cache is not None and cache.centers is not None:
            rnd = _gate_model(new_md, min_d2, weights, c_new, cache, state,
                              tile)
            # keep the two-pass total semantics: sum over the materialized
            # array, not over the partial tree
            w = rnd.min_d2 if weights is None else rnd.min_d2 * weights
            return rnd._replace(total=jnp.sum(w))
        w = new_md if weights is None else new_md * weights
        return SeedRound(new_md, jnp.sum(w),
                         self._partials(new_md, weights, n, d, m))

    def _assign_plain(self, points, centroids, weights, norms=None):
        d2 = pairwise_d2(points.astype(jnp.float32),
                         centroids.astype(jnp.float32))
        a = jnp.argmin(d2, axis=1).astype(jnp.int32)
        md = jnp.min(d2, axis=1)
        sums, counts = segment_update(points, a, centroids.shape[0], weights)
        return a, md, sums, counts


@dataclasses.dataclass(frozen=True)
class FusedBackend(Backend):
    """Single fused pass (constant/texture analogue): XLA fuses update+reduce."""

    name: ClassVar[str] = "fused"
    block: int = 4096

    def seed_round(self, points, c_new, min_d2, weights, *, cache=None,
                   state=None):
        n, d = points.shape
        m = c_new.shape[0]
        norms = None if cache is None else cache.norms
        new_md = jnp.minimum(min_d2, _matmul_min_d2(points, c_new, norms))
        if state is not None and cache is not None and cache.centers is not None:
            return _gate_model(new_md, min_d2, weights, c_new, cache, state,
                               self.seed_tile(n, d, m))
        # XLA fuses the tile partials INTO the min-update pass (one read of
        # min_d2); the scalar total is their sum — same tree as the kernel's.
        partials = self._partials(new_md, weights, n, d, m)
        return SeedRound(new_md, jnp.sum(partials), partials)

    def _assign_plain(self, points, centroids, weights, norms=None):
        a, md = assign_blocked(points, centroids, block=self.block,
                               norms=norms)
        sums, counts = segment_update(points, a, centroids.shape[0], weights)
        return a, md, sums, counts


def _rows(points, cache: Optional[RoundCache]):
    """What a Pallas round streams: the prologue's once-padded rows (in the
    stream's dtype, see `_stream_cache`) when the cache has them, else the
    (n, d) points, which the kernel wrapper pads on each call."""
    return points if cache is None or cache.rows is None else cache.rows


def _stream_cache(cache: Optional[RoundCache], stream) -> Optional[RoundCache]:
    """Once per phase, outside the loop: the padded rows in the dtype the
    rounds stream (a bf16 copy under precision='bf16'; pad rows stay 0)."""
    if cache is None or cache.rows is None or cache.rows.dtype == stream.dtype:
        return cache
    return cache._replace(rows=cache.rows.astype(stream.dtype))


@dataclasses.dataclass(frozen=True)
class PallasBackend(Backend):
    """Pallas kernels: VMEM-resident centroids + fused min-update/partials
    (``resident=False`` models the global-memory refetch for Fig. 2)."""

    name: ClassVar[str] = "pallas"
    resident: bool = True

    def prologue(self, points, m: int = 1,
                 with_bounds: bool = True) -> RoundCache:
        """Pads the points to a tile multiple ONCE for the whole call
        (``RoundCache.rows``): the kernels stream those rows every round,
        so no round re-pads the (n, d) array. The prologue kernel streams
        them too. The copy goes a tile at a time (`pad_rows_blocked`), so
        points in a column-major layout are never relaid out whole."""
        from repro.kernels import ops as kops
        n, d = points.shape
        tile = self.seed_tile(n, d, m)
        rows = kops.pad_rows_blocked(points, -(-n // tile) * tile, tile)
        if not with_bounds:
            return RoundCache(kops.point_norms(points), rows=rows)
        norms, centers, radii, center_d = kops.seed_prologue(
            points, block_n=tile, rows=rows)
        return RoundCache(norms, centers, radii, center_d, rows)

    def seed_round(self, points, c_new, min_d2, weights, *, cache=None,
                   state=None):
        from repro.kernels import ops as kops
        n, d = points.shape
        m = c_new.shape[0]
        # pin the kernel tile to seed_tile so the partials it emits line up
        # with the window the tiled sampler slices (single and batch-grid
        # launches share the block choice)
        tile = self.seed_tile(n, d, m)
        norms = None if cache is None else cache.norms
        rows = _rows(points, cache)
        if (state is not None and weights is None and cache is not None
                and cache.centers is not None):
            # cache.norms is always populated (and always fp32 — never derive
            # norms from `points` here: under bf16 streaming that would feed
            # bf16-noise into the bound, exceeding active_tiles' fp32 slack)
            active, dc, margin = bounds.seed_gate(c_new, cache,
                                                  state.tile_max)
            md, partials, tmax, pruned, skipped = \
                kops.distance_min_update_gated(
                    rows, c_new, min_d2, norms, cache.center_d, dc, margin,
                    state.partials, state.tile_max, active, block_n=tile,
                    resident_centroids=self.resident)
            # per-tile counts are fp32 (kernel vectors); cast BEFORE the
            # reduction so the counter stays exact past 2^24 points
            return SeedRound(md, jnp.sum(partials), partials, tmax, skipped,
                             jnp.sum(pruned.astype(jnp.int32)))
        min_d2, partials = kops.distance_min_update(
            rows, c_new, min_d2, norms=norms,
            resident_centroids=self.resident, block_n=tile)
        if weights is not None:
            # weighted partials need the weighted sum; recompute cheaply (the
            # weights case is only used by the small k-means|| reduce).
            partials = self._partials(min_d2, weights, n, d, m)
        if state is not None:
            # weighted + gated caller: keep the carry shapes, skip nothing
            return SeedRound(min_d2, jnp.sum(partials), partials,
                             bounds.tile_reduce_max(min_d2, tile))
        return SeedRound(min_d2, jnp.sum(partials), partials)

    def row_min_d2(self, points, idx, pending, count):
        from repro.kernels import ops as kops
        return kops.row_min_d2(points, idx, pending, count)

    def tile_cap(self, centers, radii, pending, count):
        from repro.kernels import ops as kops
        return kops.tile_cap(centers, radii, pending, count)

    def _assign_plain(self, points, centroids, weights, norms=None):
        from repro.kernels import ops as kops
        a, md, sums, counts = kops.lloyd_assign(points, centroids,
                                                norms=norms)
        if weights is not None:
            sums, counts = segment_update(points, a, centroids.shape[0],
                                          weights)
        return a, md, sums, counts

    def _assign_tiled(self, points, centroids, norms, cache, state, delta):
        from repro.kernels import ops as kops
        n, d = points.shape
        tile = self.seed_tile(n, d, centroids.shape[0])
        tps = self.tiles_per_super(-(-n // tile))
        rows = _rows(points, cache)
        if (state is not None and delta is not None
                and cache.centers is not None):
            dmax = jnp.max(delta)
            cand = bounds.assign_active_tiles(delta, centroids, state, cache,
                                              tps=tps)
            # expand to whole super-tiles HERE (the wrapper re-expands,
            # idempotently) so the gap-decay / debt bookkeeping below sees
            # exactly the tiles the kernel rewrote
            active = bounds.expand_active_supers(cand, tps)
            thresh, absorb = bounds.assign_point_scalars(delta, centroids,
                                                         state, cache)
            a, md, lb, part, gap, ssums, scounts, pruned_t, skipped = \
                kops.lloyd_assign_gated(
                    rows, centroids, norms, delta, thresh, absorb,
                    state.assignment, state.min_d2, state.point_lb,
                    state.partials, state.tile_gap, state.tile_sums,
                    state.tile_counts, active, block_n=tile, tps=tps)
            # kernel gap output: fresh for computed tiles, the ALIASED carry
            # for skipped ones — decay the latter by this step's movement so
            # it stays a valid lower bound across consecutive skips; the
            # stored per-point lb of skipped tiles decays LAZILY instead
            # (lb_debt), so the skipped blocks are never touched
            gap = bounds.decay_gap(gap, active, gap, dmax)
            debt = jnp.where(active, 0.0, state.lb_debt + dmax)
            new_state = BoundState(part, tile_gap=gap, tile_sums=ssums,
                                   tile_counts=scounts, assignment=a,
                                   min_d2=md, point_lb=lb, lb_debt=debt)
            # cast the fp32 per-tile counts BEFORE reducing (exact > 2^24)
            return AssignRound(a, md, jnp.sum(ssums, axis=0),
                               jnp.sum(scounts, axis=0), new_state, skipped,
                               jnp.sum(pruned_t.astype(jnp.int32)))
        a, md, part, gap, ssums, scounts = kops.lloyd_assign_tiled(
            rows, centroids, norms=norms, block_n=tile, tps=tps)
        new_state = BoundState(part, tile_gap=gap, tile_sums=ssums,
                               tile_counts=scounts, assignment=a, min_d2=md)
        return AssignRound(a, md, jnp.sum(ssums, axis=0),
                           jnp.sum(scounts, axis=0), new_state,
                           jnp.zeros((), jnp.int32))


@dataclasses.dataclass(frozen=True)
class MeshBackend(Backend):
    """shard_map mesh backend: points sharded on axis 0 over `axes`, centroids
    replicated (constant memory at mesh level). Wraps a local compute backend
    and adds the O(devices)-scalar collectives."""

    name: ClassVar[str] = "mesh"
    distributed: ClassVar[bool] = True
    mesh: Optional[Mesh] = None
    axes: tuple[str, ...] = ("data",)
    local: Backend = FusedBackend()

    def seed_round(self, points, c_new, min_d2, weights, *, cache=None,
                   state=None):
        rnd = self.local.seed_round(points, c_new, min_d2, weights,
                                    cache=cache, state=state)
        # the paper's thrust::reduce -> psum of local partial sums. The Gumbel
        # sampler doesn't need the normalizer, but production logging does (the
        # potential phi), so we keep the collective — it is O(1) bytes. The
        # tile partials/bound state stay SHARD-LOCAL: the distributed tiled
        # sampler combines them with one pmax/pmin pair, never gathering
        # them. The per-shard skip/prune counters compose through two more
        # O(1) psums, so `skipped`/`pruned` report POD-WIDE counts.
        return SeedRound(rnd.min_d2, jax.lax.psum(rnd.total, self.axes),
                         rnd.partials, rnd.tile_max,
                         jax.lax.psum(rnd.skipped, self.axes),
                         jax.lax.psum(rnd.pruned, self.axes))

    def seed_tile(self, n: int, d: int, m: int = 1) -> int:
        return self.local.seed_tile(n, d, m)

    def tiles_per_super(self, n_tiles: int) -> int:
        return self.local.tiles_per_super(n_tiles)

    def prologue(self, points, m: int = 1,
                 with_bounds: bool = True) -> RoundCache:
        return self.local.prologue(points, m, with_bounds)

    def row_min_d2(self, points, idx, pending, count):
        # shard-LOCAL row gather: the mesh rejection path resolves the
        # global index to the owner shard and psums the scalar (see
        # _seed_mesh), so the method itself stays local
        return self.local.row_min_d2(points, idx, pending, count)

    def tile_cap(self, centers, radii, pending, count):
        # shard-LOCAL summary pass: tile centers/radii and the tightened
        # super partials all stay shard-local (see _seed_mesh)
        return self.local.tile_cap(centers, radii, pending, count)

    def assign_update(self, points, centroids, weights, norms=None, *,
                      cache=None, state=None, delta=None):
        rnd = self.local.assign_update(points, centroids, weights, norms,
                                       cache=cache, state=state, delta=delta)
        # the per-tile/per-point bound state stays SHARD-LOCAL; only the
        # O(k*d) accumulators and the O(1) skip/prune counters cross the mesh
        sums = jax.lax.psum(rnd.sums, self.axes)      # O(k*d) per iteration
        counts = jax.lax.psum(rnd.counts, self.axes)  # O(k)
        skipped = (jax.lax.psum(rnd.skipped, self.axes)
                   if cache is not None else rnd.skipped)
        pruned = (jax.lax.psum(rnd.pruned, self.axes)
                  if cache is not None else rnd.pruned)
        return rnd._replace(sums=sums, counts=counts, skipped=skipped,
                            pruned=pruned)

    def allreduce(self, x):
        return jax.lax.psum(x, self.axes)

    def pvary(self, x):
        return jax.lax.pcast(x, self.axes, to="varying")


_LOCAL_BACKENDS: dict[str, Callable[..., Backend]] = {
    "reference": ReferenceBackend,
    "serial": functools.partial(ReferenceBackend, mode="serial"),
    "global": functools.partial(ReferenceBackend, mode="global"),
    "fused": FusedBackend,
    "pallas": PallasBackend,
    "pallas_constant": functools.partial(PallasBackend, resident=True),
    "pallas_fused": functools.partial(PallasBackend, resident=False),
}


def make_backend(name: Union[str, Backend], **opts) -> Backend:
    """Backend registry: 'reference' | 'fused' | 'pallas' | 'mesh' (plus the
    historical fine-grained aliases 'serial'/'global'/'pallas_constant'/
    'pallas_fused'). 'mesh' needs mesh=..., and accepts axes=... and
    local=<name or Backend> for the per-shard compute."""
    if isinstance(name, Backend):
        if opts:
            raise ValueError("cannot pass options with a Backend instance")
        return name
    if name == "mesh":
        mesh = opts.pop("mesh", None)
        if mesh is None:
            raise ValueError("mesh backend needs mesh=jax.make_mesh(...)")
        axes = opts.pop("axes", ("data",))
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        local = make_backend(opts.pop("local", "fused"))
        if opts:
            raise ValueError(f"unknown mesh backend options {sorted(opts)}")
        return MeshBackend(mesh=mesh, axes=axes, local=local)
    try:
        ctor = _LOCAL_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; expected one of "
            f"{sorted(_LOCAL_BACKENDS) + ['mesh']}") from None
    return ctor(**opts)


def _mesh_scoped(method):
    """Run a ClusterEngine entry point with a mesh backend's mesh in context.
    ``jax.make_mesh`` gives Explicit axes by default, and eager work on
    arrays sharded over such a mesh (the input guards, indexing inside
    ``shard_map``) needs the mesh set (``jax.set_mesh``)."""
    @functools.wraps(method)
    def scoped(self, *args, **kwargs):
        if not self.backend.distributed:
            return method(self, *args, **kwargs)
        with jax.set_mesh(self.backend.mesh):
            return method(self, *args, **kwargs)
    return scoped


def _shard_map(backend: MeshBackend, fn, *, in_specs, out_specs):
    """``jax.shard_map`` of ``fn`` over the mesh backend's mesh. The Pallas
    interpreter, which runs the kernels off the TPU, does not carry the
    operands' varying mesh axes into the kernel body ("Primitive sub
    requires varying manual axes to match"), so interpreted Pallas shards
    run with that type check off; compiled kernels keep it."""
    from repro.kernels import ops
    interpreted = (isinstance(backend.local, PallasBackend)
                   and ops.default_interpret())
    return jax.shard_map(fn, mesh=backend.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=not interpreted)


# ---------------------------------------------------------------------------
# the seeding loop (shared verbatim by local and mesh paths)
# ---------------------------------------------------------------------------


def _inject_seed_fault(fault, m, min_d2, state):
    """Deterministic corruption hook for the seeding loops (see
    repro.testing.faults.FaultSpec). Poisons the CARRIED round inputs at
    round ``fault.round`` — exactly the state a flipped bit / bad DMA would
    hit — and is a no-op for every other round and for fault=None."""
    if fault is None:
        return min_d2, state
    kind = getattr(fault, "kind", None)
    trip = jnp.asarray(m == fault.round)
    if kind == "nan_tile":
        rows = jnp.arange(min_d2.shape[0]) < min(64, min_d2.shape[0])
        bad = jnp.where(rows & trip, jnp.nan, 0.0).astype(min_d2.dtype)
        return min_d2 + bad, state
    if kind == "nan_state" and state is not None:
        parts = jnp.where(trip, state.partials.at[0].set(jnp.nan),
                          state.partials)
        return min_d2, state._replace(partials=parts)
    return min_d2, state


def _seed_parts(pts, k, w, *, round_fn, first_fn, sample_fn, take_fn,
                init_min_d2, init_state: Optional[BoundState] = None,
                guard: bool = False, tile: Optional[int] = None, fault=None):
    """Builds the generic k-means++ loop as (make_init, body, finish) so the
    one-shot ``_seed_loop`` and the checkpointed chunk runner share one body.

    carry = (m, key, centroids, indices, min_d2, state, skips, prunes, rec)

    ``guard`` arms in-flight corruption detection: every round's psum'd
    ``total`` (the paper's thrust::reduce scalar — already computed, already
    replicated on a mesh) doubles as the finite flag. A fresh NaN anywhere
    the round computed reaches ``total`` through the partial tree (computed
    tiles re-sum their rows; a poisoned carried partial is summed directly),
    so a non-finite total means the carry is untrusted: the heal branch
    DISCARDS min_d2 and the bound state and refolds rounds 0..m-1 ungated
    from the clean +inf carry. Recovery is bitwise: gated == ungated
    exactly, and the refold applies the same min-folds in the same order a
    never-corrupted run applied, so the healed carry (and every seed drawn
    from it) is bit-identical to the uncorrupted trajectory. Corruption
    that strikes rows of a tile the gate is currently SKIPPING is not
    witnessed until that tile next activates (its rows are by construction
    neither read nor written); see docs/engine.md "Failure semantics"."""
    d = pts.shape[1]
    gated = init_state is not None
    if guard and tile is None:
        raise ValueError("guarded seeding needs the partials tile height")

    def heal_min_d2(m, centroids):
        def fold(j, mdc):
            return round_fn(centroids[j], mdc, None).min_d2
        return jax.lax.fori_loop(0, m, fold, init_min_d2)

    def checked_round(m, centroids, min_d2, state):
        rnd = round_fn(centroids[m - 1], min_d2, state)
        zi = jnp.zeros((), jnp.int32)
        if not guard:
            st = (None if not gated
                  else BoundState(rnd.partials, rnd.tile_max))
            return (rnd.min_d2, rnd.partials, st,
                    jnp.asarray(rnd.skipped, jnp.int32),
                    jnp.asarray(rnd.pruned, jnp.int32), zi)
        healthy = jnp.isfinite(rnd.total)

        def keep(_):
            out = (rnd.min_d2, rnd.partials,
                   jnp.asarray(rnd.skipped, jnp.int32),
                   jnp.asarray(rnd.pruned, jnp.int32))
            return out + (rnd.tile_max,) if gated else out

        def heal(_):
            md = heal_min_d2(m, centroids)
            wmd = md if w is None else md * w
            parts = sampling.tile_partials(wmd, tile)
            out = (md, parts, zi, zi)
            return out + (bounds.tile_reduce_max(md, tile),) if gated else out

        out = jax.lax.cond(healthy, keep, heal, None)
        md, parts, rs, rp = out[:4]
        st = BoundState(parts, out[4]) if gated else None
        return md, parts, st, rs, rp, 1 - healthy.astype(jnp.int32)

    def make_init(key):
        key, k0 = jax.random.split(key)
        first = first_fn(k0)
        centroids = jnp.zeros((k, d), pts.dtype).at[0].set(take_fn(first))
        indices = jnp.zeros((k,), jnp.int32).at[0].set(first)
        zk = jnp.zeros((k,), jnp.int32)
        return (jnp.ones((), jnp.int32), key, centroids, indices,
                init_min_d2, init_state, zk, zk, zk)

    def body(carry):
        m, key, centroids, indices, min_d2, state, skips, prunes, rec = carry
        min_d2, state = _inject_seed_fault(fault, m, min_d2, state)
        min_d2, partials, state, rs, rp, rc = checked_round(
            m, centroids, min_d2, state)
        skips = skips.at[m - 1].set(rs)
        prunes = prunes.at[m - 1].set(rp)
        rec = rec.at[m - 1].set(rc)
        # rnd.total is the paper's thrust::reduce term — kept for phi logging
        # (and, under guard, as the finite flag); the cdf sampler normalizes
        # by its OWN cumsum's last entry instead: serial and parallel
        # reductions sum in different orders, and a 1-ulp difference in the
        # scale flips boundary samples. With cdf[-1] every backend picks
        # bitwise-identical seeds (the paper's quality claim, verified
        # exactly in tests/test_engine.py). The tiled sampler draws from the
        # round partials instead, touching O(n/tile + tile) elements.
        key, ks = jax.random.split(key)
        weight = min_d2 if w is None else min_d2 * w
        nxt = sample_fn(ks, weight, partials)
        centroids = jax.lax.dynamic_update_index_in_dim(
            centroids, take_fn(nxt), m, 0)
        indices = indices.at[m].set(nxt)
        return (m + 1, key, centroids, indices, min_d2, state, skips,
                prunes, rec)

    def finish(carry):
        _, _, centroids, indices, min_d2, state, skips, prunes, rec = carry
        # final D^2 update against the last chosen centroid (callers like
        # k-means|| want the potential phi over *all* k centroids).
        min_d2, state = _inject_seed_fault(fault, k, min_d2, state)
        min_d2, _parts, _st, rs, rp, rc = checked_round(
            k, centroids, min_d2, state)
        skips = skips.at[k - 1].set(rs)
        prunes = prunes.at[k - 1].set(rp)
        rec = rec.at[k - 1].set(rc)
        return centroids, indices, min_d2, skips, prunes, rec

    return make_init, body, finish


def _seed_loop(key, pts, k, w, *, round_fn, first_fn, sample_fn, take_fn,
               init_min_d2, init_state: Optional[BoundState] = None,
               guard: bool = False, tile: Optional[int] = None, fault=None):
    """Generic k-means++ loop. The four hooks are the only difference between
    the single-device and the shard_map execution; the loop structure (and its
    PRNG key schedule) is shared so all backends pick identical seeds.

    ``init_state`` enables bound gating: the loop carries the previous
    round's (partials, tile_max) into each ``round_fn`` call, so rounds skip
    every tile the triangle-inequality bound proves unchanged. Round 1
    starts from tile_max = +inf (nothing skippable), which also fills the
    state. The per-round skipped-tile counts come back as a (k,) array;
    ``guard`` additionally verifies each round's total and heals poisoned
    carries (see ``_seed_parts``) — the (k,) recovery flags are the sixth
    output."""
    make_init, body, finish = _seed_parts(
        pts, k, w, round_fn=round_fn, first_fn=first_fn, sample_fn=sample_fn,
        take_fn=take_fn, init_min_d2=init_min_d2, init_state=init_state,
        guard=guard, tile=tile, fault=fault)
    carry = jax.lax.while_loop(lambda c: c[0] < k, body, make_init(key))
    return finish(carry)


_REJECT_ATTEMPTS = 8  # truncation depth of the rejection loop; past it the
#                       round falls back to an exact full draw (still exact)


def _seed_rejection_loop(key, pts, k, w, *, round_fn, first_fn, take_fn,
                         propose_fn, pq_fn, fallback_fn, n_tiles, all_tiles,
                         refresh_block, init_min_d2,
                         init_state: Optional[BoundState] = None,
                         init_partials: Optional[jax.Array] = None,
                         max_attempts: int = _REJECT_ATTEMPTS,
                         tile: Optional[int] = None, guard: bool = False,
                         fault=None, allreduce=None,
                         prep_fn=None, hier: bool = False):
    """Rejection-sampling k-means++ loop (sampler='rejection').

    Structural difference vs ``_seed_loop``: a round does NOT run the full
    D^2 refresh. Chosen centroids accumulate in a (refresh_block, d) PENDING
    buffer and the stale (min_d2, partials) pair from the LAST refresh is the
    dominating proposal envelope ``q_i = stale_min_d2[i] * w_i`` (valid
    because seeding only ever adds centroids — ``bounds.seed_envelope``). A
    round draws from the envelope (two-level tiled inverse-CDF locally, the
    distributed tiled choice on a mesh), evaluates the exact CURRENT weight
    of only the drawn row (``p = min(q, w * row_min_d2(row, pending))`` —
    O(refresh_block * d) work), and accepts with probability p/q. The full
    min-update refresh runs only (a) when the pending buffer fills, (b) when
    all ``max_attempts`` proposals reject — the round then falls back to an
    exact full draw from the freshened weights, keeping the truncated
    mixture exactly D^2-distributed — and (c) once at the end, so the
    returned min_d2 is exact over all k seeds. Expected full passes:
    O(k / refresh_block) instead of k.

    Refresh mechanics: the pending buffer is NEVER cleared — a refresh folds
    the whole (refresh_block, d) block through the ordinary (gated)
    ``seed_round`` and resets the count; rows past the count were folded by
    an earlier refresh, so re-folding them is a value-noop under ``min``.
    The count-mask lives in the p-evaluation instead (slots >= count are
    +inf), so a freshly-refreshed envelope gives ``p == q`` BITWISE and the
    first proposal always accepts.

    PRNG schedule: round m splits ``key, ks = split(key)`` exactly like
    ``_seed_loop``, and proposal attempt 0 consumes ``ks`` through the same
    uniform derivation as ``categorical_tiled`` — so with refresh_block=1
    (every round freshens, p == q) the chosen indices are BITWISE those of
    sampler='tiled' under a shared key: the pin the distribution tests rely
    on. The exact-fallback draw uses an independent fold of ``ks``.

    Telemetry: per-round ``skips`` reports ``all_tiles`` for rounds that
    never touched the dataset and the refresh kernel's (pod-wide on a mesh)
    count otherwise; ``props``/``accs`` count envelope draws and ratio-test
    accepts (the counter contract in ``KmeansppResult``).

    Envelope guard (always on): the rejection sampler's exactness needs the
    stale envelope to DOMINATE the current weights pointwise — a negative or
    NaN stale partial breaks that precondition, and an accepted draw against
    a broken envelope is silently biased. Every round therefore checks the
    (n_tiles,) partials for fp-validity (one O(n_tiles) read, psum-combined
    on a mesh via ``allreduce``) and, when invalid, REBUILDS the stale
    envelope BEFORE proposing: the corrupt carried (min_d2, partials, bound
    state) are discarded and the m - count centroids the envelope is
    supposed to cover are refolded ungated from the clean +inf carry.
    Pending rows stay pending (they are clean, carried separately), so the
    healed envelope is BITWISE the stale envelope a never-corrupted run
    carries — every subsequent proposal, accept test and chosen seed
    replays identically (recovery is bitwise, flagged in ``rec[m]``). A
    healthy envelope executes bitwise the unguarded loop (same attempt
    keys, same uniforms). ``guard`` additionally verifies the final
    settle-refresh total; ``tile`` (the partials tile height) is required
    for the rebuild path.

    Coarse-to-fine proposals (``proposal='hier'``): ``prep_fn(partials,
    pending, count) -> (pstate, tightened)`` runs ONCE per round (and after
    a fallback refresh) to build the proposal-side state the per-attempt
    draws reuse — the movement-tightened per-tile masses, their cumulative
    tile CDF and the gathered super-tile boundaries (see
    ``sampling.super_cdf``). ``pstate`` is threaded opaquely into
    ``propose_fn(kj, weight, partials, pstate)`` and ``pq_fn(idx, weight,
    pending, count, pstate)``; ``tightened`` (int32 scalar — tiles whose
    Raff cap beat the stale partial this round) and the per-round attempt
    count land in the ``tights``/``sups`` telemetry (``hier`` flags the
    sups accounting; both stay zero on the flat path). prep state is
    DERIVED from (partials, pending, count) every round — nothing coarse
    is carried, so the stale_super fault heals through the same partials
    refold as neg_envelope.
    """
    d = pts.shape[1]
    P = max(int(refresh_block), 1)
    ar = (lambda x: x) if allreduce is None else allreduce
    if tile is None:
        raise ValueError("the rejection loop needs the partials tile height")
    if prep_fn is None:
        prep_fn = lambda partials, pending, count: (  # noqa: E731
            (), jnp.zeros((), jnp.int32))
    key, k0 = jax.random.split(key)
    first = first_fn(k0)
    c0 = take_fn(first)
    centroids = jnp.zeros((k, d), pts.dtype).at[0].set(c0)
    indices = jnp.zeros((k,), jnp.int32).at[0].set(first)
    skips = jnp.zeros((k,), jnp.int32)
    prunes = jnp.zeros((k,), jnp.int32)
    props = jnp.zeros((k,), jnp.int32)
    accs = jnp.zeros((k,), jnp.int32)
    rec = jnp.zeros((k,), jnp.int32)
    tights = jnp.zeros((k,), jnp.int32)
    sups = jnp.zeros((k,), jnp.int32)
    # pending starts as P copies of the first centroid with count = P - 1:
    # round 1's append fills it, forcing the initial refresh (duplicate rows
    # are value-noops under the min-fold), which also replaces the +inf
    # init_min_d2 with a usable envelope before the first proposal
    pending = jnp.broadcast_to(c0[None, :], (P, d)).astype(pts.dtype)
    count = jnp.asarray(P - 1, jnp.int32)

    def refresh(md, state, pending, count):
        rnd = round_fn(pending, md, state)
        state = (None if state is None
                 else BoundState(rnd.partials, rnd.tile_max))
        return (rnd.min_d2, rnd.partials, state,
                jnp.asarray(rnd.skipped, jnp.int32),
                jnp.asarray(rnd.pruned, jnp.int32),
                jnp.zeros_like(count))

    def heal_stale(m, centroids, count):
        # the carried (md, partials, state) are untrusted: refold the
        # REFRESHED PREFIX — centroids 0..m-count-1, exactly the set the
        # stale envelope is supposed to cover — ungated from the clean +inf
        # carry. Rows past the prefix are replaced by centroid 0 (duplicate
        # rows are value-noops under the min-fold) so the block shape stays
        # static. min-folds are exact and order-independent, so the rebuilt
        # envelope is BITWISE the stale one a never-corrupted run carries;
        # the still-pending rows remain pending (count unchanged) and the
        # round's proposals replay identically.
        have = jnp.arange(k)[:, None] < (m - count)
        block = jnp.where(have, centroids, centroids[0][None, :]).astype(
            pending.dtype)
        rnd = round_fn(block, init_min_d2, None)
        state = (None if init_state is None
                 else BoundState(rnd.partials,
                                 bounds.tile_reduce_max(rnd.min_d2, tile)))
        return rnd.min_d2, rnd.partials, state

    def body(m, carry):
        (key, centroids, indices, md, partials, state, pending, count,
         skips, prunes, props, accs, rec, tights, sups) = carry
        pending = jax.lax.dynamic_update_index_in_dim(
            pending, centroids[m - 1].astype(pending.dtype), count, 0)
        count = count + 1
        rs0 = jnp.asarray(all_tiles, jnp.int32)  # untouched-round default
        rp0 = jnp.zeros((), jnp.int32)

        md, partials, state, rs, rp, count = jax.lax.cond(
            count >= P,
            lambda op: refresh(op[0], op[2], op[3], op[4]),
            lambda op: (op[0], op[1], op[2], rs0, rp0, op[4]),
            (md, partials, state, pending, count))

        if fault is not None and getattr(fault, "kind", None) == "neg_envelope":
            trip = jnp.asarray(m == fault.round)
            partials = jnp.where(trip, partials.at[0].set(-1.0), partials)
        if fault is not None and getattr(fault, "kind", None) == "stale_super":
            # a torn coarse aggregate: every tile partial backing the LAST
            # super-tile goes NaN (the super state is derived from the
            # partials each round, so a corrupt super IS a corrupt slice)
            trip = jnp.asarray(m == fault.round)
            lo = max(n_tiles - bounds.tiles_per_super(n_tiles), 0)
            partials = jnp.where(trip & (jnp.arange(n_tiles) >= lo),
                                 jnp.nan, partials)

        # envelope fp-validity: one scalar reduction (psum'd on a mesh).
        # Invalid -> rebuild the stale envelope BEFORE proposing, so the
        # round's proposal/accept stream replays bitwise the clean run's.
        bad = jnp.sum(jnp.where(
            jnp.isfinite(partials) & (partials >= 0), 0.0, 1.0))
        env_ok = ar(bad) == 0
        md, partials, state = jax.lax.cond(
            env_ok, lambda op: op[:3],
            lambda op: heal_stale(m, centroids, op[3]),
            (md, partials, state, count))

        # coarse-to-fine proposal state (tightened masses + tile/super CDFs):
        # built once per round from the HEALED partials, reused per attempt
        pstate, tightened = prep_fn(partials, pending, count)

        key, ks = jax.random.split(key)
        weight = bounds.seed_envelope(md, w)
        idx, ok, att = sampling.rejection_sample(
            ks,
            lambda kj: propose_fn(kj, weight, partials, pstate),
            lambda i: pq_fn(i, weight, pending, count, pstate),
            max_attempts=max_attempts)

        def fb(op):
            md, partials, state, count, rs, rp = op
            md, partials, state, rs2, rp2, count = refresh(
                md, state, pending, count)
            nxt = fallback_fn(jax.random.fold_in(ks, 0xFB),
                              bounds.seed_envelope(md, w), partials)
            return md, partials, state, count, rs2, rp + rp2, nxt

        md, partials, state, count, rs, rp, nxt = jax.lax.cond(
            ok,
            lambda op: op[:4] + (rs, rp, idx),
            fb,
            (md, partials, state, count, rs, rp))

        centroids = jax.lax.dynamic_update_index_in_dim(
            centroids, take_fn(nxt), m, 0)
        indices = indices.at[m].set(nxt)
        skips = skips.at[m - 1].set(rs)
        prunes = prunes.at[m - 1].set(rp)
        props = props.at[m].set(att)
        accs = accs.at[m].set(ok.astype(jnp.int32))
        rec = rec.at[m].set(1 - env_ok.astype(jnp.int32))
        tights = tights.at[m].set(tightened)
        if hier:
            # every hier attempt refines exactly one super window; the exact
            # fallback draw (when taken) visits one more
            sups = sups.at[m].set(att + (1 - ok.astype(jnp.int32)))
        return (key, centroids, indices, md, partials, state, pending, count,
                skips, prunes, props, accs, rec, tights, sups)

    # the zeros init is never drawn from: round 1's append always fills the
    # buffer (count starts at P - 1), so a refresh precedes the first proposal
    if init_partials is None:
        init_partials = jnp.zeros((n_tiles,), jnp.float32)
    (key, centroids, indices, md, partials, state, pending, count, skips,
     prunes, props, accs, rec, tights, sups) = jax.lax.fori_loop(
        1, k, body,
        (key, centroids, indices, init_min_d2, init_partials,
         init_state, pending, count, skips, prunes, props, accs, rec,
         tights, sups))
    # settle the refresh debt: fold the last chosen centroid plus every
    # still-pending one, so the returned min_d2 is exact over all k seeds
    pending = jax.lax.dynamic_update_index_in_dim(
        pending, centroids[k - 1].astype(pending.dtype), count, 0)
    rnd = round_fn(pending, md, state)
    final_md = rnd.min_d2
    if guard:
        healthy = jnp.isfinite(rnd.total)
        final_md = jax.lax.cond(
            healthy,
            lambda _: rnd.min_d2,
            lambda _: round_fn(centroids.astype(pending.dtype),
                               init_min_d2, None).min_d2,
            None)
        rec = rec.at[k - 1].max(1 - healthy.astype(jnp.int32))
    skips = skips.at[k - 1].set(jnp.asarray(rnd.skipped, jnp.int32))
    prunes = prunes.at[k - 1].set(jnp.asarray(rnd.pruned, jnp.int32))
    return (centroids, indices, final_md, skips, prunes, props, accs, rec,
            tights, sups)


def _stream_of(pts: jax.Array, precision: str) -> jax.Array:
    """The array the ROUND primitives stream: a bf16 copy at half the HBM
    bytes under precision='bf16' (norms/accumulators/min_d2 stay fp32), the
    full-precision points otherwise."""
    if precision == "bf16":
        return pts.astype(jnp.bfloat16)
    if precision != "fp32":
        raise ValueError(f"unknown precision {precision!r}; "
                         "expected 'fp32' or 'bf16'")
    return pts


def seed_points(key: jax.Array, points: jax.Array, k: int,
                weights: Optional[jax.Array], backend: Backend,
                sampler: str = "cdf", *, precision: str = "fp32",
                bound_gate: bool = True,
                cache: Optional[RoundCache] = None,
                refresh_block: int = 8, proposal: str = "hier",
                max_attempts: int = _REJECT_ATTEMPTS, guard: bool = False,
                fault=None, parts: bool = False):
    """Full k-means++ seeding through `backend` (untraced core; see
    ClusterEngine.seed for the jitted entry). Samplers: 'cdf' (full inverse
    CDF — the serial algorithm; fused and pallas pick bitwise-identical
    seeds everywhere, and serial/reference match them on origin-scale data —
    see docs/engine.md "Precision & bounds" for the parity domains),
    'gumbel' (Gumbel-max), 'tiled' (two-level inverse CDF from the round's
    per-tile partials — O(n/tile + tile) post-kernel reads per round),
    'rejection' (exact rejection sampling from the STALE envelope: rounds
    skip the full D^2 refresh entirely, touching only the drawn row, and
    refresh every ``refresh_block`` seeds — see _seed_rejection_loop;
    with refresh_block=1 it picks bitwise the 'tiled' seeds).

    ``proposal`` (rejection only) picks the proposal distribution's shape:
    'hier' (default) draws coarse-to-fine — super-tile -> tile -> row, with
    the per-tile envelope tightened between refreshes by the Raff cap from
    ``kernels.ops.tile_cap`` (tile summaries, never rows) — while 'flat'
    keeps PR 6's per-tile draw. Both are exact; 'hier' at refresh_block=1
    still picks bitwise the 'tiled' seeds (no pending centroids at proposal
    time -> every cap is +inf -> the draw telescopes to the flat one).
    ``max_attempts`` is the truncation depth of the rejection loop (the
    round falls back to one exact fresh-envelope draw past it).

    The prologue (cached fp32 norms + tile centroid-balls + per-point
    center distances) runs ONCE here — no round recomputes ||x||^2 — unless
    a precomputed ``cache`` is passed in (``kmeans_points`` shares one
    prologue across the seed AND fit phases). With ``bound_gate`` the loop
    carries the per-tile bound state so each round skips every
    provably-unchanged tile and short-circuits provably-stable points
    inside active tiles (exact: fp32 results are bitwise identical to the
    ungated path); with ``precision='bf16'`` the rounds stream a bf16 copy
    of the points (seeds are still *taken* from the full-precision
    array)."""
    if proposal not in ("flat", "hier"):
        raise ValueError(f"unknown proposal {proposal!r}; "
                         "expected 'flat' or 'hier'")
    if backend.distributed:
        return _seed_mesh(key, points, k, weights, backend, sampler,
                          precision=precision, bound_gate=bound_gate,
                          refresh_block=refresh_block, proposal=proposal,
                          max_attempts=max_attempts, guard=guard,
                          fault=fault)
    n, d = points.shape
    compute_dtype = jnp.promote_types(points.dtype, jnp.float32)
    pts = points.astype(compute_dtype)
    w = None if weights is None else weights.astype(compute_dtype)
    stream = _stream_of(pts, precision)
    if cache is None:
        cache = backend.prologue(pts, with_bounds=bound_gate)
    cache = _stream_cache(cache, stream)
    tile = backend.seed_tile(n, d)
    if bound_gate:
        n_tiles = -(-n // tile)
        init_state = BoundState(jnp.zeros((n_tiles,), jnp.float32),
                                jnp.full((n_tiles,), jnp.inf, jnp.float32))
    else:
        init_state = None

    hier = sampler == "rejection" and proposal == "hier"
    n_tiles = -(-n // tile)
    tps_ = backend.tiles_per_super(n_tiles)
    if w is None:
        def first_fn(k0):
            return jax.random.randint(k0, (), 0, n, dtype=jnp.int32)
    elif sampler in ("tiled", "rejection"):
        # first seed weighted by point weights (k-means|| reduce step): keep
        # the sub-O(n) property — two-level draw over the weights' own tile
        # partials instead of a full-n cumsum. Under proposal='hier' the
        # Capó-style coreset form: each super-tile is one coreset point
        # weighted by its gathered partial mass, and only the chosen super
        # is refined (bitwise the tiled draw — see sampling.categorical_hier)
        if hier:
            def first_fn(k0):
                return sampling.categorical_hier(
                    k0, w, sampling.tile_partials(w, tile),
                    block_n=tile, tps=tps_).astype(jnp.int32)
        else:
            def first_fn(k0):
                return sampling.categorical_tiled(
                    k0, w, sampling.tile_partials(w, tile),
                    block_n=tile).astype(jnp.int32)
    else:  # first seed weighted by point weights (k-means|| reduce step)
        def first_fn(k0):
            return sampling.categorical(k0, w, method="cdf").astype(jnp.int32)

    if sampler == "rejection":
        tiny = jnp.finfo(jnp.float32).tiny
        if w is None:
            # per-tile row counts: the unweighted tile mass the Raff cap
            # multiplies into a tile-level envelope bound
            tileW = jnp.full((n_tiles,), float(tile), jnp.float32) \
                .at[n_tiles - 1].set(float(n - (n_tiles - 1) * tile))
        else:
            tileW = sampling.tile_partials(w, tile).astype(jnp.float32)

        def prep_fn(partials, pending, count):
            # movement-tightened proposal state, rebuilt each round from the
            # HEALED partials: cap_t bounds every row's distance to the
            # pending block from tile summaries alone, so
            # min(partials_t, cap_t * W_t) is a valid tile envelope mass
            if cache.centers is not None:
                cap = backend.tile_cap(cache.centers, cache.radii,
                                       pending, count)
            else:  # bound_gate off: no ball summaries -> never tighten
                cap = jnp.full((n_tiles,), jnp.inf, jnp.float32)
            capw = cap * tileW  # inf*0 -> NaN: loses every < below
            ph = jnp.where(capw < partials, capw, partials)
            tightb = ph < partials
            tcdf = jnp.cumsum(ph)
            scdf = sampling.super_cdf(tcdf, tps_)
            return ((ph, tcdf, scdf, cap, tightb),
                    jnp.sum(tightb).astype(jnp.int32))

        if hier:
            def propose_fn(kj, weight, partials, pstate):
                ph, tcdf, scdf, cap, tightb = pstate
                u = jax.random.uniform(kj, (), weight.dtype)
                return sampling.hier_index_from_uniform(
                    u, weight, ph, tcdf, scdf, block_n=tile, tps=tps_,
                    cap=cap, tight=tightb, w=w)

            def pq_fn(idx, weight, pending, count, pstate):
                # the accept test must price the draw under the SAME
                # association the proposal used: tightened tiles drew rows
                # ∝ the capped window cwin with tile mass ph_t, so
                # q~ = cwin[li] * ph_t / sum(cwin) (>= the true weight:
                # both ph_t and sum(cwin) are min-bounds of the same mass);
                # untightened tiles keep the flat q = weight[idx] bitwise
                ph, tcdf, scdf, cap, tightb = pstate
                rd2 = backend.row_min_d2(pts, idx, pending, count)
                scale = 1.0 if w is None else w[idx]
                t = idx // tile
                li = idx - t * tile
                win = sampling.tile_window(weight, t, tile)
                cw = (cap[t] if w is None
                      else cap[t] * sampling.tile_window(w, t, tile))
                cwin = jnp.where(cw < win, cw, win)
                s_t = jnp.cumsum(cwin)[tile - 1]
                q = jnp.where(tightb[t],
                              cwin[li] * (ph[t] / jnp.maximum(s_t, tiny)),
                              weight[idx])
                return jnp.minimum(q, scale * rd2), q

            def fallback_fn(kf, weight, partials):
                return sampling.categorical_hier(
                    kf, weight, partials, block_n=tile,
                    tps=tps_).astype(jnp.int32)
        else:
            def propose_fn(kj, weight, partials, pstate):
                u = jax.random.uniform(kj, (), weight.dtype)
                return sampling.tiled_index_from_uniform(u, weight, partials,
                                                         block_n=tile)

            def pq_fn(idx, weight, pending, count, pstate):
                q = weight[idx]
                rd2 = backend.row_min_d2(pts, idx, pending, count)
                scale = 1.0 if w is None else w[idx]
                return jnp.minimum(q, scale * rd2), q

            def fallback_fn(kf, weight, partials):
                return sampling.categorical_tiled(
                    kf, weight, partials, block_n=tile).astype(jnp.int32)

        (centroids, indices, min_d2, skips, prunes, props, accs, rec,
         tights, sups) = _seed_rejection_loop(
            key, pts, k, w,
            round_fn=lambda c, md, st: backend.seed_round(
                stream, c.astype(stream.dtype), md, w, cache=cache,
                state=st),
            first_fn=first_fn,
            take_fn=lambda i: pts[i],
            propose_fn=propose_fn, pq_fn=pq_fn, fallback_fn=fallback_fn,
            prep_fn=prep_fn if hier else None, hier=hier,
            n_tiles=n_tiles, all_tiles=n_tiles,
            refresh_block=refresh_block, max_attempts=max_attempts,
            init_min_d2=jnp.full((n,), jnp.inf, compute_dtype),
            init_state=init_state, tile=tile, guard=guard, fault=fault)
        return KmeansppResult(centroids.astype(points.dtype), indices,
                              min_d2, skips if bound_gate else None,
                              prunes if bound_gate else None, props, accs,
                              recovered=rec if guard else None,
                              tightened=tights, supers=sups)

    if sampler == "tiled":
        def sample_fn(ks, weight, partials):
            return sampling.categorical_tiled(
                ks, weight, partials, block_n=tile).astype(jnp.int32)
    else:
        def sample_fn(ks, weight, partials):
            return sampling.categorical(
                ks, weight, method=sampler).astype(jnp.int32)

    loop_kwargs = dict(
        round_fn=lambda c, md, st: backend.seed_round(
            stream, c.astype(stream.dtype)[None, :], md, w, cache=cache,
            state=st),
        first_fn=first_fn,
        sample_fn=sample_fn,
        take_fn=lambda i: pts[i],
        init_min_d2=jnp.full((n,), jnp.inf, compute_dtype),
        init_state=init_state,
        guard=guard, tile=tile, fault=fault,
    )
    if parts:
        # the checkpointed driver runs the SAME loop in resumable chunks:
        # hand it (make_init, body, finish) instead of running to completion
        return _seed_parts(pts, k, w, **loop_kwargs)
    centroids, indices, min_d2, skips, prunes, rec = _seed_loop(
        key, pts, k, w, **loop_kwargs)
    return KmeansppResult(centroids.astype(points.dtype), indices, min_d2,
                          skips if bound_gate else None,
                          prunes if bound_gate else None,
                          recovered=rec if guard else None)


def _seed_mesh(key, points, k, weights, backend: MeshBackend,
               sampler: str = "cdf", *, precision: str = "fp32",
               bound_gate: bool = True,
               refresh_block: int = 8, proposal: str = "hier",
               max_attempts: int = _REJECT_ATTEMPTS, guard: bool = False,
               fault=None) -> KmeansppResult:
    """Distributed seeding: the same loop inside shard_map, with the sampler
    swapped for the exact distributed Gumbel-max and point lookup for the
    psum broadcast. Collective traffic per round is independent of N.

    sampler='tiled' composes the two-level draw with the distributed choice:
    per-shard tile selection via Gumbel over the round's partials, then an
    inverse-CDF inside only the winning tile, then the usual pmax/pmin shard
    combine — each shard reads O(n_local/tile + tile) elements post-kernel.
    sampler='rejection' composes the SAME distributed choice with the
    rejection loop over per-shard STALE envelopes: the owner shard of each
    proposal evaluates the exact (p, q) pair against its local pending block
    and one O(1)-byte psum broadcasts it, so the replicated key stream makes
    every shard take the identical accept/reject decision (and identical
    proposal/accept counters) without gathering any weights. Every other
    sampler name keeps the full-scan distributed Gumbel-max."""
    if weights is not None:
        raise NotImplementedError("mesh seeding does not take weights")
    axes = backend.axes

    def local_fn(kk, pp):
        return _seed_mesh_local(
            kk, pp.astype(jnp.float32), k, backend, sampler,
            precision=precision, bound_gate=bound_gate,
            refresh_block=refresh_block, proposal=proposal,
            max_attempts=max_attempts, guard=guard, fault=fault)

    if sampler == "rejection":
        mapped = _shard_map(
            backend, local_fn,
            in_specs=(P(), P(axes)),
            out_specs=(P(), P(), P(axes), P(), P(), P(), P(), P(),
                       P(), P()))
        (centroids, indices, min_d2, skips, prunes, props, accs, rec,
         tights, sups) = mapped(key, points)
        return KmeansppResult(centroids.astype(points.dtype), indices,
                              min_d2, skips if bound_gate else None,
                              prunes if bound_gate else None, props, accs,
                              recovered=rec if guard else None,
                              tightened=tights, supers=sups)

    mapped = _shard_map(
        backend, local_fn,
        in_specs=(P(), P(axes)),
        out_specs=(P(), P(), P(axes), P(), P(), P()))
    centroids, indices, min_d2, skips, prunes, rec = mapped(key, points)
    return KmeansppResult(centroids.astype(points.dtype), indices, min_d2,
                          skips if bound_gate else None,
                          prunes if bound_gate else None,
                          recovered=rec if guard else None)


def _seed_mesh_local(kk, pts, k, backend: MeshBackend, sampler: str, *,
                     precision: str, bound_gate: bool, refresh_block: int,
                     proposal: str, max_attempts: int, guard: bool,
                     fault=None, cache: Optional[RoundCache] = None):
    """One shard's side of `_seed_mesh`, run inside ``shard_map`` on the
    shard's fp32 rows ``pts``: returns the seeding loop's raw outputs
    (centroids first). A precomputed ``cache`` (``kmeans_points``) stands in
    for this call's own prologue."""
    axes = backend.axes
    n_local, d = pts.shape
    stream = _stream_of(pts, precision)
    if cache is None:
        cache = backend.prologue(pts, with_bounds=bound_gate)
    # the drawn rows come from the fp32 padded rows when the prologue made
    # them, so the loop holds no second copy of the shard in another layout
    src = pts if cache.rows is None else cache.rows
    cache = _stream_cache(cache, stream)
    tile = backend.seed_tile(n_local, d)
    n_tiles = -(-n_local // tile)
    if bound_gate:
        init_state = BoundState(
            backend.pvary(jnp.zeros((n_tiles,), jnp.float32)),
            backend.pvary(jnp.full((n_tiles,), jnp.inf, jnp.float32)))
    else:
        init_state = None
    first_fn = lambda k0: collectives.dist_gumbel_choice(  # noqa: E731
        k0, jnp.zeros((n_local,), jnp.float32), axes)
    take_fn = lambda i: collectives.take_global(  # noqa: E731
        src, i, axes, n_local)
    init_min_d2 = backend.pvary(
        jnp.full((n_local,), jnp.inf, jnp.float32))

    if sampler == "rejection":
        hier = proposal == "hier"
        tps_ = backend.tiles_per_super(n_tiles)
        tiny = jnp.finfo(jnp.float32).tiny
        # shard-local per-tile row counts (mesh seeding is unweighted)
        tileW = jnp.full((n_tiles,), float(tile), jnp.float32) \
            .at[n_tiles - 1].set(float(n_local - (n_tiles - 1) * tile))

        def prep_fn(partials, pending, count):
            # shard-local tightening from the shard-local prologue
            # balls; the tightened-tile count is psum'd so the
            # telemetry counter stays replicated like props/accs
            if cache.centers is not None:
                cap = backend.tile_cap(cache.centers, cache.radii,
                                       pending, count)
            else:
                cap = jnp.full((n_tiles,), jnp.inf, jnp.float32)
            capw = cap * tileW
            ph = jnp.where(capw < partials, capw, partials)
            tightb = ph < partials
            tight_n = jax.lax.psum(jnp.sum(tightb.astype(jnp.int32)),
                                   axes)
            return (ph, cap, tightb, count), tight_n

        def propose_hier(kj, weight, partials, pstate):
            # count is REPLICATED (it is carried from replicated accept
            # decisions), so every shard takes the same branch and the
            # collectives inside stay aligned. Fresh-envelope rounds
            # (count == 0 — always, at refresh_block=1) route through
            # the flat draw so its key schedule, and hence the
            # sampler='tiled' bitwise pin, is preserved.
            ph, cap, tightb, count = pstate
            return jax.lax.cond(
                count > 0,
                lambda _: collectives.dist_hier_choice(
                    kj, weight, ph, tile, tps_, axes,
                    cap=cap, tight=tightb),
                lambda _: collectives.dist_tiled_choice(
                    kj, weight, partials, tile, axes),
                None)

        def pq_fn(gidx, weight, pending, count, pstate):
            # the OWNER shard evaluates the drawn row's exact current
            # weight p and envelope weight q; one (2,)-fp32 psum
            # broadcasts them, keeping the accept decision replicated.
            # Tightened tiles price the draw as the capped window the
            # hier proposal drew from (see seed_points' pq_fn twin)
            me = collectives.axis_index(axes)
            local = jnp.clip(gidx - me * n_local, 0, n_local - 1)
            rd2 = backend.row_min_d2(pts, local, pending, count)
            if hier:
                ph, cap, tightb, _ = pstate
                t = local // tile
                li = local - t * tile
                win = sampling.tile_window(weight, t, tile)
                cwin = jnp.where(cap[t] < win, cap[t], win)
                s_t = jnp.cumsum(cwin)[tile - 1]
                q_loc = jnp.where(
                    tightb[t],
                    cwin[li] * (ph[t] / jnp.maximum(s_t, tiny)),
                    weight[local])
            else:
                q_loc = weight[local]
            vec = jnp.where(me == gidx // n_local,
                            jnp.stack([jnp.minimum(q_loc, rd2), q_loc]),
                            jnp.zeros((2,), jnp.float32))
            pq = jax.lax.psum(vec, axes)
            return pq[0], pq[1]

        if hier:
            propose_fn = propose_hier
            fallback_fn = lambda kf, weight, partials: \
                collectives.dist_hier_choice(kf, weight, partials,
                                             tile, tps_, axes)
        else:
            propose_fn = lambda kj, weight, partials, pstate: \
                collectives.dist_tiled_choice(kj, weight, partials,
                                              tile, axes)
            fallback_fn = lambda kf, weight, partials: \
                collectives.dist_tiled_choice(kf, weight, partials,
                                              tile, axes)

        return _seed_rejection_loop(
            kk, pts, k, None,
            round_fn=lambda c, md, st: backend.seed_round(
                stream, c.astype(stream.dtype), md, None, cache=cache,
                state=st),
            first_fn=first_fn, take_fn=take_fn,
            propose_fn=propose_fn,
            pq_fn=pq_fn,
            fallback_fn=fallback_fn,
            prep_fn=prep_fn if hier else None, hier=hier,
            n_tiles=n_tiles,
            all_tiles=n_tiles * collectives.axis_size(axes),
            refresh_block=refresh_block, max_attempts=max_attempts,
            init_min_d2=init_min_d2, init_state=init_state,
            init_partials=backend.pvary(
                jnp.zeros((n_tiles,), jnp.float32)),
            tile=tile, guard=guard, fault=fault,
            allreduce=lambda x: jax.lax.psum(x, axes))

    if sampler == "tiled":
        def sample_fn(ks, weight, partials):
            return collectives.dist_tiled_choice(ks, weight, partials,
                                                 tile, axes)
    else:
        def sample_fn(ks, weight, partials):
            return collectives.dist_gumbel_choice(
                ks, sampling.safe_log(weight), axes)

    return _seed_loop(
        kk, pts, k, None,
        round_fn=lambda c, md, st: backend.seed_round(
            stream, c.astype(stream.dtype)[None, :], md, None,
            cache=cache, state=st),
        first_fn=first_fn,
        sample_fn=sample_fn,
        take_fn=take_fn,
        init_min_d2=init_min_d2,
        init_state=init_state,
        guard=guard, tile=tile, fault=fault,
    )


# ---------------------------------------------------------------------------
# the Lloyd loop
# ---------------------------------------------------------------------------


def _inject_fit_fault(fault, i, rnd: AssignRound) -> AssignRound:
    """Test-only corruption of one Lloyd iteration's outputs (fault matrix).
    'zero_counts' halves the psum'd sums/counts — a dropped shard's
    contribution — tripping the count-mass check; 'nan_state' poisons the
    carried partial-sum inertia, tripping the finite check."""
    if fault is None:
        return rnd
    trip = jnp.asarray(i == fault.round)
    kind = getattr(fault, "kind", None)
    if kind == "zero_counts":
        s = jnp.where(trip, 0.5, 1.0)
        return rnd._replace(sums=rnd.sums * s, counts=rnd.counts * s)
    if kind == "nan_state" and rnd.state is not None:
        parts = jnp.where(trip, rnd.state.partials.at[0].set(jnp.nan),
                          rnd.state.partials)
        return rnd._replace(state=rnd.state._replace(partials=parts))
    return rnd


def _fit_gated_parts(pts, stream, init_centroids, backend: Backend,
                     max_iters, tol, empty, norms, cache, *,
                     guard: bool = False, fault=None):
    """(cond, body, make_init) of the gated Lloyd while-loop — the carry is
    ``(i, cents, prev_inertia, inertia, prev_cents, bstate, skips, prunes,
    rec)``. Split out of ``_fit_loop`` so the checkpointed driver can run
    the SAME loop in chunks (``while_loop(cond & (i < stop), body, carry)``)
    and serialize the carry between chunks, with bitwise-identical
    iterations.

    ``guard`` adds the in-flight corruption detector: each iteration checks
    the psum'd inertia for finiteness and the psum'd count mass against the
    global n (a dropped shard's contribution shows up as missing mass —
    both checks are O(1) on top of reductions the round already does). On a
    trip the carried bound state is DISCARDED and the iteration re-runs
    ungated from the same centroids — exact gating makes the healed results
    bitwise those of a never-corrupted run; only the skip/prune counters
    differ (the rebuilt state has no per-point bounds, so the next
    iteration prunes less). ``rec[i]`` records the trip.
    """
    n, d = pts.shape
    k = init_centroids.shape[0]
    tile = backend.seed_tile(n, d, k)
    n_tiles = -(-n // tile)
    n_super = -(-n_tiles // backend.tiles_per_super(n_tiles))
    pv = backend.pvary
    init_state = BoundState(
        pv(jnp.zeros((n_tiles,), jnp.float32)),
        tile_gap=pv(jnp.full((n_tiles,), -jnp.inf, jnp.float32)),
        tile_sums=pv(jnp.zeros((n_super, k, d), jnp.float32)),
        tile_counts=pv(jnp.zeros((n_super, k), jnp.float32)),
        assignment=pv(jnp.zeros((n,), jnp.int32)),
        min_d2=pv(jnp.zeros((n,), jnp.float32)),
        point_lb=pv(jnp.full((n,), -jnp.inf, jnp.float32)),
        lb_debt=pv(jnp.zeros((n_tiles,), jnp.float32)))
    n_total = (backend.allreduce(pv(jnp.asarray(float(n), jnp.float32)))
               if guard else None)

    def cond(state):
        i, prev_inertia, inertia = state[0], state[2], state[3]
        rel = (prev_inertia - inertia) / jnp.maximum(prev_inertia, 1e-30)
        return jnp.logical_and(i < max_iters,
                               jnp.logical_or(i < 2, rel > tol))

    def body(state):
        i, cents, _, inertia, prev_cents, bstate, skips, prunes, rec = state
        delta = bounds.centroid_movement(cents, prev_cents)
        rnd = backend.assign_update(stream, cents.astype(stream.dtype),
                                    None, norms, cache=cache,
                                    state=bstate, delta=delta)
        rnd = _inject_fit_fault(fault, i, rnd)
        new_inertia = backend.allreduce(jnp.sum(rnd.state.partials))
        if not guard:
            bstate2, sums, counts = rnd.state, rnd.sums, rnd.counts
            rs = jnp.asarray(rnd.skipped, jnp.int32)
            rp = jnp.asarray(rnd.pruned, jnp.int32)
            healed = jnp.zeros((), jnp.int32)
        else:
            mass = jnp.sum(rnd.counts)  # counts are already psum'd on a mesh
            healthy = (jnp.isfinite(new_inertia)
                       & (jnp.abs(mass - n_total) < 0.5))

            def keep(_):
                return (rnd.state, rnd.sums, rnd.counts, new_inertia,
                        jnp.asarray(rnd.skipped, jnp.int32),
                        jnp.asarray(rnd.pruned, jnp.int32))

            def heal(_):
                # the carried bound state is untrusted: re-run this
                # iteration UNGATED (exact, touches every tile) and rebuild
                # the carry from its outputs. The ungated round carries no
                # per-point bounds, so point_lb/lb_debt restart pessimistic
                # (-inf / 0): later iterations prune less but compute the
                # bitwise-same results.
                r2 = backend.assign_update(stream,
                                           cents.astype(stream.dtype),
                                           None, norms, cache=cache)
                st = r2.state._replace(
                    point_lb=pv(jnp.full((n,), -jnp.inf, jnp.float32)),
                    lb_debt=pv(jnp.zeros((n_tiles,), jnp.float32)))
                return (st, r2.sums, r2.counts,
                        backend.allreduce(jnp.sum(r2.state.partials)),
                        jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))

            bstate2, sums, counts, new_inertia, rs, rp = jax.lax.cond(
                healthy, keep, heal, None)
            healed = 1 - healthy.astype(jnp.int32)
        new_cents = centroid_means(sums, counts, cents)
        if empty == "reseed":
            new_cents = reseed_split_largest(new_cents, counts)
        skips = skips.at[i].set(rs)
        prunes = prunes.at[i].set(rp)
        rec = rec.at[i].set(healed)
        return (i + 1, new_cents, inertia, new_inertia, cents, bstate2,
                skips, prunes, rec)

    def make_init():
        return (jnp.zeros((), jnp.int32),
                init_centroids.astype(jnp.float32), jnp.inf, jnp.inf,
                init_centroids.astype(jnp.float32), init_state,
                jnp.zeros((max_iters,), jnp.int32),
                jnp.zeros((max_iters,), jnp.int32),
                jnp.zeros((max_iters,), jnp.int32))

    return cond, body, make_init


def _fit_loop(pts, init_centroids, w, backend: Backend, max_iters, tol,
              empty: str = "keep", precision: str = "fp32",
              bound_gate: bool = True, cache: Optional[RoundCache] = None,
              guard: bool = False, fault=None):
    """Lloyd iterations until the relative inertia improvement falls below
    `tol` or `max_iters` is hit. The k-means potential is monotonically
    non-increasing — a property test asserts this — except under
    empty='reseed', where a reseeded centroid may transiently raise it before
    splitting the donor cluster pays off.

    The prologue runs ONCE here: cached fp32 ``||x||^2`` (norm caching — no
    iteration recomputes it) plus, under ``bound_gate``, the tile
    centroid-balls. Unweighted fits run the TILED assignment round (per-tile
    inertia partials and per-tile cluster sums/counts, reduced over the tile
    axis — the one reduction tree the gated and ungated paths share), and
    with ``bound_gate`` the loop threads a `BoundState` through every
    ``assign_update`` exactly like the seeding loop threads its round state:
    each iteration derives the per-centroid movement ``delta`` and SKIPS
    every tile the movement bound proves unchanged — exactly (fp32 results
    are bitwise identical to bound_gate=False). With precision='bf16' the
    iterations stream bf16 points/centroids while the norms, per-cluster
    accumulators, bound state and the centroid carry stay fp32.

    Returns (centroids, assignment, inertia, n_iters, skips, prunes) —
    ``skips``/``prunes`` are the (max_iters,) per-iteration skipped-tile /
    pruned-point counts, or None when the gate is off or the fit is
    weighted (the legacy accumulated path). A precomputed ``cache`` (from
    ``kmeans_points``) suppresses this call's own prologue."""
    k = init_centroids.shape[0]
    n, d = pts.shape
    stream = _stream_of(pts, precision)
    tiled = w is None
    if tiled:
        if cache is None:
            cache = backend.prologue(pts, m=k, with_bounds=bound_gate)
        cache = _stream_cache(cache, stream)
        norms = cache.norms             # once per fit, NOT once per iteration
    else:
        norms = (cache.norms if cache is not None
                 else bounds.point_norms(pts))
        cache = None

    def cond(state):
        i, _, prev_inertia, inertia = state[0], state[1], state[2], state[3]
        rel = (prev_inertia - inertia) / jnp.maximum(prev_inertia, 1e-30)
        return jnp.logical_and(i < max_iters,
                               jnp.logical_or(i < 2, rel > tol))

    if tiled and bound_gate:
        gcond, gbody, make_init = _fit_gated_parts(
            pts, stream, init_centroids, backend, max_iters, tol, empty,
            norms, cache, guard=guard, fault=fault)
        i, cents, _, inertia, _, bstate, skips, prunes, rec = \
            jax.lax.while_loop(gcond, gbody, make_init())
        return (cents, bstate.assignment, inertia, i, skips, prunes,
                rec if guard else None)

    def body(state):
        i, cents, _, inertia, a = state
        rnd = backend.assign_update(stream, cents.astype(stream.dtype), w,
                                    norms, cache=cache)
        if tiled:
            new_inertia = backend.allreduce(jnp.sum(rnd.state.partials))
        else:
            mw = rnd.min_d2 if w is None else rnd.min_d2 * w
            new_inertia = backend.allreduce(jnp.sum(mw))
        new_cents = centroid_means(rnd.sums, rnd.counts, cents)
        if empty == "reseed":
            new_cents = reseed_split_largest(new_cents, rnd.counts)
        return i + 1, new_cents, inertia, new_inertia, rnd.assignment

    init = (jnp.zeros((), jnp.int32), init_centroids.astype(jnp.float32),
            jnp.inf, jnp.inf, backend.pvary(jnp.zeros((n,), jnp.int32)))
    i, cents, _, inertia, a = jax.lax.while_loop(cond, body, init)
    return cents, a, inertia, i, None, None, None


def _check_empty(empty: str):
    if empty not in ("keep", "reseed"):
        raise ValueError(f"unknown empty-cluster policy {empty!r}; "
                         "expected 'keep' or 'reseed'")


def fit_points(points: jax.Array, init_centroids: jax.Array,
               weights: Optional[jax.Array], backend: Backend,
               max_iters: int, tol: float, empty: str = "keep",
               precision: str = "fp32", bound_gate: bool = True,
               cache: Optional[RoundCache] = None, guard: bool = False,
               fault=None) -> LloydResult:
    """Lloyd clustering through `backend` (untraced core). `empty` picks the
    empty-cluster policy: 'keep' (previous centroid survives) or 'reseed'
    (split the largest cluster — see reseed_split_largest). ``cache`` is an
    optional precomputed prologue (``kmeans_points`` shares one across the
    seed and fit phases). ``guard`` turns on the in-flight corruption
    detector (gated unweighted fits only — see ``_fit_gated_parts``)."""
    _check_empty(empty)
    if backend.distributed:
        return _fit_mesh(points, init_centroids, weights, backend,
                         max_iters, tol, empty, precision, bound_gate,
                         guard=guard, fault=fault)
    cents, a, inertia, i, skips, prunes, rec = _fit_loop(
        points, init_centroids, weights, backend, max_iters, tol, empty,
        precision, bound_gate, cache, guard=guard, fault=fault)
    return LloydResult(cents.astype(points.dtype), a, inertia, i, skips,
                       prunes, recovered=rec)


def _fit_mesh(points, init_centroids, weights, backend: MeshBackend,
              max_iters, tol, empty: str = "keep", precision: str = "fp32",
              bound_gate: bool = True, guard: bool = False,
              fault=None) -> LloydResult:
    axes = backend.axes
    gated = weights is None and bound_gate

    if weights is None:
        def local_fn(pp, cc):
            return _fit_loop(pp.astype(jnp.float32), cc, None, backend,
                             max_iters, tol, empty, precision, bound_gate,
                             guard=guard, fault=fault)
        in_specs = (P(axes), P())
        args = (points, init_centroids)
    else:
        def local_fn(pp, cc, ww):
            return _fit_loop(pp.astype(jnp.float32), cc, ww, backend,
                             max_iters, tol, empty, precision, bound_gate,
                             guard=guard, fault=fault)
        in_specs = (P(axes), P(), P(axes))
        args = (points, init_centroids, weights)

    del gated  # the skips/prunes/recovered leaves are replicated when
    #            present, absent otherwise; P() is a valid prefix spec for
    #            the empty (None) subtree too
    mapped = _shard_map(
        backend, local_fn,
        in_specs=in_specs,
        out_specs=(P(), P(axes), P(), P(), P(), P(), P()))
    cents, a, inertia, i, skips, prunes, rec = mapped(*args)
    return LloydResult(cents.astype(points.dtype), a, inertia, i, skips,
                       prunes, recovered=rec)


def kmeans_points(key: jax.Array, points: jax.Array, k: int,
                  weights: Optional[jax.Array], backend: Backend,
                  sampler: str = "cdf", max_iters: int = 50,
                  tol: float = 1e-6, empty: str = "keep",
                  precision: str = "fp32",
                  bound_gate: bool = True,
                  refresh_block: int = 8, proposal: str = "hier",
                  max_attempts: int = _REJECT_ATTEMPTS,
                  guard: bool = False) -> LloydResult:
    """End-to-end k-means++ seeding + Lloyd with ONE shared prologue.

    The seed phase and the fit phase historically each ran
    ``backend.prologue`` over the same points (two full O(n·d) streaming
    passes, two norm computations). Here the (local) backend's ``tile_m``
    is pinned to k so both phases agree on one tile geometry, the prologue
    runs once, and the same RoundCache threads through the seeding loop and
    the Lloyd loop — a jaxpr test pins that the whole kmeans program
    computes the row norms exactly once. On a mesh backend the same body
    runs once per shard inside ONE ``shard_map``: one prologue and one pad
    of each shard per call, the distributed seeding of ``_seed_mesh`` and
    the sharded Lloyd loop of ``_fit_mesh`` in one program, computing what
    the two calls compute (bitwise on the CPU, tests/test_mesh_kmeans.py)."""
    _check_empty(empty)
    body = functools.partial(
        _kmeans_shared, k=k, sampler=sampler, max_iters=max_iters, tol=tol,
        empty=empty, precision=precision, bound_gate=bound_gate,
        refresh_block=refresh_block, proposal=proposal,
        max_attempts=max_attempts, guard=guard)
    if not backend.distributed:
        return body(key, points, weights, dataclasses.replace(backend,
                                                              tile_m=k))
    if weights is not None:
        raise NotImplementedError("mesh seeding does not take weights")
    be = dataclasses.replace(
        backend, local=dataclasses.replace(backend.local, tile_m=k))

    def local_fn(kk, pp):
        r = body(kk, pp, None, be)
        return (r.centroids, r.assignment, r.inertia, r.n_iters, r.skipped,
                r.pruned, r.recovered)

    mapped = _shard_map(
        be, local_fn, in_specs=(P(), P(be.axes)),
        out_specs=(P(), P(be.axes), P(), P(), P(), P(), P()))
    cents, a, inertia, i, skips, prunes, rec = mapped(key, points)
    return LloydResult(cents, a, inertia, i, skips, prunes, recovered=rec)


def _kmeans_shared(key, points, weights, be: Backend, *, k, sampler,
                   max_iters, tol, empty, precision, bound_gate,
                   refresh_block, proposal, max_attempts,
                   guard) -> LloydResult:
    """`kmeans_points`' body over the rows it is given: all of them on a
    local backend, one shard's inside ``shard_map`` on a mesh backend."""
    pts = points.astype(jnp.promote_types(points.dtype, jnp.float32))
    cache = be.prologue(pts, m=k, with_bounds=bound_gate)
    seed_kw = dict(precision=precision, bound_gate=bound_gate, cache=cache,
                   refresh_block=refresh_block, proposal=proposal,
                   max_attempts=max_attempts, guard=guard)
    if be.distributed:
        # the seeds pass through the points' dtype, as they do between
        # the mesh `seed` and `fit` calls
        seeds = _seed_mesh_local(key, pts, k, be, sampler,
                                 **seed_kw)[0].astype(points.dtype)
    else:
        seeds = seed_points(key, pts, k, weights, be, sampler,
                            **seed_kw).centroids
    cents, a, inertia, i, skips, prunes, rec = _fit_loop(
        pts, seeds, weights, be, max_iters, tol, empty, precision,
        bound_gate, cache, guard=guard)
    return LloydResult(cents.astype(points.dtype), a, inertia, i, skips,
                       prunes, recovered=rec)


# ---------------------------------------------------------------------------
# mini-batch Lloyd (streaming)
# ---------------------------------------------------------------------------


def minibatch_step(cents, counts, batch, backend: Backend,
                   precision: str = "fp32"):
    """One mini-batch Lloyd step (Sculley 2010, batch form): per-center counts
    give each center a 1/t-decaying learning rate, so centers converge to the
    running mean of every point ever assigned to them.

        c_j <- c_j + eta_j * (batch_mean_j - c_j),  eta_j = m_j / (N_j + m_j)

    With precision='bf16' the batch streams through the SAME half-width
    tile path as full fit (bf16 points/centroids into the MXU, fp32 norms
    computed per batch, fp32 accumulators and fp32 centroid carry)."""
    pts = batch.astype(jnp.promote_types(batch.dtype, jnp.float32))
    stream = _stream_of(pts, precision)
    norms = bounds.point_norms(pts)
    rnd = backend.assign_update(stream, cents.astype(stream.dtype), None,
                                norms)
    bcounts = rnd.counts
    new_counts = counts + bcounts
    eta = jnp.where(new_counts > 0,
                    bcounts / jnp.maximum(new_counts, 1.0), 0.0)
    bmeans = rnd.sums / jnp.maximum(bcounts, 1e-12)[:, None]
    new_cents = jnp.where((bcounts > 0)[:, None],
                          cents + eta[:, None] * (bmeans - cents), cents)
    return new_cents, new_counts, jnp.sum(rnd.min_d2), rnd.assignment


BatchSource = Union[Iterable, Callable[[int], "jax.typing.ArrayLike"]]


def _iter_batches(batches: BatchSource, n_batches: Optional[int]):
    """Normalize a batch source into an iterator of arrays.

    Accepts a callable ``read_fn(step) -> array`` (wrapped in a prefetching
    ``repro.data.pipeline.DataPipeline``), a DataPipeline instance (yields
    ``(step, batch)`` pairs), or any iterable of arrays / (step, array) pairs.
    """
    from repro.data.pipeline import DataPipeline

    pipe = None
    if callable(batches) and not hasattr(batches, "__iter__"):
        if n_batches is None:
            raise ValueError("n_batches is required with a read_fn source")
        pipe = DataPipeline(batches)
        batches = iter(pipe)
    elif isinstance(batches, DataPipeline) and n_batches is None:
        # a pipeline streams forever; without a count the loop never ends
        raise ValueError("n_batches is required with a DataPipeline source")
    try:
        for i, item in enumerate(batches):
            if n_batches is not None and i >= n_batches:
                return
            if isinstance(item, tuple) and len(item) == 2:
                item = item[1]
            if isinstance(item, dict):
                item = item["points"]
            yield jnp.asarray(item)
    finally:
        if pipe is not None:
            pipe.stop()


# ---------------------------------------------------------------------------
# ClusterEngine
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "backend", "sampler",
                                             "precision", "bound_gate",
                                             "refresh_block", "proposal",
                                             "max_attempts", "guard",
                                             "fault"))
def _seed_jit(key, points, weights, k, backend, sampler, precision,
              bound_gate, refresh_block, proposal="hier", max_attempts=8,
              guard=False, fault=None):
    return seed_points(key, points, k, weights, backend, sampler,
                       precision=precision, bound_gate=bound_gate,
                       refresh_block=refresh_block, proposal=proposal,
                       max_attempts=max_attempts, guard=guard, fault=fault)


@functools.partial(jax.jit,
                   static_argnames=("backend", "max_iters", "tol", "empty",
                                    "precision", "bound_gate", "guard",
                                    "fault"))
def _fit_jit(points, init_centroids, weights, backend, max_iters, tol, empty,
             precision, bound_gate, guard=False, fault=None):
    return fit_points(points, init_centroids, weights, backend,
                      max_iters, tol, empty, precision, bound_gate,
                      guard=guard, fault=fault)


@functools.partial(jax.jit,
                   static_argnames=("k", "backend", "sampler", "max_iters",
                                    "tol", "empty", "precision",
                                    "bound_gate", "refresh_block",
                                    "proposal", "max_attempts", "guard"))
def _kmeans_jit(key, points, weights, k, backend, sampler, max_iters, tol,
                empty, precision, bound_gate, refresh_block, proposal="hier",
                max_attempts=8, guard=False):
    return kmeans_points(key, points, k, weights, backend, sampler,
                         max_iters, tol, empty, precision, bound_gate,
                         refresh_block=refresh_block, proposal=proposal,
                         max_attempts=max_attempts, guard=guard)


@functools.partial(jax.jit, static_argnames=("backend", "precision"))
def _minibatch_jit(cents, counts, batch, backend, precision):
    return minibatch_step(cents, counts, batch, backend, precision)


@functools.partial(jax.jit, static_argnames=("k", "backend", "sampler",
                                             "precision", "bound_gate",
                                             "refresh_block", "proposal",
                                             "max_attempts"))
def _seed_batched_jit(keys, points, k, backend, sampler, precision,
                      bound_gate, refresh_block, proposal="hier",
                      max_attempts=8):
    return jax.vmap(
        lambda kk, pp: seed_points(kk, pp, k, None, backend, sampler,
                                   precision=precision,
                                   bound_gate=bound_gate,
                                   refresh_block=refresh_block,
                                   proposal=proposal,
                                   max_attempts=max_attempts)
    )(keys, points)


@functools.partial(jax.jit,
                   static_argnames=("backend", "max_iters", "tol", "empty",
                                    "precision", "bound_gate"))
def _fit_batched_jit(points, init_centroids, backend, max_iters, tol, empty,
                     precision, bound_gate):
    return jax.vmap(
        lambda pp, cc: fit_points(pp, cc, None, backend, max_iters, tol,
                                  empty, precision, bound_gate)
    )(points, init_centroids)


class ClusterEngine:
    """One engine for seeding + clustering over a pluggable Backend.

    >>> eng = ClusterEngine("pallas")
    >>> seeds = eng.seed(key, points, k=50)
    >>> out = eng.fit(points, seeds.centroids)

    Backends: 'reference' (serial/global semantics), 'fused' (XLA),
    'pallas' (TPU kernels), 'mesh' (shard_map; pass mesh=..., axes=...,
    local=...). fused and pallas pick bitwise-identical seeds under the same
    key everywhere; serial/reference match them on origin-scale data (the
    matmul-form D^2 the cached-norm backends share has absolute fp32 error
    in ‖x‖², the reference diff-square form relative — see docs/engine.md);
    mesh uses the distributed Gumbel-max sampler instead, which preserves
    the distribution rather than the bits.

    Two engine-wide knobs (see docs/engine.md "Precision & bounds"):

    * ``precision`` — 'fp32' (default) or 'bf16': stream the round kernels'
      point/centroid tiles as bf16 (half the HBM bytes on the memory-bound
      rounds) with fp32 cached norms, fp32 accumulation and fp32 carried
      state. Seeds are still taken from the full-precision points.
    * ``bounds`` — True (default) carries per-tile bound state through the
      seeding loop so each round SKIPS every tile the triangle-inequality
      bound proves unchanged. Skipping is exact: the fp32 results are
      bitwise identical to bounds=False; per-round skipped-tile counts come
      back in ``KmeansppResult.skipped``.
    * ``validate`` — 'raise' (default), 'sanitize', or 'off': the
      entry-point input guard (NaN/Inf rows, degenerate weights — see
      ``core.guards``). Any setting other than 'off' ALSO turns on the
      in-flight corruption detector inside the loops: each round checks the
      psum'd total/inertia (and count mass) and, on a trip, discards the
      carried bound state and replays the round ungated — results stay
      bitwise those of an uncorrupted run, with the trip recorded in the
      result's ``recovered`` counter (see docs/engine.md "Failure
      semantics").

    Kernel failures walk a backend fallback chain (pallas -> fused ->
    reference): a ``KernelFailureError`` from a compile/launch retries the
    call on the next backend down, warning once; the hops are recorded in
    ``self.fallback_events`` and the backend that actually served the last
    call in ``self.last_backend``.
    """

    def __init__(self, backend: Union[str, Backend] = "fused", *,
                 precision: str = "fp32", bounds: bool = True,
                 validate: str = "raise", tune: str = "off",
                 tune_dir=None, **backend_opts):
        if precision not in ("fp32", "bf16"):
            raise ValueError(f"unknown precision {precision!r}; "
                             "expected 'fp32' or 'bf16'")
        if tune not in ("off", "cache", "auto"):
            raise ValueError(f"unknown tune {tune!r}; "
                             "expected 'off', 'cache' or 'auto'")
        self.backend = make_backend(backend, **backend_opts)
        self.precision = precision
        self.bounds = bool(bounds)
        self.validate = guards.check_policy(validate)
        self._guard = validate != "off"
        self.tune = tune
        self.tune_dir = tune_dir
        self._tune_cache = None   # lazy repro.tune.TuneCache
        self.fallback_events: list = []   # (failed, fallback, reason) hops
        self.last_backend: Backend = self.backend
        self._warned_fallback = False
        self._last_launch = None   # (jitted fn, abstract args), see _launch

    # -- autotune plumbing -------------------------------------------------
    def _tune_for(self, n: int, k: int, d: int, dtype):
        """(tuned backend | None, TuneRecord | None) for one call shape.

        tune='off' is the identity: callers run the engine's own backend
        and attach no provenance. 'cache' consults the persisted cache only
        (zero measurement/search calls — pinned by test); 'auto' searches
        on a miss and persists the winner. The tuned geometry is applied as
        a `dataclasses.replace` of the (local) backend — `block_n` can only
        SHRINK the heuristic pick and `tps` is clamped/pow2-floored by
        `bounds.tiles_per_super`, so any cached value is VMEM-safe even via
        the nearest-shape fallback."""
        if self.tune == "off":
            return None, None
        from repro import tune as _tune
        if self._tune_cache is None:
            self._tune_cache = _tune.TuneCache(self.tune_dir)
        rec = _tune.resolve(self._tune_cache, n=int(n), k=int(k), d=int(d),
                            backend=self.backend,
                            dtype=jnp.dtype(dtype).name, mode=self.tune)
        if rec is None:
            return None, None
        if self.backend.distributed:
            be = dataclasses.replace(
                self.backend,
                local=dataclasses.replace(self.backend.local,
                                          block_n=int(rec.block_n),
                                          tps=int(rec.tps)))
        else:
            be = dataclasses.replace(self.backend,
                                     block_n=int(rec.block_n),
                                     tps=int(rec.tps))
        return be, rec

    @staticmethod
    def _tune_sampler(sampler, refresh_block, rec, proposal="hier"):
        """Resolve sampler='auto' against a TuneRecord (tiled when tuning
        is off or nothing is known). The tuned proposal shape rides along:
        an explicit ``proposal=`` always wins, sampler='auto' with a record
        that carries one takes the record's."""
        if sampler != "auto":
            return sampler, refresh_block, proposal
        if rec is None or not rec.sampler:
            return "tiled", refresh_block, proposal
        if rec.refresh_block:
            refresh_block = int(rec.refresh_block)
        if getattr(rec, "proposal", ""):
            proposal = rec.proposal
        return rec.sampler, refresh_block, proposal

    # -- robustness plumbing ----------------------------------------------
    def _run(self, fn, backend: Optional[Backend] = None):
        """Run ``fn(backend)``, walking the kernel fallback chain on
        KernelFailureError. Each hop swaps the (local) backend for the next
        one down (pallas -> fused -> reference; a mesh backend swaps its
        per-shard ``local``), carrying the tuned geometry fields
        (``tile_m``/``block_n``/``tps``) across the swap, warns once per
        engine, and is appended to ``self.fallback_events``. The error
        escapes only when the chain is exhausted. ``backend`` overrides the
        engine's own backend for this call (the tuned replica from
        ``_tune_for``)."""
        from repro.kernels import ops
        be = self.backend if backend is None else backend
        while True:
            try:
                out = fn(be)
                self.last_backend = be
                return out
            except guards.KernelFailureError as e:
                failed = be.local.name if be.distributed else be.name
                nxt = ops.FALLBACK_CHAIN.get(failed)
                if nxt is None:
                    raise
                if be.distributed:
                    loc = dataclasses.replace(make_backend(nxt),
                                              tile_m=be.local.tile_m,
                                              block_n=be.local.block_n,
                                              tps=be.local.tps)
                    be = dataclasses.replace(be, local=loc)
                else:
                    be = dataclasses.replace(make_backend(nxt),
                                             tile_m=be.tile_m,
                                             block_n=be.block_n,
                                             tps=be.tps)
                self.fallback_events.append((failed, nxt, str(e)))
                if not self._warned_fallback:
                    warnings.warn(
                        f"kernel backend {failed!r} failed ({e}); falling "
                        f"back to {nxt!r}", RuntimeWarning, stacklevel=3)
                    self._warned_fallback = True

    def _launch(self, jitted, *args):
        """Call the compiled entry ``jitted(*args)``, remembering it (with
        shape/dtype/sharding stand-ins for the arrays, so no buffer is kept
        alive) for `lowered_last_call`."""
        def abstract(a):
            if isinstance(a, jax.Array):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=a.sharding)
            return a
        self._last_launch = (jitted, tuple(abstract(a) for a in args))
        return jitted(*args)

    def lowered_last_call(self) -> jax.stages.Lowered:
        """The lowered program of this engine's last compiled call (seed,
        fit, kmeans, minibatch step or batched call), as the backend that
        served it ran it. ``.as_text()`` shows, e.g., whether the Pallas
        kernels compiled (``tpu_custom_call``) or were interpreted."""
        if self._last_launch is None:
            raise ValueError("this engine has made no compiled call yet")
        jitted, args = self._last_launch
        if not self.backend.distributed:
            return jitted.lower(*args)
        with jax.set_mesh(self.backend.mesh):
            return jitted.lower(*args)

    # -- seeding ----------------------------------------------------------
    @_mesh_scoped
    def seed(self, key: jax.Array, points: jax.Array, k: int, *,
             weights: Optional[jax.Array] = None,
             sampler: str = "cdf",
             refresh_block: int = 8, proposal: str = "hier",
             max_attempts: int = _REJECT_ATTEMPTS,
             checkpoint_dir=None, checkpoint_every: int = 1,
             _fault=None) -> KmeansppResult:
        """K-means++ seeding: k centroids chosen from `points` ∝ D^2.

        sampler: 'cdf' (full inverse-CDF, bitwise-pinned across local
        backends), 'gumbel' (Gumbel-max), 'tiled' (two-level draw from the
        round kernel's per-tile partials — O(n/tile + tile) post-kernel reads
        per round instead of a full O(n) cumsum; same distribution), or
        'rejection' (exact rejection sampling against a STALE envelope: the
        full D^2 refresh runs only every ``refresh_block`` seeds, each round
        in between touches O(1) rows — same distribution; refresh_block=1
        reproduces 'tiled' bitwise). ``refresh_block``, ``proposal`` and
        ``max_attempts`` are rejection-only knobs (see ``seed_points``):
        proposal='hier' (default) draws coarse-to-fine through super-tiles
        with movement-tightened per-tile envelopes, 'flat' keeps the
        per-tile draw. sampler='auto' takes the tuned sampler (and
        refresh_block / proposal) from the autotune cache when ``tune=``
        is on, else 'tiled'.

        ``checkpoint_dir`` runs the loop in resumable chunks of
        ``checkpoint_every`` rounds, persisting the full carry (centroids,
        min_d2, bound state, RNG key, round counter) through the atomic
        step-dir protocol of ``repro.checkpoint``; an existing checkpoint in
        the directory resumes mid-seed and the finished result is bitwise
        the uninterrupted one. Local backends, non-rejection samplers only.
        ``_fault`` is the fault-injection hook (tests only)."""
        n = points.shape[0]
        guards.check_shape(k, n)
        points = guards.guard_points(points, self.validate)
        weights = guards.guard_weights(weights, n, self.validate)
        if checkpoint_dir is not None:
            # checkpointed runs keep the DEFAULT geometry: the carry shapes
            # are stamped into the checkpoint meta, and a tune-cache update
            # between interrupt and resume must not change them
            if sampler == "auto":
                sampler = "tiled"
            return self._seed_checkpointed(
                key, points, k, weights=weights, sampler=sampler,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=int(checkpoint_every))
        tuned_be, rec = self._tune_for(n, k, points.shape[1], points.dtype)
        sampler, refresh_block, proposal = self._tune_sampler(
            sampler, refresh_block, rec, proposal)
        res = self._run(lambda be: self._launch(
            _seed_jit, key, points, weights, k, be, sampler, self.precision,
            self.bounds, int(refresh_block), proposal, int(max_attempts),
            self._guard, _fault),
            backend=tuned_be)
        return res if rec is None else res._replace(tune=rec)

    def _resolve_order(self, points: jax.Array, order):
        """order: None (natural), an ordering name ('morton' — see
        repro.data.ordering), or a precomputed (n,) permutation array.
        Returns (perm, inv) or (None, None)."""
        if order is None:
            return None, None
        from repro.data import ordering
        if isinstance(order, str):
            return ordering.spatial_order(points, method=order)
        perm = jnp.asarray(order)
        return perm, ordering.inverse_permutation(perm)

    def _order_in(self, points, order, weights=None, *, batched=False):
        """Permute-on-entry half of the ordering plumbing (shared by fit /
        kmeans / fit_batched / kmeans_batched): returns
        (points', weights', perm, inv)."""
        perm, inv = (self._resolve_order_batched(points, order) if batched
                     else self._resolve_order(points, order))
        if perm is not None:
            if batched:
                points = jnp.take_along_axis(points, perm[..., None], axis=1)
            else:
                points = jnp.take(points, perm, axis=0)
                if weights is not None:
                    weights = jnp.take(weights, perm, axis=0)
        return points, weights, perm, inv

    @staticmethod
    def _order_out(res: LloydResult, perm, inv, *,
                   batched: bool = False) -> LloydResult:
        """Invert-on-exit half: assignment back to the caller's row order,
        permutation recorded as provenance."""
        if perm is None:
            return res
        if batched:
            a = jnp.take_along_axis(res.assignment, inv, axis=1)
        else:
            a = jnp.take(res.assignment, inv)
        return res._replace(assignment=a, reorder=perm)

    # -- full-batch Lloyd -------------------------------------------------
    @_mesh_scoped
    def fit(self, points: jax.Array, init_centroids: jax.Array, *,
            max_iters: int = 50, tol: float = 1e-6,
            weights: Optional[jax.Array] = None,
            empty: str = "keep", order=None,
            checkpoint_dir=None, checkpoint_every: int = 1,
            _fault=None) -> LloydResult:
        """Lloyd iterations from `init_centroids` until convergence.

        empty: what happens to clusters that lose all their points — 'keep'
        (previous centroid survives, the default) or 'reseed' (each empty
        centroid jumps to a nudged copy of the largest cluster's centroid and
        splits it on the next iteration).

        order: feed the kernels a tile-coherent row layout — None (natural
        order), 'morton' (Z-order curve over the coordinates), 'auto' (the
        tuned order from the autotune cache when ``tune=`` is on, else
        natural), or a precomputed (n,) permutation (e.g.
        repro.data.ordering's label_sort_order). The permutation is applied on the way in and
        INVERTED on the way out, so `assignment` is always in the caller's
        row order; the permutation used is recorded in
        ``LloydResult.reorder`` for pruning audits. Spatial coherence is
        what makes the movement-bound tile gate fire (see docs/engine.md
        "Bounded assignment").

        ``checkpoint_dir`` runs the loop in resumable chunks of
        ``checkpoint_every`` iterations, persisting the full carry
        (centroids, bound state, inertia pair, counters) through the atomic
        step-dir protocol of ``repro.checkpoint``; an existing checkpoint in
        the directory resumes mid-fit and the finished result is bitwise
        the uninterrupted one. Local backends, unweighted, bounds=True only.
        ``_fault`` is the fault-injection hook (tests only)."""
        d = points.shape[-1]
        points = guards.guard_points(points, self.validate)
        weights = guards.guard_weights(weights, points.shape[0],
                                       self.validate)
        init_centroids = guards.guard_centroids(init_centroids, d,
                                                self.validate)
        tuned_be, rec = (None, None)
        if checkpoint_dir is None:
            # checkpointed runs keep the default geometry (see seed())
            tuned_be, rec = self._tune_for(points.shape[0],
                                           init_centroids.shape[0], d,
                                           points.dtype)
        if order == "auto":
            order = rec.order if rec is not None else None
        points, weights, perm, inv = self._order_in(points, order, weights)
        if checkpoint_dir is not None:
            res = self._fit_checkpointed(
                points, init_centroids, max_iters=max_iters,
                tol=float(tol), empty=empty, weights=weights,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=int(checkpoint_every))
        else:
            res = self._run(lambda be: self._launch(
                _fit_jit, points, init_centroids, weights, be, max_iters,
                float(tol), empty, self.precision, self.bounds, self._guard,
                _fault),
                backend=tuned_be)
        if rec is not None:
            res = res._replace(tune=rec)
        return self._order_out(res, perm, inv)

    @_mesh_scoped
    def kmeans(self, key: jax.Array, points: jax.Array, k: int, *,
               init: str = "kmeans++", max_iters: int = 50, tol: float = 1e-6,
               sampler: str = "cdf", empty: str = "keep",
               weights: Optional[jax.Array] = None,
               order=None, refresh_block: int = 8, proposal: str = "hier",
               max_attempts: int = _REJECT_ATTEMPTS) -> LloydResult:
        """End-to-end: seeding (the paper's phase) + Lloyd clustering.
        ``order`` reorders the rows ONCE up front (see `fit`): both the
        seeding scan and every Lloyd iteration then see the tile-coherent
        layout, and the returned assignment is mapped back to the caller's
        row order. The kmeans++ path runs as ONE compiled call sharing a
        single prologue (norms, tile balls and the padded rows computed once
        for both phases; once per shard on a mesh backend — see
        ``kmeans_points``)."""
        points = guards.guard_points(points, self.validate)
        weights = guards.guard_weights(weights, points.shape[0],
                                       self.validate)
        tuned_be, rec = self._tune_for(points.shape[0], k,
                                       points.shape[-1], points.dtype)
        if order == "auto":
            order = rec.order if rec is not None else None
        sampler, refresh_block, proposal = self._tune_sampler(
            sampler, refresh_block, rec, proposal)
        points, weights, perm, inv = self._order_in(points, order, weights)
        if init == "kmeans++":
            guards.check_shape(k, points.shape[0])
            res = self._run(lambda be: self._launch(
                _kmeans_jit, key, points, weights, k, be, sampler, max_iters,
                float(tol), empty, self.precision, self.bounds,
                int(refresh_block), proposal, int(max_attempts),
                self._guard), backend=tuned_be)
            if rec is not None:
                res = res._replace(tune=rec)
            return self._order_out(res, perm, inv)
        if init == "kmeans||":
            if self.backend.distributed:
                raise NotImplementedError("k-means|| init runs on a local "
                                          "backend; seed locally, fit on mesh")
            from repro.core.kmeans_parallel import kmeans_parallel_init
            seeds = kmeans_parallel_init(key, points, k,
                                         backend=self.backend).centroids
        elif init == "random":
            from repro.core.kmeanspp import random_init
            seeds = random_init(key, points, k).centroids
        else:
            raise ValueError(f"unknown init {init!r}")
        res = self.fit(points, seeds, max_iters=max_iters, tol=tol,
                       weights=weights, empty=empty)
        return self._order_out(res, perm, inv)

    # -- streaming mini-batch Lloyd ---------------------------------------
    def fit_minibatch(self, init_centroids: jax.Array, batches: BatchSource,
                      *, n_batches: Optional[int] = None,
                      tol: float = 0.0, patience: int = 5,
                      order=None) -> LloydResult:
        """Streaming mini-batch k-means over fixed-size batches.

        `batches` can be a ``read_fn(step) -> (b, d) array`` (driven through a
        prefetching ``repro.data.pipeline.DataPipeline``), a DataPipeline, or
        any iterable of batches. Per-center counts give each center a
        1/t-decaying learning rate (Sculley 2010), so the result converges to
        the same fixed points as full-batch Lloyd without ever holding the
        dataset in device memory.

        The engine's ``precision`` applies per batch: with 'bf16' each batch
        streams through the same half-width tile path as full fit (fp32
        norms/accumulators/centroid carry). ``order='morton'`` Z-orders each
        batch before its step; the final batch's assignment is mapped back
        to the batch's own row order. NOTE: the mini-batch step has no
        loop-carried bound state (every batch is fresh points, so there is
        no previous iteration for a movement bound to compare against) —
        today the per-batch ordering is layout plumbing only, costing one
        argsort per batch; it becomes load-bearing if a gated/tiled
        mini-batch round lands. Prefer ordering the BATCH SOURCE itself
        (e.g. persist label-sorted shards) over this knob.

        Early stop: if `tol` > 0, stops after `patience` consecutive batches
        whose smoothed per-point inertia improves by less than `tol`
        (relative). Returns a LloydResult whose assignment/inertia refer to
        the LAST batch seen (there is no global pass in streaming mode);
        n_iters is the number of batches consumed.

        Failure semantics: each batch passes the engine's ``validate``
        guard before its step, and a batch source that keeps failing past
        the pipeline's retry budget surfaces as a typed
        ``repro.core.guards.PipelineError`` carrying the failing step index
        — the partial model state is NOT silently kept.
        """
        if self.backend.distributed:
            raise NotImplementedError(
                "mini-batch runs on a local backend; shard the batch source "
                "instead (each host streams its slice)")
        init_centroids = guards.guard_centroids(
            init_centroids, jnp.asarray(init_centroids).shape[-1],
            self.validate)
        cents = jnp.asarray(init_centroids, jnp.float32)
        counts = jnp.zeros((cents.shape[0],), jnp.float32)
        a = jnp.zeros((0,), jnp.int32)
        seen = 0
        ema = None
        stale = 0
        inv = None
        last_inertia = jnp.asarray(jnp.inf, jnp.float32)
        for batch in _iter_batches(batches, n_batches):
            batch = guards.guard_points(batch, self.validate,
                                        name=f"batch {seen}")
            perm, inv = self._resolve_order(batch, order)
            if perm is not None:
                batch = jnp.take(batch, perm, axis=0)
            cents, counts, last_inertia, a = self._run(
                lambda be: self._launch(_minibatch_jit, cents, counts, batch,
                                        be, self.precision))
            seen += 1
            if tol > 0.0:
                per_point = float(last_inertia) / max(batch.shape[0], 1)
                prev = ema
                ema = (per_point if ema is None
                       else 0.7 * ema + 0.3 * per_point)
                if prev is not None and prev - ema <= tol * max(prev, 1e-30):
                    stale += 1
                    if stale >= patience:
                        break
                else:
                    stale = 0
        if seen == 0:
            raise ValueError("empty batch source")
        if inv is not None:
            a = jnp.take(a, inv, axis=0)
        init_dtype = jnp.asarray(init_centroids).dtype
        return LloydResult(cents.astype(init_dtype), a, last_inertia,
                           jnp.asarray(seen, jnp.int32))

    # -- batched multi-problem clustering ---------------------------------
    def seed_batched(self, key: jax.Array, points: jax.Array, k: int, *,
                     sampler: str = "cdf",
                     refresh_block: int = 8, proposal: str = "hier",
                     max_attempts: int = _REJECT_ATTEMPTS) -> KmeansppResult:
        """Seed B independent (n, d) problems in one compiled call.

        `points` is (B, n, d); `key` is either one key (split per problem) or
        (B,)-batched keys. Each problem gets its own PRNG stream, so problem b
        picks exactly the seeds the single-problem path would pick under
        keys[b] — the many-tenant serve/semdedup scenario. On the pallas
        backend the vmap lowers to the batch-grid distance kernel (one launch
        per round covering every problem), not a per-problem loop.
        """
        if self.backend.distributed:
            raise NotImplementedError("use a local backend for batched "
                                      "problems (vmap inside each shard)")
        B, n, _ = points.shape
        guards.check_shape(k, n)
        # entry guard only: the in-flight detector stays OFF under vmap
        # (lax.cond becomes select there — every problem would pay the heal
        # rounds whether or not it tripped)
        points = guards.guard_points(points, self.validate)
        # a single key has ndim 0 (typed) or 1 (raw uint32); anything higher
        # is already a (B,)-batch of keys
        single_ndim = 0 if jnp.issubdtype(key.dtype, jax.dtypes.prng_key) else 1
        keys = key if key.ndim > single_ndim else jax.random.split(key, B)
        tuned_be, rec = self._tune_for(n, k, points.shape[-1], points.dtype)
        sampler, refresh_block, proposal = self._tune_sampler(
            sampler, refresh_block, rec, proposal)
        res = self._run(lambda be: self._launch(
            _seed_batched_jit, keys, points, k, be, sampler, self.precision,
            self.bounds, int(refresh_block), proposal, int(max_attempts)),
            backend=tuned_be)
        return res if rec is None else res._replace(tune=rec)

    def _resolve_order_batched(self, points: jax.Array, order):
        """Per-problem (B, n) permutations for batched fits."""
        if order is None:
            return None, None
        from repro.data import ordering
        if isinstance(order, str):
            return jax.vmap(
                lambda p: ordering.spatial_order(p, method=order))(points)
        perm = jnp.asarray(order)
        return perm, jax.vmap(ordering.inverse_permutation)(perm)

    def fit_batched(self, points: jax.Array, init_centroids: jax.Array, *,
                    max_iters: int = 50, tol: float = 1e-6,
                    empty: str = "keep", order=None) -> LloydResult:
        """Lloyd over B independent problems: points (B, n, d), inits
        (B, k, d) -> LloydResult of (B, ...) leaves. One compiled vmap call;
        iteration stops when EVERY problem has converged (n_iters is shared).
        On the pallas backend the vmap lowers to the batch-grid assign kernel
        (one launch per iteration, every problem in the grid). ``order``
        reorders each problem's rows independently (see `fit`); assignments
        come back in the caller's row order with the (B, n) permutations in
        ``LloydResult.reorder``."""
        if self.backend.distributed:
            raise NotImplementedError("use a local backend for batched "
                                      "problems (vmap inside each shard)")
        points = guards.guard_points(points, self.validate)
        init_centroids = guards.guard_centroids(
            init_centroids, points.shape[-1], self.validate)
        tuned_be, rec = self._tune_for(points.shape[1],
                                       init_centroids.shape[-2],
                                       points.shape[-1], points.dtype)
        if order == "auto":
            order = rec.order if rec is not None else None
        points, _, perm, inv = self._order_in(points, order, batched=True)
        res = self._run(lambda be: self._launch(
            _fit_batched_jit, points, init_centroids, be, max_iters,
            float(tol), empty, self.precision, self.bounds),
            backend=tuned_be)
        if rec is not None:
            res = res._replace(tune=rec)
        return self._order_out(res, perm, inv, batched=True)

    def kmeans_batched(self, key: jax.Array, points: jax.Array, k: int, *,
                       max_iters: int = 50, tol: float = 1e-6,
                       sampler: str = "cdf", empty: str = "keep",
                       order=None) -> LloydResult:
        """seed_batched + fit_batched in sequence (both single compiled
        calls). ``order`` reorders each problem ONCE up front so both phases
        see the coherent layout; assignments map back to the caller's rows."""
        if order == "auto":
            _, rec = self._tune_for(points.shape[1], k, points.shape[-1],
                                    points.dtype)
            order = rec.order if rec is not None else None
        points, _, perm, inv = self._order_in(points, order, batched=True)
        seeds = self.seed_batched(key, points, k, sampler=sampler)
        res = self.fit_batched(points, seeds.centroids, max_iters=max_iters,
                               tol=tol, empty=empty)
        return self._order_out(res, perm, inv, batched=True)

    # -- checkpointed drivers ---------------------------------------------
    def _ckpt_meta(self, kind: str, **extra) -> dict:
        meta = {"kind": kind, "precision": self.precision,
                "bounds": self.bounds}
        meta.update(extra)
        return meta

    @staticmethod
    def _check_meta(mgr, want: dict) -> Optional[int]:
        """Latest resumable step, or None for a fresh start. A checkpoint
        written by an INCOMPATIBLE call (different problem shape, sampler,
        precision ...) is a typed failure, never a silent restore."""
        step = mgr.latest_step()
        if step is None:
            return None
        got = mgr.read_manifest(step).get("meta")
        if got != want:
            raise CheckpointError(
                f"checkpoint under {mgr.dir} was written by an incompatible "
                f"call: saved meta {got} != expected {want}")
        return step

    def _seed_checkpointed(self, key, points, k, *, weights, sampler,
                           checkpoint_dir, checkpoint_every):
        """seed() with checkpoint_dir: the SAME per-round body as the jitted
        loop, driven in chunks of ``checkpoint_every`` rounds with the full
        carry (round counter, RNG key, centroids, min_d2, bound state,
        counters) persisted after each chunk. Resume picks up the latest
        step and replays the remaining rounds — the carry round-trips
        bit-exactly through the npz format, so the finished seeds are
        bitwise the uninterrupted ones."""
        from repro.checkpoint.manager import CheckpointManager
        if self.backend.distributed:
            raise CheckpointError("checkpointed seeding runs on local "
                                  "backends (seed locally, fit on mesh)")
        if sampler == "rejection":
            raise CheckpointError(
                "checkpointed seeding needs a per-round refresh; the "
                "rejection sampler's stale-envelope carry is not serialized "
                "— use sampler='tiled' (same distribution)")
        # the prologue is jitted SEPARATELY here (the parts builders run it
        # eagerly otherwise): eager vs jitted fp contraction differs by ulps
        # in the cached norms, and the bitwise-resume claim needs the chunked
        # driver to consume exactly the arrays the one-shot jit consumes
        be = self.backend
        points = jnp.asarray(points)
        cache = jax.jit(
            lambda p: be.prologue(p, with_bounds=self.bounds))(points)
        make_init, body, finish = seed_points(
            key, points, k, weights, be, sampler,
            precision=self.precision, bound_gate=self.bounds,
            cache=cache, guard=self._guard, parts=True)
        carry = make_init(key)
        typed = jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key)
        wrap = getattr(jax.random, "wrap_key_data", None)

        def ser(c):
            # npz can't hold typed PRNG keys: store the raw uint32 key data
            # (raw and typed keys drive identical threefry streams)
            lst = list(c)
            if typed:
                lst[1] = jax.random.key_data(lst[1])
            return tuple(lst)

        def unser(c):
            lst = list(c)
            if typed and wrap is not None:
                lst[1] = wrap(jnp.asarray(lst[1]))
            return tuple(lst)

        n, d = points.shape
        mgr = CheckpointManager(checkpoint_dir, async_save=False)
        meta = self._ckpt_meta("seed", n=int(n), d=int(d), k=int(k),
                               sampler=sampler,
                               weighted=weights is not None)
        step = self._check_meta(mgr, meta)
        if step is not None:
            _, s = mgr.restore(ser(carry), step=step)
            carry = unser(s)

        chunk_j = jax.jit(lambda c, stop: jax.lax.while_loop(
            lambda s: s[0] < stop, body, c))
        every = max(int(checkpoint_every), 1)
        m = int(jax.device_get(carry[0]))
        while m < k:
            carry = chunk_j(carry, jnp.asarray(min(m + every, k), jnp.int32))
            m = int(jax.device_get(carry[0]))
            mgr.save(m, ser(carry), blocking=True, meta=meta)
        # jitted like the one-shot path's tail, so the final settle round's
        # fp contraction (and thus min_d2) is bitwise the plain seed()'s
        centroids, indices, min_d2, skips, prunes, rec = jax.jit(finish)(
            carry)
        return KmeansppResult(centroids.astype(points.dtype), indices,
                              min_d2, skips if self.bounds else None,
                              prunes if self.bounds else None,
                              recovered=rec if self._guard else None)

    def _fit_checkpointed(self, points, init_centroids, *, max_iters, tol,
                          empty, weights, checkpoint_dir, checkpoint_every):
        """fit() with checkpoint_dir: the gated Lloyd body (bitwise the
        jitted loop's) driven in chunks of ``checkpoint_every`` iterations,
        the full carry (iteration counter, centroid pair, inertia pair,
        BoundState, counters) persisted after each chunk. Convergence is
        detected when a chunk stops short of its target iteration."""
        from repro.checkpoint.manager import CheckpointManager
        if self.backend.distributed or weights is not None or not self.bounds:
            raise CheckpointError(
                "checkpointed fit needs a local backend, unweighted points "
                "and bounds=True (the serialized carry is the gated loop's)")
        if empty not in ("keep", "reseed"):
            raise ValueError(f"unknown empty-cluster policy {empty!r}; "
                             "expected 'keep' or 'reseed'")
        n, d = points.shape
        k = init_centroids.shape[0]
        be = self.backend
        compute_dtype = jnp.promote_types(points.dtype, jnp.float32)
        pts = points.astype(compute_dtype)
        stream = _stream_of(pts, self.precision)
        # jitted for the same reason as _seed_checkpointed: the chunked body
        # must consume bitwise the norms/centroid-balls the one-shot fit does
        cache = jax.jit(lambda p: be.prologue(p, m=k, with_bounds=True))(pts)
        cache = _stream_cache(cache, stream)
        cond, body, make_init = _fit_gated_parts(
            pts, stream, jnp.asarray(init_centroids, jnp.float32), be,
            int(max_iters), float(tol), empty, cache.norms, cache,
            guard=self._guard)
        mgr = CheckpointManager(checkpoint_dir, async_save=False)
        meta = self._ckpt_meta("fit", n=int(n), d=int(d), k=int(k),
                               max_iters=int(max_iters), tol=float(tol),
                               empty=empty)
        carry = make_init()
        step = self._check_meta(mgr, meta)
        if step is not None:
            _, carry = mgr.restore(carry, step=step)

        chunk_j = jax.jit(lambda c, stop: jax.lax.while_loop(
            lambda s: jnp.logical_and(cond(s), s[0] < stop), body, c))
        every = max(int(checkpoint_every), 1)
        while True:
            start = int(jax.device_get(carry[0]))
            if start >= max_iters:
                break
            stop = min(start + every, int(max_iters))
            carry = chunk_j(carry, jnp.asarray(stop, jnp.int32))
            done = int(jax.device_get(carry[0]))
            if done > start:     # a no-progress chunk means the restored
                mgr.save(done, carry, blocking=True, meta=meta)  # carry had
            if done < stop:      # already converged; never re-save its step
                break            # cond false inside the chunk: converged
        i, cents, _, inertia, _, bstate, skips, prunes, rec = carry
        return LloydResult(cents.astype(points.dtype), bstate.assignment,
                           inertia, i, skips, prunes,
                           recovered=rec if self._guard else None)
