"""Exact TWO-LEVEL bound state shared by the SEEDING and ASSIGNMENT rounds
(Raff 2021 / Capó 2018): per-tile ball/gap bounds at the coarse level,
per-POINT Hamerly bounds at the fine level, and a tile → super-tile →
global accumulator hierarchy.

Seeding bound. A seeding round folds the new centroid(s) ``c_new`` into every
point's D². A point x can only improve when ``d(x, c) < d(x,
nearest-so-far)``, so by the triangle inequality a whole *tile* of points
provably cannot change when

    d(center_t, c) - r_t  >=  sqrt(max_{x in tile} min_d2[x])

where ``center_t`` is the tile's ball center and ``r_t`` its radius
(``d(x, c) >= d(center_t, c) - d(x, center_t) >= d(center_t, c) - r_t``).
Skipping such a tile is *exact*: its ``min_d2`` entries, and therefore its
per-tile partial sum, are bitwise what a full recompute would produce
(``min(md, d2)`` returns ``md`` whenever ``d2 >= md``), so the tiled sampler
composes unchanged. Capó et al. motivate this granularity: block-level — not
per-point — pruning is what pays at massive n, and the tile is exactly the
unit the ``SeedRound`` partials machinery already tracks.

Assignment (Lloyd) bound. Between iterations every centroid moves by
``delta_j = ‖c_j^{t+1} − c_j^t‖``. For a point x assigned to j0 with
second-best margin ``gap(x) = d(x, c_2nd) − d(x, c_j0)``, no label can change
as long as ``gap(x) >= delta_j0 + max_j delta_j`` (its own centroid ran away
by at most delta_j0, the best challenger closed by at most max delta). The
tile-level state carries ``tile_gap = min_x gap(x)``. Skipping a tile keeps
the carried assignment AND the carried ``min_d2``/per-cluster sums bitwise
exact only when the centroids the tile is assigned to did not move at all —
so the gate additionally requires ``delta_j == 0`` for every cluster the
tile's carried counts mark as occupied (near convergence most clusters stop
moving bitwise, which is exactly when the assignment round becomes pure
re-verification). A skipped tile's carried gap is decayed by that
iteration's ``max_j delta_j`` (:func:`decay_gap`), which keeps it a valid
lower bound across consecutive skips.

Per-point (fine-level) bounds. Inside a tile the coarse gate keeps ACTIVE,
most points may still be provably stable. Two Hamerly-style per-point bounds
prune them:

* ASSIGNMENT: ``ub[i] = sqrt(min_d2[i])`` is the EXACT distance to the
  assigned centroid (not just a bound — the exactness discipline below keeps
  ``min_d2`` exact through pruned stretches, so ``ub`` needs no storage of
  its own), and ``point_lb[i]`` is a lower bound on the second-nearest
  distance, decayed by each iteration's ``max_j delta_j``. A point
  short-circuits the k-way distance recomputation iff its OWN centroid is
  bitwise unmoved (``delta_{a(i)} == 0``) and ``point_lb[i] − ub[i] >=
  delta_max`` (with the fp32 margin): the label provably cannot change AND
  the carried ``min_d2[i]`` is bitwise what a recompute would produce (the
  matmul-form d2 of column j is elementwise in ``c_j``). The decay is
  tracked LAZILY per tile (``lb_debt``): skipped tiles pay no O(n) update —
  the debt folds into the prune threshold and is absorbed into the stored
  ``point_lb`` the next time the tile computes.
* SEEDING (Raff-style): the prologue caches ``center_d[i] = d(x_i,
  center_{t(i)})`` once per call; a seed round with new centroid c has
  ``d(x_i, c) >= dc_t − center_d[i]`` (one fresh O(n_tiles) distance
  ``dc_t = d(center_t, c)`` per round), so points with ``(dc_t −
  center_d[i])² >= min_d2[i]`` (plus margin) provably cannot improve and
  the min-update is skipped — a value-noop by construction (``min(md, d2)``
  returns ``md`` whenever ``d2 >= md``).

Hierarchical accumulators. The tiled assignment round used to materialize
per-TILE per-cluster sums/counts — O(n_tiles·k·d) HBM. The accumulators are
now per-SUPER-TILE (``tiles_per_super ≈ √n_tiles`` consecutive tiles share
one ``(k, d)`` slot, accumulated sequentially in ascending tile order inside
the kernel), capping the footprint at O(n_super·k·d). Aliasing — the carry
for skipped work — moves to the super level: a super-tile's accumulator
block is carried iff ALL its tiles are skipped, so the coarse gate is
expanded to whole super-tiles (``expand_active_supers``). A tile
force-activated only by its super is a value-noop (skipping was exact), and
its points are exactly the ones the fine-level per-point gate prunes — the
two levels compose.

The bounds are evaluated in fp32, so small conservative slacks keep rounding
from ever skipping a tile (or point) the exact-arithmetic bound would keep
(erring toward "compute it" never changes results, only saves less).

This module is pure jnp: the reference/fused backends use it directly (the
skip logic is therefore covered by the distribution/parity tests), and the
Pallas backend uses :func:`active_tiles` / :func:`assign_active_tiles` to
build the compacted active-tile index maps its gated kernels prefetch.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

# Head-room on the skip threshold. The kernels (and the bound itself)
# evaluate D^2 in the matmul form ||x||^2 - 2x.c + ||c||^2, whose fp32
# cancellation error is ABSOLUTE in the magnitude of the operands: about
# eps_f32 * (||x|| + ||c||)^2, NOT eps * d^2. A purely relative slack would
# therefore under-protect data far from the origin. _REL covers the relative
# rounding of the comparison chain; _ABS scales a per-tile magnitude term
# (||center|| + r + max||c||)^2 with ~80x head-room over eps_f32 = 1.2e-7,
# so a tile is only ever skipped when the kernel's OWN fp32 d2 provably
# cannot dip below the carried min_d2 (skipping stays bitwise exact; far
# from the origin the gate just prunes less — center your data for the best
# skip rate).
_REL = 1e-6
_ABS = 1e-5
# Distance-unit analogue of _ABS for the ASSIGNMENT gate: the per-point gap
# is a difference of square roots of matmul-form d2 values, and near-zero
# distances turn the absolute d2 error into ~sqrt(_ABS) of distance error —
# so the gap margin scales sqrt(_ABS)-sized head-room by the tile's
# distance-unit operand magnitude.
_ABS_GAP = 4e-3


class RoundCache(NamedTuple):
    """Per-dataset state computed ONCE per seed/fit call (the prologue).

    ``norms`` feeds the matmul-form distance (``||x||² - 2x·c + ||c||²``) so
    the round kernels stop recomputing ``||x||²`` every round; it is always
    fp32 even when the points stream as bf16. ``centers``/``radii`` are the
    tile centroid-balls the skip bound needs; they are ``None`` when bound
    gating is disabled (norm caching alone does not need them).

    ``rows`` are the points padded to a multiple of the tile height, once
    per call for every round: the Pallas backend's prologue is the one owner
    of that pad, and its round and assignment kernels stream these rows as
    they are (pad rows are masked by the valid count n). They are the points
    themselves when n is already a tile multiple, and ``None`` on backends
    that pad nothing (reference, fused).

    ``cols`` are the same padded points coordinate-major, ``(d, n_tiles *
    tile)`` with the points on lanes: at small d (``kernels.ops.streams_cols``,
    d <= 32) the Pallas prologue builds them once per call from
    ``points.T``, and the plain and gated seeding-round kernels stream them
    in place of ``rows`` (a row tile pads d to 128 lanes). The prologue
    kernel and the Lloyd kernels still stream ``rows``. ``None`` at larger d
    and on the other backends.
    """

    norms: jax.Array                       # (n,) fp32 ||x||²
    centers: Optional[jax.Array] = None    # (n_tiles, d) fp32 tile means
    radii: Optional[jax.Array] = None      # (n_tiles,) fp32 ball radii
    center_d: Optional[jax.Array] = None   # (n,) fp32 d(x, tile center) —
                                           # the per-point seeding bound
    rows: Optional[jax.Array] = None       # (n_tiles * tile, d) padded points
    cols: Optional[jax.Array] = None       # (d, n_tiles * tile) the same,
                                           # coordinate-major (small d)


class BoundState(NamedTuple):
    """Loop-carried bound state, unified across the two round primitives.

    The SEEDING loop carries ``(partials, tile_max)``: the previous round's
    per-tile partial sums (reused verbatim for skipped tiles) and per-tile
    max of ``min_d2`` (the skip bound's RHS).

    The ASSIGNMENT (Lloyd) loop carries ``(partials, tile_gap, tile_sums,
    tile_counts, assignment, min_d2, point_lb, lb_debt)``: per-tile inertia
    partials, the per-tile second-best margin (in DISTANCE units — the
    movement bound's LHS), the per-SUPER-TILE per-cluster sums/counts whose
    super-axis reduction is the centroid update (the hierarchical
    accumulators — ``tiles_per_super`` consecutive tiles share one slot),
    the per-point labels/D² that skipped tiles carry verbatim (the gated
    kernel's aliased buffers), the per-point Hamerly lower bound on the
    second-nearest distance, and the per-tile lazy movement debt the stored
    ``point_lb`` is stale by. The per-tile ball geometry both gates compare
    against lives in the once-per-call :class:`RoundCache`; the movement
    ``delta_j`` is derived each iteration from the loop's own consecutive
    centroids. Fields a loop does not use stay ``None`` (they are
    pytree-static).
    """

    partials: jax.Array                        # (n_tiles,) fp32
    tile_max: Optional[jax.Array] = None       # (n_tiles,) fp32 (seeding)
    tile_gap: Optional[jax.Array] = None       # (n_tiles,) fp32 (assignment)
    tile_sums: Optional[jax.Array] = None      # (n_super, k, d) fp32
    tile_counts: Optional[jax.Array] = None    # (n_super, k) fp32
    assignment: Optional[jax.Array] = None     # (n,) int32 (assignment)
    min_d2: Optional[jax.Array] = None         # (n,) fp32 (assignment)
    point_lb: Optional[jax.Array] = None       # (n,) fp32 Hamerly lower
                                               # bound on 2nd-nearest dist
    lb_debt: Optional[jax.Array] = None        # (n_tiles,) fp32 movement
                                               # debt of the stored point_lb


# historical name (PR 3's seeding-only state) — same type, seed-field layout
RoundState = BoundState


def point_norms(points: jax.Array) -> jax.Array:
    """fp32 ``||x||²`` per row — THE quantity the prologue caches."""
    x = points.astype(jnp.float32)
    return jnp.sum(x * x, axis=-1)


def tile_counts(n: int, block_n: int) -> jax.Array:
    """Valid-row count of each tile of a zero-padded (n,) -> (n_tiles, bn)."""
    n_tiles = -(-n // block_n)
    start = jnp.arange(n_tiles, dtype=jnp.int32) * block_n
    return jnp.clip(n - start, 0, block_n).astype(jnp.float32)


def prologue(points: jax.Array, block_n: int, *,
             with_bounds: bool = True) -> RoundCache:
    """Pure-jnp prologue: cached norms (+ tile centers/radii for the bound).

    Padded tail rows are excluded from center/radius (a zero pad row could
    otherwise inflate the tail tile's ball). The Pallas backend computes the
    same three arrays in one fused kernel pass (`seed_prologue_pallas`);
    cross-backend users only need the *norms* to agree bitwise — the bound
    geometry may differ in ulps without affecting results (the bound is a
    sufficient condition, never a value).
    """
    pts = points.astype(jnp.float32)
    n, d = pts.shape
    norms = jnp.sum(pts * pts, axis=1)
    if not with_bounds:
        return RoundCache(norms)
    pad = (-n) % block_n
    xp = jnp.pad(pts, ((0, pad), (0, 0))).reshape(-1, block_n, d)
    cnt = tile_counts(n, block_n)                       # (n_tiles,)
    centers = xp.sum(axis=1) / jnp.maximum(cnt, 1.0)[:, None]
    d2c = jnp.sum((xp - centers[:, None, :]) ** 2, axis=-1)  # (n_tiles, bn)
    row = jnp.arange(block_n)[None, :] < cnt[:, None]
    radii = jnp.sqrt(jnp.max(jnp.where(row, d2c, 0.0), axis=1))
    center_d = jnp.sqrt(jnp.maximum(d2c, 0.0)).reshape(-1)[:n]
    return RoundCache(norms, centers, radii, center_d)


def seed_gate(c_new: jax.Array, cache: RoundCache,
              tile_max: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Both levels of the SEEDING gate, one O(n_tiles·m) pass.

    Returns ``(active, dc, margin)``:

    * ``active`` (n_tiles,) bool — True where the tile MIGHT change this
      round: a tile is skipped only when ``(d(center_t, c) - r_t)^2 >=
      tile_max_t`` against its *nearest* new centroid with the conservative
      fp32 margin described at ``_REL``/``_ABS`` (rounding can only keep a
      tile active, never skip a changeable one).
    * ``dc`` (n_tiles,) fp32 — distance of each tile ball center to its
      nearest new centroid. Inside an ACTIVE tile, a point x with
      ``(dc_t − center_d[x])² >= min_d2[x]·(1+_REL) + margin_t`` provably
      cannot improve (``d(x, c) >= dc_t − d(x, center_t)``) — the fine,
      per-point level of the same bound, using the prologue-cached
      ``center_d`` instead of the ball radius.
    * ``margin`` (n_tiles,) fp32 — the ``_ABS``-scaled absolute slack term
      the per-point test adds (same operand-magnitude model as the tile
      test, streamed to the kernels as one per-tile scalar)."""
    c = c_new.astype(jnp.float32)
    cn = jnp.sum(c * c, axis=-1)
    ctr = cache.centers
    ctr_n2 = jnp.sum(ctr * ctr, axis=1)
    dot = jax.lax.dot_general(ctr, c, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.maximum(ctr_n2[:, None] - 2.0 * dot + cn[None, :], 0.0)
    dc = jnp.sqrt(jnp.min(d2, axis=1))                  # nearest new centroid
    lo = jnp.maximum(dc - cache.radii, 0.0)             # min dist to tile
    # magnitude of the operands feeding the kernels' matmul-form d2 for this
    # tile: every ||x|| is within ||center|| + r, every ||c|| within cmax
    cmax = jnp.sqrt(jnp.max(cn))
    margin = _ABS * (jnp.sqrt(ctr_n2) + cache.radii + cmax) ** 2
    skip = lo * lo >= tile_max * (1.0 + _REL) + margin
    return jnp.logical_not(skip), dc, margin


def active_tiles(c_new: jax.Array, cache: RoundCache,
                 tile_max: jax.Array) -> jax.Array:
    """Coarse level only of :func:`seed_gate` (historical entry point)."""
    return seed_gate(c_new, cache, tile_max)[0]


def seed_point_prune(min_d2: jax.Array, center_d: jax.Array, dc: jax.Array,
                     margin: jax.Array) -> jax.Array:
    """Per-point SEEDING prune mask for ONE tile: True where the min-update
    provably cannot change ``min_d2`` (so ``min(md, d2)`` would return ``md``
    bitwise — skipping the d2 evaluation is a value-noop). ``min_d2`` and
    ``center_d`` are the tile's (bn,) slices; ``dc``/``margin`` the tile's
    :func:`seed_gate` scalars. Shared verbatim by the pure-JAX gate model
    and the Pallas gated kernels."""
    lo = jnp.maximum(dc - center_d, 0.0)
    return lo * lo >= min_d2 * (1.0 + _REL) + margin


def seed_envelope(min_d2: jax.Array, weights) -> jax.Array:
    """The rejection sampler's stale proposal weights ``q_i = stale_min_d2 *
    w_i`` (see ``engine._seed_rejection_loop``).

    VALIDITY (the exactness precondition ``q_i >= p_i``): during seeding,
    centroids are only ever ADDED, so every point's min_d2 is monotonically
    NON-INCREASING across rounds — any stale copy of the array (and of the
    per-tile partials the tiled inverse-CDF draws from, which are sums of
    stale entries) dominates the current mass pointwise. No ball-radius or
    movement-decay argument is needed for domination itself; the ball
    machinery above gates what the *refresh* recomputes, and the refresh
    debt is exactly the pending-centroid block the loop carries in place of
    ``lb_debt``."""
    return min_d2 if weights is None else min_d2 * weights


def expand_mask(active: jax.Array, block_n: int, n: int) -> jax.Array:
    """Per-tile mask -> per-point mask (first n entries). Broadcast+reshape,
    NOT jnp.repeat: repeat lowers to a full-n cumsum, which would put an O(n)
    scan back into the jaxpr the tiled sampler is pinned to avoid."""
    n_tiles = active.shape[0]
    return jnp.broadcast_to(active[:, None],
                            (n_tiles, block_n)).reshape(-1)[:n]


def tile_reduce_max(x: jax.Array, block_n: int) -> jax.Array:
    """Per-tile max of a non-negative (n,) array (zero-padded tail) — the
    bound-state twin of ``sampling.tile_partials``."""
    n = x.shape[0]
    pad = (-n) % block_n
    xp = x if pad == 0 else jnp.pad(x, (0, pad))
    return xp.reshape(-1, block_n).max(axis=1)


def centroid_movement(new_c: jax.Array, old_c: jax.Array) -> jax.Array:
    """(k,) fp32 ``delta_j = ‖c_j^{t+1} − c_j^t‖`` — the assignment bound's
    per-centroid movement. Exactly zero iff the centroid did not move (a
    bitwise fixed point), which is the extra condition that makes skipping
    an assignment tile carry its ``min_d2`` exactly."""
    diff = new_c.astype(jnp.float32) - old_c.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(diff * diff, axis=-1))


def tiles_per_super(n_tiles: int, tps: Optional[int] = None) -> int:
    """Static super-tile width: ~√n_tiles consecutive tiles share one
    accumulator slot (power of two, so ``super_id = t // tps`` is a shift).
    Caps the hierarchical accumulators at O(n_super·k·d) with
    n_super = ceil(n_tiles / tps) ≈ √n_tiles. Problems of ≤ 8 tiles keep
    the flat layout (tps = 1): there is no accumulator footprint to cap,
    and grouping would only coarsen the skip gate's alias granularity.

    ``tps`` overrides the heuristic (the autotuner's knob); it is clamped
    to [1, next_pow2(n_tiles)] and rounded to a power of two so
    ``super_id = t // tps`` stays a shift and the aliasing argument holds.
    """
    if tps is not None and tps > 0:
        cap = 1 << max(int(n_tiles - 1).bit_length(), 0) if n_tiles > 1 else 1
        t = 1 << (int(tps).bit_length() - 1)        # floor to power of two
        return max(1, min(t, cap))
    if n_tiles <= 8:
        return 1
    return 1 << ((int(n_tiles - 1).bit_length() + 1) // 2)


def n_supers(n_tiles: int, tps: Optional[int] = None) -> int:
    return -(-n_tiles // tiles_per_super(n_tiles, tps))


def expand_active_supers(active: jax.Array, tps: int) -> jax.Array:
    """Expand a per-tile active mask to whole super-tiles (floored at one
    active super). The hierarchical accumulators alias at SUPER granularity:
    a super's sums/counts block is carried only when ALL its tiles skip, so
    any active tile force-activates its whole super — a value-noop for the
    individually-skippable tiles (skipping is exact), whose points the
    per-point gate then prunes. The floor mirrors ``compact_ids``' write-back
    guard one level up: the one force-computed super keeps every visited
    accumulator block fully written."""
    n_tiles = active.shape[0]
    pad = (-n_tiles) % tps
    sup = jnp.pad(active, (0, pad)).reshape(-1, tps).any(axis=1)
    sup = sup.at[0].set(sup[0] | jnp.logical_not(jnp.any(sup)))
    return jnp.broadcast_to(sup[:, None],
                            (sup.shape[0], tps)).reshape(-1)[:n_tiles]


def super_any(active: jax.Array, tps: int) -> jax.Array:
    """(n_super,) bool — True where ANY tile of the super-tile is active
    (i.e. the super's accumulator block was rewritten this round)."""
    pad = (-active.shape[0]) % tps
    return jnp.pad(active, (0, pad)).reshape(-1, tps).any(axis=1)


def super_reduce(tile_arr: jax.Array, tps: int) -> jax.Array:
    """Reduce a per-tile array over each super-tile's tiles (leading axis
    n_tiles -> n_super). Zero-padding the ragged last super adds exact 0.0s,
    so the tree matches the kernel's sequential accumulation bitwise-safely
    for the pure-JAX model's own gated-vs-ungated comparisons."""
    n_tiles = tile_arr.shape[0]
    pad = (-n_tiles) % tps
    if pad:
        tile_arr = jnp.pad(tile_arr,
                           ((0, pad),) + ((0, 0),) * (tile_arr.ndim - 1))
    return tile_arr.reshape((-1, tps) + tile_arr.shape[1:]).sum(axis=1)


def assign_active_tiles(delta: jax.Array, centroids: jax.Array,
                        state: BoundState, cache: RoundCache,
                        tps: Optional[int] = None) -> jax.Array:
    """(n_tiles,) bool — True where an ASSIGNMENT tile might change labels.

    Tile t is skipped only when BOTH hold:

    * ``tile_gap_t >= delta_max`` (with the conservative fp32 margin): by
      the movement bound no point's runner-up can overtake its assigned
      centroid, so no label in the tile can change; and
    * every cluster the tile's SUPER-tile's carried counts mark occupied has
      ``delta_j == 0``: the assigned centroids are bitwise where they were
      when the tile last computed, so the carried ``min_d2``/partial/sums
      are bitwise what a recompute against the new centroids would produce
      (the matmul-form d2 of row j is elementwise in c_j). The occupancy is
      tracked per super-tile (the hierarchical accumulators' granularity) —
      coarser than the true per-tile occupancy, so the check is
      conservative: it can only keep a tile active, never skip one whose
      own centroid moved.

    The fp32 slack mirrors :func:`seed_gate`: the gap was computed from
    matmul-form d2 whose cancellation error is ABSOLUTE in the operand
    magnitude, and the sqrt step can turn that into ~sqrt(eps)·magnitude of
    distance error near zero, so the margin scales ``_ABS_GAP`` by the
    tile's distance-unit magnitude (never skips a tile exact arithmetic
    would keep — rounding only prunes less)."""
    n_tiles = state.partials.shape[0]
    tps = tiles_per_super(n_tiles, tps)
    dmax = jnp.max(delta)
    occupied = state.tile_counts > 0.0                      # (n_super, k)
    moved_sup = jnp.any(occupied & (delta[None, :] > 0.0), axis=1)
    moved = moved_sup[jnp.arange(n_tiles, dtype=jnp.int32) // tps]
    skip = jnp.logical_and(
        state.tile_gap >= dmax * (1.0 + _REL)
        + _ABS_GAP * _distance_scale(centroids, cache),
        jnp.logical_not(moved))
    return jnp.logical_not(skip)


def _distance_scale(centroids: jax.Array, cache: RoundCache) -> jax.Array:
    """(n_tiles,) distance-unit operand magnitude of each tile's d2 math —
    the scale both assignment-side absolute slacks multiply."""
    c = centroids.astype(jnp.float32)
    cmax = jnp.sqrt(jnp.max(jnp.sum(c * c, axis=-1)))
    return jnp.sqrt(jnp.sum(cache.centers * cache.centers, axis=1)) \
        + cache.radii + cmax


def assign_point_scalars(delta: jax.Array, centroids: jax.Array,
                         state: BoundState, cache: RoundCache
                         ) -> tuple[jax.Array, jax.Array]:
    """The two per-tile scalars the fine-level ASSIGNMENT gate streams:

    * ``thresh`` (n_tiles,) — prune threshold with the tile's lazy
      ``lb_debt`` folded in: point i of tile t short-circuits iff its own
      centroid is bitwise unmoved and ``point_lb[i] − sqrt(min_d2[i]) >=
      thresh_t`` (i.e. the DEBT-CORRECTED lb clears the movement bound with
      the conservative fp32 margin of :func:`assign_active_tiles`).
    * ``absorb`` (n_tiles,) — ``lb_debt_t + delta_max``: what a computed
      tile subtracts from the stored ``point_lb`` of its pruned points, so
      the stored value is exact-absolute again (debt resets to zero).
    """
    dmax = jnp.max(delta)
    thresh = (dmax * (1.0 + _REL)
              + _ABS_GAP * _distance_scale(centroids, cache)
              + state.lb_debt)
    return thresh, state.lb_debt + dmax


def assign_point_prune(prev_a: jax.Array, prev_md: jax.Array,
                       prev_lb: jax.Array, delta: jax.Array,
                       thresh: jax.Array, valid: jax.Array) -> jax.Array:
    """Per-point ASSIGNMENT prune mask for ONE tile (1, bn): True where the
    point's label AND its exact ``min_d2`` provably cannot change, so the
    k-way distance recomputation short-circuits to the carried values —
    bitwise what a fresh compute would produce. The per-point inputs are
    (1, bn) rows and ``delta`` the (k, 1) movement column (the kernels'
    layout). Shared verbatim by the pure-JAX model and the Pallas gated
    kernels (the one-hot contraction instead of a gather keeps it
    Mosaic-friendly)."""
    k = delta.shape[0]
    onehot = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0) == prev_a
    own_delta = jnp.sum(jnp.where(onehot, delta, 0.0), axis=0,
                        keepdims=True)
    ub = jnp.sqrt(prev_md)
    return valid & (own_delta == 0.0) & (prev_lb - ub >= thresh)


def decay_gap(gap: jax.Array, active: jax.Array, fresh_gap: jax.Array,
              delta_max: jax.Array) -> jax.Array:
    """Next iteration's carried gap: fresh for computed tiles, carried-minus-
    movement for skipped ones (each step's ``max_j delta_j`` shrinks every
    stale margin, so a gap refreshed at iteration r stays a valid lower
    bound after any number of consecutive skips)."""
    return jnp.where(active, fresh_gap, gap - delta_max)


def ivf_gate_skip(dc: jax.Array, radius: jax.Array, center_norm: jax.Array,
                  q_norm: jax.Array, tau: jax.Array) -> jax.Array:
    """The IVF scan's per-tile kth-distance gate: True when tile t provably
    cannot beat the carried kth-best distance ``tau``.

    ``dc = d(q, center_t)``: by the triangle inequality every row x of the
    tile has ``d(q, x) >= dc - r_t``, so when ``(max(dc - r_t, 0))^2 >= tau``
    no candidate in the tile can enter the top-k. The fp32 slack mirrors
    :func:`seed_gate` — the scan evaluates candidate d2 in the matmul form,
    whose cancellation error is ABSOLUTE in the operand magnitude
    ``(||center|| + r + ||q||)^2`` — so a tile is only skipped when the
    kernel's OWN fp32 d2 values provably all exceed ``tau`` STRICTLY.
    Strictness matters for the bitwise value-noop: the blocked top-k merge
    orders by ``(d2, row)`` lexicographically, so a skipped candidate with
    ``d2 == tau`` but a smaller row id could otherwise displace the
    incumbent kth entry. With the positive margin, skipping implies
    ``d2 > tau`` for every row — gated and ungated scans return bitwise
    identical top-k (tested). ``tau = +inf`` (top-k not yet full) never
    skips. Shared verbatim by the Pallas scan kernels and the pure-jnp
    model."""
    lo = jnp.maximum(dc - radius, 0.0)
    margin = _ABS * (center_norm + radius + jnp.sqrt(q_norm)) ** 2
    return lo * lo >= tau * (1.0 + _REL) + margin


def compact_ids(active: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Compaction for the scalar-prefetched index map: returns
    ``(ids_clamped (n_tiles,) int32, n_active () int32)``.

    ``ids_clamped[i]`` is the i-th active tile id for ``i < n_active`` and the
    LAST active tile id after that, so the trailing grid steps of the gated
    kernel revisit an already-resident block (no extra HBM fetch) and are
    compute-gated off by ``i < n_active``. Stable argsort keeps active tiles
    in ascending order, preserving the pipeline's sequential-stream access
    pattern over the survivors.

    ``n_active`` is floored at 1 even when every tile clears the bound:
    grid step 0 then recomputes one skippable tile, which is a value-noop
    (skipping is exact) but guarantees every VISITED output block gets
    written — a compiled-Mosaic output block is write-only VMEM, so a
    visited-but-never-written block would flush garbage over the aliased
    buffer. Unvisited blocks are safe: the alias means their HBM contents
    are the donated inputs, untouched.
    """
    n_tiles = active.shape[0]
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    n_active = jnp.maximum(jnp.sum(active), 1).astype(jnp.int32)
    clamp = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32), n_active - 1)
    return order[clamp], n_active
