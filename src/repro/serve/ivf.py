"""Batched IVF vector search over a trained k-means model.

The clustering stack's output IS an inverted-file index: the fitted
centroids are a coarse quantizer, each cluster an inverted list. This
module closes that loop for serving:

**Build** (`IvfIndex.build`) runs the fused ``kmeans``, permutes rows with
`data.ordering.label_sort_order` so every inverted list is one contiguous
run of tiles, and records everything the scan kernels stream: per-list
``starts``/``counts`` offsets, the per-tile ball summaries the seed
prologue already computes (`core.bounds.prologue`), the (nlist, n_tiles)
list->tile coverage matrix, and — optionally — PQ residual codes through
`serve.kvquant` (codebook over ``x - centroid[label]``, plus the
reconstructed-row norms and balls the ADC path needs).

**Query** (`IvfIndex.search`) is one batched pass per call:

1. *routing* — exact top-``nprobe`` centroids per query, two-level: a
   coarse super-centroid pass bounds the nprobe-th centroid distance from
   ball geometry alone (``tau_ub`` = the max upper bound of the smallest
   ub-sorted prefix covering >= nprobe centroids), then the exact rerank
   runs only over supers whose lower bound clears ``tau_ub`` — the same
   prefix-cover argument as the seeding hierarchy, so routing is EXACT,
   never approximate;
2. *gated cluster-local scan* — the Pallas kernels in
   ``kernels/ivf_scan.py``: per-query compacted probed-tile maps steered
   through scalar prefetch (tiles outside the probed lists are never
   fetched), a per-tile kth-distance triangle-inequality gate
   (`core.bounds.ivf_gate_skip` — a bitwise value-noop), and an fp32
   lexicographic top-k merge carried across tiles (`core.topk`);
3. *scoring* — ``mode="exact"`` streams raw rows (bitwise equal to
   `IvfIndex.exhaustive` at ``nprobe == nlist``); ``mode="adc"`` streams
   uint8 PQ codes and scores via per-query LUT + routing-dot contraction
   (exact distances to the reconstructed rows, ~``n_sub/(4d)`` of the
   exact path's bytes).

Every search revalidates the list offsets against the stored layout before
trusting them (`CorruptedStateError` on mismatch — wrong neighbors are
silent, a poisoned index must never return), and reports the per-query
telemetry counters `core.telemetry.check_ivf_counters` pins.
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bounds
from repro.core.engine import ClusterEngine
from repro.core.guards import (CorruptedStateError, InvalidInputError,
                               check_policy, guard_points)
from repro.core.topk import IDX_SENTINEL
from repro.data.ordering import label_sort_order
from repro.kernels import ops as kops
from repro.serve import kvquant

__all__ = ["IvfIndex", "IvfPq", "SearchResult", "default_nprobe"]


class IvfPq(NamedTuple):
    """PQ residual storage riding on an IvfIndex (``mode="adc"`` inputs).

    ``u`` and the balls are computed over the RECONSTRUCTED rows
    ``x_hat = centroid[label] + decode(code)`` — ADC scores are exact
    distances to x_hat, so the same triangle-inequality gate stays a
    value-noop on the ADC path."""
    codes: jax.Array            # (n, n_sub) uint8, sorted row order
    codebook: kvquant.PQCodebook
    u: jax.Array                # (n,) fp32 ||x_hat||^2
    centers: jax.Array          # (n_tiles, d) balls over x_hat
    radii: jax.Array            # (n_tiles,)


class SearchResult(NamedTuple):
    """Batched search output + the per-query telemetry counters."""
    indices: jax.Array          # (Q, k) int32 CALLER row ids (IDX_SENTINEL
    #                             pads when k > n)
    dists: jax.Array            # (Q, k) fp32 squared distances
    probed_lists: jax.Array     # (Q,) int32 non-empty lists routed to
    probed_tiles: jax.Array     # (Q,) int32 tiles the scan visited
    gate_skipped: jax.Array     # (Q,) int32 visited tiles the gate skipped
    backend: str = ""           # scan backend that served the call (after
    #                             any kernel-failure fallback)


class IvfIndex(NamedTuple):
    """A trained k-means model packaged as an inverted-file index.

    Rows are stored label-sorted (``points == caller_points[perm]``);
    kernel row ids map back through ``perm``. ``layout="none"`` keeps the
    caller's row order (perm = identity) — the benchmark contrast showing
    WHY list-contiguous layouts matter — while ``starts``/``counts`` stay
    the would-be offsets so the corruption check has one invariant."""
    points: jax.Array           # (n, d) fp32, sorted rows
    norms: jax.Array            # (n,) fp32 cached ||x||^2
    centers: jax.Array          # (n_tiles, d) tile ball centers
    radii: jax.Array            # (n_tiles,) tile ball radii
    labels: jax.Array           # (n,) int32 list id per sorted row
    perm: jax.Array             # (n,) int32 sorted -> caller row map
    starts: jax.Array           # (nlist,) int32 list boundary offsets
    counts: jax.Array           # (nlist,) int32 list sizes
    centroids: jax.Array        # (nlist, d) fp32 coarse quantizer
    centroid_norms: jax.Array   # (nlist,) fp32
    super_centers: jax.Array    # (n_super, d) routing hierarchy
    super_radii: jax.Array      # (n_super,)
    super_sizes: jax.Array      # (n_super,) int32 real centroids per super
    list_tiles: jax.Array       # (nlist, n_tiles) bool coverage matrix
    block_n: int                # scan tile height (static)
    backend: str                # default scan backend
    pq: Optional[IvfPq] = None  # ADC storage (build(pq_nsub=...))

    # -- derived statics ---------------------------------------------------
    @property
    def n(self) -> int:
        return self.points.shape[0]

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_tiles(self) -> int:
        return self.centers.shape[0]

    # -- build -------------------------------------------------------------
    @classmethod
    def build(cls, points, nlist: int, *, engine: ClusterEngine | None = None,
              key: jax.Array | None = None, block_n: int | None = None,
              layout: str = "label", pq_nsub: int | None = None,
              max_iters: int = 25, validate: str = "raise") -> "IvfIndex":
        """Cluster ``points`` into ``nlist`` inverted lists and package the
        scan inputs. ``layout="label"`` (default) sorts rows so each list is
        a contiguous tile run; ``layout="none"`` keeps caller order (the
        scattered-layout baseline). ``pq_nsub`` adds PQ residual storage
        for ``mode="adc"`` (d % pq_nsub == 0)."""
        check_policy(validate)
        if layout not in ("label", "none"):
            raise InvalidInputError(
                f"unknown layout {layout!r}; expected 'label' or 'none'")
        points = guard_points(points, validate, name="points")
        pts = jnp.asarray(points, jnp.float32)
        n, d = pts.shape
        if not 0 < nlist <= n:
            raise InvalidInputError(
                f"need 0 < nlist <= n, got nlist={nlist}, n={n}")
        eng = ClusterEngine("fused", tune="cache") if engine is None \
            else engine
        if key is None:
            key = jax.random.PRNGKey(0)
        res = eng.kmeans(key, pts, nlist, max_iters=max_iters)
        centroids = jnp.asarray(res.centroids, jnp.float32)
        labels = jnp.asarray(res.assignment, jnp.int32)

        if layout == "label":
            perm, _, starts, counts = label_sort_order(
                labels, nlist=nlist, return_offsets=True)
        else:
            perm = jnp.arange(n, dtype=jnp.int32)
            counts = jnp.bincount(labels, length=nlist).astype(jnp.int32)
            starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
        spts = pts[perm]
        slab = labels[perm]

        if block_n is None:
            # the tile is the probe granularity: aim for ~4 tiles per
            # inverted list (pow2, >= 128) so nprobe of nlist lists maps to
            # ~nprobe/nlist of the tiles — capped by the VMEM-validated
            # round-kernel pick
            cap = kops.choose_block_n(n, d, 1, batched=True)
            tgt = 1 << max(7, (n // (4 * nlist)).bit_length() - 1)
            block_n = max(128, min(cap, tgt))
        rc = bounds.prologue(spts, block_n)
        n_tiles = rc.centers.shape[0]

        # routing hierarchy: pow2 groups of ~sqrt(nlist) consecutive
        # centroids; ball stats over REAL members only (masked pad)
        g = _super_group_size(int(nlist))
        n_sup = -(-nlist // g)
        cpad = jnp.pad(centroids, ((0, n_sup * g - nlist), (0, 0)))
        member = (jnp.arange(n_sup * g) < nlist).reshape(n_sup, g)
        sizes = member.sum(axis=1).astype(jnp.int32)
        grp = cpad.reshape(n_sup, g, d)
        sup_c = (jnp.where(member[:, :, None], grp, 0.0).sum(axis=1)
                 / jnp.maximum(sizes, 1)[:, None])
        sup_d2 = jnp.sum((grp - sup_c[:, None, :]) ** 2, axis=-1)
        sup_r = jnp.sqrt(jnp.max(jnp.where(member, sup_d2, 0.0), axis=1))

        tile_of_row = (jnp.arange(n, dtype=jnp.int32)
                       // jnp.int32(block_n))
        list_tiles = jnp.zeros((nlist, n_tiles), bool) \
            .at[slab, tile_of_row].max(True)

        pq = None
        if pq_nsub is not None:
            resid = spts - centroids[slab]
            cb = kvquant.build_codebook(
                jax.random.fold_in(key, 1), resid, n_sub=pq_nsub,
                engine=engine, validate=validate)
            codes = kvquant.encode(resid, cb, validate=validate)
            xhat = (kvquant.decode(codes, cb).astype(jnp.float32)
                    + centroids[slab])
            arc = bounds.prologue(xhat, block_n)
            pq = IvfPq(codes, cb, arc.norms, arc.centers, arc.radii)

        backend = getattr(eng.backend, "name", "fused")
        return cls(points=spts, norms=rc.norms, centers=rc.centers,
                   radii=rc.radii, labels=slab, perm=perm, starts=starts,
                   counts=counts, centroids=centroids,
                   centroid_norms=bounds.point_norms(centroids),
                   super_centers=sup_c, super_radii=sup_r,
                   super_sizes=sizes, list_tiles=list_tiles,
                   block_n=int(block_n), backend=backend, pq=pq)

    # -- query -------------------------------------------------------------
    def search(self, queries, k: int, nprobe: int | None = None, *,
               mode: str = "exact", gate: bool = True,
               backend: str | None = None,
               validate: str = "raise") -> SearchResult:
        """Batched top-``k``: route each query to its top-``nprobe``
        centroids, scan only those lists' tiles. ``nprobe=None`` consults
        the tune cache's advisory column (:func:`default_nprobe`).
        ``mode="adc"`` scores against the PQ reconstruction (requires
        ``build(pq_nsub=...)``); ``gate=False`` disables the (value-noop)
        kth-distance tile gate, for benchmarking its traffic effect.
        Raises `CorruptedStateError` if the stored list offsets disagree
        with the layout — never returns silently-wrong neighbors."""
        check_policy(validate)
        if mode not in ("exact", "adc"):
            raise InvalidInputError(
                f"unknown mode {mode!r}; expected 'exact' or 'adc'")
        if mode == "adc" and self.pq is None:
            raise InvalidInputError(
                "mode='adc' needs PQ storage: build(pq_nsub=...)")
        self._check_offsets()
        queries = guard_points(queries, validate, name="queries")
        q = jnp.asarray(queries, jnp.float32)
        if q.ndim != 2 or q.shape[1] != self.points.shape[1]:
            raise InvalidInputError(
                f"queries shape {q.shape} does not match index dimension "
                f"{self.points.shape[1]}")
        if not 0 < k:
            raise InvalidInputError(f"need k >= 1, got k={k}")
        if nprobe is None:
            nprobe = default_nprobe(self.n, self.nlist,
                                    self.points.shape[1])
        nprobe = max(1, min(int(nprobe), self.nlist))

        probed, qdots = _route(q, self.centroids, self.centroid_norms,
                               self.super_centers, self.super_radii,
                               self.super_sizes, nprobe=nprobe)
        tiles = (probed.astype(jnp.float32)
                 @ self.list_tiles.astype(jnp.float32)) > 0.0
        ids, n_active = jax.vmap(bounds.compact_ids)(tiles)
        probed_lists = jnp.sum(probed & (self.counts > 0)[None, :],
                               axis=1).astype(jnp.int32)

        be = self.backend if backend is None else backend
        dists, rows, skipped, served = self._scan(
            q, qdots, ids, n_active, k=int(k), mode=mode, gate=gate,
            backend=be)
        return SearchResult(indices=_map_rows(rows, self.perm),
                            dists=dists, probed_lists=probed_lists,
                            probed_tiles=n_active.astype(jnp.int32),
                            gate_skipped=skipped, backend=served)

    def exhaustive(self, queries, k: int) -> tuple[jax.Array, jax.Array]:
        """Brute-force batched top-k over every row — the ground truth
        ``search`` at ``nprobe == nlist`` equals BITWISE (same cached
        norms, same per-row dot arithmetic, same lexicographic tie-break
        over sorted-row ids). Returns (indices, dists) in caller ids."""
        from repro.kernels.ref import ivf_bruteforce_topk

        q = jnp.asarray(queries, jnp.float32)
        dists, rows = ivf_bruteforce_topk(q, self.points, self.norms,
                                          k=min(int(k), self.n))
        if k > self.n:      # pad like the scan's sentinel slots
            pad = int(k) - self.n
            dists = jnp.pad(dists, ((0, 0), (0, pad)),
                            constant_values=jnp.inf)
            rows = jnp.pad(rows, ((0, 0), (0, pad)),
                           constant_values=IDX_SENTINEL)
        return _map_rows(rows, self.perm), dists

    # -- internals ---------------------------------------------------------
    def _scan(self, q, qdots, ids, n_active, *, k: int, mode: str,
              gate: bool, backend: str):
        """Dispatch the gated scan, walking the kernel fallback chain on
        KernelFailureError (same degradation policy as the engine, which
        also warns). Returns (dists, rows, gate_skipped, backend that
        served)."""
        from repro.core.guards import KernelFailureError
        from repro.kernels import ref as kref

        kk = min(k, self.n)
        be = backend
        while True:
            try:
                if mode == "exact":
                    if be == "pallas":
                        out = kops.ivf_scan(
                            q, self.points, self.norms, self.centers,
                            self.radii, ids, n_active, k=kk,
                            block_n=self.block_n, gate=gate)
                    else:
                        kops._check_forced()
                        out = kref.ivf_scan_ref(
                            q, self.points, self.norms, self.centers,
                            self.radii, ids, n_active, k=kk,
                            block_n=self.block_n, gate=gate)
                else:
                    lut = _adc_lut(q, self.pq.codebook)
                    if be == "pallas":
                        out = kops.ivf_adc_scan(
                            q, lut, qdots, self.pq.codes, self.labels,
                            self.pq.u, self.pq.centers, self.pq.radii,
                            ids, n_active, k=kk, block_n=self.block_n,
                            gate=gate)
                    else:
                        kops._check_forced()
                        out = kref.ivf_adc_scan_ref(
                            q, lut, qdots, self.pq.codes, self.labels,
                            self.pq.u, self.pq.centers, self.pq.radii,
                            ids, n_active, k=kk, block_n=self.block_n,
                            gate=gate)
                break
            except KernelFailureError as e:
                nxt = kops.FALLBACK_CHAIN.get(be)
                if nxt is None:
                    raise
                warnings.warn(f"ivf scan backend {be!r} failed ({e}); "
                              f"falling back to {nxt!r}", RuntimeWarning,
                              stacklevel=3)
                be = nxt
        dists, rows, skipped = out
        if k > self.n:      # sentinel-pad the impossible slots
            pad = k - self.n
            dists = jnp.pad(dists, ((0, 0), (0, pad)),
                            constant_values=jnp.inf)
            rows = jnp.pad(rows, ((0, 0), (0, pad)),
                           constant_values=IDX_SENTINEL)
        return dists, rows, skipped, be

    def _check_offsets(self) -> None:
        """Host-side offset revalidation, ALWAYS on (independent of the
        ``validate`` policy): the scan trusts ``starts``/``counts`` to
        describe the stored layout, and a poisoned offset table would
        return silently-wrong neighbors — the one failure mode serving can
        never have. Cost: one (nlist,)-sized numpy pass per search."""
        starts = np.asarray(self.starts)
        counts = np.asarray(self.counts)
        nlist = self.nlist
        if starts.shape != (nlist,) or counts.shape != (nlist,):
            raise CorruptedStateError(
                f"ivf index offsets have shapes {starts.shape}/"
                f"{counts.shape}, expected ({nlist},): rebuild the index")
        if (counts < 0).any() or (starts < 0).any():
            raise CorruptedStateError(
                "ivf index offsets contain negative entries: rebuild the "
                "index")
        if int(counts.sum()) != self.n:
            raise CorruptedStateError(
                f"ivf list sizes sum to {int(counts.sum())} != n={self.n}: "
                "rebuild the index")
        expect = np.cumsum(counts) - counts
        if not np.array_equal(starts, expect):
            raise CorruptedStateError(
                "ivf list starts disagree with exclusive-cumsum(counts): "
                "rebuild the index")


def default_nprobe(n: int, nlist: int, d: int) -> int:
    """The ``nprobe=None`` resolution: the tune cache's advisory ``nprobe``
    column for this (n, k=nlist, d) shape under the "ivf" backend key, else
    the nlist/8 heuristic (`tune.search._advisory`'s rationale)."""
    from repro import tune

    rec = tune.resolve(tune.TuneCache(None), n=int(n), k=int(nlist),
                       d=int(d), backend="ivf", dtype="float32",
                       mode="cache")
    if rec is not None and int(rec.nprobe) > 0:
        return min(int(rec.nprobe), int(nlist))
    return max(1, int(nlist) // 8)


def _super_group_size(nlist: int) -> int:
    """Centroids per super group: the pow2 nearest ~sqrt(nlist). Build and
    routing must agree on this — ``sup_of_list`` in :func:`_route` is
    reconstructed from it, and any mismatch maps centroids to the wrong
    super ball, breaking the exact-routing guarantee."""
    return 1 << ((int(nlist - 1).bit_length() + 1) // 2) if nlist > 1 else 1


@functools.partial(jax.jit, static_argnames=("nprobe",))
def _route(q, centroids, centroid_norms, sup_c, sup_r, sup_sizes, *,
           nprobe: int):
    """Exact top-``nprobe`` centroid routing.

    Coarse pass: per (query, super) bounds ``lb = max(d - R, 0)^2`` /
    ``ub = (d + R)^2`` from the super ball; ``tau_ub`` = the largest ub of
    the smallest ub-sorted prefix covering >= nprobe centroids, so the
    nprobe-th best centroid distance is <= tau_ub and every top-nprobe
    centroid's super satisfies ``lb <= tau_ub``. The exact rerank masks
    non-surviving supers' centroids to +inf — by that argument it can never
    mask a true top-nprobe centroid, so routing equals the full-rerank
    result exactly (fp slack mirrors `core.bounds`' gate margins: at
    ``nprobe == nlist`` every super survives and routing IS the full
    rerank). Returns (probed (Q, nlist) bool, qdots (Q, nlist) fp32 — the
    routing dots the ADC path reuses)."""
    nlist = centroids.shape[0]
    g = _super_group_size(nlist)
    qn = jnp.sum(q * q, axis=1)                                # (Q,)

    sc2 = jnp.sum(sup_c * sup_c, axis=1)
    hi = jax.lax.Precision.HIGHEST    # exact routing needs fp32 dots
    sd2 = jnp.maximum(qn[:, None] - 2.0 * jnp.matmul(q, sup_c.T, precision=hi)
                      + sc2[None, :], 0.0)
    sd = jnp.sqrt(sd2)                                         # (Q, n_sup)
    lb = jnp.maximum(sd - sup_r[None, :], 0.0) ** 2
    ub = (sd + sup_r[None, :]) ** 2
    order = jnp.argsort(ub, axis=1)
    csum = jnp.cumsum(jnp.take_along_axis(
        jnp.broadcast_to(sup_sizes[None, :], ub.shape), order, axis=1),
        axis=1)
    pos = jnp.argmax(csum >= nprobe, axis=1)
    tau_ub = jnp.take_along_axis(jnp.take_along_axis(ub, order, axis=1),
                                 pos[:, None], axis=1)[:, 0]
    margin = bounds._ABS * (jnp.sqrt(sc2)[None, :] + sup_r[None, :]
                            + jnp.sqrt(qn)[:, None]) ** 2
    survive = lb <= tau_ub[:, None] * (1.0 + bounds._REL) + margin

    qdots = jnp.matmul(q, centroids.T, precision=hi)          # (Q, nlist)
    cd2 = jnp.maximum(qn[:, None] - 2.0 * qdots
                      + centroid_norms[None, :], 0.0)
    sup_of_list = jnp.arange(nlist, dtype=jnp.int32) // jnp.int32(g)
    cd2m = jnp.where(survive[:, sup_of_list], cd2, jnp.inf)
    lid = jnp.broadcast_to(jnp.arange(nlist, dtype=jnp.int32)[None, :],
                           cd2m.shape)
    _, sel = jax.vmap(lambda v, i: jax.lax.sort((v, i), num_keys=2))(
        cd2m, lid)
    probed = jnp.zeros((q.shape[0], nlist), bool) \
        .at[jnp.arange(q.shape[0])[:, None], sel[:, :nprobe]].set(True)
    return probed, qdots


@jax.jit
def _adc_lut(q, cb: kvquant.PQCodebook):
    """Per-query inner-product LUT over the residual codebook:
    ``lut[q, s, c] = q_s . codebook[s, c]`` — the one table ADC scoring
    contracts every streamed code against."""
    n_sub, n_codes, dsub = cb.centroids.shape
    qsub = q.reshape(q.shape[0], n_sub, dsub)
    return jnp.einsum("qsd,scd->qsc", qsub,
                      cb.centroids.astype(jnp.float32),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


@jax.jit
def _map_rows(rows, perm):
    """Sorted-layout kernel row ids -> caller row ids, sentinel-preserving."""
    n = perm.shape[0]
    safe = jnp.clip(rows, 0, n - 1)
    return jnp.where(rows == IDX_SENTINEL, IDX_SENTINEL, perm[safe])
