"""ClusterEngine seam tests: backend parity (bitwise-identical seeds),
weighted seeding, empty-cluster fallback, mini-batch convergence, and batched
multi-problem clustering."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quality
from repro.core.engine import (ClusterEngine, FusedBackend, MeshBackend,
                               PallasBackend, ReferenceBackend, make_backend)
from repro.core.lloyd import assign, update
from repro.data.pipeline import DataPipeline
from repro.data.synthetic import blobs


def _points(n=512, d=2, k=8, seed=0):
    pts, _ = blobs(n, d, k, seed=seed)
    return jnp.asarray(pts)


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

def test_make_backend_names():
    assert isinstance(make_backend("reference"), ReferenceBackend)
    assert make_backend("serial").mode == "serial"
    assert make_backend("global").mode == "global"
    assert isinstance(make_backend("fused"), FusedBackend)
    assert make_backend("pallas").resident
    assert not make_backend("pallas_fused").resident
    b = make_backend("fused")
    assert make_backend(b) is b
    with pytest.raises(ValueError):
        make_backend("cuda")
    with pytest.raises(ValueError):
        make_backend("mesh")  # needs mesh=


# ---------------------------------------------------------------------------
# acceptance: same key => bitwise-identical seeds across backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "fused", "pallas"])
def test_seed_parity_across_backends(backend):
    pts = _points(n=512, k=8)
    key = jax.random.PRNGKey(42)
    ref = ClusterEngine("reference", mode="serial").seed(key, pts, 10)
    got = ClusterEngine(backend).seed(key, pts, 10)
    np.testing.assert_array_equal(np.asarray(ref.indices),
                                  np.asarray(got.indices))
    np.testing.assert_array_equal(np.asarray(ref.centroids),
                                  np.asarray(got.centroids))


def test_shims_route_through_engine():
    """The historical kmeanspp(variant=...) entry picks the same seeds as the
    engine with the mapped backend."""
    from repro.core import kmeanspp
    pts = _points(n=300, d=3)
    key = jax.random.PRNGKey(7)
    for variant, backend in (("serial", ReferenceBackend(mode="serial")),
                             ("fused", FusedBackend()),
                             ("pallas_constant", PallasBackend(resident=True))):
        a = kmeanspp(key, pts, 6, variant=variant)
        b = ClusterEngine(backend).seed(key, pts, 6)
        np.testing.assert_array_equal(np.asarray(a.indices),
                                      np.asarray(b.indices))


# ---------------------------------------------------------------------------
# weighted seeding
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "fused", "pallas"])
def test_weighted_seeding_respects_zero_weights(backend):
    pts = _points(n=256, d=2, k=4, seed=2)
    w = jnp.where(jnp.arange(256) < 128, 1.0, 0.0)
    res = ClusterEngine(backend).seed(jax.random.PRNGKey(0), pts, 6, weights=w)
    idx = np.asarray(res.indices)
    assert (idx < 128).all(), f"zero-weight point chosen as seed: {idx}"


def test_weighted_seeding_parity_across_backends():
    pts = _points(n=256, d=2, k=4, seed=3)
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (256,))) + 0.1
    key = jax.random.PRNGKey(1)
    ref = ClusterEngine("reference").seed(key, pts, 5, weights=w)
    for backend in ("fused", "pallas"):
        got = ClusterEngine(backend).seed(key, pts, 5, weights=w)
        np.testing.assert_array_equal(np.asarray(ref.indices),
                                      np.asarray(got.indices))


# ---------------------------------------------------------------------------
# Lloyd through the engine + empty-cluster fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "fused", "pallas"])
def test_fit_matches_reference_inertia(backend):
    pts = _points(n=600, d=3, k=6)
    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(0), pts, 6).centroids
    ref = ClusterEngine("reference").fit(pts, seeds, max_iters=10)
    got = ClusterEngine(backend).fit(pts, seeds, max_iters=10)
    np.testing.assert_allclose(float(got.inertia), float(ref.inertia),
                               rtol=1e-5)


def test_empty_cluster_keeps_prev_centroid_in_update():
    pts = jnp.asarray([[0.0, 0.0], [1.0, 1.0], [1.1, 1.0]])
    cents = jnp.asarray([[0.0, 0.0], [1.0, 1.0], [99.0, 99.0]])
    a, _ = assign(pts, cents)
    new = update(pts, a, 3, prev_centroids=cents)
    np.testing.assert_allclose(np.asarray(new)[2], [99.0, 99.0])


@pytest.mark.parametrize("backend", ["fused", "pallas"])
def test_empty_cluster_fallback_in_engine_fit(backend):
    pts = jnp.asarray([[0.0, 0.0], [0.1, 0.0], [1.0, 1.0], [1.1, 1.0]])
    cents = jnp.asarray([[0.0, 0.0], [1.0, 1.0], [99.0, 99.0]])
    res = ClusterEngine(backend).fit(pts, cents, max_iters=3)
    # the far centroid owns no points and must survive every iteration
    np.testing.assert_allclose(np.asarray(res.centroids)[2], [99.0, 99.0])


def test_weighted_fit_pulls_centroid_to_heavy_points():
    pts = jnp.asarray([[0.0, 0.0], [1.0, 0.0]])
    w = jnp.asarray([3.0, 1.0])
    res = ClusterEngine("fused").fit(pts, jnp.asarray([[0.4, 0.0]]),
                                     max_iters=2, weights=w)
    np.testing.assert_allclose(np.asarray(res.centroids)[0, 0], 0.25,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# mini-batch Lloyd
# ---------------------------------------------------------------------------

def _mb_setup(n=8192, d=2, k=8, batch=512, seed=1):
    full = jnp.asarray(blobs(n, d, k, seed=seed)[0])
    np_pts = np.asarray(full)

    def read_fn(step):
        lo = (step * batch) % n
        return np_pts[lo:lo + batch]

    return full, read_fn


def test_minibatch_converges_to_full_batch_quality():
    full, read_fn = _mb_setup()
    eng = ClusterEngine("fused")
    seeds = eng.seed(jax.random.PRNGKey(1), full[:512], 8).centroids
    mb = eng.fit_minibatch(seeds, read_fn, n_batches=32)
    assert int(mb.n_iters) == 32
    phi_mb = float(quality.inertia(full, mb.centroids))
    phi_full = float(eng.fit(full, seeds, max_iters=30).inertia)
    assert phi_mb < 1.5 * phi_full, (phi_mb, phi_full)


def test_minibatch_accepts_pipeline_and_early_stops():
    full, read_fn = _mb_setup()
    eng = ClusterEngine("fused")
    seeds = eng.seed(jax.random.PRNGKey(1), full[:512], 8).centroids
    pipe = DataPipeline(read_fn)
    mb = eng.fit_minibatch(seeds, pipe, n_batches=64, tol=1e-3, patience=3)
    assert 0 < int(mb.n_iters) <= 64
    # a well-separated blob problem plateaus long before 64 batches
    assert int(mb.n_iters) < 64
    assert mb.assignment.shape == (512,)


def test_minibatch_rejects_empty_source():
    eng = ClusterEngine("fused")
    with pytest.raises(ValueError):
        eng.fit_minibatch(jnp.zeros((2, 2)), [])


def test_minibatch_requires_count_for_infinite_sources():
    """read_fn and DataPipeline sources stream forever — without n_batches
    the loop would never terminate, so both must raise up front."""
    eng = ClusterEngine("fused")
    read_fn = lambda step: np.zeros((4, 2), np.float32)
    with pytest.raises(ValueError, match="n_batches"):
        eng.fit_minibatch(jnp.zeros((2, 2)), read_fn)
    with pytest.raises(ValueError, match="n_batches"):
        eng.fit_minibatch(jnp.zeros((2, 2)), DataPipeline(read_fn))


def test_minibatch_propagates_read_fn_failure():
    """A dying prefetch thread must raise, not deadlock the consumer."""
    def bad_read(step):
        raise IOError(f"shard {step} missing")

    eng = ClusterEngine("fused")
    with pytest.raises(RuntimeError, match="read_fn failed"):
        eng.fit_minibatch(jnp.zeros((2, 2)), bad_read, n_batches=4)


def test_assign_use_pallas_returns_pair():
    pts = _points(n=200, d=3)
    cents = pts[:4]
    a, md = assign(pts, cents, use_pallas=True)
    a2, md2 = assign(pts, cents)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a2))
    np.testing.assert_allclose(np.asarray(md), np.asarray(md2),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# batched multi-problem clustering
# ---------------------------------------------------------------------------

def test_seed_batched_matches_per_problem():
    B = 3
    bpts = jnp.stack([_points(n=256, d=2, k=4, seed=s) for s in range(B)])
    eng = ClusterEngine("fused")
    keys = jax.random.split(jax.random.PRNGKey(3), B)
    batched = eng.seed_batched(keys, bpts, 5)
    assert batched.centroids.shape == (B, 5, 2)
    for b in range(B):
        single = eng.seed(keys[b], bpts[b], 5)
        np.testing.assert_array_equal(np.asarray(batched.indices[b]),
                                      np.asarray(single.indices))


def test_kmeans_batched_end_to_end():
    B, n, k = 4, 1024, 6
    bpts = jnp.stack([_points(n=n, d=2, k=k, seed=10 + s) for s in range(B)])
    out = ClusterEngine("fused").kmeans_batched(jax.random.PRNGKey(2), bpts, k,
                                                max_iters=25)
    assert out.centroids.shape == (B, k, 2)
    assert out.inertia.shape == (B,)
    for b in range(B):
        # every problem must reach blob-quality inertia (spread 0.05, d=2)
        assert float(out.inertia[b]) / n < 3 * 2 * 0.05 ** 2, b


def test_seed_batched_pallas_matches_fused_and_single():
    """The batch-grid pallas kernel path picks the same seeds as the fused
    vmap path AND as B single-problem pallas calls (no fallback, no drift)."""
    B = 3
    bpts = jnp.stack([_points(n=300, d=3, k=4, seed=20 + s) for s in range(B)])
    keys = jax.random.split(jax.random.PRNGKey(9), B)
    pal = ClusterEngine("pallas").seed_batched(keys, bpts, 5)
    fus = ClusterEngine("fused").seed_batched(keys, bpts, 5)
    np.testing.assert_array_equal(np.asarray(pal.indices),
                                  np.asarray(fus.indices))
    for b in range(B):
        single = ClusterEngine("pallas").seed(keys[b], bpts[b], 5)
        np.testing.assert_array_equal(np.asarray(pal.indices[b]),
                                      np.asarray(single.indices))


def test_seed_batched_pallas_tiled_sampler():
    B = 2
    bpts = jnp.stack([_points(n=256, d=2, k=4, seed=30 + s) for s in range(B)])
    keys = jax.random.split(jax.random.PRNGKey(12), B)
    out = ClusterEngine("pallas").seed_batched(keys, bpts, 4, sampler="tiled")
    idx = np.asarray(out.indices)
    assert ((0 <= idx) & (idx < 256)).all()
    for b in range(B):
        assert len(set(idx[b].tolist())) == 4, idx[b]


def test_kmeans_batched_pallas_end_to_end():
    """Acceptance: kmeans_batched on the pallas backend (batch-grid kernels)
    reaches the same inertia as the fused path on every problem."""
    B, n, k = 3, 512, 4
    bpts = jnp.stack([_points(n=n, d=2, k=k, seed=40 + s) for s in range(B)])
    key = jax.random.PRNGKey(5)
    pal = ClusterEngine("pallas").kmeans_batched(key, bpts, k, max_iters=15)
    fus = ClusterEngine("fused").kmeans_batched(key, bpts, k, max_iters=15)
    assert pal.centroids.shape == (B, k, 2)
    np.testing.assert_allclose(np.asarray(pal.inertia),
                               np.asarray(fus.inertia), rtol=1e-4)


def test_batched_rejects_mesh_backend():
    mesh = jax.make_mesh((1,), ("data",))
    eng = ClusterEngine(MeshBackend(mesh=mesh, axes=("data",)))
    with pytest.raises(NotImplementedError):
        eng.seed_batched(jax.random.PRNGKey(0), jnp.zeros((2, 8, 2)), 2)


# ---------------------------------------------------------------------------
# two-level tiled sampling (ISSUE 2 tentpole)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "fused", "pallas"])
def test_tiled_sampler_seeds_are_valid_and_distinct(backend):
    pts = _points(n=512, k=8)
    res = ClusterEngine(backend).seed(jax.random.PRNGKey(4), pts, 10,
                                      sampler="tiled")
    idx = np.asarray(res.indices)
    assert ((0 <= idx) & (idx < 512)).all()
    assert len(set(idx.tolist())) == 10, idx
    assert np.isfinite(np.asarray(res.centroids)).all()


def test_tiled_sampler_parity_fused_vs_pallas():
    """Fused and pallas backends produce per-tile partials with the same tile
    height and the same per-tile sums, so the two-level draw picks the same
    seeds under one key."""
    pts = _points(n=700, d=3, k=6, seed=5)
    key = jax.random.PRNGKey(11)
    a = ClusterEngine("fused").seed(key, pts, 7, sampler="tiled")
    b = ClusterEngine("pallas").seed(key, pts, 7, sampler="tiled")
    np.testing.assert_array_equal(np.asarray(a.indices),
                                  np.asarray(b.indices))


def test_tiled_sampler_quality_matches_cdf():
    """Same-distribution claim at the phi level: tiled seeding's potential is
    within the usual k-means++ run-to-run band of cdf seeding."""
    pts = _points(n=4096, d=2, k=16, seed=6)
    eng = ClusterEngine("fused")
    phis = {}
    for sampler in ("cdf", "tiled"):
        phi = [float(quality.inertia(
            pts, eng.seed(jax.random.PRNGKey(s), pts, 16,
                          sampler=sampler).centroids)) for s in range(3)]
        phis[sampler] = sum(phi) / len(phi)
    assert phis["tiled"] < 2.5 * phis["cdf"], phis
    assert phis["cdf"] < 2.5 * phis["tiled"], phis


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: isinstance(
                        x, (jax.extend.core.Jaxpr, jax.extend.core.ClosedJaxpr))):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _iter_eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _iter_eqns(sub)


def _cumsum_operand_sizes(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    sizes = set()
    for eqn in _iter_eqns(jaxpr.jaxpr):
        if "cumsum" in eqn.primitive.name:
            sizes.add(eqn.invars[0].aval.shape)
    return sizes

def test_tiled_sampler_has_no_full_n_cumsum_in_jaxpr():
    """Acceptance: with sampler='tiled' the post-kernel sampling reads
    O(n/bn + bn) elements — the traced program must contain no cumsum over
    the full (n,) array, only the (n_tiles,) and (block_n,) scans. The cdf
    sampler is the control: it must show the full-n cumsum."""
    from repro.core import engine as eng_mod
    n = 16384
    pts = jnp.zeros((n, 2), jnp.float32)
    key = jax.random.PRNGKey(0)
    backend = FusedBackend()
    tile = backend.seed_tile(n, 2)
    assert tile < n, "probe must span multiple tiles"

    def seed_with(sampler):
        return lambda k, p: eng_mod.seed_points(k, p, 4, None, backend,
                                                sampler)

    tiled_sizes = _cumsum_operand_sizes(seed_with("tiled"), key, pts)
    assert (n,) not in tiled_sizes, tiled_sizes
    assert tiled_sizes <= {(n // tile,), (tile,)}, tiled_sizes

    cdf_sizes = _cumsum_operand_sizes(seed_with("cdf"), key, pts)
    assert (n,) in cdf_sizes, cdf_sizes


# ---------------------------------------------------------------------------
# empty-cluster reseeding (split the largest cluster)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["fused", "pallas"])
def test_empty_reseed_revives_dead_centroid(backend):
    pts = jnp.asarray([[0.0, 0.0], [0.1, 0.0], [1.0, 1.0], [1.1, 1.0]])
    cents = jnp.asarray([[0.0, 0.0], [1.0, 1.0], [99.0, 99.0]])
    keep = ClusterEngine(backend).fit(pts, cents, max_iters=5)
    res = ClusterEngine(backend).fit(pts, cents, max_iters=5, empty="reseed")
    # keep-policy leaves the far centroid dead; reseed must pull it back in
    np.testing.assert_allclose(np.asarray(keep.centroids)[2], [99.0, 99.0])
    assert np.abs(np.asarray(res.centroids)[2]).max() < 50.0
    assert float(res.inertia) < float(keep.inertia)
    # every cluster owns at least one point after reseeding
    assert len(set(np.asarray(res.assignment).tolist())) == 3


def test_empty_reseed_noop_when_no_empty_clusters():
    pts = _points(n=400, d=2, k=4, seed=8)
    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(0), pts,
                                        4).centroids
    a = ClusterEngine("fused").fit(pts, seeds, max_iters=10)
    b = ClusterEngine("fused").fit(pts, seeds, max_iters=10, empty="reseed")
    np.testing.assert_allclose(np.asarray(a.centroids),
                               np.asarray(b.centroids), rtol=1e-6)


def test_fit_rejects_unknown_empty_policy():
    with pytest.raises(ValueError, match="empty-cluster"):
        ClusterEngine("fused").fit(jnp.zeros((4, 2)), jnp.zeros((2, 2)),
                                   empty="explode")


# ---------------------------------------------------------------------------
# precision & bounds (ISSUE 3 tentpole): norm caching, bf16 streaming,
# exact tile skipping
# ---------------------------------------------------------------------------

def _coherent_points(n=16384, d=2, k=4, seed=0):
    """Blob data sorted by label: tiles become spatially coherent (roughly
    one blob per 4096-point tile at the defaults), which is what makes
    block-level pruning fire (Capó et al.)."""
    pts, labels = blobs(n, d, k, seed=seed)
    return jnp.asarray(pts[np.argsort(labels, kind="stable")])


@pytest.mark.parametrize("backend", ["reference", "fused", "pallas"])
def test_bound_gating_is_bitwise_exact(backend):
    """Acceptance: the fp32 + bounds path is bitwise identical to the ungated
    path — same seeds, same min_d2 — while actually skipping tiles."""
    pts = _coherent_points()
    key = jax.random.PRNGKey(3)
    on = ClusterEngine(backend).seed(key, pts, 12)
    off = ClusterEngine(backend, bounds=False).seed(key, pts, 12)
    np.testing.assert_array_equal(np.asarray(on.indices),
                                  np.asarray(off.indices))
    np.testing.assert_array_equal(np.asarray(on.min_d2),
                                  np.asarray(off.min_d2))
    np.testing.assert_array_equal(np.asarray(on.centroids),
                                  np.asarray(off.centroids))
    assert off.skipped is None
    assert on.skipped is not None and on.skipped.shape == (12,)
    # reference here is mode='global', which gates via the pure-JAX model —
    # it must actually skip, like fused/pallas
    assert int(jnp.sum(on.skipped)) > 0, np.asarray(on.skipped)


def test_serial_reference_never_skips():
    """mode='serial' is the paper's CPU baseline: it carries the bound-state
    contract but never gates (skipped stays 0 every round)."""
    pts = _coherent_points()
    res = ClusterEngine("reference", mode="serial").seed(jax.random.PRNGKey(3),
                                                         pts, 6)
    assert res.skipped is not None
    np.testing.assert_array_equal(np.asarray(res.skipped), np.zeros(6))


@pytest.mark.parametrize("offset", [100.0, -3000.0])
def test_bound_gating_exact_far_from_origin(offset):
    """The skip margin must be ABSOLUTE in the operand magnitude: the matmul
    form's fp32 cancellation error grows with ||x||^2, so off-origin data is
    where a relative-only slack would silently break bitwise exactness."""
    pts = _coherent_points(seed=13) + offset
    key = jax.random.PRNGKey(14)
    for backend in ("fused", "pallas"):
        on = ClusterEngine(backend).seed(key, pts, 10)
        off = ClusterEngine(backend, bounds=False).seed(key, pts, 10)
        np.testing.assert_array_equal(np.asarray(on.indices),
                                      np.asarray(off.indices))
        np.testing.assert_array_equal(np.asarray(on.min_d2),
                                      np.asarray(off.min_d2))


def test_bound_gating_skip_counts_agree_fused_vs_pallas():
    """Both gated implementations (pure-JAX model vs compacted kernel) see
    the same bound decisions on the same data."""
    pts = _coherent_points(seed=4)
    key = jax.random.PRNGKey(5)
    f = ClusterEngine("fused").seed(key, pts, 10)
    p = ClusterEngine("pallas").seed(key, pts, 10)
    np.testing.assert_array_equal(np.asarray(f.indices),
                                  np.asarray(p.indices))
    # the two prologues' tile geometry is only ulp-equal, so a bound sitting
    # exactly on the threshold may flip one tile's decision: counts must
    # agree to +-1 tile per round (results stay bitwise identical either
    # way — skipping is exact)
    np.testing.assert_allclose(np.asarray(f.skipped),
                               np.asarray(p.skipped), atol=1)
    assert int(jnp.sum(f.skipped)) > 0


def test_bound_gating_with_tiled_sampler_and_batched():
    """Tile skipping composes with the tiled sampler (skipped tiles reuse
    their prior partials) and with the batch-grid path."""
    pts = _coherent_points(seed=6)
    key = jax.random.PRNGKey(7)
    for backend in ("fused", "pallas"):
        on = ClusterEngine(backend).seed(key, pts, 8, sampler="tiled")
        off = ClusterEngine(backend, bounds=False).seed(key, pts, 8,
                                                        sampler="tiled")
        np.testing.assert_array_equal(np.asarray(on.indices),
                                      np.asarray(off.indices))
    bpts = jnp.stack([_coherent_points(n=4096, seed=s) for s in (8, 9)])
    keys = jax.random.split(jax.random.PRNGKey(10), 2)
    bat_p = ClusterEngine("pallas").seed_batched(keys, bpts, 6)
    bat_f = ClusterEngine("fused").seed_batched(keys, bpts, 6)
    np.testing.assert_array_equal(np.asarray(bat_p.indices),
                                  np.asarray(bat_f.indices))
    assert bat_p.skipped.shape == (2, 6)
    np.testing.assert_array_equal(np.asarray(bat_p.skipped),
                                  np.asarray(bat_f.skipped))


def test_mesh_backend_composes_skip_counters():
    """The mesh path psums the per-shard skipped-tile counts (pod-wide
    counter) and stays gated end to end."""
    mesh = jax.make_mesh((1,), ("data",))
    pts = _coherent_points(n=4096, seed=11)
    eng = ClusterEngine(MeshBackend(mesh=mesh, axes=("data",)))
    res = eng.seed(jax.random.PRNGKey(1), pts, 8)
    assert res.skipped is not None and res.skipped.shape == (8,)
    local = ClusterEngine("fused", bounds=False).seed(jax.random.PRNGKey(1),
                                                      pts, 8)
    assert np.isfinite(np.asarray(res.centroids)).all()
    # same data, same tile geometry: the mesh run's total potential matches
    # the ungated local run's at the same quality level (different sampler)
    assert float(quality.inertia(pts, res.centroids)) < \
        5 * float(quality.inertia(pts, local.centroids))


@pytest.mark.parametrize("backend", ["fused", "pallas"])
def test_bf16_streaming_quality_parity(backend):
    """precision='bf16' halves the streamed bytes; seeds stay valid (taken
    from the full-precision points) and the Lloyd inertia lands within a
    few percent of fp32 on the paper's blob config."""
    pts = _points(n=4096, d=2, k=16, seed=12)
    key = jax.random.PRNGKey(2)
    f32 = ClusterEngine(backend)
    b16 = ClusterEngine(backend, precision="bf16")
    s32 = f32.seed(key, pts, 16)
    s16 = b16.seed(key, pts, 16)
    idx = np.asarray(s16.indices)
    assert ((0 <= idx) & (idx < 4096)).all()
    # seed centroids are gathered from the fp32 array even when streaming bf16
    np.testing.assert_array_equal(
        np.asarray(s16.centroids),
        np.asarray(pts[jnp.asarray(idx)]))
    phi32 = float(f32.fit(pts, s32.centroids, max_iters=25).inertia)
    phi16 = float(b16.fit(pts, s32.centroids, max_iters=25).inertia)
    assert abs(phi16 - phi32) / phi32 < 0.15, (phi16, phi32)


def test_bf16_fit_streams_bf16_points():
    """The bf16 fit must actually stream bf16 tiles: its jaxpr carries a
    bf16 (n, d) operand into the while-loop body."""
    from repro.core import engine as eng_mod
    pts = jnp.zeros((512, 4), jnp.float32)
    cents = jnp.zeros((4, 4), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, c: eng_mod.fit_points(p, c, None, FusedBackend(), 5, 1e-6,
                                        "keep", "bf16"))(pts, cents)
    assert "bf16" in str(jaxpr.jaxpr)


# ---------------------------------------------------------------------------
# norms computed once per call, not once per round (jaxpr pin)
# ---------------------------------------------------------------------------

def _point_norm_reductions(jaxpr, n, d):
    """reduce_sum eqns that look like a ||x||^2 row-norm over point rows:
    2-D operand with trailing dim d and a leading dim much larger than k /
    n_tiles — catches both the full (n, d) jnp form and the Pallas kernels'
    per-tile (block_n, d) form."""
    out = []
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name != "reduce_sum":
            continue
        shape = eqn.invars[0].aval.shape
        if len(shape) == 2 and shape[1] == d and shape[0] >= 1024:
            out.append(shape)
    return out


def _loop_bodies(jaxpr):
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name in ("while", "scan"):
            for v in eqn.params.values():
                for sub in jax.tree_util.tree_leaves(
                        v, is_leaf=lambda x: isinstance(
                            x, (jax.extend.core.Jaxpr, jax.extend.core.ClosedJaxpr))):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        yield sub.jaxpr
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        yield sub


@pytest.mark.parametrize("backend", [FusedBackend(), PallasBackend()])
def test_seed_computes_point_norms_once_per_call(backend):
    """Acceptance: ||x||^2 appears in the seed jaxpr OUTSIDE the round loop
    (the prologue) and never inside the loop body — norm caching drops d
    FLOPs/point/round."""
    from repro.core import engine as eng_mod
    n, d = 16384, 2
    pts = jnp.zeros((n, d), jnp.float32)
    key = jax.random.PRNGKey(0)
    jaxpr = jax.make_jaxpr(
        lambda kk, pp: eng_mod.seed_points(kk, pp, 4, None, backend))(key,
                                                                     pts)
    assert _point_norm_reductions(jaxpr.jaxpr, n, d), \
        "prologue must compute the row norms"
    for body in _loop_bodies(jaxpr.jaxpr):
        assert not _point_norm_reductions(body, n, d), \
            "round loop must NOT recompute ||x||^2"


@pytest.mark.parametrize("backend", [FusedBackend(), PallasBackend()])
def test_fit_computes_point_norms_once_per_call(backend):
    from repro.core import engine as eng_mod
    n, d = 16384, 2
    pts = jnp.zeros((n, d), jnp.float32)
    cents = jnp.zeros((8, d), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda pp, cc: eng_mod.fit_points(pp, cc, None, backend, 5, 1e-6))(
        pts, cents)
    assert _point_norm_reductions(jaxpr.jaxpr, n, d)
    for body in _loop_bodies(jaxpr.jaxpr):
        assert not _point_norm_reductions(body, n, d), \
            "Lloyd loop must NOT recompute ||x||^2"


def test_kmeans_computes_point_norms_exactly_once():
    """Acceptance (ISSUE 5 satellite): ``kmeans`` runs ONE prologue shared
    by the seed and fit phases — the traced program contains EXACTLY one
    row-norm reduction over the (n, d) points (it used to contain two, one
    per phase), and none inside any loop body."""
    from repro.core import engine as eng_mod
    n, d, k = 16384, 2, 4
    pts = jnp.zeros((n, d), jnp.float32)
    key = jax.random.PRNGKey(0)
    jaxpr = jax.make_jaxpr(
        lambda kk, pp: eng_mod.kmeans_points(kk, pp, k, None,
                                             FusedBackend()))(key, pts)
    norms = _point_norm_reductions(jaxpr.jaxpr, n, d)
    assert len(norms) == 1, norms
    for body in _loop_bodies(jaxpr.jaxpr):
        assert not _point_norm_reductions(body, n, d), \
            "no kmeans loop may recompute ||x||^2"


def test_kmeans_shared_prologue_matches_two_phase_quality():
    """The fused one-prologue kmeans must cluster exactly as well as the
    historical seed-then-fit composition (same seeds under the cdf sampler:
    min_d2 is tile-independent, so the draw is identical)."""
    pts = _points(n=4096, d=2, k=8, seed=21)
    key = jax.random.PRNGKey(22)
    eng = ClusterEngine("fused")
    res = eng.kmeans(key, pts, 8, max_iters=15)
    seeds = eng.seed(key, pts, 8).centroids
    two = eng.fit(pts, seeds, max_iters=15)
    np.testing.assert_array_equal(np.asarray(res.centroids),
                                  np.asarray(two.centroids))
    assert float(res.inertia) == float(two.inertia)


def test_seed_reports_per_point_prune_telemetry():
    """KmeansppResult.pruned: > 0 on coherent data, identical between the
    pure-JAX model and the Pallas kernel, absent when gating is off."""
    pts = _coherent_points(seed=20)
    key = jax.random.PRNGKey(21)
    f = ClusterEngine("fused").seed(key, pts, 10)
    p = ClusterEngine("pallas").seed(key, pts, 10)
    assert f.pruned is not None and f.pruned.shape == (10,)
    assert int(jnp.sum(f.pruned)) > 0, np.asarray(f.pruned)
    np.testing.assert_allclose(np.asarray(f.pruned), np.asarray(p.pruned),
                               atol=2)
    off = ClusterEngine("fused", bounds=False).seed(key, pts, 10)
    assert off.pruned is None


# ---------------------------------------------------------------------------
# kernel block-size selection (satellite: pick_block_n call-site clamp)
# ---------------------------------------------------------------------------

def test_choose_block_n_never_exceeds_point_count():
    from repro.kernels.ops import choose_block_n, pick_block_n
    assert pick_block_n(2, 8) == 4096         # unchanged VMEM-budget picker
    assert choose_block_n(300, 2, 8) == 256   # clamped DOWN below n
    assert choose_block_n(4096, 2, 8) == 4096
    assert choose_block_n(50, 2, 8) == 128    # lane-minimum floor
    for n in (50, 100, 129, 300, 900, 5000):
        bn = choose_block_n(n, 2, 8)
        assert bn >= 128
        assert bn <= max(n, 128), (n, bn)


def test_kernel_wrappers_handle_ragged_n():
    """Non-multiple-of-block n goes through the padded/masked path."""
    from repro.kernels import ops, ref
    pts = jax.random.normal(jax.random.PRNGKey(0), (337, 5))
    cents = jax.random.normal(jax.random.PRNGKey(1), (3, 5))
    md = jnp.full((337,), jnp.inf)
    got_md, partials = ops.distance_min_update(pts, cents, md)
    want_md, want_total = ref.distance_min_update_ref(pts, cents, md)
    np.testing.assert_allclose(np.asarray(got_md), np.asarray(want_md),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(jnp.sum(partials)), float(want_total),
                               rtol=1e-4)


@pytest.mark.parametrize("backend", ["fused", "pallas"])
def test_lowered_last_call_is_the_program_that_ran(backend):
    """`lowered_last_call` re-lowers the engine's last compiled call from
    abstract stand-ins of its arguments: compiled and run on the same
    inputs, it reproduces that call's result bitwise."""
    eng = ClusterEngine(backend)
    with pytest.raises(ValueError, match="no compiled call"):
        eng.lowered_last_call()
    pts = _points(n=1024, k=6)
    key = jax.random.PRNGKey(3)
    res = eng.kmeans(key, pts, 6, max_iters=5)
    again = eng.lowered_last_call().compile()(key, pts, None)
    np.testing.assert_array_equal(np.asarray(again.centroids),
                                  np.asarray(res.centroids))
    np.testing.assert_array_equal(np.asarray(again.assignment),
                                  np.asarray(res.assignment))
    # the next call replaces it
    fit = eng.fit(pts, res.centroids, max_iters=3)
    again = eng.lowered_last_call().compile()(pts, res.centroids, None)
    np.testing.assert_array_equal(np.asarray(again.centroids),
                                  np.asarray(fit.centroids))
