"""Bounded Lloyd engine tests (ISSUE 4 tentpole): bitwise gated-vs-ungated
fit parity across backends (single, batch-grid, vmap), movement-bound skip
telemetry, spatial-ordering plumbing, kernel-level tiled/gated parity, and
the bf16 mini-batch path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bounds, quality
from repro.core.engine import ClusterEngine, FusedBackend, MeshBackend
from repro.data import ordering
from repro.data.synthetic import blobs
from repro.kernels import ops, ref


def _coherent(n=16384, d=2, k=4, seed=0, spread=0.05):
    pts, labels = blobs(n, d, k, seed=seed, spread=spread)
    order = np.argsort(labels, kind="stable")
    return jnp.asarray(pts[order])


# ---------------------------------------------------------------------------
# acceptance: fp32 bounded Lloyd is bitwise identical to the ungated path
# ---------------------------------------------------------------------------


# 16384 + 77 is not a tile multiple: the pallas kernels stream the
# prologue's once-padded rows
@pytest.mark.parametrize("n", [16384, 16384 + 77])
@pytest.mark.parametrize("backend", ["reference", "fused", "pallas"])
def test_bounded_fit_is_bitwise_exact(backend, n):
    """The gated fit must produce BITWISE the ungated fit's centroids,
    assignment, inertia and iteration count — while actually skipping."""
    pts = _coherent(n=n)
    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(0), pts,
                                        4).centroids
    on = ClusterEngine(backend).fit(pts, seeds, max_iters=10, tol=-1.0)
    off = ClusterEngine(backend, bounds=False).fit(pts, seeds, max_iters=10,
                                                   tol=-1.0)
    np.testing.assert_array_equal(np.asarray(on.centroids),
                                  np.asarray(off.centroids))
    np.testing.assert_array_equal(np.asarray(on.assignment),
                                  np.asarray(off.assignment))
    assert float(on.inertia) == float(off.inertia)
    assert int(on.n_iters) == int(off.n_iters)
    assert off.skipped is None
    assert on.skipped is not None and on.skipped.shape == (10,)
    assert int(jnp.sum(on.skipped)) > 0, np.asarray(on.skipped)


@pytest.mark.parametrize("offset", [100.0, -3000.0])
def test_bounded_fit_exact_far_from_origin(offset):
    """The gap margin is ABSOLUTE in the operand magnitude (matmul-form fp32
    cancellation grows with ||x||^2): off-origin data is where a
    relative-only slack would silently break the bitwise claim."""
    pts = _coherent(seed=3) + offset
    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(4), pts,
                                        4).centroids
    for backend in ("fused", "pallas"):
        on = ClusterEngine(backend).fit(pts, seeds, max_iters=8, tol=-1.0)
        off = ClusterEngine(backend, bounds=False).fit(pts, seeds,
                                                       max_iters=8, tol=-1.0)
        np.testing.assert_array_equal(np.asarray(on.centroids),
                                      np.asarray(off.centroids))
        np.testing.assert_array_equal(np.asarray(on.assignment),
                                      np.asarray(off.assignment))
        assert float(on.inertia) == float(off.inertia)


def test_bounded_fit_exact_on_shuffled_rows():
    """Shuffled rows give the gate nothing to prune — results must stay
    exactly the ungated fit's (exactness is layout-independent)."""
    pts = jnp.asarray(blobs(8192, 2, 4, seed=5)[0])
    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(6), pts,
                                        4).centroids
    on = ClusterEngine("fused").fit(pts, seeds, max_iters=8)
    off = ClusterEngine("fused", bounds=False).fit(pts, seeds, max_iters=8)
    np.testing.assert_array_equal(np.asarray(on.centroids),
                                  np.asarray(off.centroids))
    assert float(on.inertia) == float(off.inertia)


def test_bounded_fit_skip_counts_agree_fused_vs_pallas():
    """The pure-JAX gate model and the compacted gated kernel must make the
    same skip decisions iteration by iteration."""
    pts = _coherent(seed=7)
    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(8), pts,
                                        4).centroids
    f = ClusterEngine("fused").fit(pts, seeds, max_iters=10, tol=-1.0)
    p = ClusterEngine("pallas").fit(pts, seeds, max_iters=10, tol=-1.0)
    np.testing.assert_allclose(np.asarray(f.skipped), np.asarray(p.skipped),
                               atol=1)
    assert int(jnp.sum(f.skipped)) > 0


def test_bounded_fit_skip_rate_on_label_sorted_blobs():
    """Acceptance trajectory: well-separated label-sorted blobs reach a
    >= 50% assignment-tile skip rate by iteration 3 (0-indexed)."""
    n, d, k = 2 ** 16, 8, 16
    pts = _coherent(n=n, d=d, k=k, seed=0)
    eng = ClusterEngine("fused")
    seeds = eng.seed(jax.random.PRNGKey(1), pts, k).centroids
    res = eng.fit(pts, seeds, max_iters=6, tol=-1.0)
    n_tiles = -(-n // eng.backend.seed_tile(n, d, k))
    rate = np.asarray(res.skipped, np.float64) / n_tiles
    assert rate[3] >= 0.5, rate
    # skipping must not have changed the result
    off = ClusterEngine("fused", bounds=False).fit(pts, seeds, max_iters=6,
                                                   tol=-1.0)
    assert float(res.inertia) == float(off.inertia)


def test_bounded_fit_with_reseed_policy_stays_exact():
    """empty='reseed' moves centroids discontinuously — the movement bound
    must force recomputation (reseeded centroids have delta > 0) and keep
    gated == ungated bitwise."""
    pts = _coherent(seed=9)
    cents = jnp.concatenate([pts[:3], jnp.full((1, 2), 99.0)])
    on = ClusterEngine("fused").fit(pts, cents, max_iters=8, empty="reseed")
    off = ClusterEngine("fused", bounds=False).fit(pts, cents, max_iters=8,
                                                   empty="reseed")
    np.testing.assert_array_equal(np.asarray(on.centroids),
                                  np.asarray(off.centroids))
    assert float(on.inertia) == float(off.inertia)


# ---------------------------------------------------------------------------
# batch-grid / vmap / mesh composition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [4096, 4096 + 77])
def test_bounded_fit_batched_matches_per_problem(n):
    """fit_batched (gated, batch-grid kernels under vmap) is bitwise the
    per-problem gated fit, and per-problem skip counters come back (B, it)."""
    B = 3
    bpts = jnp.stack([_coherent(n=n, seed=10 + s) for s in range(B)])
    binit = jnp.stack([bpts[s][:4] for s in range(B)])
    for backend in ("fused", "pallas"):
        bat = ClusterEngine(backend).fit_batched(bpts, binit, max_iters=6,
                                                 tol=-1.0)
        assert bat.skipped.shape == (B, 6)
        for b in range(B):
            single = ClusterEngine(backend).fit(bpts[b], binit[b],
                                                max_iters=6, tol=-1.0)
            np.testing.assert_array_equal(np.asarray(bat.centroids[b]),
                                          np.asarray(single.centroids))
            np.testing.assert_array_equal(np.asarray(bat.assignment[b]),
                                          np.asarray(single.assignment))
            np.testing.assert_array_equal(np.asarray(bat.skipped[b]),
                                          np.asarray(single.skipped))


def test_bounded_fit_batched_gated_vs_ungated():
    B = 2
    bpts = jnp.stack([_coherent(n=4096, seed=20 + s) for s in range(B)])
    binit = jnp.stack([bpts[s][:4] for s in range(B)])
    on = ClusterEngine("pallas").fit_batched(bpts, binit, max_iters=6)
    off = ClusterEngine("pallas", bounds=False).fit_batched(bpts, binit,
                                                            max_iters=6)
    np.testing.assert_array_equal(np.asarray(on.centroids),
                                  np.asarray(off.centroids))
    np.testing.assert_array_equal(np.asarray(on.assignment),
                                  np.asarray(off.assignment))


def test_mesh_fit_composes_skip_counters():
    """The mesh fit psums the per-shard skipped-tile counts and matches the
    local fit's quality (1-device mesh: bitwise the local backend)."""
    mesh = jax.make_mesh((1,), ("data",))
    pts = _coherent(n=8192, seed=11)
    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(2), pts,
                                        4).centroids
    res = ClusterEngine(MeshBackend(mesh=mesh, axes=("data",))).fit(
        pts, seeds, max_iters=8, tol=-1.0)
    local = ClusterEngine("fused").fit(pts, seeds, max_iters=8, tol=-1.0)
    assert res.skipped is not None and res.skipped.shape == (8,)
    np.testing.assert_array_equal(np.asarray(res.skipped),
                                  np.asarray(local.skipped))
    np.testing.assert_array_equal(np.asarray(res.centroids),
                                  np.asarray(local.centroids))


# ---------------------------------------------------------------------------
# result reporting (KmeansppResult-style audit surface for fit)
# ---------------------------------------------------------------------------


def test_fit_result_reports_skips_and_reorder_provenance():
    pts = _coherent(n=8192, seed=12)
    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(3), pts,
                                        4).centroids
    res = ClusterEngine("fused").fit(pts, seeds, max_iters=20)
    # counters beyond the converged iteration stay zero (the shared contract
    # in repro.core.telemetry, pinned by tests/test_telemetry_contract.py)
    from repro.core import telemetry
    it = int(res.n_iters)
    assert it < 20
    telemetry.check_converged_zeros(res.skipped, it, 20, "skipped")
    assert res.reorder is None          # natural order: no provenance
    ordered = ClusterEngine("fused").fit(pts, seeds, max_iters=20,
                                         order="morton")
    assert ordered.reorder is not None and ordered.reorder.shape == (8192,)
    # the recorded permutation IS a permutation
    assert np.array_equal(np.sort(np.asarray(ordered.reorder)),
                          np.arange(8192))


def test_weighted_fit_keeps_legacy_contract():
    """Weighted fits take the legacy accumulated path: no skip telemetry,
    same numbers as before."""
    pts = _coherent(n=2048, seed=13)
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (2048,))) + 0.1
    res = ClusterEngine("fused").fit(pts, pts[:4], max_iters=6, weights=w)
    assert res.skipped is None


# ---------------------------------------------------------------------------
# spatial ordering: repro.data.ordering + engine plumbing
# ---------------------------------------------------------------------------


def test_morton_order_is_a_permutation_with_inverse():
    pts = jnp.asarray(blobs(1000, 3, 4, seed=1)[0])
    perm, inv = ordering.morton_order(pts)
    assert np.array_equal(np.sort(np.asarray(perm)), np.arange(1000))
    np.testing.assert_array_equal(np.asarray(perm[inv]), np.arange(1000))
    np.testing.assert_array_equal(np.asarray(inv[perm]), np.arange(1000))


def test_morton_order_improves_tile_coherence():
    """Z-ordering shuffled blobs must recover most of the skip rate the
    shuffled layout loses."""
    n, d, k = 2 ** 15, 8, 16
    pts = jnp.asarray(blobs(n, d, k, seed=2)[0])     # shuffled labels
    eng = ClusterEngine("fused")
    seeds = eng.seed(jax.random.PRNGKey(7), pts, k).centroids
    shuf = eng.fit(pts, seeds, max_iters=6, tol=-1.0)
    mort = eng.fit(pts, seeds, max_iters=6, tol=-1.0, order="morton")
    assert int(jnp.sum(mort.skipped)) > int(jnp.sum(shuf.skipped))
    assert int(jnp.sum(mort.skipped)) > 0


def test_morton_order_handles_one_dimension():
    """d=1 caps the per-dim bits at 16 (32//1 would overflow int32) and
    degenerates to a plain coordinate sort."""
    x = jax.random.uniform(jax.random.PRNGKey(0), (257, 1))
    perm, inv = ordering.morton_order(x)
    assert np.array_equal(np.sort(np.asarray(perm)), np.arange(257))
    sorted_x = np.asarray(x[perm, 0])
    assert (np.diff(sorted_x) >= -1e-4).all()   # 16-bit quantized sort
    np.testing.assert_array_equal(np.asarray(perm[inv]), np.arange(257))


def test_label_sort_order_groups_labels():
    labels = jnp.asarray([2, 0, 1, 0, 2, 1], jnp.int32)
    perm, inv = ordering.label_sort_order(labels)
    np.testing.assert_array_equal(np.asarray(labels[perm]),
                                  [0, 0, 1, 1, 2, 2])
    np.testing.assert_array_equal(np.asarray(perm[inv]), np.arange(6))


def test_spatial_order_dispatch_and_errors():
    pts = jnp.zeros((8, 2))
    with pytest.raises(ValueError, match="unknown ordering"):
        ordering.spatial_order(pts, method="hilbert")
    with pytest.raises(ValueError, match="labels"):
        ordering.spatial_order(pts, method="label")


def test_fit_order_returns_original_row_order():
    """order='morton' must hand results back in the CALLER's row order: the
    reported inertia must match an inertia recomputed from the returned
    (assignment, centroids) against the caller's points."""
    pts = jnp.asarray(blobs(4096, 2, 4, seed=3)[0])
    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(9), pts,
                                        4).centroids
    res = ClusterEngine("fused").fit(pts, seeds, max_iters=10,
                                     order="morton")
    diff = pts - res.centroids[res.assignment]
    phi = float(jnp.sum(jnp.sum(diff * diff, axis=1)))
    np.testing.assert_allclose(phi, float(res.inertia), rtol=1e-4)
    # and the clustering quality matches the natural-order fit
    nat = ClusterEngine("fused").fit(pts, seeds, max_iters=10)
    np.testing.assert_allclose(float(res.inertia), float(nat.inertia),
                               rtol=1e-4)


def test_fit_order_accepts_precomputed_permutation():
    pts, labels = blobs(2 ** 15, 8, 8, seed=4)
    pts = jnp.asarray(pts)
    perm, _ = ordering.label_sort_order(jnp.asarray(labels))
    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(10), pts,
                                        8).centroids
    res = ClusterEngine("fused").fit(pts, seeds, max_iters=8, tol=-1.0,
                                     order=perm)
    np.testing.assert_array_equal(np.asarray(res.reorder), np.asarray(perm))
    assert int(jnp.sum(res.skipped)) > 0   # label sort makes the gate fire


def test_kmeans_batched_order_matches_natural_quality():
    B, n, k = 2, 2048, 4
    bpts = jnp.stack([jnp.asarray(blobs(n, 2, k, seed=30 + s)[0])
                      for s in range(B)])
    key = jax.random.PRNGKey(11)
    nat = ClusterEngine("fused").kmeans_batched(key, bpts, k, max_iters=15)
    mort = ClusterEngine("fused").kmeans_batched(key, bpts, k, max_iters=15,
                                                 order="morton")
    assert mort.reorder.shape == (B, n)
    for b in range(B):
        diff = bpts[b] - mort.centroids[b][mort.assignment[b]]
        phi = float(jnp.sum(jnp.sum(diff * diff, axis=1)))
        np.testing.assert_allclose(phi, float(mort.inertia[b]), rtol=1e-4)
        assert phi < 3 * float(nat.inertia[b]) + 1e-6


# ---------------------------------------------------------------------------
# kernel-level parity (tiled vs oracle, gated vs tiled)
# ---------------------------------------------------------------------------


ASSIGN_TILED_SHAPES = [(1000, 5, 7, 128), (512, 2, 4, 128), (100, 3, 2, 128)]


@pytest.mark.parametrize("n,d,k,bn", ASSIGN_TILED_SHAPES)
def test_lloyd_assign_tiled_matches_ref(n, d, k, bn):
    pts = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    cents = jax.random.normal(jax.random.PRNGKey(1), (k, d))
    tps = bounds.tiles_per_super(-(-n // bn))
    got = ops.lloyd_assign_tiled(pts, cents, block_n=bn)
    want = ref.lloyd_assign_tiled_ref(pts, cents, bn, tps)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for g, w, tol in zip(got[1:], want[1:], (1e-6, 1e-5, 1e-5, 1e-5, 0)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=tol)
    # reduced super-tile sums equal the accumulated kernel's totals
    a2, md2, sums2, counts2 = ops.lloyd_assign(pts, cents)
    np.testing.assert_allclose(np.asarray(got[4].sum(0)), np.asarray(sums2),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got[5].sum(0)),
                                  np.asarray(counts2))


def test_lloyd_assign_tiled_hierarchy_fires_above_floor():
    """Above 8 tiles the accumulators are per-SUPER (n_super ≈ √n_tiles),
    capping the footprint the flat layout paid per tile."""
    n, d, k, bn = 2048, 3, 5, 128
    grid = -(-n // bn)                       # 16 tiles
    tps = bounds.tiles_per_super(grid)
    assert 1 < tps < grid
    pts = jax.random.normal(jax.random.PRNGKey(7), (n, d))
    cents = jax.random.normal(jax.random.PRNGKey(8), (k, d))
    got = ops.lloyd_assign_tiled(pts, cents, block_n=bn)
    assert got[4].shape == (-(-grid // tps), k, d)
    want = ref.lloyd_assign_tiled_ref(pts, cents, bn, tps)
    np.testing.assert_allclose(np.asarray(got[4]), np.asarray(want[4]),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got[5]), np.asarray(want[5]))


def _no_prune_prev(n, grid, k, d, bn):
    """Carry arrays that make the per-point gate a no-op (lb = -inf) and
    carry recognizable values for skipped blocks."""
    z = jnp.zeros
    n_super = -(-grid // bounds.tiles_per_super(grid))
    return dict(delta=z((k,)), thresh=jnp.full((grid,), jnp.inf),
                absorb=z((grid,)), pa=z((n,), jnp.int32), pmd=z((n,)),
                plb=jnp.full((n,), -jnp.inf), pp=z((grid,)), pg=z((grid,)),
                pss=z((n_super, k, d)), psc=z((n_super, k)))


def test_lloyd_assign_gated_all_active_bitwise_equals_tiled():
    n, d, k, bn = 1000, 5, 7, 128
    pts = jax.random.normal(jax.random.PRNGKey(2), (n, d))
    cents = jax.random.normal(jax.random.PRNGKey(3), (k, d))
    nrm = ops.point_norms(pts)
    grid = -(-n // bn)
    tiled = ops.lloyd_assign_tiled(pts, cents, norms=nrm, block_n=bn)
    pv = _no_prune_prev(n, grid, k, d, bn)
    gated = ops.lloyd_assign_gated(
        pts, cents, nrm, pv["delta"], pv["thresh"], pv["absorb"], pv["pa"],
        pv["pmd"], pv["plb"], pv["pp"], pv["pg"], pv["pss"], pv["psc"],
        jnp.ones((grid,), bool), block_n=bn)
    a, md, lb, part, gap, ssums, scounts, pruned, skipped = gated
    for g, t in zip((a, md, part, gap, ssums, scounts), tiled):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(t))
    assert int(skipped) == 0
    assert float(jnp.sum(pruned)) == 0.0   # thresh=+inf: nothing prunes


def test_lloyd_assign_gated_skipping_carries_previous_blocks():
    """Inactive tiles keep ALL aliased outputs bitwise; with unchanged
    centroids the carried values equal a recompute, so the full outputs are
    bitwise the tiled kernel's."""
    n, d, k, bn = 1024, 3, 5, 128
    pts = jax.random.normal(jax.random.PRNGKey(4), (n, d))
    cents = jax.random.normal(jax.random.PRNGKey(5), (k, d))
    nrm = ops.point_norms(pts)
    grid = -(-n // bn)
    assert bounds.tiles_per_super(grid) == 1   # flat: masks are super-aligned
    prev = ops.lloyd_assign_tiled(pts, cents, norms=nrm, block_n=bn)
    pv = _no_prune_prev(n, grid, k, d, bn)
    active = jnp.arange(grid) % 3 == 0
    gated = ops.lloyd_assign_gated(
        pts, cents, nrm, pv["delta"], pv["thresh"], pv["absorb"],
        prev[0], prev[1], pv["plb"], prev[2], prev[3], prev[4], prev[5],
        active, block_n=bn)
    a, md, lb, part, gap, ssums, scounts, pruned, skipped = gated
    # active tiles recompute values bitwise-equal to the carries (centroids
    # unchanged + thresh=+inf disables the per-point path), skipped tiles
    # alias them — so every output equals the tiled kernel's
    for g, t in zip((a, md, part, gap, ssums, scounts), prev):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(t))
    # skipped tiles' lb and pruned counters keep the donated carries
    act_pt = np.repeat(np.asarray(active), bn)[:n]
    np.testing.assert_array_equal(np.asarray(lb)[~act_pt],
                                  np.asarray(pv["plb"])[~act_pt])
    np.testing.assert_array_equal(np.asarray(pruned)[~np.asarray(active)],
                                  0.0)
    assert int(skipped) == grid - int(jnp.sum(active))


def test_lloyd_assign_gated_per_point_prune_is_bitwise_exact():
    """A real carried state + zero movement: most points prune, and every
    output still equals the all-fresh tiled kernel's bitwise (the per-point
    short-circuit is a value-noop)."""
    n, d, k, bn = 1024, 3, 5, 128
    pts = jax.random.normal(jax.random.PRNGKey(14), (n, d))
    cents = jax.random.normal(jax.random.PRNGKey(15), (k, d))
    nrm = ops.point_norms(pts)
    grid = -(-n // bn)
    prev = ops.lloyd_assign_tiled(pts, cents, norms=nrm, block_n=bn)
    a0, md0 = prev[0], prev[1]
    # true per-point lb from the oracle (second-best distance)
    d2 = np.array(ref._d2(pts, cents))
    d2[np.arange(n), np.asarray(a0)] = np.inf
    plb = jnp.asarray(np.sqrt(d2.min(axis=1)), jnp.float32)
    gated = ops.lloyd_assign_gated(
        pts, cents, nrm, jnp.zeros((k,)), jnp.full((grid,), 1e-3),
        jnp.zeros((grid,)), a0, md0, plb, prev[2], prev[3], prev[4],
        prev[5], jnp.ones((grid,), bool), block_n=bn)
    a, md, lb, part, gap, ssums, scounts, pruned, skipped = gated
    assert float(jnp.sum(pruned)) > 0.5 * n     # the fine level fires
    for g, t in zip((a, md, part, ssums, scounts),
                    (prev[0], prev[1], prev[2], prev[4], prev[5])):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(t))


def test_lloyd_assign_gated_batched_matches_single():
    B, n, d, k, bn = 3, 512, 2, 4, 128
    keys = jax.random.split(jax.random.PRNGKey(6), 4)
    pts = jax.random.normal(keys[0], (B, n, d))
    cents = jax.random.normal(keys[1], (B, k, d))
    nrm = jax.vmap(ops.point_norms)(pts)
    grid = -(-n // bn)
    prev = jax.vmap(lambda p, c, nr: ops.lloyd_assign_tiled(
        p, c, norms=nr, block_n=bn))(pts, cents, nrm)
    pv = _no_prune_prev(n, grid, k, d, bn)
    bcast = lambda x: jnp.broadcast_to(x[None], (B,) + x.shape)
    active = jnp.arange(grid)[None, :] % (jnp.arange(B)[:, None] + 2) == 0
    args = (pts, cents, nrm, bcast(pv["delta"]), bcast(pv["thresh"]),
            bcast(pv["absorb"]), prev[0], prev[1], bcast(pv["plb"]),
            prev[2], prev[3], prev[4], prev[5], active)
    out = jax.vmap(lambda p, c, nr, dl, th, ab, pa, pm, pl, pp, pg, ts, tc,
                   ac: ops.lloyd_assign_gated(p, c, nr, dl, th, ab, pa, pm,
                                              pl, pp, pg, ts, tc, ac,
                                              block_n=bn))(*args)
    for b in range(B):
        single = ops.lloyd_assign_gated(*[x[b] for x in args], block_n=bn)
        for x, y in zip([o[b] for o in out], single):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_assign_gate_model_requires_unmoved_assigned_centroids():
    """A centroid that moved even slightly keeps every tile it owns active —
    the carried min_d2 would otherwise be stale."""
    pts = _coherent(n=4096, seed=14)
    be = FusedBackend()
    cache = be.prologue(pts, m=4)
    tile = be.seed_tile(4096, 2, 4)
    cents = jnp.asarray(pts[::1024][:4], jnp.float32)
    first = be.assign_update(pts, cents, None, cache.norms, cache=cache)
    st = first.state
    # no movement at all: every occupied tile with a healthy gap may skip
    delta0 = jnp.zeros((4,), jnp.float32)
    active0 = bounds.assign_active_tiles(delta0, cents, st, cache)
    # every centroid moved: nothing may skip
    delta1 = jnp.full((4,), 0.5, jnp.float32)
    active1 = bounds.assign_active_tiles(delta1, cents, st, cache)
    assert bool(jnp.all(active1))
    assert int(jnp.sum(active0)) <= int(jnp.sum(active1))


# ---------------------------------------------------------------------------
# bound-state edge cases (ISSUE 5 satellite): k=1, n < one tile, multi-skip
# decay, reseed-vs-gate interaction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["reference", "fused", "pallas"])
def test_bounded_fit_k1_is_exact(backend):
    """k = 1 has no runner-up: per-point lb and tile gaps are +inf, so after
    the first iteration everything is provably stable — and the gated fit
    must still be bitwise the ungated one."""
    pts = _coherent(n=4096, k=1, seed=21)
    init = pts[:1]
    on = ClusterEngine(backend).fit(pts, init, max_iters=5, tol=-1.0)
    off = ClusterEngine(backend, bounds=False).fit(pts, init, max_iters=5,
                                                   tol=-1.0)
    np.testing.assert_array_equal(np.asarray(on.centroids),
                                  np.asarray(off.centroids))
    np.testing.assert_array_equal(np.asarray(on.assignment),
                                  np.asarray(off.assignment))
    assert float(on.inertia) == float(off.inertia)
    # once the single centroid stops moving the fine level prunes everything
    assert int(on.pruned[-1]) == 4096, np.asarray(on.pruned)


@pytest.mark.parametrize("backend", ["fused", "pallas"])
def test_bounded_fit_smaller_than_one_tile(backend):
    """n below the 128-lane tile floor: one padded tile, one super — the
    whole hierarchy degenerates without breaking exactness."""
    pts = jnp.asarray(blobs(100, 2, 3, seed=22)[0])
    init = pts[:3]
    on = ClusterEngine(backend).fit(pts, init, max_iters=6, tol=-1.0)
    off = ClusterEngine(backend, bounds=False).fit(pts, init, max_iters=6,
                                                   tol=-1.0)
    np.testing.assert_array_equal(np.asarray(on.centroids),
                                  np.asarray(off.centroids))
    assert float(on.inertia) == float(off.inertia)
    s = ClusterEngine(backend).seed(jax.random.PRNGKey(23), pts, 3)
    s_off = ClusterEngine(backend, bounds=False).seed(jax.random.PRNGKey(23),
                                                      pts, 3)
    np.testing.assert_array_equal(np.asarray(s.indices),
                                  np.asarray(s_off.indices))


def test_decay_gap_stays_valid_across_three_plus_skips():
    """A tile skipped for >= 3 consecutive iterations carries a gap decayed
    by each step's max movement; when centroids then stop moving bitwise the
    carried state is still exact (pinned against the ungated fit), and the
    per-iteration skip telemetry shows the multi-skip streak."""
    pts = _coherent(n=2 ** 15, d=8, k=16, seed=24)
    eng = ClusterEngine("fused")
    seeds = eng.seed(jax.random.PRNGKey(25), pts, 16).centroids
    res = eng.fit(pts, seeds, max_iters=12, tol=-1.0)
    off = ClusterEngine("fused", bounds=False).fit(pts, seeds, max_iters=12,
                                                   tol=-1.0)
    np.testing.assert_array_equal(np.asarray(res.centroids),
                                  np.asarray(off.centroids))
    assert float(res.inertia) == float(off.inertia)
    skips = np.asarray(res.skipped)
    # at least one run of >= 3 consecutive iterations with skipped tiles
    streak = best = 0
    for s in skips:
        streak = streak + 1 if s > 0 else 0
        best = max(best, streak)
    assert best >= 3, skips
    # the unit-level property behind it: decayed gaps never exceed what
    # per-step decay justifies
    gap = jnp.asarray([5.0, 3.0])
    active = jnp.asarray([False, False])
    g = gap
    for _ in range(3):
        g = bounds.decay_gap(g, active, jnp.zeros_like(g), jnp.asarray(1.0))
    np.testing.assert_allclose(np.asarray(g), [2.0, 0.0])


def test_reseed_invalidates_bounds_and_stays_exact():
    """empty='reseed' teleports a centroid: every point/tile whose bound
    could be stale must recompute (the reseeded cluster has delta > 0, so
    its points fail the own-centroid check and dmax spikes the thresholds)
    — and gated == ungated stays bitwise through the reseed."""
    pts = _coherent(n=2 ** 14, d=8, k=8, seed=26)
    # one far-away dead centroid forces a reseed on iteration 1
    cents = jnp.concatenate([pts[:7], jnp.full((1, 8), 500.0)])
    on = ClusterEngine("fused").fit(pts, cents, max_iters=10, tol=-1.0,
                                    empty="reseed")
    off = ClusterEngine("fused", bounds=False).fit(pts, cents, max_iters=10,
                                                   tol=-1.0, empty="reseed")
    np.testing.assert_array_equal(np.asarray(on.centroids),
                                  np.asarray(off.centroids))
    np.testing.assert_array_equal(np.asarray(on.assignment),
                                  np.asarray(off.assignment))
    assert float(on.inertia) == float(off.inertia)
    # the reseed's teleport (huge dmax) must disable pruning on the next
    # iteration: no point can clear a threshold scaled by the jump
    skips = np.asarray(on.skipped)
    prunes = np.asarray(on.pruned)
    assert skips[1] == 0 and prunes[1] == 0, (skips, prunes)
    # pruning resumes once the split settles
    assert prunes[2:].sum() > 0, prunes


# ---------------------------------------------------------------------------
# bf16 mini-batch streaming (satellite)
# ---------------------------------------------------------------------------


def test_minibatch_bf16_streams_and_matches_fp32_quality():
    n, d, k, batch = 8192, 2, 8, 512
    full = jnp.asarray(blobs(n, d, k, seed=15)[0])
    np_pts = np.asarray(full)

    def read_fn(step):
        lo = (step * batch) % n
        return np_pts[lo:lo + batch]

    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(12), full[:512],
                                        k).centroids
    f32 = ClusterEngine("fused").fit_minibatch(seeds, read_fn, n_batches=24)
    b16 = ClusterEngine("fused", precision="bf16").fit_minibatch(
        seeds, read_fn, n_batches=24)
    phi32 = float(quality.inertia(full, f32.centroids))
    phi16 = float(quality.inertia(full, b16.centroids))
    assert abs(phi16 - phi32) / phi32 < 0.15, (phi16, phi32)


def test_minibatch_bf16_jaxpr_streams_bf16():
    from repro.core import engine as eng_mod
    cents = jnp.zeros((4, 2), jnp.float32)
    counts = jnp.zeros((4,), jnp.float32)
    batch = jnp.zeros((256, 2), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda c, n, b: eng_mod.minibatch_step(c, n, b, FusedBackend(),
                                               "bf16"))(cents, counts, batch)
    assert "bf16" in str(jaxpr.jaxpr)


def test_minibatch_order_morton_returns_batch_row_order():
    n, d, k, batch = 4096, 2, 4, 512
    full = jnp.asarray(blobs(n, d, k, seed=16)[0])
    np_pts = np.asarray(full)

    def read_fn(step):
        lo = (step * batch) % n
        return np_pts[lo:lo + batch]

    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(13), full[:512],
                                        k).centroids
    res = ClusterEngine("fused").fit_minibatch(seeds, read_fn, n_batches=8,
                                               order="morton")
    assert res.assignment.shape == (batch,)
    # the last batch's assignment is in the BATCH's own row order: its
    # inertia against the returned centroids must sit within the one-step
    # centroid-update drift of the reported (pre-update) inertia — a
    # scrambled (non-inverted) assignment would be off by >10x on blobs
    last = jnp.asarray(read_fn(7))
    diff = last - res.centroids[res.assignment]
    phi = float(jnp.sum(jnp.sum(diff * diff, axis=1)))
    np.testing.assert_allclose(phi, float(res.inertia), rtol=0.05)


# ---------------------------------------------------------------------------
# k-means|| tiled weighted reduce (satellite)
# ---------------------------------------------------------------------------


def test_weighted_tiled_seeding_respects_zero_weights():
    pts = jnp.asarray(blobs(512, 2, 4, seed=17)[0])
    w = jnp.where(jnp.arange(512) < 256, 1.0, 0.0)
    for backend in ("fused", "pallas"):
        res = ClusterEngine(backend).seed(jax.random.PRNGKey(14), pts, 6,
                                          weights=w, sampler="tiled")
        idx = np.asarray(res.indices)
        assert (idx < 256).all(), idx


def test_kmeans_parallel_reduce_has_no_full_n_cumsum():
    """The k-means|| weighted reduce now draws with the tiled sampler: no
    cumsum over the full candidate axis may appear in the traced program
    once the candidate set spans multiple tiles."""
    from repro.core.kmeans_parallel import kmeans_parallel_init
    from repro.kernels.ops import choose_block_n
    n, d, k, rounds = 4096, 2, 4, 4
    l = 2 * k
    n_cand = rounds * l + 1
    pts = jnp.zeros((n, d), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda kk, pp: kmeans_parallel_init(kk, pp, k, rounds=rounds))(
        jax.random.PRNGKey(0), pts)
    import tests.test_engine as te
    sizes = set()
    for eqn in te._iter_eqns(jaxpr.jaxpr):
        if "cumsum" in eqn.primitive.name:
            sizes.add(eqn.invars[0].aval.shape)
    assert (n_cand,) not in sizes, sizes


def test_kmeans_parallel_quality_with_tiled_reduce():
    pts = jnp.asarray(blobs(4096, 2, 8, seed=18)[0])
    from repro.core.kmeans_parallel import kmeans_parallel_init
    res = kmeans_parallel_init(jax.random.PRNGKey(15), pts, 8)
    idx = np.asarray(res.indices)
    assert ((0 <= idx) & (idx < 4096)).all()
    assert len(set(idx.tolist())) == 8
    phi = float(quality.inertia(pts, res.centroids))
    rand = jnp.asarray(pts[np.random.default_rng(0).choice(4096, 8)])
    assert phi < 2.0 * float(quality.inertia(pts, rand)) + 1e-6


# ---------------------------------------------------------------------------
# bench schema gate (ISSUE 5 satellite): the CI smoke must fail loudly when
# a BENCH_round section loses its prune/accumulator columns
# ---------------------------------------------------------------------------


def test_bench_schema_checker_guards_prune_columns():
    import json
    import pathlib

    from benchmarks.check_schema import check_file, check_payload

    base = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
            / "BENCH_round.json")
    assert check_file(base) == []            # the checked-in baseline passes
    payload = json.loads(base.read_text())
    stripped = {"rows": [{k: v for k, v in r.items() if k != "prune_rate"}
                         for r in payload["rows"]]}
    errs = check_payload("round", stripped)
    assert errs and all("prune_rate" in e for e in errs), errs
    # a section that silently disappears is also an error
    only_seed = {"rows": [r for r in payload["rows"]
                          if r["bench"] == "round_traffic"]}
    errs = check_payload("round", only_seed)
    assert any("never emitted" in e for e in errs), errs


# ---------------------------------------------------------------------------
# serve/kvquant ordering plumb (satellite)
# ---------------------------------------------------------------------------


def test_kvquant_codebook_accepts_order():
    from repro.serve import kvquant
    key = jax.random.PRNGKey(0)
    vecs = jax.random.normal(key, (1024, 16))
    cb = kvquant.build_codebook(key, vecs, n_sub=4, n_codes=16,
                                lloyd_iters=3, order="morton")
    assert cb.centroids.shape == (4, 16, 4)
    pq = kvquant.PQCache(kvquant.encode(vecs, cb), cb)
    err = float(kvquant.reconstruction_error(vecs, pq))
    base = kvquant.build_codebook(key, vecs, n_sub=4, n_codes=16,
                                  lloyd_iters=3)
    base_err = float(kvquant.reconstruction_error(
        vecs, kvquant.PQCache(kvquant.encode(vecs, base), base)))
    assert err < 2.0 * base_err + 1e-6


# ---------------------------------------------------------------------------
# checkpointed gated fit (ISSUE 7): mid-fit resume is bitwise the
# uninterrupted run — the serialized carry IS the loop carry
# ---------------------------------------------------------------------------


# the pallas case's n is not a tile multiple: the chunked driver's jitted
# prologue pads the points once, like the one-shot fit's
@pytest.mark.parametrize("backend,n", [("fused", 4096), ("pallas", 4096 + 77)])
def test_checkpointed_fit_matches_plain_bitwise(tmp_path, backend, n):
    pts = _coherent(n=n, seed=30)
    eng = ClusterEngine(backend)
    seeds = eng.seed(jax.random.PRNGKey(30), pts, 4).centroids
    plain = eng.fit(pts, seeds, max_iters=9, tol=-1.0)
    ck = eng.fit(pts, seeds, max_iters=9, tol=-1.0,
                 checkpoint_dir=tmp_path, checkpoint_every=3)
    np.testing.assert_array_equal(np.asarray(plain.centroids),
                                  np.asarray(ck.centroids))
    np.testing.assert_array_equal(np.asarray(plain.assignment),
                                  np.asarray(ck.assignment))
    assert float(plain.inertia) == float(ck.inertia)
    assert int(plain.n_iters) == int(ck.n_iters)
    np.testing.assert_array_equal(np.asarray(plain.skipped),
                                  np.asarray(ck.skipped))


def test_checkpointed_fit_resumes_mid_fit_bitwise(tmp_path):
    """Crash simulation: run to completion, drop the newest step dirs (as
    if the job died mid-run), re-invoke — the resumed run restores the
    latest surviving carry, replays the remaining iterations, and finishes
    bit-identical to the uninterrupted fit."""
    import shutil
    from repro.checkpoint.manager import CheckpointManager
    pts = _coherent(n=4096, seed=31)
    eng = ClusterEngine("fused")
    seeds = eng.seed(jax.random.PRNGKey(31), pts, 4).centroids
    plain = eng.fit(pts, seeds, max_iters=10, tol=-1.0)
    eng.fit(pts, seeds, max_iters=10, tol=-1.0,
            checkpoint_dir=tmp_path, checkpoint_every=2)
    mgr = CheckpointManager(tmp_path)
    steps = mgr.all_steps()
    assert steps[-1] == 10
    for step in steps[-2:]:                   # lose the last two checkpoints
        shutil.rmtree(tmp_path / f"step_{step:08d}")
    resumed = eng.fit(pts, seeds, max_iters=10, tol=-1.0,
                      checkpoint_dir=tmp_path, checkpoint_every=2)
    np.testing.assert_array_equal(np.asarray(plain.centroids),
                                  np.asarray(resumed.centroids))
    np.testing.assert_array_equal(np.asarray(plain.assignment),
                                  np.asarray(resumed.assignment))
    assert float(plain.inertia) == float(resumed.inertia)
    assert int(plain.n_iters) == int(resumed.n_iters)


def test_checkpointed_fit_detects_convergence(tmp_path):
    """A chunk that stops short of its target iteration means the loop
    converged: no further chunks run, and n_iters matches the plain fit."""
    from repro.checkpoint.manager import CheckpointManager
    pts = _coherent(n=4096, seed=32)
    eng = ClusterEngine("fused")
    seeds = eng.seed(jax.random.PRNGKey(32), pts, 4).centroids
    plain = eng.fit(pts, seeds, max_iters=30)
    assert int(plain.n_iters) < 30
    ck = eng.fit(pts, seeds, max_iters=30, checkpoint_dir=tmp_path,
                 checkpoint_every=4)
    assert int(ck.n_iters) == int(plain.n_iters)
    assert float(ck.inertia) == float(plain.inertia)
    # no checkpoints were written past convergence
    assert CheckpointManager(tmp_path).latest_step() <= int(plain.n_iters) + 4


def test_checkpointed_fit_rejects_unsupported_modes(tmp_path):
    from repro.core.guards import CheckpointError
    pts = _coherent(n=1024, seed=33)
    with pytest.raises(CheckpointError, match="bounds=True"):
        ClusterEngine("fused", bounds=False).fit(
            pts, pts[:4], max_iters=3, checkpoint_dir=tmp_path)
    w = jnp.ones((1024,), jnp.float32)
    with pytest.raises(CheckpointError, match="unweighted"):
        ClusterEngine("fused").fit(pts, pts[:4], max_iters=3, weights=w,
                                   checkpoint_dir=tmp_path)
