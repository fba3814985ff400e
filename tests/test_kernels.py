"""Pallas kernel tests: interpret-mode execution swept over shapes/dtypes,
assert_allclose against the pure-jnp oracles in kernels/ref.py, plus
BITWISE pins between the plain and bound-gated kernel paths (tile skipping
is exact) and between single and batch-grid launches, and the small-d
seeding rounds' coordinate-major twins against the row kernels."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bounds
from repro.kernels import ops, ref
from repro.kernels.kmeans_distance import (distance_min_update_gated_pallas,
                                           distance_min_update_pallas,
                                           seed_prologue_pallas)
from repro.kernels.lloyd_assign import lloyd_assign_pallas


def _mk(n, d, k, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    pts = jax.random.normal(k1, (n, d), dtype)
    cents = jax.random.normal(k2, (k, d), dtype)
    md = jnp.abs(jax.random.normal(k3, (n,), jnp.float32)) * 4
    return pts, cents, md


SHAPES = [  # (n, d, k_new, block_n) — ragged edges, tiny dims, big tiles
    (128, 2, 1, 128),
    (100, 2, 1, 128),          # n < block, padded tail
    (1000, 3, 1, 256),         # ragged
    (1024, 64, 1, 256),
    (513, 128, 2, 128),        # multiple new centroids + ragged
    (4096, 8, 4, 1024),
]


@pytest.mark.parametrize("n,d,k,block_n", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_distance_min_update_matches_ref(n, d, k, block_n, dtype):
    pts, cents, md = _mk(n, d, k, dtype)
    got_md, partials = distance_min_update_pallas(
        pts, ops.point_norms(pts), cents, md, block_n=block_n,
        resident=True, interpret=True)
    want_md, want_total = ref.distance_min_update_ref(pts, cents, md)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got_md), np.asarray(want_md),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(float(jnp.sum(partials)), float(want_total),
                               rtol=tol * max(n, 1))


@pytest.mark.parametrize("resident", [True, False])
def test_distance_kernel_resident_vs_streamed(resident):
    """Constant-memory analogue (resident) and global analogue agree exactly."""
    pts, cents, md = _mk(777, 16, 1, jnp.float32)
    got_md, _ = distance_min_update_pallas(pts, ops.point_norms(pts), cents,
                                           md, block_n=128,
                                           resident=resident, interpret=True)
    want_md, _ = ref.distance_min_update_ref(pts, cents, md)
    np.testing.assert_allclose(np.asarray(got_md), np.asarray(want_md),
                               rtol=1e-5, atol=1e-6)


def test_raw_kernels_require_explicit_interpret():
    """`ops` is the single place the interpret default lives: the raw kernel
    entry points must refuse to run without an explicit choice (silently
    interpreting on a real TPU was the failure mode)."""
    pts, cents, md = _mk(128, 2, 1, jnp.float32)
    nrm = ops.point_norms(pts)
    with pytest.raises(TypeError):
        distance_min_update_pallas(pts, nrm, cents, md, block_n=128,
                                   resident=True)
    with pytest.raises(TypeError):
        lloyd_assign_pallas(pts, nrm, cents, block_n=128)
    with pytest.raises(TypeError):
        seed_prologue_pallas(pts, block_n=128)


ASSIGN_SHAPES = [
    (128, 2, 4, 128),
    (1000, 8, 16, 256),
    (513, 64, 7, 128),
    (2048, 32, 50, 512),
]


@pytest.mark.parametrize("n,d,k,block_n", ASSIGN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lloyd_assign_matches_ref(n, d, k, block_n, dtype):
    pts, cents, _ = _mk(n, d, k, dtype, seed=3)
    a, md, sums, counts = lloyd_assign_pallas(pts, ops.point_norms(pts),
                                              cents, block_n=block_n,
                                              interpret=True)
    a_ref, md_ref, sums_ref, counts_ref = ref.lloyd_assign_ref(pts, cents)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    # ties can differ between argmin orders only when distances are equal —
    # random data: assert exact match
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a_ref))
    np.testing.assert_allclose(np.asarray(md), np.asarray(md_ref),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(sums_ref),
                               rtol=tol, atol=tol * 10)
    np.testing.assert_allclose(np.asarray(counts), np.asarray(counts_ref),
                               rtol=0, atol=0)


def test_ops_dispatch_and_block_pick():
    """ops.* wrappers pick a legal block size and agree with refs."""
    pts, cents, md = _mk(900, 7, 1, jnp.float32, seed=9)
    got_md, partials = ops.distance_min_update(pts, cents, md)
    want_md, want_total = ref.distance_min_update_ref(pts, cents, md)
    np.testing.assert_allclose(np.asarray(got_md), np.asarray(want_md),
                               rtol=1e-5, atol=1e-6)
    a, md2, sums, counts = ops.lloyd_assign(pts, cents.repeat(3, 0))
    assert a.shape == (900,) and sums.shape == (3, 7)
    assert ops.pick_block_n(4096, 256) >= 128
    assert ops.pick_block_n(2, 8) == 4096


# ---------------------------------------------------------------------------
# batch-grid kernels (multi-tenant clustering)
# ---------------------------------------------------------------------------

BATCHED_SHAPES = [  # (B, n, d, k, block_n)
    (2, 128, 2, 1, 128),
    (3, 300, 4, 2, 128),       # ragged n
    (2, 1024, 16, 4, 256),
]


@pytest.mark.parametrize("B,n,d,k,block_n", BATCHED_SHAPES)
def test_distance_min_update_batched_matches_per_problem(B, n, d, k, block_n):
    from repro.kernels.kmeans_distance import (
        distance_min_update_batched_pallas)
    pts = jax.random.normal(jax.random.PRNGKey(0), (B, n, d))
    cents = jax.random.normal(jax.random.PRNGKey(1), (B, k, d))
    md = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (B, n))) * 4
    nrm = jax.vmap(ops.point_norms)(pts)
    got_md, got_p = distance_min_update_batched_pallas(
        pts, nrm, cents, md, block_n=block_n, interpret=True)
    assert got_p.shape == (B, -(-n // block_n))
    for b in range(B):
        want_md, want_p = distance_min_update_pallas(
            pts[b], nrm[b], cents[b], md[b], block_n=block_n,
            resident=True, interpret=True)
        # row b of the batch-grid launch is bitwise the single-problem kernel
        np.testing.assert_array_equal(np.asarray(got_md[b]),
                                      np.asarray(want_md))
        np.testing.assert_array_equal(np.asarray(got_p[b]),
                                      np.asarray(want_p))


@pytest.mark.parametrize("B,n,d,k,block_n", BATCHED_SHAPES)
def test_lloyd_assign_batched_matches_per_problem(B, n, d, k, block_n):
    from repro.kernels.lloyd_assign import lloyd_assign_batched_pallas
    k = max(k, 2)
    pts = jax.random.normal(jax.random.PRNGKey(3), (B, n, d))
    cents = jax.random.normal(jax.random.PRNGKey(4), (B, k, d))
    nrm = jax.vmap(ops.point_norms)(pts)
    a, md, sums, counts = lloyd_assign_batched_pallas(
        pts, nrm, cents, block_n=block_n, interpret=True)
    for b in range(B):
        a1, md1, s1, c1 = lloyd_assign_pallas(pts[b], nrm[b], cents[b],
                                              block_n=block_n, interpret=True)
        np.testing.assert_array_equal(np.asarray(a[b]), np.asarray(a1))
        np.testing.assert_array_equal(np.asarray(md[b]), np.asarray(md1))
        np.testing.assert_array_equal(np.asarray(sums[b]), np.asarray(s1))
        np.testing.assert_array_equal(np.asarray(counts[b]), np.asarray(c1))


def test_ops_vmap_dispatches_to_batch_grid_kernel():
    """jax.vmap over the ops wrappers must lower to ONE batch-grid pallas
    call, not B per-problem calls (the custom_vmap rule)."""
    B, n, d, k = 3, 256, 4, 2
    pts = jax.random.normal(jax.random.PRNGKey(5), (B, n, d))
    cents = jax.random.normal(jax.random.PRNGKey(6), (B, k, d))
    md = jnp.full((B, n), jnp.inf)

    out_md, partials = jax.vmap(
        lambda p, c, m: ops.distance_min_update(p, c, m))(pts, cents, md)
    for b in range(B):
        want_md, want_total = ref.distance_min_update_ref(pts[b], cents[b],
                                                          md[b])
        np.testing.assert_allclose(np.asarray(out_md[b]), np.asarray(want_md),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(jnp.sum(partials[b])),
                                   float(want_total), rtol=1e-4)

    a, md2, sums, counts = jax.vmap(
        lambda p, c: ops.lloyd_assign(p, c))(pts, cents)
    for b in range(B):
        a_ref, md_ref, s_ref, c_ref = ref.lloyd_assign_ref(pts[b], cents[b])
        np.testing.assert_array_equal(np.asarray(a[b]), np.asarray(a_ref))
        np.testing.assert_allclose(np.asarray(s_ref), np.asarray(sums[b]),
                                   rtol=1e-5, atol=1e-4)


def test_pick_block_n_batched_accounting():
    """The batch-grid accounting (extra in-flight centroid block) can only
    shrink the tile, and the partials/accumulator terms keep the historical
    picks for small shapes."""
    assert ops.pick_block_n(2, 8) == 4096
    assert ops.pick_block_n(2, 8, batched=True) == 4096
    for d, k in ((2, 8), (64, 256), (512, 1024), (4096, 256)):
        assert ops.pick_block_n(d, k, batched=True) <= ops.pick_block_n(d, k)
        assert ops.pick_block_n(d, k, batched=True) >= 128


def test_pick_block_n_accounts_norms_and_bound_state():
    """`pick_block_n` and its mirror tests used to hand-copy the VMEM
    working-set formula — and the copies drifted (ISSUE 8 satellite).
    `ops.vmem_working_set` is now the single shared budget table: the pick
    must be the LARGEST power of two whose summed working set fits the
    budget (maximality: doubling must NOT fit unless capped), and the
    itemized table must name every buffer family the kernels keep
    resident."""
    budget = ops._VMEM_BUDGET
    for d, k in ((2, 8), (64, 256), (512, 1024), (4096, 256)):
        bn = ops.pick_block_n(d, k)

        def working(b):
            return sum(ops.vmem_working_set(d, k, b).values())

        assert working(bn) <= budget or bn == 128
        if bn < 4096:
            assert working(2 * bn) > budget


def test_vmem_working_set_is_the_shared_budget_table():
    """The itemized table IS the accounting `pick_block_n` sums — and the
    buffer families the kernels keep resident are all present by name, so
    a kernel change that adds a resident buffer has exactly one place to
    record it (and this test to update)."""
    ws = ops.vmem_working_set(64, 256, 1024)
    assert set(ws) == {"stream", "norms", "accumulators", "bound_scalars",
                      "super_accumulators", "point_carries", "center_d",
                      "movement", "gate_scalars"}
    assert all(v > 0 for v in ws.values())
    # the batched grid keeps one extra in-flight centroid block resident
    wsb = ops.vmem_working_set(64, 256, 1024, batched=True)
    assert set(wsb) - set(ws) == {"batched_centroids"}
    assert wsb["batched_centroids"] == 4 * 256 * 64
    # dtype_bytes halves exactly the streaming term, nothing else
    ws2 = ops.vmem_working_set(64, 256, 1024, dtype_bytes=2)
    assert ws2["stream"] == ws["stream"] // 2
    assert {k: v for k, v in ws2.items() if k != "stream"} == \
        {k: v for k, v in ws.items() if k != "stream"}


def test_pick_block_n_per_point_buffers_shrink_or_hold_the_pick():
    """Adding the per-point bound buffers (4 extra fp32-equivalent streams
    per row) can only shrink the tile vs a hypothetical pick without them —
    and at the paper's shapes the pick is unchanged (the buffers are small
    next to the point block)."""
    assert ops.pick_block_n(2, 8) == 4096          # paper shapes: unchanged
    for d, k in ((2, 8), (64, 256), (512, 1024), (4096, 256), (8192, 512)):
        bn = ops.pick_block_n(d, k)
        assert 128 <= bn <= 4096


def test_pick_block_n_bf16_half_width_stream():
    """dtype_bytes=2 budgets the bf16 streaming blocks: the half-width point
    tile can only grow the pick, never shrink it (the fp32 norms block and
    accumulators are precision-independent)."""
    assert ops.pick_block_n(2, 8, dtype_bytes=2) == 4096
    for d, k in ((64, 256), (512, 1024), (4096, 256), (8192, 512)):
        bf16 = ops.pick_block_n(d, k, dtype_bytes=2)
        fp32 = ops.pick_block_n(d, k)
        assert bf16 >= fp32, (d, k, bf16, fp32)
    # at least one big-d shape must actually benefit from the half width
    assert any(ops.pick_block_n(d, 256, dtype_bytes=2)
               > ops.pick_block_n(d, 256) for d in (2048, 4096, 8192))


# ---------------------------------------------------------------------------
# prologue kernel + bound-gated kernels (exact tile skipping)
# ---------------------------------------------------------------------------


def test_prologue_kernel_matches_jnp():
    """The fused prologue kernel's norms are BITWISE the jnp row norms (the
    reference/fused backends' cache), and the tile geometry + per-point
    center distances match the pure model tightly."""
    pts, _, _ = _mk(1000, 5, 1, jnp.float32, seed=7)
    norms, centers, radii, center_d = seed_prologue_pallas(pts, block_n=256,
                                                           interpret=True)
    cache = bounds.prologue(pts, 256)
    np.testing.assert_array_equal(np.asarray(norms), np.asarray(cache.norms))
    np.testing.assert_allclose(np.asarray(centers), np.asarray(cache.centers),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(radii), np.asarray(cache.radii),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(center_d),
                               np.asarray(cache.center_d),
                               rtol=1e-6, atol=1e-7)
    assert center_d.shape == (1000,)
    # every point sits inside its tile ball
    tile_r = np.repeat(np.asarray(radii), 256)[:1000]
    assert (np.asarray(center_d) <= tile_r + 1e-6).all()


def _gated_setup(n=1000, d=3, block_n=128, seed=0):
    pts, _, md = _mk(n, d, 1, jnp.float32, seed=seed)
    nrm = ops.point_norms(pts)
    grid = -(-n // block_n)
    pp0 = jnp.zeros((grid,), jnp.float32)
    tm0 = jnp.full((grid,), jnp.inf, jnp.float32)
    return pts, md, nrm, grid, pp0, tm0


def _no_prune_fine(n, grid):
    """center_d/dc/margin that keep the per-point seeding gate silent
    (dc = 0 -> lower bound 0 -> never clears a positive min_d2)."""
    return (jnp.zeros((n,), jnp.float32), jnp.zeros((grid,), jnp.float32),
            jnp.zeros((grid,), jnp.float32))


@pytest.mark.parametrize("n,block_n", [(1000, 128), (512, 128), (100, 128)])
def test_gated_all_active_bitwise_equals_plain(n, block_n):
    """With every tile active the gated kernel IS the plain kernel, bitwise
    (same md, same partials), plus the per-tile max bound state."""
    pts, md, nrm, grid, pp0, tm0 = _gated_setup(n=n, block_n=block_n)
    cents = jax.random.normal(jax.random.PRNGKey(5), (1, pts.shape[1]))
    active = jnp.ones((grid,), bool)
    cd, dc, mg = _no_prune_fine(n, grid)
    g_md, g_p, g_tm, pruned, skipped = ops.distance_min_update_gated(
        pts, cents, md, nrm, cd, dc, mg, pp0, tm0, active, block_n=block_n)
    p_md, p_p = ops.distance_min_update(pts, cents, md, norms=nrm,
                                        block_n=block_n)
    np.testing.assert_array_equal(np.asarray(g_md), np.asarray(p_md))
    np.testing.assert_array_equal(np.asarray(g_p), np.asarray(p_p))
    np.testing.assert_array_equal(
        np.asarray(g_tm), np.asarray(bounds.tile_reduce_max(p_md, block_n)))
    assert int(skipped) == 0
    assert float(jnp.sum(pruned)) == 0.0


def test_gated_skipping_is_bitwise_exact():
    """Acceptance pin: a round that skips tiles AND prunes points produces
    BITWISE the plain kernel's outputs — min_d2, partials AND tile_max —
    because both bound levels are sufficient conditions (skipped tiles alias
    their prior state; pruned points' min-update is a provable no-op)."""
    pts, md0, nrm, grid, pp0, tm0 = _gated_setup(n=1024, d=2, block_n=128)
    cache = bounds.RoundCache(nrm, *seed_prologue_pallas(
        pts, block_n=128, interpret=True)[1:])
    # round 1: everything active, fills the bound state
    c1 = pts[3:4]
    a1, dc1, mg1 = bounds.seed_gate(c1, cache, tm0)
    md1, p1, tm1, pr1, _ = ops.distance_min_update_gated(
        pts, c1, md0, nrm, cache.center_d, dc1, mg1, pp0, tm0, a1,
        block_n=128)
    # round 2: a far-away centroid — most tiles provably cannot change
    c2 = jnp.full((1, 2), 50.0)
    a2, dc2, mg2 = bounds.seed_gate(c2, cache, tm1)
    assert int(jnp.sum(a2)) < grid, "probe must actually skip tiles"
    md2, p2, tm2, pr2, skipped = ops.distance_min_update_gated(
        pts, c2, md1, nrm, cache.center_d, dc2, mg2, p1, tm1, a2,
        block_n=128)
    # one tile is always computed (compact_ids' write-back guard)
    assert int(skipped) == grid - max(int(jnp.sum(a2)), 1) > 0
    # the fine level fires inside the force-computed tile: every point of a
    # skippable tile is individually prunable against the far centroid
    assert float(jnp.sum(pr2)) > 0
    want_md, want_p = ops.distance_min_update(pts, c2, md1, norms=nrm,
                                              block_n=128)
    np.testing.assert_array_equal(np.asarray(md2), np.asarray(want_md))
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(want_p))
    np.testing.assert_array_equal(
        np.asarray(tm2), np.asarray(bounds.tile_reduce_max(want_md, 128)))


def test_gated_batched_matches_single():
    """vmap over the gated wrapper lowers to the batch-grid gated kernel and
    row b is bitwise the single-problem gated kernel on problem b (including
    per-problem skip/prune counts)."""
    B, n, d, bn = 3, 512, 2, 128
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    pts = jax.random.normal(keys[0], (B, n, d))
    cents = jnp.stack([jnp.full((1, d), 30.0 * b) for b in range(B)])
    md = jnp.abs(jax.random.normal(keys[1], (B, n))) * 2
    nrm = jax.vmap(ops.point_norms)(pts)
    grid = -(-n // bn)
    pp = jnp.abs(jax.random.normal(keys[2], (B, grid)))
    tm = jnp.abs(jax.random.normal(jax.random.fold_in(keys[2], 1), (B, grid)))
    cd = jnp.abs(jax.random.normal(jax.random.fold_in(keys[2], 2), (B, n)))
    dc = jnp.abs(jax.random.normal(jax.random.fold_in(keys[2], 3),
                                   (B, grid))) * 3
    mg = jnp.full((B, grid), 1e-4)
    # a mix of active/inactive tiles per problem
    active = jnp.arange(grid)[None, :] % (jnp.arange(B)[:, None] + 2) == 0
    out = jax.vmap(lambda p, c, m, nr, b_cd, b_dc, b_mg, a, b_pp, b_tm:
                   ops.distance_min_update_gated(p, c, m, nr, b_cd, b_dc,
                                                 b_mg, b_pp, b_tm, a,
                                                 block_n=bn))(
        pts, cents, md, nrm, cd, dc, mg, active, pp, tm)
    for b in range(B):
        s = ops.distance_min_update_gated(pts[b], cents[b], md[b], nrm[b],
                                          cd[b], dc[b], mg[b], pp[b], tm[b],
                                          active[b], block_n=bn)
        for o, w in zip(out[:4], s[:4]):
            np.testing.assert_array_equal(np.asarray(o[b]), np.asarray(w))
        assert int(out[4][b]) == int(s[4])


# ---------------------------------------------------------------------------
# coordinate-major (small d) round kernels: (d, block_n) tiles of (d, n_pad)
# ---------------------------------------------------------------------------

COLS_DIMS = [1, 2, 3, 8, 32]


def _layouts_agree(got, want, d):
    """The two layouts' outputs: bitwise at d <= 3; past that the CPU's dot
    sums the d products of a (d, block_n) tile in another order than those
    of a (block_n, d) tile, so they agree to fp32 rounding."""
    if d <= 3:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("d", COLS_DIMS)
def test_cols_round_matches_rows(d):
    """The plain coordinate-major round gives the row round's min_d2 and
    partials, n not a tile multiple; it pads unpadded (d, n) points
    itself."""
    n, bn = 1000, 128
    pts, cents, md = _mk(n, d, 2, jnp.float32, seed=d)
    nrm = ops.point_norms(pts)
    cols = ops.pad_cols(pts.T, -(-n // bn) * bn)
    got = ops.distance_min_update(pts, cents, md, norms=nrm, block_n=bn,
                                  cols=cols)
    want = ops.distance_min_update(pts, cents, md, norms=nrm, block_n=bn)
    for g, w in zip(got, want):
        _layouts_agree(g, w, d)
    unpadded = ops.distance_min_update(pts, cents, md, norms=nrm,
                                       block_n=bn, cols=pts.T)
    for g, w in zip(unpadded, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("d", COLS_DIMS)
def test_cols_gated_round_matches_rows(d):
    """The gated coordinate-major round against the gated row round on the
    same inputs: a round with every tile active, then one that skips tiles
    and prunes points. min_d2, partials and tile max agree as the layouts
    do; pruned and skipped are equal. The coordinate-major gated round is
    bitwise its own plain round, as the row kernels are."""
    n, bn = 1000, 128
    pts, md0, nrm, grid, pp0, tm0 = _gated_setup(n=n, d=d, block_n=bn,
                                                 seed=d)
    cols = ops.pad_cols(pts.T, grid * bn)
    cache = bounds.RoundCache(nrm, *seed_prologue_pallas(
        pts, block_n=bn, interpret=True)[1:])

    def both(c, md, pp, tm):
        a, dc, mg = bounds.seed_gate(c, cache, tm)
        args = (pts, c, md, nrm, cache.center_d, dc, mg, pp, tm, a)
        return (ops.distance_min_update_gated(*args, block_n=bn, cols=cols),
                ops.distance_min_update_gated(*args, block_n=bn), a)

    got, want, _ = both(pts[3:4], md0, pp0, tm0)
    for g, w in zip(got[:3], want[:3]):
        _layouts_agree(g, w, d)
    assert int(got[4]) == int(want[4]) == 0
    # round 2 from the row round's state: a far centroid skips tiles
    md1, p1, tm1 = want[:3]
    c2 = jnp.full((1, d), 50.0)
    got, want, a2 = both(c2, md1, p1, tm1)
    assert int(jnp.sum(a2)) < grid, "probe must actually skip tiles"
    assert int(got[4]) == int(want[4]) > 0
    assert float(jnp.sum(got[3])) > 0
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))
    for g, w in zip(got[:3], want[:3]):
        _layouts_agree(g, w, d)
    plain_md, plain_p = ops.distance_min_update(pts, c2, md1, norms=nrm,
                                                block_n=bn, cols=cols)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(plain_md))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(plain_p))
    np.testing.assert_array_equal(
        np.asarray(got[2]), np.asarray(bounds.tile_reduce_max(plain_md, bn)))


def test_cols_under_vmap_stream_rows():
    """Under vmap the wrappers given ``cols`` run the row batch-grid
    kernels on the rows: bitwise the wrappers without ``cols`` under vmap,
    plain and gated."""
    B, n, d, bn = 2, 300, 2, 128
    keys = jax.random.split(jax.random.PRNGKey(21), 3)
    pts = jax.random.normal(keys[0], (B, n, d))
    cols = jnp.swapaxes(pts, 1, 2)
    cents = jax.random.normal(keys[1], (B, 1, d))
    md = jnp.abs(jax.random.normal(keys[2], (B, n))) * 2
    nrm = jax.vmap(ops.point_norms)(pts)
    grid = -(-n // bn)
    active = jnp.arange(grid)[None, :] % 2 == jnp.arange(B)[:, None]

    def plain(p, c, m, nr, a, cl=None):
        return ops.distance_min_update(p, c, m, norms=nr, block_n=bn, cols=cl)

    def gated(p, c, m, nr, a, cl=None):
        return ops.distance_min_update_gated(
            p, c, m, nr, jnp.zeros((n,)), jnp.zeros((grid,)),
            jnp.zeros((grid,)), jnp.zeros((grid,)),
            jnp.full((grid,), jnp.inf), a, block_n=bn, cols=cl)

    for fn in (plain, gated):
        got = jax.vmap(fn)(pts, cents, md, nrm, active, cols)
        want = jax.vmap(fn)(pts, cents, md, nrm, active)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("d", [2, 32, 33, 128, 784])
def test_prologue_builds_cols_at_small_d_only(d):
    """The Pallas prologue builds ``RoundCache.cols`` — the padded rows,
    transposed — where `ops.streams_cols` says so (d <= 32), and leaves it
    None at larger d, with and without the bound balls."""
    from repro.core.engine import PallasBackend
    pts = _mk(300, d, 1, jnp.float32, seed=d)[0]
    assert ops.streams_cols(d) == (d <= 32)
    for with_bounds in (True, False):
        cache = PallasBackend().prologue(pts, with_bounds=with_bounds)
        if d > 32:
            assert cache.cols is None
            continue
        assert cache.cols.shape == (d, cache.rows.shape[0])
        np.testing.assert_array_equal(np.asarray(cache.cols),
                                      np.asarray(cache.rows.T))


@pytest.mark.parametrize("d", [2, 8])
def test_engine_seeding_on_cols_matches_rows(d):
    """The Pallas seeding loop on the coordinate-major copy picks the seeds
    the row stream picks, with the same skip and prune counts."""
    from repro.core.engine import PallasBackend, seed_points
    from repro.data.synthetic import blobs
    pts = jnp.asarray(blobs(700, d, 6, seed=d)[0])
    be = PallasBackend()
    cache = be.prologue(pts)
    assert cache.cols is not None
    key = jax.random.PRNGKey(3)
    got = seed_points(key, pts, 8, None, be, cache=cache)
    want = seed_points(key, pts, 8, None, be, cache=cache._replace(cols=None))
    for name in ("indices", "skipped", "pruned"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=name)
    _layouts_agree(got.min_d2, want.min_d2, d)


# ---------------------------------------------------------------------------
# argmin tie-breaking parity (duplicate centroids, e.g. after empty='reseed')
# ---------------------------------------------------------------------------


def test_argmin_tie_break_parity_across_paths():
    """Duplicate centroids produce exact distance ties; every assignment path
    (oracle, pallas single, pallas batch-grid, blocked-XLA) must resolve them
    to the SAME (lowest) index — tile skipping and reseeding both rely on
    deterministic ties."""
    from repro.core.engine import (FusedBackend, PallasBackend,
                                   ReferenceBackend, assign_blocked)
    pts, _, _ = _mk(600, 4, 1, jnp.float32, seed=13)
    base = jax.random.normal(jax.random.PRNGKey(14), (3, 4))
    cents = jnp.concatenate([base, base[1:2], base[0:1]])  # dup rows 1 and 0
    a_ref, _, _, _ = ref.lloyd_assign_ref(pts, cents)
    assert int(jnp.max(a_ref)) <= 2, "ties must resolve to the first copy"
    a_pal, _, _, _ = ops.lloyd_assign(pts, cents)
    np.testing.assert_array_equal(np.asarray(a_pal), np.asarray(a_ref))
    a_blk, _ = assign_blocked(pts, cents)
    np.testing.assert_array_equal(np.asarray(a_blk), np.asarray(a_ref))
    bpts = jnp.stack([pts, pts[::-1]])
    bc = jnp.stack([cents, cents])
    a_b, _, _, _ = jax.vmap(lambda p, c: ops.lloyd_assign(p, c))(bpts, bc)
    np.testing.assert_array_equal(np.asarray(a_b[0]), np.asarray(a_ref))
    for be in (ReferenceBackend(), FusedBackend(), PallasBackend()):
        rnd = be.assign_update(pts, cents, None)
        np.testing.assert_array_equal(np.asarray(rnd.assignment),
                                      np.asarray(a_ref), err_msg=be.name)
        # the tiled (bounded-fit) path must break ties identically
        cache = be.prologue(pts, m=cents.shape[0], with_bounds=False)
        tiled = be.assign_update(pts, cents, None, cache=cache)
        np.testing.assert_array_equal(np.asarray(tiled.assignment),
                                      np.asarray(a_ref), err_msg=be.name)


def test_kernel_inside_seeding_loop():
    """Pallas round used end-to-end inside kmeanspp gives identical seeds."""
    from repro.core import kmeanspp
    pts, _, _ = _mk(512, 4, 1, jnp.float32, seed=11)
    key = jax.random.PRNGKey(5)
    ref_res = kmeanspp(key, pts, 7, variant="fused", sampler="cdf")
    pal_res = kmeanspp(key, pts, 7, variant="pallas_fused", sampler="cdf")
    np.testing.assert_array_equal(np.asarray(ref_res.indices),
                                  np.asarray(pal_res.indices))


# ---------------------------------------------------------------------------
# flash attention kernel (memory-term §Perf kernel)
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, Sq, Skv, H, KH, hd, causal, window, cap, bq, bk)
    (2, 128, 128, 4, 2, 32, True, 0, 0.0, 64, 64),
    (1, 200, 200, 4, 4, 16, True, 0, 0.0, 64, 64),      # ragged seq
    (2, 64, 256, 8, 2, 32, False, 0, 0.0, 64, 128),     # cross attention
    (1, 256, 256, 2, 1, 64, True, 64, 50.0, 64, 64),    # window + softcap
    (1, 96, 96, 2, 2, 128, True, 0, 0.0, 32, 32),       # hd 128
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(case, dtype):
    from repro.kernels.flash_attention import flash_attention
    B, Sq, Skv, H, KH, hd, causal, window, cap, bq, bk = case
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(keys[1], (B, Skv, KH, hd), dtype)
    v = jax.random.normal(keys[2], (B, Skv, KH, hd), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window, cap=cap,
                          block_q=bq, block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   cap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_decode_offset():
    """q_offset (chunked prefill / decode) masks exactly like the oracle."""
    from repro.kernels.flash_attention import flash_attention
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (1, 32, 2, 32))
    k = jax.random.normal(keys[1], (1, 128, 2, 32))
    v = jax.random.normal(keys[2], (1, 128, 2, 32))
    got = flash_attention(q, k, v, causal=True, q_offset=64,
                          block_q=32, block_k=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, q_offset=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# tile_cap: the movement-tightening kernel (ISSUE 9)
# ---------------------------------------------------------------------------

TILE_CAP_SHAPES = [  # (n_tiles, d, m) — tiny, ragged-ish, multi-pending
    (1, 2, 1),
    (4, 2, 1),
    (16, 8, 4),
    (7, 3, 8),
    (33, 16, 2),
]


@pytest.mark.parametrize("n_tiles,d,m", TILE_CAP_SHAPES)
def test_tile_cap_matches_ref(n_tiles, d, m):
    from repro.kernels.kmeans_distance import tile_cap_pallas
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    centers = jax.random.normal(keys[0], (n_tiles, d), jnp.float32)
    radii = jnp.abs(jax.random.normal(keys[1], (n_tiles,), jnp.float32))
    pending = jax.random.normal(keys[2], (m, d), jnp.float32)
    for count in {0, 1, m}:
        cnt = jnp.asarray(count, jnp.int32)
        got = tile_cap_pallas(centers, radii, pending, cnt, interpret=True)
        want = ref.tile_cap_ref(centers, radii, pending, cnt)
        if count == 0:
            assert np.all(np.isinf(np.asarray(got))), \
                "count==0 must return +inf everywhere (no tightening)"
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_tile_cap_dominates_rows():
    """The Raff bound is an UPPER bound: cap_t >= d(x_i, pending_j)^2 for
    every row i inside tile t's ball and every pending j < count — the
    property that keeps the tightened envelope valid (and the draw exact)."""
    key = jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(key)
    n, d, bn, m = 512, 4, 128, 3
    pts = jax.random.normal(k1, (n, d), jnp.float32) * 3
    pending = jax.random.normal(k2, (m, d), jnp.float32)
    n_tiles = n // bn
    xt = pts.reshape(n_tiles, bn, d)
    centers = xt.mean(axis=1)
    radii = jnp.sqrt(jnp.max(jnp.sum((xt - centers[:, None, :]) ** 2, axis=-1),
                             axis=1))
    cap = ref.tile_cap_ref(centers, radii, pending, jnp.asarray(m, jnp.int32))
    d2 = jnp.min(jnp.sum((pts[:, None, :] - pending[None, :, :]) ** 2,
                         axis=-1), axis=1).reshape(n_tiles, bn)
    slack = np.asarray(cap)[:, None] - np.asarray(d2)
    assert np.all(slack >= -1e-3), f"cap violated by {slack.min()}"


def test_tile_cap_op_vmaps_via_ref():
    """ops.tile_cap under vmap (the batched seeding path) routes to the ref
    twin and matches a per-problem loop of the kernel."""
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    B, n_tiles, d, m = 3, 8, 4, 2
    centers = jax.random.normal(keys[0], (B, n_tiles, d), jnp.float32)
    radii = jnp.abs(jax.random.normal(keys[1], (B, n_tiles), jnp.float32))
    pending = jax.random.normal(keys[2], (B, m, d), jnp.float32)
    counts = jnp.asarray([0, 1, 2], jnp.int32)
    got = jax.vmap(lambda c, r, p, ct: ops.tile_cap(c, r, p, ct,
                                                    interpret=True))(
        centers, radii, pending, counts)
    for b in range(B):
        want = ref.tile_cap_ref(centers[b], radii[b], pending[b], counts[b])
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
