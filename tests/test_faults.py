"""Fault-injection matrix (ISSUE 7 tentpole): every injected fault either
RECOVERS BITWISE (the guarded loop heals and the final result equals a
never-corrupted run's) or raises a typed ClusteringError subclass — never a
silent wrong answer. Covers traced-compute corruption (NaN'd tiles, poisoned
bound state, lost psum contributions, broken rejection envelopes), forced
kernel failures walking the backend fallback chain, and host-side pipeline
deaths."""
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import telemetry
from repro.core.engine import ClusterEngine, MeshBackend
from repro.core.guards import (ClusteringError, InvalidInputError,
                               KernelFailureError, PipelineError)
from repro.data import DataPipeline
from repro.data.synthetic import blobs
from repro.testing import (FaultSpec, flaky_read_fn, force_kernel_failure,
                           kill_prefetch)


def _coherent(n=16384, d=2, k=8, seed=0):
    pts, labels = blobs(n, d, k, seed=seed, spread=0.05)
    return jnp.asarray(pts[np.argsort(labels, kind="stable")])


def _same_seed(a, b):
    np.testing.assert_array_equal(np.asarray(a.centroids),
                                  np.asarray(b.centroids))
    np.testing.assert_array_equal(np.asarray(a.indices),
                                  np.asarray(b.indices))
    np.testing.assert_array_equal(np.asarray(a.min_d2), np.asarray(b.min_d2))


def _same_fit(a, b):
    np.testing.assert_array_equal(np.asarray(a.centroids),
                                  np.asarray(b.centroids))
    np.testing.assert_array_equal(np.asarray(a.assignment),
                                  np.asarray(b.assignment))
    assert float(a.inertia) == float(b.inertia)
    assert int(a.n_iters) == int(b.n_iters)


# ---------------------------------------------------------------------------
# in-flight corruption: the guarded loops detect, heal, and recover BITWISE
# ---------------------------------------------------------------------------


def test_seed_nan_tile_recovers_bitwise():
    """NaN'd D^2 rows poison the round total; the heal refolds the chosen
    prefix ungated and the final seeds are bit-identical to a clean run."""
    pts = _coherent()
    eng = ClusterEngine("fused", validate="raise")
    clean = eng.seed(jax.random.PRNGKey(1), pts, 8)
    assert clean.recovered is not None
    telemetry.check_recovered(clean.recovered, 8, expect=np.zeros(8))
    hurt = eng.seed(jax.random.PRNGKey(1), pts, 8,
                    _fault=FaultSpec("nan_tile", round=2))
    _same_seed(clean, hurt)
    telemetry.check_recovered(hurt.recovered, 8)
    assert int(np.asarray(hurt.recovered)[1]) == 1   # round m at slot m-1


def test_seed_poisoned_bound_state_recovers_when_witnessed():
    """A NaN'd carried partial in a tile the gate SKIPS is summed straight
    into the round total (skipped tiles reuse the carry) — the exact blind
    spot a correctness-only reading would miss. Detection fires on a round
    whose gate skips the poisoned tile (tile 0: with this key and data that
    is round 6); recovery is bitwise either way."""
    pts = _coherent()
    eng = ClusterEngine("fused", validate="raise")
    clean = eng.seed(jax.random.PRNGKey(1), pts, 8)
    assert int(np.asarray(clean.skipped)[5]) > 0    # round 6 skips tiles
    hurt = eng.seed(jax.random.PRNGKey(1), pts, 8,
                    _fault=FaultSpec("nan_state", round=6))
    _same_seed(clean, hurt)
    assert int(np.asarray(hurt.recovered)[5]) == 1
    # the same poison in a round that recomputes every tile is overwritten
    # before anything reads it: harmless, not flagged — and still bitwise
    active = eng.seed(jax.random.PRNGKey(1), pts, 8,
                      _fault=FaultSpec("nan_state", round=1))
    _same_seed(clean, active)


# REPRO_FAULTS=1 (the dedicated CI step) widens the matrix to every
# injectable fit iteration; the default tier-1 run keeps a representative
# pair so the suite stays fast.
_FIT_FAULT_ROUNDS = ((2, 3, 4, 5, 6)
                     if os.environ.get("REPRO_FAULTS", "") == "1"
                     else (2, 4))


@pytest.mark.parametrize("kind", ["zero_counts", "nan_state"])
@pytest.mark.parametrize("rd", _FIT_FAULT_ROUNDS)
def test_fit_faults_recover_bitwise(kind, rd):
    """A halved psum contribution (lost shard) or NaN'd bound state trips
    the per-iteration health check; the heal runs one ungated round,
    rebuilds the bound state, and the fit converges bit-identically."""
    pts = _coherent()
    eng = ClusterEngine("fused", validate="raise")
    seeds = eng.seed(jax.random.PRNGKey(1), pts, 8).centroids
    clean = eng.fit(pts, seeds, max_iters=8, tol=-1.0)
    telemetry.check_recovered(clean.recovered, 8, expect=np.zeros(8))
    hurt = eng.fit(pts, seeds, max_iters=8, tol=-1.0,
                   _fault=FaultSpec(kind, round=rd))
    _same_fit(clean, hurt)
    assert int(np.asarray(hurt.recovered)[rd]) == 1


def test_fit_guard_off_returns_no_recovery_telemetry():
    pts = _coherent(n=4096)
    eng = ClusterEngine("fused", validate="off")
    seeds = eng.seed(jax.random.PRNGKey(2), pts, 4).centroids
    res = eng.fit(pts, seeds, max_iters=4)
    assert res.recovered is None and seeds is not None


def test_rejection_envelope_corruption_replays_bitwise():
    """A negative stale partial breaks the dominance precondition; the
    guard rebuilds the STALE envelope (refreshed prefix only) before
    proposing, so even the proposal/accept counters replay bitwise."""
    pts = _coherent(n=8192)
    eng = ClusterEngine("fused", validate="raise")
    clean = eng.seed(jax.random.PRNGKey(2), pts, 8, sampler="rejection")
    hurt = eng.seed(jax.random.PRNGKey(2), pts, 8, sampler="rejection",
                    _fault=FaultSpec("neg_envelope", round=3))
    _same_seed(clean, hurt)
    np.testing.assert_array_equal(np.asarray(clean.proposals),
                                  np.asarray(hurt.proposals))
    np.testing.assert_array_equal(np.asarray(clean.accepts),
                                  np.asarray(hurt.accepts))
    rec = np.asarray(hurt.recovered)
    assert rec[3] == 1 and rec.sum() == 1
    telemetry.check_rejection_counters(hurt.proposals, hurt.accepts, 8,
                                       max_attempts=8,
                                       recovered=hurt.recovered)


# REPRO_FAULTS=1 widens the torn-coarse-aggregate matrix to every injectable
# rejection round; tier-1 keeps one representative round.
_SUPER_FAULT_ROUNDS = ((2, 3, 4, 5, 6)
                       if os.environ.get("REPRO_FAULTS", "") == "1"
                       else (3,))


@pytest.mark.parametrize("rd", _SUPER_FAULT_ROUNDS)
def test_rejection_stale_super_heals_via_prefix_refold(rd):
    """A torn coarse aggregate (every tile partial backing the LAST super
    NaN'd) trips the same fp-validity guard as neg_envelope; the heal
    refolds the refreshed prefix, and because the super-tile proposal state
    is DERIVED from the healed partials each round, the coarse-to-fine draw
    — indices, proposal/accept counters, AND the tightened/supers counters —
    replays bitwise against a never-corrupted run."""
    pts = _coherent(n=8192)
    eng = ClusterEngine("fused", validate="raise")
    clean = eng.seed(jax.random.PRNGKey(2), pts, 8, sampler="rejection",
                     proposal="hier")
    hurt = eng.seed(jax.random.PRNGKey(2), pts, 8, sampler="rejection",
                    proposal="hier",
                    _fault=FaultSpec("stale_super", round=rd))
    _same_seed(clean, hurt)
    for name in ("proposals", "accepts", "tightened", "supers"):
        np.testing.assert_array_equal(np.asarray(getattr(clean, name)),
                                      np.asarray(getattr(hurt, name)))
    rec = np.asarray(hurt.recovered)
    assert rec[rd] == 1 and rec.sum() == 1
    telemetry.check_hier_counters(hurt.tightened, hurt.supers,
                                  hurt.proposals, 8, hier=True)


def test_mesh_guarded_fit_recovers_bitwise():
    """The health predicate is psum-replicated: every shard takes the same
    heal branch, and the mesh fit recovers bit-identically too."""
    mesh = jax.make_mesh((1,), ("data",))
    pts = _coherent(n=8192)
    eng = ClusterEngine(MeshBackend(mesh=mesh, axes=("data",)),
                        validate="raise")
    seeds = ClusterEngine("fused").seed(jax.random.PRNGKey(3), pts,
                                        8).centroids
    clean = eng.fit(pts, seeds, max_iters=6, tol=-1.0)
    hurt = eng.fit(pts, seeds, max_iters=6, tol=-1.0,
                   _fault=FaultSpec("zero_counts", round=2))
    _same_fit(clean, hurt)
    assert int(np.asarray(hurt.recovered)[2]) == 1


# ---------------------------------------------------------------------------
# kernel failures: fallback chain pallas -> fused -> reference, typed at the
# end of the chain — with provenance
# ---------------------------------------------------------------------------


def test_kernel_failure_walks_fallback_chain():
    pts = _coherent(n=4096)
    eng = ClusterEngine("pallas", validate="raise")
    want = ClusterEngine("fused", validate="raise").seed(
        jax.random.PRNGKey(4), pts, 4)
    with warnings.catch_warnings(record=True) as wlist:
        warnings.simplefilter("always")
        with force_kernel_failure("injected launch failure"):
            got = eng.seed(jax.random.PRNGKey(4), pts, 4)
    _same_seed(want, got)
    assert [e[:2] for e in eng.fallback_events] == [("pallas", "fused")]
    assert "injected launch failure" in eng.fallback_events[0][2]
    assert eng.last_backend.name == "fused"
    msgs = [str(w.message) for w in wlist
            if issubclass(w.category, RuntimeWarning)]
    assert len(msgs) == 1 and "falling back to 'fused'" in msgs[0]


def test_kernel_failure_warns_only_once():
    pts = _coherent(n=2048)
    eng = ClusterEngine("pallas")
    with warnings.catch_warnings(record=True) as wlist:
        warnings.simplefilter("always")
        with force_kernel_failure():
            eng.seed(jax.random.PRNGKey(5), pts, 4)
            eng.seed(jax.random.PRNGKey(6), pts, 4)
    msgs = [w for w in wlist if issubclass(w.category, RuntimeWarning)]
    assert len(msgs) == 1
    assert len(eng.fallback_events) == 2     # provenance still records both


def test_exhausted_fallback_chain_raises_typed():
    """reference has nowhere to fall: a failure that survives the whole
    chain surfaces as the typed KernelFailureError (a ClusteringError),
    not a silent result."""
    eng = ClusterEngine("pallas")

    def always_fail(be):
        raise KernelFailureError(f"dead on {be.name}")

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(KernelFailureError, match="dead on reference"):
            eng._run(always_fail)
    assert [e[:2] for e in eng.fallback_events] == [
        ("pallas", "fused"), ("fused", "reference")]
    assert isinstance(KernelFailureError("x"), ClusteringError)
    # the terminal link is kernel-free BY CONSTRUCTION: the reference
    # backend computes inline jnp and still serves under a forced failure
    pts = _coherent(n=1024)
    with force_kernel_failure("dead"):
        res = ClusterEngine("reference").seed(jax.random.PRNGKey(7), pts, 4)
    assert np.isfinite(np.asarray(res.centroids)).all()


def test_mesh_kernel_failure_swaps_local_backend():
    """On a mesh the LOCAL compute backend is what can kernel-fail; the
    walker swaps it in place, keeping the mesh wrapper (and its
    collectives) intact."""
    from repro.core.engine import PallasBackend
    mesh = jax.make_mesh((1,), ("data",))
    pts = _coherent(n=4096)
    eng = ClusterEngine(MeshBackend(mesh=mesh, axes=("data",),
                                    local=PallasBackend()))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with force_kernel_failure():
            res = eng.fit(pts, pts[:4], max_iters=3)
    assert eng.fallback_events[0][:2] == ("pallas", "fused")
    assert eng.last_backend.distributed
    assert eng.last_backend.local.name == "fused"
    assert np.isfinite(float(res.inertia))


def test_fused_backend_is_kernel_free():
    """The fused (and reference) backends compute inline jnp — they are
    fallback TARGETS, immune to kernel launch failures by construction."""
    pts = _coherent(n=2048)
    eng = ClusterEngine("fused")
    with force_kernel_failure("boom"):
        res = eng.fit(pts, pts[:4], max_iters=3)
    assert eng.fallback_events == []
    assert np.isfinite(float(res.inertia))


# ---------------------------------------------------------------------------
# entry guards: garbage in -> typed error (or sanitized), never NaN out
# ---------------------------------------------------------------------------


def test_malformed_inputs_raise_typed_errors():
    pts = _coherent(n=512)
    eng = ClusterEngine("fused", validate="raise")
    bad = np.asarray(pts).copy()
    bad[3, 0] = np.inf
    with pytest.raises(InvalidInputError, match="non-finite"):
        eng.seed(jax.random.PRNGKey(0), bad, 4)
    with pytest.raises(InvalidInputError, match="0 < k <= n"):
        eng.seed(jax.random.PRNGKey(0), pts, 0)
    with pytest.raises(InvalidInputError, match="0 < k <= n"):
        eng.seed(jax.random.PRNGKey(0), pts[:3], 4)
    with pytest.raises(InvalidInputError, match="weights"):
        eng.seed(jax.random.PRNGKey(0), pts, 4,
                 weights=-np.ones(512, np.float32))
    with pytest.raises(InvalidInputError, match="non-finite"):
        eng.fit(pts, np.full((4, 2), np.nan, np.float32), max_iters=2)
    assert issubclass(InvalidInputError, (ClusteringError, ValueError))


def test_sanitize_policy_zeroes_rows_and_stays_bitwise_on_clean_input():
    pts = np.asarray(_coherent(n=2048))
    bad = pts.copy()
    bad[7] = np.nan
    san = ClusterEngine("fused", validate="sanitize")
    res = san.seed(jax.random.PRNGKey(8), bad, 4)
    assert np.isfinite(np.asarray(res.centroids)).all()
    # clean input passes through UNTOUCHED: sanitize == off bitwise
    a = san.seed(jax.random.PRNGKey(8), pts, 4)
    b = ClusterEngine("fused", validate="off").seed(jax.random.PRNGKey(8),
                                                    pts, 4)
    _same_seed(a, b)


# ---------------------------------------------------------------------------
# host-side pipeline faults
# ---------------------------------------------------------------------------


def test_transient_read_failures_are_retried():
    fails = {1: 2, 3: 1}      # step 1 flakes twice, step 3 once
    pipe = DataPipeline(
        flaky_read_fn(lambda s: {"x": np.full((4,), s)}, fail_steps=fails),
        prefetch=1, backoff=0.01)
    got = [next(iter(pipe))[0] for _ in range(5)]
    pipe.stop()
    assert got == [0, 1, 2, 3, 4]
    assert fails == {1: 0, 3: 0}             # every flake was consumed


def test_dead_prefetch_thread_raises_typed_pipeline_error():
    pipe = DataPipeline(lambda s: {"x": np.zeros(2)}, prefetch=1)
    it = iter(pipe)
    next(it)
    kill_prefetch(pipe)
    with pytest.raises(PipelineError) as ei:
        for _ in range(8):
            next(it)
    pipe.stop()
    assert ei.value.step is not None
    assert isinstance(ei.value, ClusteringError)


def test_minibatch_surfaces_pipeline_error_with_step():
    eng = ClusterEngine("fused")
    boom = 5

    def read_fn(step):
        if step == boom:
            raise IOError("storage gone")
        return np.random.default_rng(step).normal(size=(128, 2)).astype(
            np.float32)

    pipe = DataPipeline(read_fn, prefetch=1, retries=2, backoff=0.01)
    with pytest.raises(PipelineError, match="read_fn failed") as ei:
        eng.fit_minibatch(np.zeros((4, 2), np.float32), pipe, n_batches=16)
    assert ei.value.step == boom


# ---------------------------------------------------------------------------
# serving-index state corruption: a poisoned offset table must raise typed,
# never return silently-wrong neighbors
# ---------------------------------------------------------------------------


def _small_index():
    from repro.serve import IvfIndex
    pts, _ = blobs(1024, 8, 8, seed=3)
    return IvfIndex.build(jnp.asarray(pts), 8, block_n=128)


@pytest.mark.parametrize("kind", ["shifted_start", "short_count",
                                  "negative_count"])
def test_corrupt_list_offsets_raises_typed_on_search(kind):
    from repro.core.guards import CorruptedStateError
    from repro.testing.faults import corrupt_list_offsets

    idx = _small_index()
    qs = jnp.asarray(blobs(4, 8, 8, seed=4)[0])
    # sanity: the uncorrupted index serves
    idx.search(qs, 5, nprobe=8)
    bad = corrupt_list_offsets(idx, kind=kind)
    with pytest.raises(CorruptedStateError, match="rebuild the index"):
        bad.search(qs, 5, nprobe=8)
    # the check is always on — validate='off' relaxes input guards only
    with pytest.raises(CorruptedStateError):
        bad.search(qs, 5, nprobe=8, validate="off")


def test_ivf_search_survives_forced_kernel_failure_via_fallback():
    """A forced Pallas failure walks the scan dispatch down the fallback
    chain to the bitwise-identical ref twin instead of surfacing."""
    idx = _small_index()
    qs = jnp.asarray(blobs(4, 8, 8, seed=4)[0])
    clean = idx.search(qs, 5, nprobe=8, backend="pallas")
    with force_kernel_failure("ivf scan down"):
        with pytest.raises(KernelFailureError):
            idx.search(qs, 5, nprobe=8, backend="reference")
    # chain exhausted -> typed raise; pallas entry would need a non-forced
    # fused hop, which the force blocks too — both end typed, never silent
    hurt = idx.search(qs, 5, nprobe=8, backend="pallas")
    np.testing.assert_array_equal(np.asarray(clean.indices),
                                  np.asarray(hurt.indices))
