"""Pins the engine-wide round-counter contract (repro.core.telemetry):
fixed-length int32 counters, zero-filled slots for rounds that never ran,
and the rejection sampler's proposals/accepts relations — stated ONCE there
instead of per-test ad hoc checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import telemetry
from repro.core.engine import _REJECT_ATTEMPTS, ClusterEngine


def _pts(n=4096, d=8, seed=1):
    return jax.random.normal(jax.random.key(seed), (n, d), jnp.float32)


# ---------------------------------------------------------------------------
# the checkers themselves reject contract violations
# ---------------------------------------------------------------------------


def test_check_counter_rejects_violations():
    with pytest.raises(AssertionError):
        telemetry.check_counter(None, 4)
    with pytest.raises(AssertionError):  # wrong length
        telemetry.check_counter(np.zeros(3, np.int32), 4)
    with pytest.raises(AssertionError):  # wrong dtype
        telemetry.check_counter(np.zeros(4, np.float32), 4)
    with pytest.raises(AssertionError):  # negative
        telemetry.check_counter(np.array([1, -1, 0, 0], np.int32), 4)
    with pytest.raises(AssertionError):  # non-zero past convergence
        telemetry.check_converged_zeros(np.array([2, 1, 1, 0], np.int32), 2, 4)
    telemetry.check_converged_zeros(np.array([2, 1, 0, 0], np.int32), 2, 4)


def test_check_rejection_counters_rejects_violations():
    ok_p = np.array([0, 1, 2, 1], np.int32)
    ok_a = np.array([0, 1, 0, 1], np.int32)
    telemetry.check_rejection_counters(ok_p, ok_a, 4, max_attempts=8)
    with pytest.raises(AssertionError):  # proposed on round 0
        telemetry.check_rejection_counters(
            np.array([1, 1, 1, 1], np.int32), ok_a, 4, max_attempts=8)
    with pytest.raises(AssertionError):  # accepts not 0/1
        telemetry.check_rejection_counters(
            ok_p, np.array([0, 2, 0, 1], np.int32), 4, max_attempts=8)
    with pytest.raises(AssertionError):  # over the truncation depth
        telemetry.check_rejection_counters(
            np.array([0, 9, 1, 1], np.int32), ok_a, 4, max_attempts=8)


# ---------------------------------------------------------------------------
# engine results obey the contract
# ---------------------------------------------------------------------------


def test_seed_counters_obey_contract():
    for sampler in ("tiled", "rejection"):
        res = ClusterEngine("fused").seed(jax.random.key(0), _pts(), 8,
                                          sampler=sampler)
        telemetry.check_counter(res.skipped, 8, "skipped")
        telemetry.check_counter(res.pruned, 8, "pruned")


def test_rejection_counters_obey_contract():
    res = ClusterEngine("fused").seed(jax.random.key(0), _pts(), 12,
                                      sampler="rejection", refresh_block=4)
    telemetry.check_rejection_counters(res.proposals, res.accepts, 12,
                                       max_attempts=_REJECT_ATTEMPTS)
    # non-rejection samplers don't grow the counters
    tiled = ClusterEngine("fused").seed(jax.random.key(0), _pts(), 12,
                                        sampler="tiled")
    assert tiled.proposals is None and tiled.accepts is None


def test_fit_counters_zero_filled_past_convergence():
    # a draw that converges well inside max_iters (16 iterations)
    pts = _pts(n=2048, d=2, seed=5)
    seeds = ClusterEngine("fused").seed(jax.random.key(1), pts, 4).centroids
    res = ClusterEngine("fused").fit(pts, seeds, max_iters=25)
    it = int(res.n_iters)
    assert it < 25
    telemetry.check_converged_zeros(res.skipped, it, 25, "skipped")
    telemetry.check_converged_zeros(res.pruned, it, 25, "pruned")
