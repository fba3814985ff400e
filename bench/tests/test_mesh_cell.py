"""The four-chip cell ``mnist8m_mesh_job`` at a test size (n=8,192 rows of
the configuration's d=784 over four fake CPU devices, k=32, Lloyd to
convergence), run by ``mesh_cell_worker.py`` in a subprocess: the sharded
reference agrees with the gathered one, the program passes the cell's
limits, and the bf16 control and each planted fault do not."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

WORKER = Path(__file__).with_name("mesh_cell_worker.py")


@pytest.fixture(scope="module")
def worker_out():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, str(WORKER)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_rows_are_sharded_and_block_free(worker_out):
    assert worker_out["devices"] == 4 and worker_out["points_sharded"] == 4
    assert worker_out["generator_block_free"]
    assert 0.4 < worker_out["generator_zero_share"] < 0.6


@pytest.mark.parametrize("answer", ["converged", "early"])
def test_sharded_numbers_equal_gathered(worker_out, answer):
    """The cluster sums are exact on both sides; the distances are float32
    per row on the chips against float64 on the host, which moves
    ``assign_gap`` by a few parts in 10**4 where it is near 0."""
    got = worker_out[f"parity_{answer}"]
    want = got["gathered"]
    assert got["sharded"]["centroid_gap_eps"] == pytest.approx(
        want["centroid_gap_eps"], rel=1e-6)
    assert got["sharded"]["assign_gap"] == pytest.approx(
        want["assign_gap"], rel=1e-3)


def test_program_is_correct(worker_out):
    assert worker_out["program"]["correct"], worker_out["program"]


@pytest.mark.parametrize("run", ["control_bf16", "fault_unchanged",
                                 "fault_half", "fault_altered",
                                 "fault_iters=5"])
def test_control_and_faults_are_not_correct(worker_out, run):
    assert not worker_out[run]["correct"], worker_out[run]
