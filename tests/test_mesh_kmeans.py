"""The one-program mesh ``kmeans``: four fake CPU devices in a SUBPROCESS
(``mesh_kmeans_worker.py``), the shards on the Pallas kernels in interpret
mode at d=784, each shard 4,173 rows (no tile multiple)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

_WORKER = Path(__file__).parent / "mesh_kmeans_worker.py"


@pytest.fixture(scope="module")
def worker_out():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, str(_WORKER)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, \
        f"worker failed\nstdout: {proc.stdout[-4000:]}\nstderr: {proc.stderr[-4000:]}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_one_program_is_bitwise_seed_then_fit(worker_out):
    """k=256, 3 Lloyd iterations: centroids, assignment, inertia and
    iteration count equal those of mesh ``seed`` then mesh ``fit`` under the
    same key, although the shared prologue's tile (2,048 rows) is not the
    one the seeding alone takes (4,096)."""
    assert worker_out["bitwise_ok"]
    assert worker_out["served_by"] == "pallas"


def test_fused_one_program_is_bitwise_seed_then_fit(worker_out):
    """The same on the fused (XLA) local backend, whose ``shard_map`` keeps
    JAX's varying-mesh-axes type check on, so a missing ``pvary`` or
    ``psum`` in the mesh seeding, fit or kmeans body fails here."""
    assert worker_out["fused_bitwise_ok"]
    assert worker_out["fused_check_vma"] == [True]


def test_answer_is_a_lloyd_fixed_point(worker_out):
    """Run to convergence on 12 blobs, k=12: every centroid within 16 fp32
    eps of the data's RMS coordinate of the float64 mean of its rows, and no
    row nearer another centroid in float64 beyond a tie."""
    assert worker_out["converged_iters"] < 100
    assert worker_out["centroid_gap_eps"] < 16.0
    assert worker_out["rows_nearer_elsewhere"] == 0


def test_mesh_fit_matches_one_device_reference(worker_out):
    """From one seed in each blob, 3 Lloyd iterations on the mesh give the
    assignment of the one-device reference backend, and centroids within 16
    fp32 eps of the RMS coordinate of its."""
    assert worker_out["fit_iters"][0] == worker_out["fit_iters"][1]
    assert worker_out["fit_assign_match"]
    assert worker_out["fit_centroid_gap_eps"] < 16.0


def test_one_pad_of_each_shard_per_call(worker_out):
    """The traced program is one ``shard_map`` with exactly one pad of the
    (4173, 784) shard (the prologue's blocked copy into padded rows), and
    no row pad inside any loop body."""
    assert worker_out["shard_maps"] == 1
    assert worker_out["shard_pads"] == 1
    assert worker_out["loop_pads"] == []
