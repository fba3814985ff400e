"""Small cells for the benchmark's own tests, on whatever JAX finds (the
CPU here; the Pallas kernels then run in interpret mode).

    python -m pytest bench/tests
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

# Lloyd runs to convergence at these sizes, so that which job of the window
# the seed samples does not decide whether assign_gap clears its limit.
SMALL = {
    "kmeans_paper": dict(n=8192, k=8, max_iters=200, data={}),
    "sift1m_ivf4096": dict(n=8192, d=16, queries=256, nlist=32, nprobe=8,
                           max_iters=200, data=dict(latent_dim=4)),
}


# Cells whose files are kept under bench/ while BENCHMARK.json does not list
# them (PERF.md section 7): (configuration, traffic mix).
UNLISTED = {"sift1m_build": ("sift1m_ivf4096", "build_jobs")}


def small_cell(name: str):
    """The cell as ``BENCHMARK.json`` has it (or as its files under
    ``bench/`` define it), its sizes cut for a test: same generator,
    traffic and limits."""
    from bench import harness

    try:
        cell = harness.load_cell(name)
    except harness.CellError:
        cfg, mix = UNLISTED[name]
        cell = harness.Cell(
            name, {"name": name, "config": cfg, "traffic": mix, "chips": 1},
            harness.load_json(harness.BENCH / "configs" / f"{cfg}.json"),
            harness.load_json(harness.BENCH / "traffic" / f"{mix}.json"),
            [], [])
    small = dict(SMALL[cell.cfg["name"]])
    cell.cfg["data"] = dict(cell.cfg["data"], **small.pop("data"))
    cell.cfg.update(small)
    cell.mix = dict(cell.mix, sample_queries=128)
    if "batch" in cell.mix:     # fewer queries to a batch than in the pool
        cell.mix["batch"] = min(cell.mix["batch"], 100)
    return cell


def run_small(name: str, seed: int = 2 ** 40 + 3, control: str | None = None,
              seconds: float = 0.5) -> dict:
    from bench import harness

    return harness.run_cell(small_cell(name), seed, seconds, False,
                            t_begin=time.perf_counter(), control=control)


@pytest.fixture
def run():
    return run_small
