"""A run whose timed path is broken underneath comes out not correct, once
for each fault the cell can have (``bench/faults.py``). The look for a chip
is skipped; the rest of the run (data, set-up, window, check) is the
harness's own.

None of the faults crosses chips, so the exchange between chips is not
among them: every cell runs on one chip. Uniform seeding is not among them
either: after the Lloyd loop its answer is as good as k-means++'s on these
data, so no number of the answer tells the two apart (PERF.md section 2).
"""
from __future__ import annotations

import pytest

from bench import faults

FAULTS = [("paper_job", "unchanged"), ("paper_job", "half"),
          ("paper_job", "altered"), ("paper_job", "iters=1"),
          ("sift1m_build", "unchanged"), ("sift1m_build", "half"),
          ("sift1m_build", "altered"), ("sift1m_build", "iters=2"),
          ("sift1m_search_batch", "half"), ("sift1m_search_batch", "altered"),
          ("sift1m_search_batch", "nprobe=4")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_is_not_correct(run, cell, fault):
    with faults.planted(fault, search="search" in cell):
        res = run(cell)
    assert not res["correct"], res["checks"]
