"""The comparison that decides ``correct`` fails its control, at a size a
test run holds: the program passes its cell's limits and the control, in
the program's place, does not (see PERF.md section 2 for the readings on
the chip at the cells' own sizes)."""
from __future__ import annotations

import pytest

CELLS = ["paper_job", "sift1m_build", "sift1m_search_batch"]


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(run, cell):
    res = run(cell)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(run, cell):
    res = run(cell, control="bf16")
    assert not res["correct"], res["checks"]
