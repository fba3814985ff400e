"""The reduction from trace events to per-layer numbers, on small traces."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import kernel_names as kn
from bench import trace_reduce as tr

HERE = Path(__file__).resolve().parent


def test_reduce_hand_made_trace():
    events = {
        "devices": {"/device:TPU:0": {
            "modules": [["jit_f", 90, 200], ["jit_ivf_scan_pallas", 295, 110]],
            "ops": [["%while.1 = (s32[]) while(...)", 100, 100, 0],
                    ["%lloyd_assign_gated_pallas.7 = (s32[1,8])", 110, 50, 1],
                    ["%pad.3 = f32[8,2] pad(...)", 170, 20, 0],
                    ["%closed_call.2 = (f32[32,1,10]) custom-call", 300, 100,
                     1],
                    ["%outside.1 = f32[] add(...)", 0, 50, 0]]}},
        "spans": [["window", 80, 420], ["search", 90, 150],
                  ["to_host", 250, 40]]}
    red = tr.reduce(events)
    assert red.window_s == pytest.approx(420e-9)
    assert red.busy_s == pytest.approx(200e-9)
    assert red.total_op_s == pytest.approx(200e-9)  # self times: no double
    #                                                 count of the while body
    assert red.time(kernel=True, prefixes=kn.LLOYD) == pytest.approx(50e-9)
    assert red.time(kernel=True, module=kn.SCAN_PROGRAM) == \
        pytest.approx(100e-9)
    assert red.time(kernel=False) == pytest.approx(50e-9)   # while + pad
    assert red.time(kernel=True, prefixes=kn.SEEDING) == 0.0
    assert [g[0] for g in red.gaps] == ["to_host", "none", "search"]
    assert [g[1] for g in red.gaps] == pytest.approx([100e-9, 100e-9,
                                                      20e-9])
    bd = tr.breakdown(red, n=2)
    assert bd["device_ops"][0][0].startswith("jit_ivf_scan_pallas: ")
    assert len(bd["idle_gaps"]) == 2


def test_ops_are_averaged_over_devices():
    events = {"devices": {
        "/device:TPU:0": {"modules": [], "ops": [["%a.1 = f32[]", 0, 10, 0]]},
        "/device:TPU:1": {"modules": [], "ops": [["%a.1 = f32[]", 0, 30, 0]]}},
        "spans": [["window", 0, 40]]}
    red = tr.reduce(events)
    assert red.busy_s == pytest.approx(20e-9)
    assert red.total_op_s == pytest.approx(40e-9)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "spans": [["job", 0, 5]]})


def test_read_xplane_finds_the_benchmarks_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("job"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = tr.read_xplane(tr.find_xplane(str(tmp_path)))
    names = [s[0] for s in ev["spans"]]
    assert names.count("window") == 1 and names.count("job") == 2
    red = tr.reduce(ev)
    assert red.window_s > 0


RECORDED = sorted(HERE.glob("data/*.events.json"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_chip_trace(path):
    """Excerpts of real chip traces (events of stage 1, kept as JSON) with
    the numbers the reduction gave when they were recorded."""
    rec = json.loads(path.read_text())
    red = tr.reduce(rec["events"])
    want = rec["expect"]
    assert red.window_s == pytest.approx(want["window_s"])
    assert red.busy_s == pytest.approx(want["busy_s"])
    assert red.total_op_s == pytest.approx(want["total_op_s"])
    assert red.time(kernel=True) == pytest.approx(want["kernel_s"])
    assert red.time(kernel=True, prefixes=kn.SEEDING) == \
        pytest.approx(want["seeding_s"])
    assert red.time(kernel=True, prefixes=kn.LLOYD) == \
        pytest.approx(want["lloyd_s"])
    assert red.time(kernel=True, module=kn.SCAN_PROGRAM) == \
        pytest.approx(want["scan_s"])
