"""One run of one cell: data, set-up, the measured window, the check, the
metrics and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name:

* the cell's entry in ``BENCHMARK.json`` names its ``config`` and
  ``traffic``;
* the configuration is the ``file`` of its entry under ``configs``
  (``bench/configs/<name>.json``): sizes, the data generator and its
  parameters, and the ``limits`` of the compared numbers;
* the traffic mix is ``bench/traffic/<name>.json``; its ``driver`` key names
  the module ``bench.drivers.<driver>`` that runs it;
* each metric is ``bench/metrics/<name>.py``, whose ``read(run)`` returns the
  number, or None where it finds nothing to read.
"""
from __future__ import annotations

import gzip
import importlib
import importlib.util
import json
import os
import shutil
import time
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"


class CellError(RuntimeError):
    """The benchmark cannot run the cell as asked."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    entry: dict
    cfg: dict
    mix: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{sorted(entries)}")
    entry = entries[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[entry["config"]]
    cfg = load_json(root / cfg_entry["file"])
    mix = load_json(root / "bench" / "traffic" / f"{entry['traffic']}.json")
    return Cell(name, entry, cfg, mix,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def _metric_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '__')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Run:
    """What a metric's ``read`` sees."""
    cfg: dict
    mix: dict
    setup_s: float
    win: dict
    trace: object = None            # trace_reduce.Reduced, --trace 1 only


class _CompileCounter:
    """Backend compilations while ``on``."""

    def __init__(self):
        import jax.monitoring
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kwargs):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _limit_ok(value: float, limit: dict) -> bool:
    if "max" in limit:
        return value <= limit["max"]
    return value >= limit["min"]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_begin: float, control: str | None = None) -> dict:
    """One run; returns the result object (without printing it).
    ``control`` (``"bf16"`` or ``"high"``) puts the plain reference at that
    precision in the program's place; the benchmark's runs never do."""
    import jax

    from bench import trace_reduce
    from bench.reference.data import generate

    driver = importlib.import_module(f"bench.drivers.{cell.mix['driver']}")
    devices = jax.devices()
    data = generate(cell.cfg, cell.mix.get("data_seed", seed))
    state = driver.setup(cell.cfg, cell.mix, data, seed, control=control)
    setup_s = time.perf_counter() - t_begin

    compiles = _CompileCounter()
    trace_dir = OUT / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no Python call events
        opts.host_tracer_level = 1        # the benchmark's spans, no more
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        seconds = min(seconds, float(cell.mix["trace_seconds"]))
    compiles.on = True
    with jax.profiler.TraceAnnotation("window"):
        win = driver.window(state, seconds, cell.mix, seed)
    compiles.on = False
    if trace:
        jax.profiler.stop_trace()
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    t_check = time.perf_counter()
    numbers = driver.check(state, win, cell.cfg, cell.mix, data, seed)
    del state, data
    check_s = time.perf_counter() - t_check
    checks = []
    for name, value in numbers:
        limit = cell.cfg["limits"][name]
        checks.append((name, float(value), limit, _limit_ok(value, limit)))
    correct = all(c[3] for c in checks) and win["failed"] == 0

    run = Run(cell.cfg, cell.mix, setup_s, win)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": int(win["attempted"]),
              "failed": int(win["failed"])}
    if trace:
        events = trace_reduce.read_xplane(trace_reduce.find_xplane(
            str(trace_dir)))
        with gzip.open(trace_dir / "events.json.gz", "wt") as f:
            json.dump(events, f)
        red = trace_reduce.reduce(events)
        run.trace = red
        metrics = cell.per_layer
        device.update(busy_s=red.busy_s, window_s=red.window_s)
    else:
        metrics = cell.end_to_end
    values = {}
    for m in metrics:
        v = _metric_reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["metrics"] = values
    result["device"] = device
    if trace:
        result["breakdown"] = trace_reduce.breakdown(run.trace)
    result["compiles_in_window"] = compiles.count
    result["phases_s"] = {"setup": setup_s,
                          "window": win["t_end"] - win["t_start"],
                          "check": check_s}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim, _ in checks}
    return result


def check_lines(result: dict) -> list:
    """Plain lines, one per compared number, for the end of stderr."""
    dev = result["device"]
    tag = f"[{dev['platform']} {dev['kind']} x{dev['count']}]"
    lines = []
    for name, c in result["checks"].items():
        lim = c["limit"]
        op, bound = ("<=", lim["max"]) if "max" in lim else (">=", lim["min"])
        ok = _limit_ok(c["value"], lim)
        lines.append(f"{tag} check {name} = {c['value']!r} (limit {op} "
                     f"{bound}): {'ok' if ok else 'FAILED'}")
    lines.append(f"{tag} check failed_calls = {result['failed']} (limit <= "
                 f"0): {'ok' if result['failed'] == 0 else 'FAILED'}")
    return lines


def use_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
