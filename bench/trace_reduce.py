"""From a profiler trace to what the per-layer metrics read.

Two stages, so that the second can be checked on a small recorded trace:

1. :func:`read_xplane` reads the ``.xplane.pb`` the JAX profiler wrote and
   keeps, on the profiler's one clock (ns):

   * per device plane (``/device:...``), the compiled programs (``XLA
     Modules`` line: ``[name, start, duration]``, the name without its
     ``(id)``) and the operations (``XLA Ops`` line: ``[text, start,
     duration, kernel]``, where ``text`` is the head of the HLO text,
     ``%<op> = <type>...``, and ``kernel`` is 1 for a compiled Pallas kernel,
     a ``tpu_custom_call``);
   * the benchmark's own host spans (``jax.profiler.TraceAnnotation`` in
     the harness and drivers, named in :data:`SPAN_NAMES`).

2. :func:`reduce` turns those events into a :class:`Reduced`. Operations
   nest on the ``XLA Ops`` line (a ``while`` holds its body's operations),
   so each operation is given its self time: its time in the traced window
   (the ``window`` span) less that of the operations inside it. Self times
   add up to the device's busy time, the union of operation intervals. Each
   operation is filed under the program that ran it. The idle gaps are named
   by the innermost host span around them.

    python -m bench.trace_reduce <trace dir>   # every plane and line, by hand
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

SPAN_NAMES = ("window", "warmup", "build", "job", "put", "search", "to_host")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
TEXT_CHARS = 96


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str) -> dict:
    """``{"devices": {plane: {"modules": [...], "ops": [...]}}, "spans":
    [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    mods.extend([e.name.split("(")[0], e.start_ns,
                                 e.duration_ns] for e in line.events)
                elif line.name == OPS_LINE:
                    ops.extend([e.name[:TEXT_CHARS], e.start_ns,
                                e.duration_ns, int(KERNEL_MARK in e.name)]
                               for e in line.events)
            if ops:
                devices[plane.name] = {"modules": mods, "ops": ops}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events if e.name in SPAN_NAMES)
    return {"devices": devices, "spans": spans}


def op_name(text: str) -> str:
    """``%pad.224 = f32[...] ...`` -> ``pad.224``."""
    return text.split(" = ")[0].lstrip("%")


@dataclass
class Reduced:
    window_s: float
    busy_s: float                     # union of operations, mean per device
    ops: dict = field(default_factory=dict)   # (module, text, kernel) ->
    #                                           self seconds, all devices
    gaps: list = field(default_factory=list)  # [host span, seconds],
    #                                           longest first

    def time(self, *, kernel=None, module=None, prefixes=None) -> float:
        """Self seconds of the operations that match every filter given:
        ``kernel`` (a Pallas kernel or not), ``module`` (the program's name
        starts with it), ``prefixes`` (the operation's name starts with one
        of them)."""
        total = 0.0
        for (mod, text, kern), s in self.ops.items():
            if kernel is not None and bool(kern) != kernel:
                continue
            if module is not None and not mod.startswith(module):
                continue
            if prefixes is not None and not op_name(text).startswith(
                    tuple(prefixes)):
                continue
            total += s
        return total

    @property
    def total_op_s(self) -> float:
        return sum(self.ops.values())

    @property
    def idle_pct(self) -> float:
        """Share of the window, in %, in which no operation ran on the
        device: ``100 * (1 - busy / window)``."""
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def top_ops(self, n: int = 10) -> list:
        """``[["<program>: <HLO head>", seconds], ...]``, most first."""
        return sorted(([f"{m}: {t}", s] for (m, t, _), s in self.ops.items()),
                      key=lambda kv: -kv[1])[:n]


def _self_times(ops, w0, w1) -> dict:
    """Self ns of each operation clipped to [w0, w1]: its clipped time less
    that of the operations nested in it. ``{index: ns}``."""
    clipped = []
    for i, (_, s, d, _) in enumerate(ops):
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            clipped.append((a, a - b, b, i))    # outermost first at a tie
    clipped.sort()
    own = {}
    stack = []          # (end, index) of the open ancestors
    for a, _, b, i in clipped:
        while stack and stack[-1][0] <= a:
            stack.pop()
        own[i] = b - a
        if stack:
            end, parent = stack[-1]
            own[parent] -= min(b, end) - a
        stack.append((b, i))
    return own


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(events: dict) -> Reduced:
    """The traced window is the first ``window`` span; device operations
    are clipped to it."""
    wins = [s for s in events["spans"] if s[0] == "window"]
    if not wins:
        raise ValueError("the trace holds no 'window' span")
    w0 = wins[0][1]
    w1 = w0 + wins[0][2]
    spans = [s for s in events["spans"] if s[0] != "window"]
    ops_s: dict = {}
    busy_total = 0.0
    gaps = []
    devices = events["devices"]
    for dev in devices.values():
        ops = dev["ops"]
        mods = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for i, own in _self_times(ops, w0, w1).items():
            text, s = ops[i][0], ops[i][1]
            j = bisect.bisect_right(starts, s) - 1
            mod = mods[j][0] if j >= 0 and s < mods[j][1] + mods[j][2] \
                else "?"
            key = (mod, text, ops[i][3])
            ops_s[key] = ops_s.get(key, 0.0) + own * 1e-9
        merged = _union([max(s, w0), min(s + d, w1)] for _, s, d, _ in ops
                        if min(s + d, w1) > max(s, w0))
        busy_total += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append([_span_at(spans, (a + b) / 2), (b - a) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy_total * 1e-9 / max(len(devices), 1),
                   ops=ops_s, gaps=gaps)


def _span_at(spans, t) -> str:
    """The innermost (shortest) host span that covers time ``t``."""
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "none"


def breakdown(red: Reduced, n: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations with the most
    self time, and the longest idle gaps, each named by the host span
    around it."""
    return {"device_ops": red.top_ops(n), "idle_gaps": red.gaps[:n]}


def summary(path: str, top: int = 25) -> dict:
    """Every plane and line of a trace with its event count, time span and
    the names that took most time: for looking at a trace by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            tot: dict = {}
            for e in evs:
                tot[e.name] = tot.get(e.name, 0) + e.duration_ns
            lines.append({
                "line": line.name, "events": len(evs),
                "start_ns": min(e.start_ns for e in evs),
                "end_ns": max(e.start_ns + e.duration_ns for e in evs),
                "top_ns": sorted(tot.items(), key=lambda kv: -kv[1])[:top]})
        out.append({"plane": plane.name, "lines": lines})
    return {"path": path, "planes": out}


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(summary(find_xplane(sys.argv[1])), indent=1))
