#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload paper_job --seed 123 --seconds 30 --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. The run makes
its data from ``--seed`` on the device, sets up and warms every shape the
cell uses (``setup_s``), measures for ``--seconds`` seconds, checks the
answers of the window against the plain references in ``bench/reference``
and prints, as the last line of stdout, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``,
each compared number with its limit. The same numbers end stderr.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with 2.
"""
from __future__ import annotations

import time

T_BEGIN = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.entry["chips"]:
        print(f"bench: {args.workload} needs {cell.entry['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              "device(s). Nothing was run.", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_begin=T_BEGIN)
    print("\n".join(harness.check_lines(result)), file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
