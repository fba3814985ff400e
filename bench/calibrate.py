#!/usr/bin/env python3
"""Readings for setting a cell's limits, many seeds in one process.

    python3 bench/calibrate.py --workload paper_job --seeds 1,2,3 --seconds 5
    python3 bench/calibrate.py --workload paper_job --seeds 1,2,3 --control bf16
    python3 bench/calibrate.py --workload paper_job --seeds 1,2,3 --fault iters=5
    python3 bench/calibrate.py --workload sift1m_search_batch --seeds 1 \\
        --nprobe-sweep 16,32,64,128

The first form drives whole runs of the cell (set-up, a short window, the
check) and prints each seed's compared numbers as one JSON line. With
``--control bf16`` (or ``high``) the runs put the plain reference at that
precision in the program's place: k-means++ and Lloyd
(``bench/reference/kmeans.py``) for clustering jobs, every row scored for
searches. With ``--fault`` the runs plant that fault of
``bench/faults.py`` under the program. ``--nprobe-sweep`` builds the cell's
index per seed and prints recall@k against the exact top-k at each nprobe,
over ``--queries`` pool queries. A search cell whose traffic fixes its
``data_seed`` builds its index once for all seeds. The benchmark's own
runs never run this.
Needs a TPU, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def nprobe_sweep(cell, seed: int, nprobes, n_queries: int) -> dict:
    import jax
    import numpy as np

    from bench.drivers.search import build_index, program_search
    from bench.reference import checks
    from bench.reference.data import generate

    data = generate(cell.cfg, seed)
    t0 = time.perf_counter()
    search = program_search(cell.cfg, *build_index(cell.cfg, data))
    out = {"seed": seed, "build_s": time.perf_counter() - t0}
    pool = np.asarray(data["queries"])[:n_queries]
    truth, _ = checks.exact_topk(data["points"], np.asarray(data["points"]),
                                 pool, cell.cfg["k"])
    q = jax.device_put(pool)
    for nprobe in nprobes:
        search(q, nprobe)                   # compile this nprobe
        t0 = time.perf_counter()
        ids, _, ok = search(q, nprobe)
        ids = np.asarray(ids)
        out[f"search_s_nprobe_{nprobe}"] = time.perf_counter() - t0
        hits = sum(len(set(f) & set(t)) for f, t in zip(ids.tolist(),
                                                        truth.tolist()))
        out[f"recall_at_nprobe_{nprobe}"] = hits / truth.size
        out[f"pallas_at_nprobe_{nprobe}"] = bool(ok)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", choices=("bf16", "high"), default=None,
                    help="put the control at this precision in the "
                         "program's place")
    ap.add_argument("--fault", default=None,
                    help="plant this fault of bench/faults.py under the "
                         "program")
    ap.add_argument("--nprobe-sweep", default="")
    ap.add_argument("--queries", type=int, default=2000)
    args = ap.parse_args(argv)

    import contextlib

    from bench import faults, harness

    cell = harness.load_cell(args.workload)
    harness.use_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU; nothing was run", file=sys.stderr)
        return 2
    if "data_seed" in cell.mix:     # every seed searches the same index:
        from bench.drivers import search        # build it once
        build, built = search.build_index, {}

        def build_once(cfg, data):
            if data["seed"] not in built:
                built[data["seed"]] = build(cfg, data)
            return built[data["seed"]]
        search.build_index = build_once
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.nprobe_sweep:
            line = nprobe_sweep(cell, seed, [int(p) for p in
                                             args.nprobe_sweep.split(",")],
                                args.queries)
        else:
            plant = (faults.planted(args.fault, search="search" in
                                    cell.mix["driver"])
                     if args.fault else contextlib.nullcontext())
            with plant:
                res = harness.run_cell(cell, seed, args.seconds, False,
                                       t_begin=time.perf_counter(),
                                       control=args.control)
            line = {"seed": seed, "control": args.control,
                    "fault": args.fault,
                    "correct": res["correct"], "metrics": res["metrics"],
                    "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                    "checks": {k: v["value"]
                               for k, v in res["checks"].items()}}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
