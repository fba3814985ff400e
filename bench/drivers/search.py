"""Closed-loop search: one client, one request at a time.

Traffic keys: ``batch`` — queries per request, taken in turn from the
configuration's held-out query pool, in an order drawn from the seed and
cycled; ``sample_queries`` — how many answered queries are checked against
the reference, drawn from the seed; ``data_seed`` (optional, read by the
harness) — make the data from this seed instead of ``--seed``, so that every
run searches the same index and pool and ``--seed`` changes only the order
of the queries and the checked sample.

The index is built once in set-up (``IvfIndex.build`` with the Pallas
engine and the key ``fold_in(data seed, 1)``), and one request is made
before the window to warm its shapes. A request is timed from its query
rows on the host to its ids and distances on the host.

The check holds the sampled answers to the exact float64 top-k over the
rows of each query's ``nprobe`` nearest lists of that index, rank by rank
up to ties (``probe_rank_errors``: routing, gate, scan, merge and id
mapping at the configured ``nprobe``), to the exact float64 top-k over all
rows (``recall_at_<k>``), and their distances to those of the returned rows
(``dist_err_eps``); the index's own layout is checked first
(``layout_errors``), since the probe truth takes its lists.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench.reference import checks
from bench.reference.data import base_key


def build_index(cfg: dict, data: dict):
    """The index every search of the cell runs on: ``IvfIndex.build`` with
    the Pallas engine and the key ``fold_in(data seed, 1)``; and whether
    the build ran on the Pallas kernels without a fallback."""
    from repro.core import ClusterEngine
    from repro.serve import IvfIndex

    eng = ClusterEngine("pallas", precision=cfg["precision"])
    with jax.profiler.TraceAnnotation("build"):
        idx = jax.block_until_ready(IvfIndex.build(
            data["points"], cfg["nlist"], engine=eng,
            key=jax.random.fold_in(base_key(data["seed"]), 1),
            max_iters=cfg["max_iters"]))
    return idx, not eng.fallback_events


def program_search(cfg: dict, idx, ok: bool):
    """``IvfIndex.search`` on ``idx`` at the configured ``nprobe``."""
    def search(q, nprobe=cfg["nprobe"]):
        res = idx.search(q, cfg["k"], nprobe=nprobe)
        return res.indices, res.dists, ok and res.backend == "pallas"
    return search


def control_search(cfg: dict, data: dict, precision: str):
    """The plain search in the program's place, every row scored with its
    dot products at ``precision`` (``"bf16"`` or ``"high"``): the
    control."""
    points = data["points"]

    def search(q):
        ids, d2 = checks.nearest(q, points, cfg["k"], precision=precision)
        return ids, d2, True
    return search


def setup(cfg: dict, mix: dict, data: dict, seed: int, *,
          control: str | None = None) -> dict:
    idx, ok = build_index(cfg, data)
    search = (control_search(cfg, data, control) if control
              else program_search(cfg, idx, ok))
    pool = np.asarray(data["queries"])
    order = np.random.default_rng([seed, 3]).permutation(pool.shape[0])
    state = {"search": search, "index": idx, "pool": pool, "order": order,
             "batch": int(mix["batch"])}
    with jax.profiler.TraceAnnotation("warmup"):
        _request(state, state["batch"])
    return state


def _request(state: dict, offset: int):
    b, order = state["batch"], state["order"]
    rows = order[(offset + np.arange(b)) % order.shape[0]]
    with jax.profiler.TraceAnnotation("put"):
        q = jax.device_put(state["pool"][rows])
    with jax.profiler.TraceAnnotation("search"):
        ids, dists, ok = state["search"](q)
    with jax.profiler.TraceAnnotation("to_host"):
        ids, dists = jax.device_get((ids, dists))
    return rows, ids, dists, ok


def window(state: dict, seconds: float, mix: dict, seed: int) -> dict:
    b = state["batch"]
    lat, rows, ids, dists = [], [], [], []
    failed = 0
    t_start = time.perf_counter()
    r = 0
    while True:
        t0 = time.perf_counter()
        q_rows, q_ids, q_d, ok = _request(state, r * b)
        t1 = time.perf_counter()
        lat.append(t1 - t0)
        rows.append(q_rows)
        ids.append(q_ids)
        dists.append(q_d)
        failed += not ok
        r += 1
        if t1 - t_start >= seconds:
            break
    return {"t_start": t_start, "t_end": t1, "latencies": lat,
            "units": r * b, "requests": r, "attempted": r, "failed": failed,
            "rows": np.concatenate(rows), "ids": np.concatenate(ids),
            "dists": np.concatenate(dists)}


def check(state: dict, win: dict, cfg: dict, mix: dict, data: dict,
          seed: int) -> list:
    """The index's layout, then recall and distance error over
    ``sample_queries`` answered queries drawn from the seed:
    ``[(name, value), ...]``."""
    points = data["points"]
    idx = state["index"]
    layout = checks.layout_errors(points, idx.points, idx.perm, idx.labels,
                                  idx.starts, idx.counts)
    row_list = np.full(points.shape[0], -1, np.int64)
    row_list[np.asarray(idx.perm)] = np.asarray(idx.labels)
    centroids = np.asarray(idx.centroids)
    del idx
    state.clear()   # the index goes before the reference
    answered = win["rows"].shape[0]
    take = min(int(mix["sample_queries"]), answered)
    pick = np.sort(np.random.default_rng([seed, 5])
                   .choice(answered, take, replace=False))
    host = np.asarray(points)
    queries = np.asarray(data["queries"])[win["rows"][pick]]
    ids = win["ids"][pick]
    nums = checks.search_numbers(points, host, queries, ids,
                                 win["dists"][pick], cfg["k"])
    k = cfg["k"]
    return [("dist_err_eps", nums["dist_err_eps"]),
            ("layout_errors", layout),
            ("probe_rank_errors", checks.probe_rank_errors(
                points, host, queries, ids, centroids, row_list,
                cfg["nprobe"], k)),
            (f"recall_at_{k}", nums["recall_at_k"])]
