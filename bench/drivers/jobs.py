"""Clustering jobs back to back, in one closed loop.

Traffic keys: ``entry`` — ``kmeans`` (``ClusterEngine.kmeans`` on the
configuration's ``k``) or ``ivf_build`` (``IvfIndex.build`` into the
configuration's ``nlist`` lists); ``sample_jobs`` — how many of the window's
jobs are checked against the reference, drawn from the seed.

Job ``j`` of the window uses the key ``fold_in(seed, 2 + j)``; the warm-up
job uses ``fold_in(seed, 1)``. A job ends when every array of its answer is
ready. The window runs whole jobs until ``seconds`` have passed, so it ends
when the last job ends.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench.reference import checks
from bench.reference import kmeans as ref_kmeans
from bench.reference.data import base_key


def _kmeans_job(cfg, eng, points):
    def job(key):
        res = eng.kmeans(key, points, cfg["k"], max_iters=cfg["max_iters"],
                         tol=cfg["tol"])
        return jax.block_until_ready({"centroids": res.centroids,
                                      "assignment": res.assignment,
                                      "n_iters": res.n_iters})
    return job


def _build_job(cfg, eng, points):
    from repro.serve import IvfIndex

    def job(key):
        idx = IvfIndex.build(points, cfg["nlist"], engine=eng, key=key,
                             max_iters=cfg["max_iters"])
        return jax.block_until_ready(idx)
    return job


ENTRIES = {"kmeans": _kmeans_job, "ivf_build": _build_job}


class _Reference:
    """The plain k-means (and IVF layout) in the program's place, at a
    lower precision: the control. Quacks like the engine for ``window``."""
    fallback_events: list = []

    def __init__(self, cfg, entry, points, precision):
        k = cfg["nlist"] if entry == "ivf_build" else cfg["k"]

        def job(key):
            c, a, iters = ref_kmeans.kmeans(key, points, k=k,
                                            max_iters=cfg["max_iters"],
                                            tol=cfg["tol"],
                                            precision=precision)
            if entry == "ivf_build":
                return jax.block_until_ready(
                    ref_kmeans.ivf_layout(points, c, a))
            return jax.block_until_ready({"centroids": c, "assignment": a,
                                          "n_iters": iters})
        self.job = job


def setup(cfg: dict, mix: dict, data: dict, seed: int, *,
          control: str | None = None) -> dict:
    """The engine, the job and one warm-up job. ``control`` (``"bf16"`` or
    ``"high"``) puts the plain reference at that precision in the
    program's place."""
    if control:
        eng = _Reference(cfg, mix["entry"], data["points"], control)
        job = eng.job
    else:
        from repro.core import ClusterEngine

        eng = ClusterEngine("pallas", precision=cfg["precision"])
        job = ENTRIES[mix["entry"]](cfg, eng, data["points"])
    with jax.profiler.TraceAnnotation("warmup"):
        job(jax.random.fold_in(base_key(seed), 1))
    return {"engine": eng, "job": job, "key": base_key(seed),
            "fallbacks": len(eng.fallback_events)}


def _served_by_pallas(eng) -> bool:
    if isinstance(eng, _Reference):
        return True
    be = eng.last_backend
    served = be.local.name if be.distributed else be.name
    return served == "pallas"


def window(state: dict, seconds: float, mix: dict, seed: int) -> dict:
    """Jobs back to back for ``seconds``; keeps a reservoir sample of
    ``sample_jobs`` answers, drawn from the seed."""
    eng, job = state["engine"], state["job"]
    rng = np.random.default_rng([seed, 7])
    keep = int(mix["sample_jobs"])
    sample: list = []
    times, failed = [], 0
    t_start = time.perf_counter()
    j = 0
    while True:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("job"):
            out = job(jax.random.fold_in(state["key"], 2 + j))
        t1 = time.perf_counter()
        times.append((t0, t1))
        if len(eng.fallback_events) > state["fallbacks"] \
                or not _served_by_pallas(eng):
            failed += 1
            state["fallbacks"] = len(eng.fallback_events)
        if len(sample) < keep:
            sample.append((j, out))
        else:
            slot = rng.integers(0, j + 1)
            if slot < keep:
                sample[slot] = (j, out)
        del out
        j += 1
        if t1 - t_start >= seconds:
            break
    return {"t_start": t_start, "t_end": times[-1][1], "times": times,
            "units": j, "attempted": j, "failed": failed,
            "sample": sorted(sample, key=lambda s: s[0])}


def check(state: dict, win: dict, cfg: dict, mix: dict, data: dict,
          seed: int) -> list:
    """The compared numbers of each sampled job, worst over the sample:
    ``[(name, value), ...]``. For ``kmeans`` jobs, ``lloyd_stop_gap`` is
    ``assign_gap`` where the job stopped before ``max_iters``, and 0 where
    it ran every iteration: a job that the stopping rule ended (a relative
    drop of the potential of at most ``tol``) is that close to a Lloyd
    fixed point, and one whose loop was cut short is not."""
    state.clear()   # the engine and its buffers go before the reference
    entry = mix["entry"]
    points = data["points"]
    host = np.asarray(points)
    worst: dict = {}
    while win["sample"]:
        _, out = win["sample"].pop(0)
        nums = {}
        if entry == "ivf_build":
            nums["layout_errors"] = checks.layout_errors(
                points, out.points, out.perm, out.labels, out.starts,
                out.counts)
            perm = np.asarray(out.perm)
            a = np.zeros(points.shape[0], np.int64)
            if nums["layout_errors"] == 0:
                a[perm] = np.asarray(out.labels)
            c = out.centroids
        else:
            c, a = out["centroids"], out["assignment"]
            stopped_early = int(out["n_iters"]) < cfg["max_iters"]
        part = checks.partition_numbers(points, host, c, a)
        del out, c, a
        nums["centroid_gap_eps"] = part["centroid_gap_eps"]
        nums["assign_gap"] = part["assign_gap"]
        if entry == "kmeans":
            nums["lloyd_stop_gap"] = part["assign_gap"] if stopped_early \
                else 0.0
        for name, v in nums.items():
            worst[name] = max(worst.get(name, v), v)
    return sorted(worst.items())
