"""K-means jobs back to back on points sharded by row over a mesh, in one
closed loop: ``ClusterEngine("mesh", local="pallas").kmeans`` over the mesh
the points live on, one compiled program a job.

Traffic keys as for :mod:`bench.drivers.jobs` (``entry`` ``kmeans`` only,
``sample_jobs``); the window is ``jobs.window``, unchanged: job ``j`` uses
the key ``fold_in(seed, 2 + j)``, the warm-up job ``fold_in(seed, 1)``.
The check holds each sampled answer to the plain reference worked shard by
shard (``bench/reference/sharded.py``): ``centroid_gap_eps``,
``assign_gap`` and ``lloyd_stop_gap`` as ``jobs.check`` defines them. The
control puts the plain sharded k-means of the same module in the
program's place.
"""
from __future__ import annotations

import jax

from bench.drivers import jobs
from bench.reference import sharded
from bench.reference.data import base_key

window = jobs.window


class _Reference(jobs._Reference):
    """The plain sharded k-means in the program's place, at a lower
    precision: the control."""

    def __init__(self, cfg, points, precision):
        def job(key):
            c, a, iters = sharded.kmeans(key, points, k=cfg["k"],
                                         max_iters=cfg["max_iters"],
                                         tol=cfg["tol"], precision=precision)
            return jax.block_until_ready({"centroids": c, "assignment": a,
                                          "n_iters": iters})
        self.job = job


def setup(cfg: dict, mix: dict, data: dict, seed: int, *,
          control: str | None = None) -> dict:
    """The mesh engine over the points' own mesh, the job and one warm-up
    job. ``control`` (``"bf16"`` or ``"high"``) puts the plain sharded
    reference at that precision in the program's place."""
    if mix["entry"] != "kmeans":
        raise ValueError(f"mesh_jobs runs kmeans jobs, not {mix['entry']!r}")
    points = data["points"]
    if control:
        eng = _Reference(cfg, points, control)
        job = eng.job
    else:
        from repro.core import ClusterEngine

        eng = ClusterEngine("mesh", mesh=points.sharding.mesh,
                            local="pallas", precision=cfg["precision"])
        job = jobs.ENTRIES["kmeans"](cfg, eng, points)
    with jax.profiler.TraceAnnotation("warmup"):
        job(jax.random.fold_in(base_key(seed), 1))
    return {"engine": eng, "job": job, "key": base_key(seed),
            "fallbacks": len(eng.fallback_events)}


def check(state: dict, win: dict, cfg: dict, mix: dict, data: dict,
          seed: int) -> list:
    """The compared numbers of each sampled job, worst over the sample:
    ``[(name, value), ...]``."""
    state.clear()   # the engine and its buffers go before the reference
    worst: dict = {}
    while win["sample"]:
        _, out = win["sample"].pop(0)
        part = sharded.partition_numbers(data["points"], out["centroids"],
                                         out["assignment"])
        stopped_early = int(out["n_iters"]) < cfg["max_iters"]
        del out
        nums = {"centroid_gap_eps": part["centroid_gap_eps"],
                "assign_gap": part["assign_gap"],
                "lloyd_stop_gap": part["assign_gap"] if stopped_early
                else 0.0}
        for name, v in nums.items():
            worst[name] = max(worst.get(name, v), v)
    return sorted(worst.items())
