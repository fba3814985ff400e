"""Faults planted under the timed path: for the tests of the comparison that
decides ``correct`` (``bench/tests/test_faults.py``) and for the fault
readings of ``bench/calibrate.py``. The benchmark's own runs never plant
one.

    with faults.planted("iters=5"):
        ...   # every ClusterEngine.kmeans call runs 5 Lloyd iterations

Clustering jobs (``ClusterEngine.kmeans``, also under ``IvfIndex.build``):

* ``unchanged`` — the Lloyd step returns its centroids unchanged;
* ``half`` — half of the rows left out, the centroids the means of the rest;
* ``altered`` — one centroid of the answer altered;
* ``iters=<m>`` — the Lloyd loop cut to ``m`` iterations;
* ``uniform`` — uniform seeding (``init="random"``) in place of k-means++.

Searches (``IvfIndex.search``):

* ``half`` — half of a batch's queries left out, their answers copied;
* ``altered`` — one returned id of each query altered;
* ``nprobe=<m>`` — ``m`` lists probed in place of the configured number.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

JOB = ("unchanged", "half", "altered", "iters", "uniform")
SEARCH = ("half", "altered", "nprobe")


def _job_fault(patch, name: str, arg: str):
    from repro.core import engine

    if name == "unchanged":
        patch(engine, "centroid_means", lambda sums, counts, prev: prev)
        return
    kmeans = engine.ClusterEngine.kmeans

    def broken(self, key, points, k, **kw):
        if name == "iters":
            kw["max_iters"] = int(arg)
        if name == "uniform":
            kw["init"] = "random"
        res = kmeans(self, key, points, k, **kw)
        if name == "half":
            half = points.shape[0] // 2
            a = res.assignment[:half]
            sums = jax.ops.segment_sum(points[:half], a, num_segments=k)
            cnt = jax.ops.segment_sum(jnp.ones(half), a, num_segments=k)
            c = jnp.where(cnt[:, None] > 0,
                          sums / jnp.maximum(cnt, 1)[:, None], res.centroids)
            res = res._replace(centroids=c.astype(res.centroids.dtype))
        if name == "altered":
            res = res._replace(centroids=res.centroids.at[0].set(points[0]))
        return res
    patch(engine.ClusterEngine, "kmeans", broken)


def _search_fault(patch, name: str, arg: str):
    from repro.serve import ivf

    search = ivf.IvfIndex.search

    def broken(self, queries, k, nprobe=None, **kw):
        if name == "nprobe":
            return search(self, queries, k, int(arg), **kw)
        if name == "half":
            half = queries.shape[0] // 2
            res = search(self, queries[:half], k, nprobe, **kw)
            pad = queries.shape[0] - half
            return res._replace(
                indices=jnp.concatenate([res.indices, res.indices[:pad]]),
                dists=jnp.concatenate([res.dists, res.dists[:pad]]))
        res = search(self, queries, k, nprobe, **kw)
        ids = res.indices.at[:, 0].set((res.indices[:, 0] + 1) % self.n)
        return res._replace(indices=ids)
    patch(ivf.IvfIndex, "search", broken)


@contextlib.contextmanager
def planted(spec: str, *, search: bool = False):
    """Plant the fault ``spec`` (``name`` or ``name=arg``) under the
    program for the duration of the block; ``search`` picks the search
    faults."""
    name, _, arg = spec.partition("=")
    if name not in (SEARCH if search else JOB):
        raise ValueError(f"unknown fault {spec!r}")
    saved = []

    def patch(obj, attr, value):
        saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, value)

    (_search_fault if search else _job_fault)(patch, name, arg)
    jax.clear_caches()
    try:
        yield
    finally:
        for obj, attr, value in reversed(saved):
            setattr(obj, attr, value)
        jax.clear_caches()
