"""Distributed k-means++ / k-means over a device mesh (shard_map).

This lifts the paper's thread-per-point decomposition to pod scale: points are
sharded over the data axes of the mesh, centroids are replicated (the
constant-memory idea applied at mesh level), and each seeding round is

    local fused D^2 min-update  ->  local partial sum  ->  psum  ->
    distributed Gumbel-max sample  ->  psum-broadcast of the winning point

Per-round collective traffic is O(devices) scalars + O(d) for the winner
broadcast — independent of N, which is what makes this the 1000-node design.

The round logic itself now lives in ``repro.core.engine`` (MeshBackend wraps a
local compute backend with the psum collectives); this module keeps the
historical ``dist_*`` entry points and re-exports the collective helpers that
moved to ``repro.core.collectives``.
"""
from __future__ import annotations

from typing import Sequence, Union

import jax

from repro.core.collectives import (axis_index, axis_size,  # noqa: F401
                                    dist_gumbel_choice, dist_hier_choice,
                                    dist_tiled_choice, ring_psum,
                                    take_global)
from repro.core.engine import (ClusterEngine, KmeansppResult, LloydResult,
                               MeshBackend, make_backend)
from jax.sharding import Mesh

__all__ = ["dist_kmeanspp", "dist_lloyd", "dist_kmeans", "dist_gumbel_choice",
           "dist_tiled_choice", "dist_hier_choice",
           "take_global", "ring_psum", "mesh_engine"]


def mesh_engine(mesh: Mesh, axes: Union[str, Sequence[str]] = "data",
                variant: str = "fused") -> ClusterEngine:
    """ClusterEngine over a MeshBackend; `variant` picks the per-shard compute
    ('fused', 'pallas_constant', 'pallas_fused', ...)."""
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    return ClusterEngine(MeshBackend(mesh=mesh, axes=axes_t,
                                     local=make_backend(variant)))


def dist_kmeanspp(key: jax.Array, points: jax.Array, k: int, *, mesh: Mesh,
                  axes: Union[str, Sequence[str]] = "data",
                  variant: str = "fused") -> KmeansppResult:
    """Distributed k-means++ seeding. `points` sharded on axis 0 over `axes`."""
    return mesh_engine(mesh, axes, variant).seed(key, points, k)


def dist_lloyd(points: jax.Array, init_centroids: jax.Array, *, mesh: Mesh,
               axes: Union[str, Sequence[str]] = "data", max_iters: int = 50,
               tol: float = 1e-6) -> LloydResult:
    return mesh_engine(mesh, axes).fit(points, init_centroids,
                                       max_iters=max_iters, tol=tol)


def dist_kmeans(key: jax.Array, points: jax.Array, k: int, *, mesh: Mesh,
                axes: Union[str, Sequence[str]] = "data", variant: str = "fused",
                max_iters: int = 50) -> LloydResult:
    eng = mesh_engine(mesh, axes, variant)
    seeds = eng.seed(key, points, k)
    return eng.fit(points, seeds.centroids, max_iters=max_iters)
