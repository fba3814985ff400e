"""Production meshes. A FUNCTION, not a module constant — importing this
module never touches jax device state (the dry-run sets XLA_FLAGS first).

The LM train/serve steps place arrays with sharding constraints and rely on
XLA's propagation for the rest, so their meshes have Auto axes
(``jax.make_mesh`` defaults to Explicit)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips (v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips across DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_data: int = 4, n_model: int = 2) -> Mesh:
    """Small mesh for CI tests (requires xla_force_host_platform_device_count
    >= n_data * n_model in the test process)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def n_batch_shards(mesh: Mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n
