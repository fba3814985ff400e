"""``digits_like``: MNIST-shaped pixel rows, made row-sharded over every
device. ``components`` affine slabs of dimension ``latent_dim`` in ``d``
dimensions (the ``lowrank_mixture`` formula: a shared random basis scaled
per slab by ``U(scale_low, scale_high)``, a slab centre drawn from
``N(0, spread^2 I)``, isotropic ``noise``), clipped to [0, 1], so that
about half of a row's coordinates are 0, as on pixel rows.

Each row is drawn from its own key, ``fold_in(key, row)``, so the rows do
not depend on how they are made: each device fills its shard in blocks of
``block_rows`` rows inside one loop, and holds no more than its shard and one
block. The rows come in the chip's default layout for their shape, as
``device_put`` gives any user's array. ``n`` must divide evenly over the
devices."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

AXIS = "data"


def _row(key, params, *, latent_dim, noise):
    centres, basis, scale = params
    kc, kz, ke = jax.random.split(key, 3)
    comp = jax.random.randint(kc, (), 0, centres.shape[0])
    z = jax.random.normal(kz, (latent_dim,), jnp.float32)
    slab = jnp.matmul(z, basis, precision=jax.lax.Precision.HIGHEST)
    eps = jax.random.normal(ke, (centres.shape[1],), jnp.float32)
    return jnp.clip(centres[comp] + scale[comp] * slab
                    + jnp.float32(noise) * eps, 0.0, 1.0)


def _shard(key, params, *, n_local, block_rows, latent_dim, noise):
    """This device's ``n_local`` rows, ``block_rows`` at a time; the last
    block starts early enough to end at the shard's end (its overlap
    rewrites rows with their own values)."""
    d = params[0].shape[1]
    first = jax.lax.axis_index(AXIS) * n_local
    rows = partial(_row, params=params, latent_dim=latent_dim, noise=noise)

    def block(b, out):
        start = jnp.minimum(b * block_rows, n_local - block_rows)
        idx = first + start + jnp.arange(block_rows)
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
        return jax.lax.dynamic_update_slice(out, jax.vmap(rows)(keys),
                                            (start, 0))

    out = jax.lax.pcast(jnp.zeros((n_local, d), jnp.float32), AXIS,
                        to="varying")
    return jax.lax.fori_loop(0, -(-n_local // block_rows), block, out)


def make(key, *, n, d, components, latent_dim, spread, noise, scale_low,
         scale_high, block_rows, mesh=None):
    """The (n, d) float32 rows, sharded by row over ``mesh`` (one axis,
    ``"data"``; by default every device, on an axis of ``Auto`` type, so
    that plain array code runs on the rows without naming a sharding)."""
    if mesh is None:
        mesh = jax.make_mesh((jax.device_count(),), (AXIS,),
                             axis_types=(jax.sharding.AxisType.Auto,))
    n_local = n // mesh.size
    if n_local * mesh.size != n:
        raise ValueError(f"n={n} does not divide over {mesh.size} devices")
    block_rows = min(block_rows, n_local)
    km, kw, ks, kr = jax.random.split(key, 4)
    params = (jnp.float32(spread) * jax.random.normal(km, (components, d)),
              jax.random.normal(kw, (latent_dim, d), jnp.float32)
              / jnp.sqrt(jnp.float32(latent_dim)),
              jax.random.uniform(ks, (components,), jnp.float32, scale_low,
                                 scale_high))
    body = partial(_shard, n_local=n_local, block_rows=block_rows,
                   latent_dim=latent_dim, noise=noise)
    sharded = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                            out_specs=P(AXIS))
    return jax.jit(sharded, out_shardings=NamedSharding(mesh, P(AXIS)))(
        kr, params)


def generate(key, cfg: dict) -> dict:
    p = cfg["data"]
    return {"points": make(
        key, n=cfg["n"], d=cfg["d"], components=p["components"],
        latent_dim=p["latent_dim"], spread=p["spread"], noise=p["noise"],
        scale_low=p["scale_low"], scale_high=p["scale_high"],
        block_rows=p["block_rows"])}
