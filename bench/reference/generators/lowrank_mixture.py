"""``lowrank_mixture``: SIFT-shaped descriptors. ``components`` affine slabs
of dimension ``latent_dim`` in ``d`` dimensions: a shared random basis,
scaled per slab by ``U(scale_low, scale_high)``, offset by a slab centre
drawn from ``N(0, spread^2 I)``, plus isotropic ``noise``. Neighbourhoods
are low-dimensional and continuous, so a query's nearest rows spread over
several inverted lists, as on real descriptors. The first ``n`` rows are
the base, the next ``queries`` rows the held-out query pool."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("rows", "d", "components", "latent_dim",
                                   "spread", "noise", "scale_low",
                                   "scale_high"))
def _lowrank_mixture(key, *, rows, d, components, latent_dim, spread, noise,
                     scale_low, scale_high):
    km, kw, ks, kc, kz, ke = jax.random.split(key, 6)
    centres = jnp.float32(spread) * jax.random.normal(
        km, (components, d), jnp.float32)
    basis = jax.random.normal(kw, (latent_dim, d), jnp.float32) \
        / jnp.sqrt(jnp.float32(latent_dim))
    scale = jax.random.uniform(ks, (components,), jnp.float32, scale_low,
                               scale_high)
    comp = jax.random.randint(kc, (rows,), 0, components)
    z = jax.random.normal(kz, (rows, latent_dim), jnp.float32)
    slab = jnp.matmul(z, basis, precision=jax.lax.Precision.HIGHEST)
    eps = jax.random.normal(ke, (rows, d), jnp.float32)
    return centres[comp] + scale[comp][:, None] * slab \
        + jnp.float32(noise) * eps


def generate(key, cfg: dict) -> dict:
    p = cfg["data"]
    n, nq = cfg["n"], cfg.get("queries", 0)
    rows = _lowrank_mixture(
        key, rows=n + nq, d=cfg["d"], components=p["components"],
        latent_dim=p["latent_dim"], spread=p["spread"], noise=p["noise"],
        scale_low=p["scale_low"], scale_high=p["scale_high"])
    out = {"points": rows[:n]}
    if nq:
        out["queries"] = rows[n:]
    return out
