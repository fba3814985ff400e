"""``blobs``: ``components`` Gaussian blobs with centres uniform in
``[low, high]^d`` and per-coordinate standard deviation ``spread``, rows in
random order (the paper's 2-D synthetic data)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("n", "d", "components", "spread", "low",
                                   "high"))
def _blobs(key, *, n, d, components, spread, low, high):
    kc, kl, kn = jax.random.split(key, 3)
    centres = jax.random.uniform(kc, (components, d), jnp.float32, low, high)
    labels = jax.random.randint(kl, (n,), 0, components)
    noise = jax.random.normal(kn, (n, d), jnp.float32)
    return centres[labels] + jnp.float32(spread) * noise


def generate(key, cfg: dict) -> dict:
    p = cfg["data"]
    return {"points": _blobs(key, n=cfg["n"], d=cfg["d"],
                             components=p["components"], spread=p["spread"],
                             low=p["low"], high=p["high"])}
