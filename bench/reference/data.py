"""Every cell's inputs, made on the device from ``--seed``.

A configuration file names its generator (``data.generator``), a module of
:mod:`bench.reference.generators`, and gives its parameters. The same seed
gives the same arrays.
"""
from __future__ import annotations

import importlib

import jax


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any seed in [0, 2**64)."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"--seed must lie in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def generate(cfg: dict, seed: int) -> dict:
    """The cell's data arrays on the default device in the configuration's
    ``dtype``, ready, and under ``"seed"`` the seed they were made from."""
    gen = importlib.import_module(
        f"bench.reference.generators.{cfg['data']['generator']}")
    out = gen.generate(jax.random.fold_in(base_key(seed), 0), cfg)
    out = {name: a.astype(cfg["dtype"]) for name, a in out.items()}
    return dict(jax.block_until_ready(out), seed=seed)
