"""JAX's persistent compilation cache, in one place for every entry point.

``chip_smoke.py``, ``benchmarks/run.py`` and the examples call
:func:`use_compile_cache` before they compile anything. A set
``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it itself, and nothing else is
set). Otherwise the cache lives at a fixed directory inside the checkout,
``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is part of what a
later run must find again, so it never comes from a temp name, a pid or the
clock.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
