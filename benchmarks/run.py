"""Benchmark harness — one module per paper table/figure + quality + roofline.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig1 fig3  # subset
"""
from __future__ import annotations

import sys
import time

from benchmarks import (autotune, dist_scaling, fig1_global, fig2_constant,
                        fig3_texture, ivf_search, minibatch, quality_parity,
                        roofline, round_traffic, seed_sampling)
from repro.compile_cache import use_compile_cache

MODULES = {
    "fig1": fig1_global,
    "fig2": fig2_constant,
    "fig3": fig3_texture,
    "quality": quality_parity,
    "dist": dist_scaling,
    "minibatch": minibatch,
    "roofline": roofline,
    "seed": seed_sampling,
    "round": round_traffic,
    "tune": autotune,
    "ivf": ivf_search,
}


def main() -> None:
    which = sys.argv[1:] or list(MODULES)
    for name in which:
        mod = MODULES[name]
        print(f"\n===== {name} ({mod.__name__}) =====")
        t0 = time.time()
        mod.main()
        print(f"===== {name} done in {time.time() - t0:.1f}s =====")


if __name__ == "__main__":
    use_compile_cache()
    main()
