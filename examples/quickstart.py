"""Quickstart: the paper's algorithm end-to-end in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py

1. Generates the paper's workload (2-D Gaussian blobs).
2. Seeds through the ClusterEngine with the serial reference backend (the
   paper's CPU baseline) and the parallel backends — identical seeds under a
   matched PRNG key (the paper's quality claim).
3. Runs Lloyd clustering, a streaming mini-batch fit, and a batched
   multi-problem fit, reporting inertia + timing for each.
"""
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.core import quality
from repro.core.engine import ClusterEngine
from repro.data.synthetic import blobs

N, D, K = 100_000, 2, 50     # paper sweeps N=1-10M, k=10-100 (GPU-sized)


def main():
    print(f"k-means++ quickstart: N={N}, d={D}, k={K}")
    np_pts = blobs(N, D, K, seed=0)[0]
    pts = jnp.asarray(np_pts)
    key = jax.random.PRNGKey(0)

    results = {}
    for backend in ("serial", "global", "fused"):
        eng = ClusterEngine(backend)
        t0 = time.perf_counter()
        res = eng.seed(key, pts, K)
        jax.block_until_ready(res.centroids)
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = eng.seed(key, pts, K)
        jax.block_until_ready(res.centroids)
        t = time.perf_counter() - t0
        phi = float(quality.inertia(pts, res.centroids))
        results[backend] = res
        print(f"  seeding [{backend:7s}]  {t*1e3:8.1f} ms  "
              f"(first call incl. compile {t_compile*1e3:7.0f} ms)  "
              f"phi={phi:.1f}")

    same = (results["serial"].indices == results["fused"].indices).all()
    print(f"  serial == parallel seeds: {bool(same)}  (paper's quality claim)")

    eng = ClusterEngine("fused")
    t0 = time.perf_counter()
    out = eng.kmeans(key, pts, K, max_iters=50)
    jax.block_until_ready(out.centroids)
    print(f"  + Lloyd clustering: {time.perf_counter()-t0:.2f}s, "
          f"{int(out.n_iters)} iters, final phi={float(out.inertia):.1f}")

    # streaming mini-batch: the device only ever holds one 4096-point batch
    batch = 4096

    def read_fn(step):
        lo = (step * batch) % N
        return np_pts[lo:lo + batch]

    t0 = time.perf_counter()
    mb = eng.fit_minibatch(results["fused"].centroids, read_fn, n_batches=24)
    jax.block_until_ready(mb.centroids)
    phi_mb = float(quality.inertia(pts, mb.centroids))
    print(f"  + mini-batch Lloyd: {time.perf_counter()-t0:.2f}s over "
          f"{int(mb.n_iters)} x {batch}-point batches, phi={phi_mb:.1f}")

    # batched multi-problem: 4 tenants clustered in one compiled call
    B, n_small = 4, 8192
    bpts = jnp.stack([jnp.asarray(blobs(n_small, D, 8, seed=s)[0])
                      for s in range(B)])
    t0 = time.perf_counter()
    bout = eng.kmeans_batched(jax.random.PRNGKey(1), bpts, 8, max_iters=20)
    jax.block_until_ready(bout.centroids)
    print(f"  + batched multi-problem: {B} problems of n={n_small} in "
          f"{time.perf_counter()-t0:.2f}s, phi={[round(float(p), 2) for p in bout.inertia]}")


if __name__ == "__main__":
    use_compile_cache()
    main()
