"""Data-pipeline example: semantic dedup via distributed-style k-means++
(paper integration #3).

    PYTHONPATH=src python examples/semdedup_pipeline.py

Builds a corpus of document embeddings with planted near-duplicates, runs
SemDeDup (cluster with k-means++ seeding, drop near-duplicates within
clusters), and verifies the planted duplicates are removed while distinct
documents survive.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.data.semdedup import semdedup
from repro.data.synthetic import blobs


def main():
    rng = np.random.default_rng(0)
    base, _ = blobs(2000, 64, 20, seed=0, spread=0.2)
    # plant 300 near-duplicates (tiny perturbations of existing docs)
    dup_src = rng.integers(0, 2000, size=300)
    dups = base[dup_src] + rng.normal(0, 1e-3, size=(300, 64)).astype(np.float32)
    corpus = jnp.asarray(np.concatenate([base, dups]))

    res = semdedup(jax.random.PRNGKey(0), corpus, k=20, threshold=0.999)
    kept = int(res.n_kept)
    dup_kept = int(res.keep_mask[2000:].sum())
    print(f"[semdedup] corpus 2300 docs (300 planted dups) -> kept {kept}")
    print(f"[semdedup] planted duplicates surviving: {dup_kept} / 300")
    assert dup_kept < 30, "dedup failed to catch planted duplicates"
    assert int(res.keep_mask[:2000].sum()) > 1900, "too many originals dropped"
    print("[semdedup] OK")


if __name__ == "__main__":
    use_compile_cache()
    main()
