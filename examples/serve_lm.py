"""Serving example: batched requests through the Engine + KV-cache PQ.

    PYTHONPATH=src python examples/serve_lm.py

1. Serves a smoke LM with continuous batching (more requests than slots).
2. Builds a k-means++ product-quantization codebook over the KV cache of a
   long prompt (paper integration #1) and reports compression/error.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs.registry import get_config
from repro.models.registry import get_model
from repro.serve import Engine, ServeConfig, kvquant


def main():
    cfg = get_config("deepseek-7b", smoke=True)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    # --- batched generation ------------------------------------------------
    eng = Engine(cfg, params, ServeConfig(max_batch=4, max_len=96,
                                          max_new_tokens=16))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=rng.integers(8, 48))
               .astype(np.int32) for _ in range(10)]
    t0 = time.perf_counter()
    outs = eng.generate(prompts)
    dt = time.perf_counter() - t0
    n_tok = sum(map(len, outs))
    print(f"[serve_lm] {len(prompts)} requests -> {n_tok} tokens "
          f"in {dt:.2f}s ({n_tok/dt:.1f} tok/s incl. compile)")

    # --- KV-cache PQ (long-context path) ------------------------------------
    long_prompt = rng.integers(0, cfg.vocab, size=512).astype(np.int32)
    _, cache = model.prefill(params, {"tokens": jnp.asarray(long_prompt)[None]})
    k_cache = cache["k"]                       # (L, 1, S, KH, hd)
    flat = k_cache.reshape(-1, k_cache.shape[-1])
    pq = kvquant.compress_kv(jax.random.PRNGKey(1), flat, n_sub=4)
    err = float(kvquant.reconstruction_error(flat, pq))
    ratio = kvquant.compression_ratio(flat, pq)
    print(f"[serve_lm] KV PQ: {ratio:.1f}x compression, "
          f"relative reconstruction MSE {err:.4f}")
    print("[serve_lm] OK")


if __name__ == "__main__":
    use_compile_cache()
    main()
