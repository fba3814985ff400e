"""End-to-end driver: train a ~100M-param LM for a few hundred steps on CPU.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

Uses a reduced gemma2-family config (~100M params), the synthetic token
stream, AdamW with warmup+cosine, periodic async checkpoints, preemption
handling, and the straggler monitor — the production loop end to end.
Loss must fall from ~uniform (log V ~ 6.2) to well below it.
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.compile_cache import use_compile_cache
from repro.configs.common import ArchConfig
from repro.data.pipeline import DataPipeline
from repro.data.synthetic import TokenStream
from repro.launch.step import init_train_state, make_train_step
from repro.optim import AdamWConfig
from repro.train.loop import LoopConfig, train

# ~100M params: 8 layers x d512 (vocab 8192 dominates: 2*8192*512 = 8.4M,
# per-layer ~ 3.4M; total ~ 96M fp32)
CFG = ArchConfig(
    name="train-demo-100m", family="dense",
    n_layers=8, d_model=768, n_heads=12, n_kv_heads=4, d_ff=2304,
    vocab=8192, remat=False,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(
        jax.eval_shape(lambda: init_train_state(
            CFG, jax.random.PRNGKey(0))["params"])))
    print(f"[train_lm] {CFG.name}: {n_params/1e6:.0f}M params, "
          f"{args.steps} steps, batch {args.batch} x seq {args.seq}")

    opt = AdamWConfig(lr=6e-4, warmup_steps=args.steps // 20 + 1,
                      decay_steps=args.steps)
    state = init_train_state(CFG, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(CFG, opt), donate_argnums=(0,))
    stream = TokenStream(CFG.vocab, seed=0)
    pipe = DataPipeline(lambda s: stream.read(s, args.batch, args.seq),
                        prefetch=2)
    ckpt = CheckpointManager(args.ckpt, keep=2)
    state, summary = train(state, step_fn, pipe,
                           LoopConfig(total_steps=args.steps, save_every=100,
                                      log_every=20),
                           ckpt=ckpt)
    losses = summary["losses"]
    k = max(len(losses) // 10, 1)
    print(f"[train_lm] loss: first-{k} mean {np.mean(losses[:k]):.3f} -> "
          f"last-{k} mean {np.mean(losses[-k:]):.3f} "
          f"(uniform would be {np.log(CFG.vocab):.3f})")
    assert np.mean(losses[-k:]) < np.mean(losses[:k]), "loss did not fall!"
    print("[train_lm] OK")


if __name__ == "__main__":
    use_compile_cache()
    main()
