"""Training driver.

  PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --resume auto

Runs the fault-tolerant loop (repro.train.loop) on the synthetic token
stream; --smoke selects the reduced config (CPU-runnable), full configs are
for real hardware. Optional --mesh runs data/model-parallel on the local
devices (requires xla_force_host_platform_device_count or real chips).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import get_config
from repro.data.pipeline import DataPipeline
from repro.data.synthetic import TokenStream
from repro.launch.mesh import make_test_mesh
from repro.launch.step import (init_train_state, make_train_step,
                               train_state_shardings)
from repro.models.sharding import use_mesh
from repro.optim import AdamWConfig, CompressConfig
from repro.train.loop import LoopConfig, train


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8", "kmeans"])
    ap.add_argument("--mesh", default=None,
                    help="e.g. '4x2' => (data=4, model=2) over local devices")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    compress = CompressConfig(codec=args.compress)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          decay_steps=args.steps)

    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_test_mesh(d, m)

    with use_mesh(mesh):
        state = init_train_state(cfg, jax.random.PRNGKey(args.seed),
                                 compress=compress)
        step_fn = make_train_step(cfg, opt_cfg, compress=compress)
        sshard = None
        if mesh is not None:
            sshard = train_state_shardings(mesh, state)
            state = jax.device_put(state, sshard)
        jstep = jax.jit(step_fn, donate_argnums=(0,),
                        in_shardings=(sshard, None) if mesh else None,
                        out_shardings=(sshard, None) if mesh else None)

        stream = TokenStream(cfg.vocab, seed=args.seed)
        pipe = DataPipeline(
            lambda s: stream.read(s, args.batch, args.seq), prefetch=2)
        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        loop_cfg = LoopConfig(total_steps=args.steps,
                              save_every=args.save_every)
        state, summary = train(state, jstep, pipe, loop_cfg, ckpt=ckpt,
                               resume=(args.resume == "auto"),
                               state_shardings=sshard)

    losses = summary["losses"]
    if losses:
        k = max(len(losses) // 10, 1)
        print(f"[train] loss first-{k}-mean {np.mean(losses[:k]):.4f} "
              f"last-{k}-mean {np.mean(losses[-k:]):.4f} "
              f"steps {summary['final_step']} "
              f"stragglers {summary['stragglers']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
