"""Training launcher: data pipeline → sharded train loop → checkpoints.

Runs on any mesh (single device for smoke, production pod via dry-run).
Demonstrates the full fault-tolerance story:

* deterministic data addressing (resume = restore step counter),
* atomic + async checkpointing with keep-k GC,
* elastic restore (restart on a different mesh reshards automatically),
* optional int8 error-feedback gradient compression.

Usage (CPU-scale smoke):
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
      --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, get_reduced
from repro.models import abstract_params, init_params
from repro.training import (
    AdamWConfig,
    CheckpointManager,
    SyntheticStream,
    TrainConfig,
    adamw_init,
    make_train_step,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_reduced(args.arch) if args.smoke else get_config(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(cfg, loss_chunk=64)

    train_cfg = TrainConfig(
        optimizer=AdamWConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps),
        microbatches=args.microbatches,
        compress_grads=args.compress_grads,
    )
    step_fn = jax.jit(make_train_step(cfg, train_cfg), donate_argnums=(0, 1))

    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    opt_state = adamw_init(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params:,}")

    start_step = 0
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if mgr and mgr.latest_step() is not None:
        start_step, (params, opt_state) = mgr.restore((params, opt_state))
        print(f"restored checkpoint at step {start_step}")

    stream = SyntheticStream(cfg.vocab_size, args.batch, args.seq, seed=args.seed)

    t_last, tok_acc = time.time(), 0
    for step in range(start_step, args.steps):
        batch = jax.tree.map(jnp.asarray, stream.batch_at(step))
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        tok_acc += args.batch * args.seq
        if (step + 1) % 5 == 0 or step == start_step:
            dt = time.time() - t_last
            print(
                f"step {step + 1:5d} loss={float(metrics['loss']):.4f} "
                f"gnorm={float(metrics['grad_norm']):.3f} "
                f"lr={float(metrics['lr']):.2e} tok/s={tok_acc / max(dt, 1e-9):,.0f}"
            )
            t_last, tok_acc = time.time(), 0
        if mgr and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, (params, opt_state))
    if mgr:
        mgr.save(args.steps, (params, opt_state), blocking=True)
        print(f"final checkpoint: step {args.steps} -> {args.ckpt_dir}")


if __name__ == "__main__":
    main()
