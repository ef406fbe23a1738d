"""Serving launcher: batched continuous-batching engine over any arch.

Usage (CPU-scale smoke):
  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --requests 6 --prompt-len 12 --max-len 48
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, get_reduced
from repro.models import init_params
from repro.serving import ServeConfig, ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=48)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_reduced(args.arch) if args.smoke else get_config(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(
        cfg, params,
        ServeConfig(batch_slots=args.slots, max_len=args.max_len,
                    temperature=args.temperature, eos_token=1),
    )
    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(2, cfg.vocab_size, size=args.prompt_len))
        for _ in range(args.requests)
    ]
    t0 = time.time()
    outputs = engine.run(prompts, max_ticks=args.max_len * 2)
    dt = time.time() - t0
    total_tokens = sum(len(o) for o in outputs)
    for i, out in enumerate(outputs):
        print(f"request {i}: generated {len(out)} tokens: {out[:12]}...")
    print(
        f"\nserved {args.requests} requests on {args.slots} slots in {dt:.1f}s "
        f"({total_tokens / max(dt, 1e-9):.1f} tok/s aggregate)"
    )


if __name__ == "__main__":
    main()
