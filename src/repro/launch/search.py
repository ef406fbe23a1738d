"""Search launcher: WU-UCT (or any baseline) on any registered environment.

Everything goes through the one front door, ``repro.core.build_searcher``:
the ``--algo/--engine/--batch`` flags map 1:1 onto ``SearchSpec`` fields.

Episode play (one search per move):
  PYTHONPATH=src python -m repro.launch.search --env tap --algo wu_uct \
      --workers 16 --simulations 128 --episodes 2

Batched multi-root mode (B independent searches in lockstep through the
fused Pallas tree_select kernel; reports searches/sec):
  PYTHONPATH=src python -m repro.launch.search --env bandit --algo wu_uct \
      --batch 32 --workers 8 --simulations 64

The wave engine is the default; ``--engine async`` selects the async-slot
engine (the paper's master–worker interleaving: no slot waits for the
slowest rollout).  Combined with ``--batch`` it runs B trees × W slots in
one program with the rollout batch flattened to [B·W]:
  PYTHONPATH=src python -m repro.launch.search --env bandit --algo wu_uct \
      --batch 32 --workers 16 --simulations 128 --engine async
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import SearchSpec, build_searcher, play_episode
from repro.distributed import constrain_search_batch
from repro.envs import make_bandit_tree, make_random_mdp, make_tap_game


def make_env(name: str):
    return {
        "tap": lambda: make_tap_game(grid_size=6, num_colors=4, goal_count=10,
                                     step_budget=20),
        "tap_hard": lambda: make_tap_game(grid_size=7, num_colors=5,
                                          goal_count=14, step_budget=30),
        "bandit": lambda: make_bandit_tree(depth=6, num_actions=4),
        "mdp": lambda: make_random_mdp(num_states=32, num_actions=4, horizon=16),
    }[name]()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="tap",
                    choices=["tap", "tap_hard", "bandit", "mdp"])
    ap.add_argument("--algo", default="wu_uct",
                    choices=["wu_uct", "uct", "treep", "treep_vc", "leafp", "rootp"])
    ap.add_argument("--workers", type=int, default=16)
    ap.add_argument("--simulations", type=int, default=128)
    ap.add_argument("--episodes", type=int, default=2)
    ap.add_argument("--max-depth", type=int, default=10)
    ap.add_argument("--width", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=0,
                    help="B>0: run B root states through the batched "
                         "multi-root engine instead of episode play")
    ap.add_argument("--engine", default="wave", choices=["wave", "async"],
                    help="wave: barrier per wave; async: slot-level "
                         "interleaving (refill the instant a rollout settles)")
    args = ap.parse_args()
    use_compile_cache()

    env = make_env(args.env)
    spec = SearchSpec(
        algo=args.algo,
        engine=args.engine,
        batch=args.batch,
        num_simulations=args.simulations,
        wave_size=args.workers,
        max_depth=args.max_depth,
        max_sim_steps=20,
        max_width=min(args.width, env.num_actions),
        gamma=0.99,
    )

    if args.batch > 0:
        B = args.batch
        # constrain is a no-op without a mesh; under one, shards the B (and
        # async [B·W]) axis over ('pod', 'data').
        search = build_searcher(env, spec, constrain=constrain_search_batch)
        roots = jax.vmap(env.init)(
            jax.random.split(jax.random.PRNGKey(args.seed), B)
        )
        rngs = jax.random.split(jax.random.PRNGKey(args.seed + 1), B)
        res = jax.block_until_ready(search(roots, rngs))  # compile
        t0 = time.time()
        res = jax.block_until_ready(search(roots, rngs))
        dt = time.time() - t0
        acts = np.asarray(res.action)
        cfg = spec.config
        print(f"{args.algo}[{args.engine}] B={B} W={cfg.wave_size} "
              f"T={cfg.num_simulations}: "
              f"{B / dt:.1f} searches/s  wall={dt:.2f}s  "
              f"actions={acts[:min(B, 16)].tolist()}"
              f"{'…' if B > 16 else ''}  overflowed={bool(res.overflowed.any())}")
        return

    searcher = build_searcher(env, spec)
    rets, steps = [], []
    for ep in range(args.episodes):
        t0 = time.time()
        ret, moves, done = play_episode(
            env, spec.config, jax.random.PRNGKey(args.seed + ep), max_moves=32,
            searcher=searcher,
        )
        rets.append(ret)
        steps.append(moves)
        print(
            f"episode {ep}: return={ret:.3f} game_steps={moves} done={done} "
            f"wall={time.time() - t0:.1f}s"
        )
    print(
        f"\n{args.algo} W={args.workers}: return={np.mean(rets):.3f}"
        f"±{np.std(rets):.3f} game_steps={np.mean(steps):.1f}"
    )


if __name__ == "__main__":
    main()
