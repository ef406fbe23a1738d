#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs in one process on the machine it is started on.  It exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for.  With ``--trace 0`` the result line holds the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a device trace
of the window.  The numbers the correctness check compared, each with its
limit, are the last lines on standard error and the last key of the line.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import run_cell
    from repro.compile_cache import use_compile_cache

    import jax

    # Every program goes to the cache, so that only a checkout's first run
    # of a cell compiles.
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
