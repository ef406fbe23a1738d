# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

Each module maps to one paper artifact (see DESIGN.md §7):
  bench_speedup         — Fig. 4(a-b) + Table 3 (speedup vs workers)
  bench_worker_perf     — Fig. 4(c-d)          (performance vs workers)
  bench_parallel_algos  — Table 1              (WU-UCT vs TreeP/LeafP/RootP)
  bench_treep_variants  — Table 5 / App. E     (virtual pseudo-count TreeP)
  bench_time_breakdown  — Fig. 2(b-c)          (phase time breakdown)
  bench_regret          — beyond-paper exact-regret study (Sec. 4 claims)
  bench_batched_search  — beyond-paper multi-root throughput (searches/sec vs B)
  bench_batched_async   — beyond-paper batched async-slot engine vs vmap baseline

Roofline tables come from ``python -m benchmarks.roofline`` (reads the
dry-run artifacts; see EXPERIMENTS.md §Roofline).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import warnings

# The failure modes one bench module can legitimately hit while the rest of
# the sweep should still run: bad shapes/params (ValueError/TypeError),
# compile/XLA errors (RuntimeError), missing record fields (KeyError/
# AttributeError/IndexError), overflow (ArithmeticError), optional deps
# (ImportError) and artifact IO (OSError).  A KeyboardInterrupt or a
# typo-level NameError still aborts the whole run — see JX004 in
# ``python -m repro.analysis.lint --rules``.
_BENCH_ERRORS = (
    RuntimeError, ValueError, TypeError, KeyError, AttributeError,
    IndexError, ArithmeticError, ImportError, NotImplementedError, OSError,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-list of module names")
    ap.add_argument("--fast", action="store_true", help="reduced budgets")
    ap.add_argument(
        "--json-dir", default=".",
        help="where BENCH_*.json perf baselines are written",
    )
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    # Machine-readable perf baselines: modules listed here append structured
    # records which land in BENCH_<module>.json next to the CSV on stdout,
    # so later PRs can diff throughput against this run.
    json_records: dict[str, list] = {"model_eval": []}

    from . import (
        bench_async_scaling,
        bench_batched_async,
        bench_batched_search,
        bench_model_eval,
        bench_parallel_algos,
        bench_regret,
        bench_speedup,
        bench_time_breakdown,
        bench_treep_variants,
        bench_worker_perf,
    )

    modules = {
        "speedup": lambda: bench_speedup.run(
            num_simulations=32 if args.fast else 64,
            waves=(1, 4, 16) if args.fast else (1, 2, 4, 8, 16),
        ),
        "worker_perf": lambda: bench_worker_perf.run(
            episodes=1 if args.fast else 3,
            num_simulations=16 if args.fast else 32,
        ),
        "parallel_algos": lambda: bench_parallel_algos.run(
            episodes=1 if args.fast else 3,
            num_simulations=32 if args.fast else 64,
        ),
        "treep_variants": lambda: bench_treep_variants.run(
            episodes=1 if args.fast else 3,
            num_simulations=32 if args.fast else 64,
        ),
        "time_breakdown": lambda: bench_time_breakdown.run(),
        "regret": lambda: bench_regret.run(trials=2 if args.fast else 5),
        "async_scaling": lambda: bench_async_scaling.run(
            num_simulations=32 if args.fast else 64,
        ),
        "batched_search": lambda: bench_batched_search.run(
            num_simulations=32 if args.fast else 64,
            batch_sizes=(1, 8) if args.fast else (1, 8, 32),
        ),
        "batched_async": lambda: bench_batched_async.run(
            num_simulations=32 if args.fast else 128,
            wave_size=8 if args.fast else 16,
            batch_sizes=(1, 8) if args.fast else (1, 8, 32),
        ),
        "model_eval": lambda: bench_model_eval.run(
            num_simulations=8 if args.fast else 16,
            wave_size=4,
            batch_sizes=(1,) if args.fast else (1, 4),
            depths=(8,) if args.fast else (8, 64),
            serving_batch=2 if args.fast else 4,
            records=json_records["model_eval"],
        ),
    }
    selected = args.only.split(",") if args.only else list(modules)

    print("name,us_per_call,derived")
    for name in selected:
        t0 = time.time()
        ok = True
        try:
            for line in modules[name]():
                print(line, flush=True)
        except _BENCH_ERRORS as e:
            ok = False
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            # warnings dedups identical messages, so a module that fails
            # the same way in a loop of invocations warns once per process.
            warnings.warn(
                f"benchmark module {name!r} failed "
                f"({type(e).__name__}: {e}); its rows are omitted",
                stacklevel=2,
            )
        print(f"# {name} took {time.time() - t0:.1f}s", file=sys.stderr)
        # Only a COMPLETE run may become the committed perf baseline — a
        # partial sweep would silently read as a full one in future diffs.
        if ok and json_records.get(name):
            path = f"{args.json_dir}/BENCH_{name}.json"
            with open(path, "w") as f:
                json.dump(
                    {"fast": args.fast, "rows": json_records[name]}, f,
                    indent=2,
                )
            print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
