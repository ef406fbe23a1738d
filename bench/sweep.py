#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell, to find once the highest
rate the system sustains.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> \\
        --rates 4,8,16,...

In one process, one run of the cell per rate (its traffic file's
``rate_per_s`` replaced), each printed as one JSON line: decisions per
second, time to decision at p50 and p90, and the backlog at the close of
the window (requests submitted and not yet answered).  A rate is sustained
where the answered rate keeps up with the offered one and the backlog stays
within what the ring and the tree rows hold.  Exits non-zero without a TPU.
"""

import argparse
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import harness, registry
    from repro.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU found", file=sys.stderr)
        return 2
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    base = registry.cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell["traffic"]["rate_per_s"] = rate
        s = harness.serve(cell, args.seed, args.seconds, False,
                          t_start=time.perf_counter())
        w = s.window
        ttd = np.asarray(w.latencies + s.late_latencies) * 1e3
        print(json.dumps({
            "rate_per_s": rate,
            "offered": w.attempted / s.window_s,
            "decisions_per_s": len(w.latencies) / s.window_s,
            "ttd_p50_ms": float(np.quantile(ttd, 0.5)),
            "ttd_p90_ms": float(np.quantile(ttd, 0.9)),
            "backlog_at_close": w.attempted - len(w.latencies),
            "unanswered": s.unanswered,
            "lateness_ms_max": float(max(w.lateness, default=0.0) * 1e3),
        }), flush=True)
        del s, w    # free this rate's weights before the next rate's
    return 0


if __name__ == "__main__":
    sys.exit(main())
