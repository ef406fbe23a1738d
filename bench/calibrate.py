#!/usr/bin/env python3
"""Readings a cell's correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1,2,... [--control-seeds 1,2,3]

In one process, for each seed: a run of the cell exactly as
``bench/run.py`` makes it (set-up, a window of ``--seconds`` at the cell's
load, the wait for late answers), then the numbers ``bench/checks.py``
compares, through the harness's own comparison.  For the control seeds it
also reads the control in the program's place, through the same
comparison: the reference computed with float8 linear layers (the
architecture module's ``last_logits``, ``quant="fp8"``) at the same slots
and tokens, and the tree policy's rule computed in bfloat16
(``bench.tree_ref``) on the same trees; and two faults planted in the
reference put in the program's place, a selection that always takes the
first child it may and one that takes the worst value.  One JSON line per seed; the benchmark's own runs
never run the control.  Exits non-zero without a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell: dict, seed: int, seconds: float, control: bool) -> dict:
    import numpy as np

    from bench import checks, harness, reference, tree_ref

    served = harness.serve(cell, seed, seconds, False,
                           t_start=time.perf_counter())
    r = harness.read(served)
    verdict = harness.compare(served, cell["limits"], r)
    out = {"seed": seed, "correct": checks.passed(verdict)}
    out.update({k: c["value"] for k, c in verdict.items()})
    out.update(
        logit_rel_err_median=float(np.median(r.logit_errs)),
        slots=len(r.logit_errs), longest=int(served.sample["len"].max()),
        tree_nodes=int(r.select_gaps.size),
        decisions=len(served.window.latencies), setup_s=served.setup_s,
    )
    if control:
        tree, beta = served.tree, cell["search"]["beta"]
        sample = served.sample
        ctl = harness.Readings(
            want=r.want,
            logit_errs=reference.rel_l2(served.arch.last_logits(
                served.weights, cell["config"], sample["tokens"],
                sample["len"], quant="fp8"), r.want),
            select_gaps=tree_ref.select_gaps(
                tree, tree_ref.control_choice(tree, beta), beta),
        )
        cv = harness.compare(served, cell["limits"], ctl)
        out["control_correct"] = checks.passed(cv)
        out["control_logit_rel_err"] = cv["logit_rel_err"]["value"]
        out["control_logit_rel_err_min_slot"] = float(ctl.logit_errs.min())
        out["control_select_gap"] = cv["select_gap"]["value"]
        for name, acts in _faults(tree, beta).items():
            out[f"{name}_select_gap"] = float(
                tree_ref.select_gaps(tree, acts, beta).max())
    return out


def _faults(tree: dict, beta: float) -> dict:
    """Selections broken where they are made: the first child the policy
    may take, and the child of the worst value."""
    import numpy as np

    from bench import tree_ref

    _, may = tree_ref.scores(tree, beta)
    flipped = dict(tree, V=-np.asarray(tree["V"]))
    return {"first_child": np.argmax(may, axis=-1),
            "worst_value": np.argmax(tree_ref.scores(flipped, beta)[0],
                                     axis=-1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    import jax

    from bench import registry
    from repro.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU found", file=sys.stderr)
        return 2
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = registry.cell(args.workload)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    seeds = [int(s) for s in args.seeds.split(",")]
    for s in seeds + sorted(control - set(seeds)):
        print(json.dumps(readings(cell, s, args.seconds, s in control)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
