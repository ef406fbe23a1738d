"""Schema guard for committed perf baselines (CI benchmark-smoke).

Wall-clock numbers drift with hardware, so CI cannot diff them — but the
*shape* of a baseline is load-bearing: later PRs join rows by ``kind`` and
read specific fields, and a silently renamed kind or dropped field turns
every downstream comparison into a no-op.  This checker compares a freshly
generated ``BENCH_<module>.json`` (typically from ``run.py --fast``)
against the committed baseline and fails on:

* kinds present in the baseline but missing from the fresh run (a bench
  path stopped producing them);
* per-kind field sets that no longer cover the baseline's fields;
* known kinds whose rows drop a REQUIRED field (``REQUIRED_FIELDS``) —
  downstream consumers read these by name (e.g.
  ``serving.search_service`` sizes paged pools from
  ``batch_ceiling.ceiling_ratio``; the frontier rows' ``top_k`` /
  ``frontier_hits`` feed the hit-rate comparison), so they are pinned
  explicitly rather than inferred from whatever the baseline happened
  to contain.

Fresh runs may ADD kinds/fields (that is how baselines grow); they may not
lose any.  Usage::

    python -m benchmarks.check_schema --baseline BENCH_model_eval.json \
        --fresh /tmp/bench/BENCH_model_eval.json
"""

from __future__ import annotations

import argparse
import json
import sys


# Fields that rows of a kind must ALWAYS carry, independent of what the
# committed baseline contains — these are read by name elsewhere in the
# repo, so losing one is a break even if the baseline predates it.
REQUIRED_FIELDS: dict[str, set[str]] = {
    "batch_ceiling": {"ceiling_ratio", "peak_blocks", "block_size"},
    "frontier_decode": {
        "top_k", "frontier_hits", "searches_per_sec", "us_per_tick",
    },
    "frontier_speedup": {"top_k", "speedup", "cached_seconds"},
    "serving_eval": {
        "requests", "batch", "requests_per_sec", "slot_idle_frac",
        "admissions", "ticks", "host_rounds",
    },
    "serving_fused": {
        "requests", "requests_per_sec", "host_rounds",
        "host_rounds_per_request", "host_paced_host_rounds",
        "host_rounds_reduction",
    },
    "serving_speedup": {
        "requests", "speedup", "sequential_seconds", "fused_seconds",
    },
}


def field_sets(rows: list[dict]) -> dict[str, set[str]]:
    """kind -> union of field names over that kind's rows."""
    out: dict[str, set[str]] = {}
    for r in rows:
        out.setdefault(r.get("kind", "?"), set()).update(r.keys())
    return out


def check(baseline: dict, fresh: dict) -> list[str]:
    errors = []
    base, new = field_sets(baseline["rows"]), field_sets(fresh["rows"])
    for kind, fields in sorted(base.items()):
        if kind not in new:
            errors.append(f"kind {kind!r} missing from fresh run")
            continue
        lost = fields - new[kind]
        if lost:
            errors.append(f"kind {kind!r} lost fields {sorted(lost)}")
    for kind, required in sorted(REQUIRED_FIELDS.items()):
        if kind not in new:
            continue
        missing = required - new[kind]
        if missing:
            errors.append(
                f"kind {kind!r} is missing required fields "
                f"{sorted(missing)}"
            )
    if not fresh["rows"]:
        errors.append("fresh run produced no rows")
    return errors


def _load(path: str, role: str) -> dict:
    """Read one report, failing with a pointed message instead of a
    traceback — a missing/corrupt baseline is a usage error, not a crash
    (and never a silently-passing check)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except OSError as e:
        print(f"cannot read {role} report {path}: {e}", file=sys.stderr)
        sys.exit(2)
    except json.JSONDecodeError as e:
        print(f"{role} report {path} is not valid JSON: {e}", file=sys.stderr)
        sys.exit(2)
    if not isinstance(data.get("rows"), list):
        print(f"{role} report {path} has no 'rows' list", file=sys.stderr)
        sys.exit(2)
    return data


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", required=True)
    ap.add_argument("--fresh", required=True)
    args = ap.parse_args()
    baseline = _load(args.baseline, "baseline")
    fresh = _load(args.fresh, "fresh")
    errors = check(baseline, fresh)
    for e in errors:
        print(f"SCHEMA DRIFT: {e}", file=sys.stderr)
    if errors:
        sys.exit(1)
    kinds = sorted(field_sets(fresh["rows"]))
    print(f"schema ok: {len(fresh['rows'])} rows, kinds {kinds}")


if __name__ == "__main__":
    main()
