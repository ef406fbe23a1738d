"""The comparison that decides ``correct``.

Four numbers, each against its limit from the cell's workload file:

* ``logit_rel_err`` — the largest relative L2 error, over a seeded sample
  of evaluator slots at the close of the window (the longest among them),
  between the next-token logits the timed path stored for the slot's token
  prefix and the float32 reference's for the same prefix.  It covers the
  model step as served: staging prefill, cached or paged decode ticks with
  the attention kernels, and catch-up chunks.
* ``select_gap`` — the tree policy: at every node of the search trees the
  window left, the program's selection (the ``tree_select`` kernel call of
  the refill) against the plain WU-UCT rule of ``bench/tree_ref.py`` on the
  same statistics, in-flight counts included; the number is the widest gap
  by which the child the program takes scores below the best child.
* ``decision_faults`` — decisions that break the search's contract as the
  reference states it: the action is a rank in ``[0, top_k)`` that the
  search tried and a most-visited root action; every root child it tried
  got its simulations back (at least one visit) and no untried child has
  any; the children's visits are at most the simulation budget (a
  simulation may also end at the root itself, so they can fall short of
  it); the tree did not overflow.  An exact comparison: limit 0.
* ``unanswered`` — submitted requests with no answer a minute after the
  window closed.  Limit 0.
"""

from __future__ import annotations

import numpy as np


def decision_faults(results, *, top_k: int, num_simulations: int) -> int:
    bad = 0
    for r in results:
        a = int(r.action)
        n = np.asarray(r.root_n, np.float64)
        tried = np.isfinite(np.asarray(r.root_v, np.float64))
        ok = (
            0 <= a < top_k
            and tried[a]
            and n[a] == n.max()
            and bool(np.all(n[tried] >= 1))
            and bool(np.all(n[~tried] == 0))
            and n.sum() <= num_simulations
            and not bool(r.overflowed)
        )
        bad += not ok
    return bad


def _worst(xs) -> float:
    xs = np.asarray(xs, np.float64)
    return float(xs.max()) if xs.size else float("inf")


def evaluate(limits: dict, *, logit_errs, select_gaps, results,
             unanswered: int, top_k: int, num_simulations: int) -> dict:
    """``{name: {"value", "limit"}}`` for every number compared.  An empty
    sample reads inf: a check with nothing to compare fails."""
    return {
        "logit_rel_err": {"value": _worst(logit_errs),
                          "limit": limits["logit_rel_err"]},
        "select_gap": {"value": _worst(select_gaps),
                       "limit": limits["select_gap"]},
        "decision_faults": {
            "value": decision_faults(results, top_k=top_k,
                                     num_simulations=num_simulations),
            "limit": limits["decision_faults"],
        },
        "unanswered": {"value": int(unanswered),
                       "limit": limits["unanswered"]},
    }


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
