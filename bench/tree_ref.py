"""Plain reference of the search's tree policy, in numpy and float64.

WU-UCT's selection (Liu et al., ICLR 2020, eq. 4): at a node with completed
visits ``N`` and unobserved (in-flight) visits ``O``, each child the policy
may take, one that exists and is not waiting for its expansion, scores

    V' + beta * sqrt(2 ln(N + O) / (N' + O')),

where a child with no visits of either kind scores +inf, and the policy
takes a best-scoring child.  ``select_gaps`` reads, at every node of the
trees, how far below the best the child the program took scores.  It
imports nothing of the program; the tree arrays are host copies of what
the timed path left.

The control is the same rule computed in bfloat16 (``control_choice``), the
step below the float32 the program states for its tree statistics.
"""

from __future__ import annotations

import numpy as np
from ml_dtypes import bfloat16


def scores(tree: dict, beta: float) -> tuple[np.ndarray, np.ndarray]:
    """``(score [B, M, A], may_take [B, M, A])``; ``-inf`` where the policy
    may not take the child."""
    kids = np.asarray(tree["children"])
    safe = np.maximum(kids, 0)
    b = np.arange(kids.shape[0])[:, None, None]
    may = (kids >= 0) & ~np.asarray(tree["pending"])[b, safe]
    n = np.asarray(tree["N"], np.float64)
    o = np.asarray(tree["O"], np.float64)
    v = np.asarray(tree["V"], np.float64)
    visits = n[b, safe] + o[b, safe]
    parent = np.maximum(n + o, 1.0)[:, :, None]
    with np.errstate(divide="ignore", invalid="ignore"):
        explore = beta * np.sqrt(2.0 * np.log(parent) / visits)
    s = np.where(visits > 0, v[b, safe] + explore, np.inf)
    return np.where(may, s, -np.inf), may


def select_gaps(tree: dict, acts, beta: float) -> np.ndarray:
    """At every node where the policy has a child to take: the best score
    less the score of child ``acts[b, m]`` (0 where it is a best child,
    inf where it may not be taken)."""
    s, may = scores(tree, beta)
    acts = np.asarray(acts, np.int64)
    a = s.shape[-1]
    inside = (acts >= 0) & (acts < a)
    chosen = np.take_along_axis(s, np.clip(acts, 0, a - 1)[..., None],
                                -1)[..., 0]
    chosen = np.where(inside, chosen, -np.inf)
    best = s.max(-1)
    with np.errstate(invalid="ignore"):
        gap = np.where(chosen == best, 0.0, best - chosen)
    return gap[may.any(-1)]


def control_choice(tree: dict, beta: float) -> np.ndarray:
    """The child the rule takes with its statistics and arithmetic rounded
    to bfloat16: ``[B, M]``, the first best."""
    def r(x):
        return np.asarray(x, np.float32).astype(bfloat16).astype(np.float32)

    low = {k: (r(tree[k]) if k in ("N", "O", "V") else tree[k])
           for k in ("children", "pending", "N", "O", "V")}
    s, _ = scores(low, beta)
    return np.argmax(r(s), axis=-1)
