"""Pallas TPU kernel: fused tree-policy selection over batched children tables.

The master-side hot op of every selection rule in this package is

    a = argmax_a  score_kind(child stats, parent stats)

For batched multi-root search (``B`` trees advancing in lockstep — the
throughput mode of this framework), the statistics of all children of the
``B`` current nodes are gathered into dense ``[B, A]`` tables and this kernel
fuses score computation + masked argmax in one VMEM pass, instead of
materializing scores and running a separate argmax reduction.  One program
handles a ``[block_b, A]`` tile.

Score variants (``kind``) mirror :func:`repro.core.policies.child_scores`,
which stays the interpret-mode reference:

* ``wu_uct``   — paper eq. (4): unobserved counts ``O`` correct both terms.
* ``uct``      — paper eq. (2): classic UCB1-over-trees.
* ``treep``    — eq. (2) over virtual-loss-adjusted values ``V − VL``.
* ``treep_vc`` — eq. (7), App. E: virtual loss + virtual pseudo-count with
                 ``c = O`` in-flight queries, applied non-destructively.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

KINDS = ("wu_uct", "uct", "treep", "treep_vc")


def _scores(nc, oc, vc, vlc, n_p, o_p, *, kind, beta, r_vl, n_vl):
    """Per-action scores for a [bb, A] tile; ops mirror policies.child_scores
    exactly (same order, same clamps) so tie-breaks agree bitwise."""
    if kind == "wu_uct":
        log_term = jnp.log(jnp.maximum(n_p + o_p, 1.0))          # [bb, 1]
        denom = nc + oc
        explore = beta * jnp.sqrt(2.0 * log_term / jnp.maximum(denom, 1e-9))
        explore = jnp.where(denom > 0, explore, jnp.inf)
        return vc + explore
    if kind == "uct":
        log_term = jnp.log(jnp.maximum(n_p, 1.0))
        explore = beta * jnp.sqrt(2.0 * log_term / jnp.maximum(nc, 1e-9))
        explore = jnp.where(nc > 0, explore, jnp.inf)
        return vc + explore
    if kind == "treep":
        log_term = jnp.log(jnp.maximum(n_p, 1.0))
        explore = beta * jnp.sqrt(2.0 * log_term / jnp.maximum(nc, 1e-9))
        explore = jnp.where(nc > 0, explore, jnp.inf)
        return (vc - vlc) + explore
    if kind == "treep_vc":
        c = oc
        v_adj = (nc * vc - c * r_vl) / jnp.maximum(nc + c * n_vl, 1e-9)
        log_term = jnp.log(jnp.maximum(n_p + o_p, 1.0))
        denom = nc + c * n_vl
        explore = beta * jnp.sqrt(2.0 * log_term / jnp.maximum(denom, 1e-9))
        explore = jnp.where(denom > 0, explore, jnp.inf)
        return v_adj + explore
    raise ValueError(f"unknown policy kind: {kind}")


def _select_kernel(
    nc_ref,     # [block_b, A] child N
    oc_ref,     # [block_b, A] child O
    vc_ref,     # [block_b, A] child V
    vlc_ref,    # [block_b, A] child VL (virtual-loss accumulator)
    np_ref,     # [block_b, 1] parent N
    op_ref,     # [block_b, 1] parent O
    valid_ref,  # [block_b, A] i32 mask
    act_ref,    # [block_b, 1] i32 out — argmax action
    score_ref,  # [block_b, 1] f32 out — best score
    *,
    kind: str,
    beta: float,
    r_vl: float,
    n_vl: float,
):
    nc = nc_ref[...].astype(jnp.float32)
    oc = oc_ref[...].astype(jnp.float32)
    vc = vc_ref[...].astype(jnp.float32)
    vlc = vlc_ref[...].astype(jnp.float32)
    n_p = np_ref[...].astype(jnp.float32)
    o_p = op_ref[...].astype(jnp.float32)
    valid = valid_ref[...] != 0

    score = _scores(nc, oc, vc, vlc, n_p, o_p, kind=kind, beta=beta,
                    r_vl=r_vl, n_vl=n_vl)
    score = jnp.where(valid, score, NEG_INF)

    best = jnp.max(score, axis=1, keepdims=True)              # [bb, 1]
    bb, a = score.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (bb, a), 1)
    # first argmax: smallest index achieving the max
    cand = jnp.where(score == best, idx, a)
    act_ref[...] = jnp.min(cand, axis=1, keepdims=True).astype(jnp.int32)
    score_ref[...] = best


def tree_select_fwd(
    n_c: jax.Array,     # [B, A]
    o_c: jax.Array,     # [B, A]
    v_c: jax.Array,     # [B, A]
    n_p: jax.Array,     # [B]
    o_p: jax.Array,     # [B]
    valid: jax.Array,   # [B, A] bool
    vl_c: jax.Array | None = None,  # [B, A] (TreeP only; zeros if None)
    *,
    kind: str = "wu_uct",
    beta: float = 1.0,
    r_vl: float = 1.0,
    n_vl: float = 1.0,
    block_b: int = 256,
    interpret: bool = True,
):
    if kind not in KINDS:
        raise ValueError(f"unknown policy kind: {kind!r}; expected one of {KINDS}")
    b, a = n_c.shape
    if vl_c is None:
        vl_c = jnp.zeros_like(v_c)
    block_b = min(block_b, b)
    # Pad the batch axis up to a block multiple; padded rows are all-invalid.
    pad = (-b) % block_b
    if pad:
        pad2 = lambda x: jnp.pad(x, ((0, pad), (0, 0)))
        pad1 = lambda x: jnp.pad(x, ((0, pad),))
        n_c, o_c, v_c, vl_c = map(pad2, (n_c, o_c, v_c, vl_c))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
        n_p, o_p = pad1(n_p), pad1(o_p)
    bp = b + pad
    kernel = functools.partial(
        _select_kernel, kind=kind, beta=beta, r_vl=r_vl, n_vl=n_vl
    )
    act, score = pl.pallas_call(
        kernel,
        name="tree_select",
        grid=(bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, a), lambda i: (i, 0)),
            pl.BlockSpec((block_b, a), lambda i: (i, 0)),
            pl.BlockSpec((block_b, a), lambda i: (i, 0)),
            pl.BlockSpec((block_b, a), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, a), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        n_c,
        o_c,
        v_c,
        vl_c,
        n_p.reshape(bp, 1),
        o_p.reshape(bp, 1),
        valid.astype(jnp.int32),
    )
    return act[:b, 0], score[:b, 0]
