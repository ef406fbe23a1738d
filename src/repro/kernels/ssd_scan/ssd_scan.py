"""Pallas TPU kernel: Mamba-2 SSD chunked scan.

The SSD recurrence  h_t = exp(dA_t)·h_{t-1} + x_t ⊗ B_t,  y_t = C_t·h_t
is computed chunk-by-chunk: a quadratic intra-chunk term (two MXU matmuls
over [Q, Q] score tiles) plus an inter-chunk state pass.  The [P, N] state
for one (batch, head) lives in VMEM scratch across the sequential chunk grid
axis — the state never round-trips to HBM, which is the TPU-native version
of the paper's "keep the recurrent state in SRAM" GPU formulation.

Grid: (B·H, n_chunks); chunk axis sequential.  B/C are shared across heads
(Mamba-2 single group) and their BlockSpec index maps select by batch only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    xdt_ref,    # [Q, P]   (x · dt)
    da_ref,     # [Q, 1]   (dt · A, negative)
    b_ref,      # [Q, N]
    c_ref,      # [Q, N]
    y_ref,      # [Q, P]
    h_scr,      # [P, N] f32 — carried state
    *,
    chunk: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    xdt = xdt_ref[...].astype(jnp.float32)        # [Q, P]
    da = da_ref[...].astype(jnp.float32)[:, 0]    # [Q]
    bm = b_ref[...].astype(jnp.float32)           # [Q, N]
    cm = c_ref[...].astype(jnp.float32)           # [Q, N]

    cum = jnp.cumsum(da)                          # [Q]
    total = cum[-1]

    # Intra-chunk: scores[i, j] = (C_i · B_j) · exp(cum_i − cum_j) for i ≥ j.
    cb = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                             # [Q, Q]
    seg = cum[:, None] - cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    y_intra = jax.lax.dot_general(
        cb * decay, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                             # [Q, P]

    # Inter-chunk: y_i += exp(cum_i) · C_i · h_prevᵀ.
    h_prev = h_scr[...]                           # [P, N]
    y_inter = jax.lax.dot_general(
        cm, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * jnp.exp(cum)[:, None]                     # [Q, P]

    y_ref[...] = (y_intra + y_inter).astype(y_ref.dtype)

    # State update: h ← exp(total)·h + Σ_j exp(total − cum_j)·xdt_j ⊗ B_j.
    w_end = jnp.exp(total - cum)                  # [Q]
    s_chunk = jax.lax.dot_general(
        xdt * w_end[:, None], bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                             # [P, N]
    h_scr[...] = h_prev * jnp.exp(total) + s_chunk


def ssd_scan_fwd(
    xdt: jax.Array,   # [B, S, H, P]
    dA: jax.Array,    # [B, S, H]
    Bmat: jax.Array,  # [B, S, N]
    Cmat: jax.Array,  # [B, S, N]
    *,
    chunk: int = 256,
    interpret: bool = True,
) -> jax.Array:
    b, s, h, p = xdt.shape
    n = Bmat.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    # [B, S, H, P] → [B·H, S, P]; dA → [B·H, S, 1]; B/C stay [B, S, N].
    xr = jnp.moveaxis(xdt, 2, 1).reshape(b * h, s, p)
    dar = jnp.moveaxis(dA, 2, 1).reshape(b * h, s, 1)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        name="ssd_scan",
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, chunk, 1), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, chunk, n), lambda bh, ci, h=h: (bh // h, ci, 0)),
            pl.BlockSpec((None, chunk, n), lambda bh, ci, h=h: (bh // h, ci, 0)),
        ],
        out_specs=pl.BlockSpec((None, chunk, p), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, p), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xr, dar, Bmat, Cmat)
    return jnp.moveaxis(y.reshape(b, h, s, p), 1, 2)
