"""Pallas TPU kernel: blockwise causal GQA flash attention (fwd).

Grid: (batch·q_heads, q_blocks, kv_blocks) with the kv dimension sequential
("arbitrary") so the online-softmax running state (m, l, acc) persists in
VMEM scratch across kv iterations.  GQA is handled in the K/V BlockSpec
index maps (kv head = q head // group) — no materialized head broadcast.
Fully-masked (future) kv blocks are skipped with ``pl.when``, so causal
compute is ~half of the dense S² (unlike the jnp oracle, which masks).

VMEM per program ≈ (block_q + 2·block_k)·head_dim·2B + block_q·block_k·4B
+ acc block_q·head_dim·4B — e.g. (256, 512) blocks at D=128: ~1.1 MB, far
under the ~16 MB/core budget; MXU-aligned (multiples of 128) throughout.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(
    q_ref,      # [block_q, D]
    k_ref,      # [block_k, D]
    v_ref,      # [block_k, D]
    o_ref,      # [block_q, D]
    m_scr,      # [block_q, 1] f32
    l_scr,      # [block_q, 1] f32
    acc_scr,    # [block_q, D] f32
    *,
    scale: float,
    block_q: int,
    block_k: int,
    n_kv: int,
    causal: bool,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kv_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # Causal block skipping: compute only blocks intersecting the triangle.
    run = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                             # [bq, bk]
        if causal:
            s = jnp.where(q_pos >= kv_pos, s, NEG_INF)
        m_prev = m_scr[...]                                   # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(q_pos >= kv_pos, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[...] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)
        ).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,   # [B, Sq, Hq, D]
    k: jax.Array,   # [B, Sk, Hkv, D]
    v: jax.Array,   # [B, Sk, Hkv, D]
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = True,
) -> jax.Array:
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    n_q, n_kv = sq // block_q, sk // block_k
    scale = 1.0 / math.sqrt(d)

    # [B, S, H, D] -> [B, H, S, D] so blocks are (seq, head_dim) tiles.
    qt = jnp.swapaxes(q, 1, 2).reshape(b * hq, sq, d)
    kt = jnp.swapaxes(k, 1, 2)                     # [B, Hkv, Sk, D]
    vt = jnp.swapaxes(v, 1, 2)

    kernel = functools.partial(
        _fa_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        n_kv=n_kv,
        causal=causal,
    )

    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(b * hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec(
                (None, None, block_k, d),
                lambda bh, qi, ki, hq=hq, group=group: (
                    bh // hq, (bh % hq) // group, ki, 0
                ),
            ),
            pl.BlockSpec(
                (None, None, block_k, d),
                lambda bh, qi, ki, hq=hq, group=group: (
                    bh // hq, (bh % hq) // group, ki, 0
                ),
            ),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qt, kt, vt)

    return jnp.swapaxes(out.reshape(b, hq, sq, d), 1, 2)
