"""Pallas TPU kernel: single-token GQA decode attention over a long KV cache.

Decode is memory-bound (the whole valid KV prefix streams through VMEM once
per token), so the kernel's job is to keep that stream dense: KV blocks of
``block_k`` rows are brought in along a sequential grid axis while the
online-softmax state (m, l, acc) for all q heads of one batch element stays
resident in VMEM scratch.  Compute on blocks entirely beyond ``kv_len`` is
skipped.

Layout: all q heads of one batch element are processed together, so each KV
block is read once per batch element rather than once per head — the GQA
bandwidth saving that motivates grouped KV.  The cache keeps its
``[B, S, Hkv, D]`` layout in HBM; the kernel sees it through the free
row-major view ``[B, S, Hkv·D]``, and a static loop over the ``Hkv`` heads
slices each head's lane-aligned ``[block_k, D]`` column block.  The q heads
sharing KV head ``h`` form the ``[group, D]`` row block ``h`` of a
``[Hkv, group, D]`` query view, so every in-kernel contraction is a plain
2-D matmul — the form the TPU compiler accepts — and the KV block is never
repeated ``group`` times.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))   # [R, D] x [N, D] -> [R, N]
_NN = (((1,), (0,)), ((), ()))   # [R, N] x [N, D] -> [R, D]


def init_state(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def fold_heads(q_ref, k_ref, v_ref, valid, m_scr, l_scr, acc_scr, *, scale):
    """Fold one block of keys/values into every KV head's softmax state.

    ``q_ref`` is ``[Hkv, R, D]``; ``k_ref``/``v_ref`` are ``[N, Hkv·D]``;
    ``valid`` is the ``[R, N]`` attend mask shared by all heads.
    """
    hkv, _, d = q_ref.shape
    for h in range(hkv):
        cols = slice(h * d, (h + 1) * d)
        v = v_ref[:, cols]
        s = jax.lax.dot_general(
            q_ref[h], k_ref[:, cols], _NT, preferred_element_type=jnp.float32
        ) * scale                                             # [R, N]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32
        )
        m_scr[h] = m_new


def fold_prefix_block(ki, kv_len, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                      *, scale: float, block_k: int):
    """Fold cache block ``ki`` (positions ``>= kv_len`` masked); blocks
    wholly past ``kv_len`` are skipped."""

    @pl.when(ki * block_k < kv_len)
    def _compute():
        r = q_ref.shape[1]
        pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (r, block_k), 1
        )
        fold_heads(q_ref, k_ref, v_ref, pos < kv_len, m_scr, l_scr, acc_scr,
                   scale=scale)


def normalized(l_scr, acc_scr, dtype):
    return (acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)).astype(dtype)


def softmax_scratch(hkv: int, rows: int, d: int):
    return [
        pltpu.VMEM((hkv, rows, 1), jnp.float32),
        pltpu.VMEM((hkv, rows, 1), jnp.float32),
        pltpu.VMEM((hkv, rows, d), jnp.float32),
    ]


COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary")
)


def _decode_kernel(
    len_ref,    # [B] i32 (SMEM) — per-batch valid KV prefix length
    q_ref,      # [Hkv, group, D]
    k_ref,      # [block_k, Hkv·D]
    v_ref,      # [block_k, Hkv·D]
    o_ref,      # [Hkv, group, D]
    m_scr,      # [Hkv, group, 1] f32
    l_scr,      # [Hkv, group, 1] f32
    acc_scr,    # [Hkv, group, D] f32
    *,
    scale: float,
    block_k: int,
    n_kv: int,
):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        init_state(m_scr, l_scr, acc_scr)

    fold_prefix_block(
        ki, len_ref[pl.program_id(0)], q_ref, k_ref, v_ref,
        m_scr, l_scr, acc_scr, scale=scale, block_k=block_k,
    )

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[...] = normalized(l_scr, acc_scr, o_ref.dtype)


def _paged_decode_kernel(table_ref, len_ref, *refs, **kw):
    """Page-table decode: the math is the dense split-KV kernel's — only the
    *addressing* differs.  ``table_ref`` is consumed by the BlockSpec index
    maps (scalar prefetch drives the K/V page DMA), so logical position
    ``pi·block_size + j`` of batch row ``b`` streams from physical pool block
    ``table[b, pi]`` while the online-softmax state never notices."""
    del table_ref
    _decode_kernel(len_ref, *refs, **kw)


def paged_decode_attention_fwd(
    q: jax.Array,           # [B, Hq, D]
    pool_k: jax.Array,      # [P, block_size, Hkv, D] — shared block pool
    pool_v: jax.Array,      # [P, block_size, Hkv, D]
    page_table: jax.Array,  # [B, n_pages] i32 — pool block id per logical page
    kv_len: jax.Array,      # [] or [B] i32 — valid prefix length per row
    *,
    interpret: bool = True,
) -> jax.Array:
    """Single-token GQA decode over a block-sparse (paged) KV cache.

    Logical KV position ``t`` of batch row ``b`` lives at pool row
    ``(page_table[b, t // block_size], t % block_size)``.  The sequential
    grid axis walks pages instead of contiguous cache blocks; the page id is
    read from SMEM (scalar prefetch) inside the K/V index maps, so each
    page's DMA is issued directly against the pool — no dense gather of the
    cache ever materializes.  Entries beyond ``ceil(kv_len / block_size)``
    may be garbage: they are clipped into range (the DMA must stay in
    bounds) and their scores are masked by ``kv_len`` exactly like the dense
    kernel's tail.
    """
    b, hq, d = q.shape
    p, block_size, hkv, _ = pool_k.shape
    n_pages = page_table.shape[1]
    group = hq // hkv
    lens = jnp.broadcast_to(
        jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,)
    )
    table = jnp.clip(page_table.astype(jnp.int32), 0, p - 1)

    kernel = functools.partial(
        _paged_decode_kernel, scale=1.0 / math.sqrt(d), block_k=block_size,
        n_kv=n_pages,
    )
    page = pl.BlockSpec(
        (None, block_size, hkv * d),
        lambda bi, pi, tab, lens: (tab[bi, pi], 0, 0),
    )
    rows = pl.BlockSpec(
        (None, hkv, group, d), lambda bi, pi, tab, lens: (bi, 0, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages),
        in_specs=[rows, page, page],
        out_specs=rows,
        scratch_shapes=softmax_scratch(hkv, group, d),
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(
        table, lens, q.reshape(b, hkv, group, d),
        pool_k.reshape(p, block_size, hkv * d),
        pool_v.reshape(p, block_size, hkv * d),
    )
    return out.reshape(b, hq, d)


def decode_attention_fwd(
    q: jax.Array,        # [B, Hq, D]
    k_cache: jax.Array,  # [B, S, Hkv, D]
    v_cache: jax.Array,  # [B, S, Hkv, D]
    kv_len: jax.Array,   # [] or [B] i32 — ragged per-batch prefix lengths
    *,
    block_k: int = 512,
    interpret: bool = True,
) -> jax.Array:
    b, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    group = hq // hkv
    block_k = min(block_k, s)
    assert s % block_k == 0
    n_kv = s // block_k
    # Scalar and per-batch (continuous batching / async-slot cache) lengths
    # share one kernel: the scalar broadcasts to a [B] SMEM vector.
    lens = jnp.broadcast_to(
        jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,)
    )

    kernel = functools.partial(
        _decode_kernel, scale=1.0 / math.sqrt(d), block_k=block_k, n_kv=n_kv,
    )
    kv = pl.BlockSpec((None, block_k, hkv * d), lambda bi, ki: (bi, ki, 0))
    rows = pl.BlockSpec((None, hkv, group, d), lambda bi, ki: (bi, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid=(b, n_kv),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), rows, kv, kv],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        scratch_shapes=softmax_scratch(hkv, group, d),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(
        lens, q.reshape(b, hkv, group, d),
        k_cache.reshape(b, s, hkv * d), v_cache.reshape(b, s, hkv * d),
    )
    return out.reshape(b, hq, d)
