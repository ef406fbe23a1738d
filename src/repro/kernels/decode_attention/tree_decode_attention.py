"""Pallas TPU kernel: tree-batched speculative decode attention.

Frontier expansion scores all ``A`` candidate children of a settled leaf in
one forward — the queries differ only in their final token, so the shared
prefix K/V should stream through VMEM ONCE for the whole candidate set, not
once per candidate.  The kernel is the split-KV decode kernel widened to
``A·group`` query rows per KV head: prefix blocks fold into the
online-softmax state exactly as before (now per candidate), and the last
grid step folds in the speculative tail — each candidate's own K/V entry,
which lives OUTSIDE the cache — under a caller-supplied ``[A, A]`` tree mask
(identity for a flat frontier: candidate ``i`` attends only tail entry
``i``).

Query rows are laid out ``[Hkv, A·group, D]`` (candidate-major within each
KV head), so, as in the decode kernel, every contraction is a 2-D matmul
against one head's lane-aligned column block of the ``[N, Hkv·D]`` K/V view.

The paged variant walks the page table via scalar prefetch, identical to
``_paged_decode_kernel``: only the addressing differs, the math is shared.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import (
    COMPILER_PARAMS,
    fold_heads,
    fold_prefix_block,
    init_state,
    normalized,
    softmax_scratch,
)


def _tree_decode_kernel(
    len_ref,    # [B] i32 (SMEM) — per-batch valid KV prefix length
    q_ref,      # [Hkv, A·group, D]
    k_ref,      # [block_k, Hkv·D]
    v_ref,      # [block_k, Hkv·D]
    ks_ref,     # [A, Hkv·D] — speculative tail keys for this row
    vs_ref,     # [A, Hkv·D]
    mask_ref,   # [A·group, A] i32 — tree mask row per query (nonzero: attend)
    o_ref,      # [Hkv, A·group, D]
    m_scr,      # [Hkv, A·group, 1] f32
    l_scr,      # [Hkv, A·group, 1] f32
    acc_scr,    # [Hkv, A·group, D] f32
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
    def _tail_and_finalize():
        # Fold the speculative tail (A extra K/V entries, masked by the tree
        # mask) into the online-softmax state, then normalize.  Runs after
        # the prefix fold of this block (pl.when bodies run in order).
        fold_heads(q_ref, ks_ref, vs_ref, mask_ref[...] != 0,
                   m_scr, l_scr, acc_scr, scale=scale)
        o_ref[...] = normalized(l_scr, acc_scr, o_ref.dtype)


def _paged_tree_decode_kernel(table_ref, len_ref, *refs, **kw):
    del table_ref
    _tree_decode_kernel(len_ref, *refs, **kw)


def _rows(q, hkv):
    """``[B, A, Hq, D]`` -> ``[B, Hkv, A·group, D]`` (candidate-major)."""
    b, a, hq, d = q.shape
    g = hq // hkv
    return q.reshape(b, a, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, a * g, d
    )


def _unrows(o, a):
    """Inverse of :func:`_rows`."""
    b, hkv, r, d = o.shape
    g = r // a
    return o.reshape(b, hkv, a, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, a, hkv * g, d
    )


def _row_mask(tree_mask, a, group):
    """``[A, A]`` tree mask (None = identity) -> one row per query row."""
    if tree_mask is None:
        mask = jnp.eye(a, dtype=jnp.int32)
    else:
        mask = jnp.asarray(tree_mask).astype(jnp.int32)
    return jnp.repeat(mask, group, axis=0)


def tree_decode_attention_fwd(
    q: jax.Array,           # [B, A, Hq, D]
    k_cache: jax.Array,     # [B, S, Hkv, D]
    v_cache: jax.Array,     # [B, S, Hkv, D]
    k_spec: jax.Array,      # [B, A, Hkv, D]
    v_spec: jax.Array,      # [B, A, Hkv, D]
    kv_len: jax.Array,      # [] or [B] i32
    tree_mask: jax.Array | None = None,   # [A, A]; None = identity
    *,
    block_k: int = 512,
    interpret: bool = True,
) -> jax.Array:
    b, a, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    group = hq // hkv
    r = a * group
    block_k = min(block_k, s)
    assert s % block_k == 0
    n_kv = s // block_k
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))

    kernel = functools.partial(
        _tree_decode_kernel, scale=1.0 / math.sqrt(d), block_k=block_k,
        n_kv=n_kv,
    )
    kv = pl.BlockSpec((None, block_k, hkv * d), lambda bi, ki: (bi, ki, 0))
    tail = pl.BlockSpec((None, a, hkv * d), lambda bi, ki: (bi, 0, 0))
    rows = pl.BlockSpec((None, hkv, r, d), lambda bi, ki: (bi, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        name="tree_decode_attention",
        grid=(b, n_kv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM), rows, kv, kv, tail, tail,
            pl.BlockSpec((r, a), lambda bi, ki: (0, 0)),
        ],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((b, hkv, r, d), q.dtype),
        scratch_shapes=softmax_scratch(hkv, r, d),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(
        lens, _rows(q, hkv),
        k_cache.reshape(b, s, hkv * d), v_cache.reshape(b, s, hkv * d),
        k_spec.reshape(b, a, hkv * d), v_spec.reshape(b, a, hkv * d),
        _row_mask(tree_mask, a, group),
    )
    return _unrows(out, a)


def paged_tree_decode_attention_fwd(
    q: jax.Array,           # [B, A, Hq, D]
    pool_k: jax.Array,      # [P, block_size, Hkv, D]
    pool_v: jax.Array,      # [P, block_size, Hkv, D]
    page_table: jax.Array,  # [B, n_pages] i32
    k_spec: jax.Array,      # [B, A, Hkv, D]
    v_spec: jax.Array,      # [B, A, Hkv, D]
    kv_len: jax.Array,      # [] or [B] i32
    tree_mask: jax.Array | None = None,
    *,
    interpret: bool = True,
) -> jax.Array:
    """Tree decode whose shared prefix lives in a paged block pool.

    The sequential grid axis walks logical pages; the physical pool block id
    comes from scalar-prefetched ``page_table`` inside the K/V index maps,
    so no dense gather of the prefix ever materializes.  Garbage table
    entries beyond the live pages are clipped into range and masked by
    ``kv_len``, exactly like ``paged_decode_attention_fwd``.
    """
    b, a, hq, d = q.shape
    p, block_size, hkv, _ = pool_k.shape
    n_pages = page_table.shape[1]
    group = hq // hkv
    r = a * group
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    table = jnp.clip(page_table.astype(jnp.int32), 0, p - 1)

    kernel = functools.partial(
        _paged_tree_decode_kernel, scale=1.0 / math.sqrt(d),
        block_k=block_size, n_kv=n_pages,
    )
    page = pl.BlockSpec(
        (None, block_size, hkv * d),
        lambda bi, pi, tab, lens: (tab[bi, pi], 0, 0),
    )
    tail = pl.BlockSpec(
        (None, a, hkv * d), lambda bi, pi, tab, lens: (bi, 0, 0)
    )
    rows = pl.BlockSpec(
        (None, hkv, r, d), lambda bi, pi, tab, lens: (bi, 0, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages),
        in_specs=[
            rows, page, page, tail, tail,
            pl.BlockSpec((r, a), lambda bi, pi, tab, lens: (0, 0)),
        ],
        out_specs=rows,
        scratch_shapes=softmax_scratch(hkv, r, d),
    )
    out = pl.pallas_call(
        kernel,
        name="paged_tree_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, r, d), q.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(
        table, lens, _rows(q, hkv),
        pool_k.reshape(p, block_size, hkv * d),
        pool_v.reshape(p, block_size, hkv * d),
        k_spec.reshape(b, a, hkv * d), v_spec.reshape(b, a, hkv * d),
        _row_mask(tree_mask, a, group),
    )
    return _unrows(out, a)
