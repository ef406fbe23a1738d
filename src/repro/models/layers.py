"""Transformer building blocks: RMSNorm, RoPE, GQA attention, SwiGLU, MoE.

Everything is a pure function over a parameter dict.  Attention defaults to a
chunked online-softmax formulation ("flash in jnp") whose memory is
O(S·chunk) instead of O(S²) — this is also the oracle the Pallas kernel in
``repro.kernels.flash_attention`` is validated against, and the path the
multi-pod dry-run compiles (Pallas cannot target the CPU backend).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Norms & rotary embeddings
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dtype)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S] (or [S])."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[..., None, :]                       # [B, S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def chunked_attention(
    q: jax.Array,           # [B, Sq, Hq, D]
    k: jax.Array,           # [B, Sk, Hkv, D]
    v: jax.Array,           # [B, Sk, Hkv, D]
    *,
    causal: bool = True,
    q_offset: jax.Array | int = 0,
    kv_len: Optional[jax.Array] = None,   # valid KV prefix length (decode)
    chunk: int = 1024,
) -> jax.Array:
    """Online-softmax attention over KV chunks (GQA-aware).

    ``q_offset`` is the absolute position of q[0] (for causal masking during
    chunked prefill / decode); it may be a scalar or a per-row ``[B]`` vector
    (ragged chunked catch-up: every row decodes its chunk at its own
    offset).  ``kv_len`` masks the KV tail (cache slots that have not been
    written yet); scalar or per-row ``[B]``.
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)
    chunk = min(chunk, sk)
    if sk % chunk != 0:
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_len = jnp.minimum(kv_len, sk) if kv_len is not None else jnp.int32(sk)
        sk = sk + pad
    n_chunks = sk // chunk

    # Inputs stay in their storage dtype (bf16 on TPU); matmuls accumulate in
    # f32 via preferred_element_type — no f32 copy of K/V ever materializes
    # (an f32 cache copy doubles HBM traffic and, sharded, doubles any
    # resharding collective — see EXPERIMENTS.md §Perf iteration B1).
    qf = q.reshape(b, sq, hkv, group, d)
    kc = k.reshape(b, n_chunks, chunk, hkv, d)
    vc = v.reshape(b, n_chunks, chunk, hkv, d)

    # [Bq, Sq] with Bq in {1, B}: scalar offsets broadcast, vector offsets
    # give each row its own causal frontier.
    q_pos = (
        jnp.asarray(q_offset, jnp.int32).reshape(-1, 1) + jnp.arange(sq)
    )
    kl = None if kv_len is None else jnp.asarray(kv_len).reshape(-1)

    def body(carry, xs):
        m, l, acc = carry
        k_i, v_i, idx = xs
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qf, k_i,
            preferred_element_type=jnp.float32,
        ) * scale                                             # [B,Hkv,G,Sq,C]
        kv_pos = idx * chunk + jnp.arange(chunk)              # [C]
        mask = jnp.ones((q_pos.shape[0], sq, chunk), jnp.bool_)
        if causal:
            mask = mask & (q_pos[:, :, None] >= kv_pos[None, None, :])
        if kl is not None:
            mask = mask & (kv_pos[None, None, :] < kl[:, None, None])
        mask = mask[:, None, None]                       # [B?,1,1,Sq,C]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(v_i.dtype), v_i,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l, acc), None

    init = (
        jnp.full((b, hkv, group, sq), NEG_INF, jnp.float32),
        jnp.zeros((b, hkv, group, sq), jnp.float32),
        jnp.zeros((b, hkv, group, sq, d), jnp.float32),
    )
    xs = (
        jnp.moveaxis(kc, 1, 0),
        jnp.moveaxis(vc, 1, 0),
        jnp.arange(n_chunks),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, xs)
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out = jnp.moveaxis(out.reshape(b, hq, sq, d), 1, 2)       # [B,Sq,Hq,D]
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,          # [B, 1, Hq, D]
    k_cache: jax.Array,    # [B, S, Hkv, D]
    v_cache: jax.Array,    # [B, S, Hkv, D]
    kv_len: jax.Array,     # [] or [B] — number of valid cache entries
) -> jax.Array:
    """Single-token attention over a (possibly long) KV cache."""
    b, _, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)
    # Storage-dtype streaming with f32 accumulation (see §Perf iteration B1):
    # never materialize an f32 copy of the KV cache.
    qf = q.reshape(b, hkv, group, d)
    scores = jnp.einsum(
        "bhgd,bshd->bhgs", qf, k_cache, preferred_element_type=jnp.float32
    ) * scale
    pos = jnp.arange(s)
    valid = pos[None, :] < jnp.reshape(kv_len, (-1, 1))       # [B or 1, S]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def paged_decode_attention(
    q: jax.Array,           # [B, 1, Hq, D]
    pool_k: jax.Array,      # [P, block_size, Hkv, D] — shared block pool
    pool_v: jax.Array,      # [P, block_size, Hkv, D]
    page_table: jax.Array,  # [B, n_pages] i32
    kv_len: jax.Array,      # [] or [B]
) -> jax.Array:
    """Single-token attention over a paged (block-sparse) KV cache.

    jnp oracle for the Pallas kernel in ``kernels/decode_attention``: gather
    each row's pages into a dense view, then run the ragged decode path.
    Table entries beyond ``ceil(kv_len / block_size)`` may be garbage — they
    are clipped into pool range and their positions masked by ``kv_len``.
    """
    b = q.shape[0]
    p, block_size, hkv, d = pool_k.shape
    n_pages = page_table.shape[1]
    tab = jnp.clip(page_table.astype(jnp.int32), 0, p - 1)
    k = pool_k[tab].reshape(b, n_pages * block_size, hkv, d)
    v = pool_v[tab].reshape(b, n_pages * block_size, hkv, d)
    return decode_attention(q, k, v, kv_len)


def tree_decode_attention(
    q: jax.Array,           # [B, A, Hq, D] — A speculative queries per row
    k_cache: jax.Array,     # [B, S, Hkv, D]
    v_cache: jax.Array,     # [B, S, Hkv, D]
    k_spec: jax.Array,      # [B, A, Hkv, D] — speculative tail keys
    v_spec: jax.Array,      # [B, A, Hkv, D]
    kv_len: jax.Array,      # [] or [B] — number of valid cache entries
    tree_mask: Optional[jax.Array] = None,   # [A, A] bool; default identity
) -> jax.Array:
    """Tree-batched speculative decode: A candidate tokens share one prefix.

    Every query sits at absolute position ``kv_len`` and attends to the full
    valid prefix plus the speculative tail entries ``tree_mask[i, :]`` allows
    (identity by default: each candidate sees only its own K/V).  The tail
    K/V live OUTSIDE the cache — nothing here writes cache state, which is
    what makes the frontier scores safe to throw away or commit later.

    jnp oracle for the Pallas kernel in ``kernels/decode_attention``.
    """
    b, a, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qf = q.reshape(b, a, hkv, group, d)
    scores = jnp.einsum(
        "bahgd,bshd->bahgs", qf, k_cache, preferred_element_type=jnp.float32
    ) * scale                                                  # [B,A,Hkv,G,S]
    pos = jnp.arange(s)
    valid = pos[None, :] < jnp.reshape(kv_len, (-1, 1))        # [B or 1, S]
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    tail = jnp.einsum(
        "bahgd,bjhd->bahgj", qf, k_spec, preferred_element_type=jnp.float32
    ) * scale                                                  # [B,A,Hkv,G,A]
    if tree_mask is None:
        tree_mask = jnp.eye(a, dtype=jnp.bool_)
    attend = jnp.asarray(tree_mask).astype(jnp.bool_)
    tail = jnp.where(attend[None, :, None, None, :], tail, NEG_INF)
    full = jnp.concatenate([scores, tail], axis=-1)            # [B,A,Hkv,G,S+A]
    p = jax.nn.softmax(full, axis=-1)
    v_full = jnp.concatenate([v_cache, v_spec], axis=1)        # [B,S+A,Hkv,D]
    out = jnp.einsum(
        "bahgs,bshd->bahgd", p.astype(v_full.dtype), v_full,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, a, hq, d).astype(q.dtype)


def paged_tree_decode_attention(
    q: jax.Array,           # [B, A, Hq, D]
    pool_k: jax.Array,      # [P, block_size, Hkv, D]
    pool_v: jax.Array,      # [P, block_size, Hkv, D]
    page_table: jax.Array,  # [B, n_pages] i32
    k_spec: jax.Array,      # [B, A, Hkv, D]
    v_spec: jax.Array,      # [B, A, Hkv, D]
    kv_len: jax.Array,      # [] or [B]
    tree_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Tree-batched speculative decode over a paged prefix (jnp oracle).

    Same gather-then-dense strategy as ``paged_decode_attention``: table
    entries beyond the live pages may be garbage — clipped into pool range,
    positions masked by ``kv_len``.
    """
    b = q.shape[0]
    p, block_size, hkv, d = pool_k.shape
    n_pages = page_table.shape[1]
    tab = jnp.clip(page_table.astype(jnp.int32), 0, p - 1)
    k = pool_k[tab].reshape(b, n_pages * block_size, hkv, d)
    v = pool_v[tab].reshape(b, n_pages * block_size, hkv, d)
    return tree_decode_attention(q, k, v, k_spec, v_spec, kv_len, tree_mask)


# ---------------------------------------------------------------------------
# Attention module (projections + rope + cache handling)
# ---------------------------------------------------------------------------


def init_attention(key, cfg, d_model=None, dtype=None):
    d = d_model or cfg.d_model
    hd, hq, hkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    dtype = dtype or cfg.dtype
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    p = {
        "wq": (jax.random.normal(k1, (d, hq * hd)) * std).astype(dtype),
        "wk": (jax.random.normal(k2, (d, hkv * hd)) * std).astype(dtype),
        "wv": (jax.random.normal(k3, (d, hkv * hd)) * std).astype(dtype),
        "wo": (jax.random.normal(k4, (hq * hd, d)) * std).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    return p


def attention_qkv(p, cfg, x, positions, rope: bool = True):
    b, s, _ = x.shape
    hd, hq, hkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _use_pallas(cfg) -> bool:
    """Whether attention runs through the Pallas kernels.

    ``attn_impl='auto'`` follows the platform: the kernels on a TPU backend,
    the jnp paths (their test oracles) elsewhere.  ``'pallas'`` and
    ``'xla'`` force one side (interpret-mode parity tests, the CPU dry-run).
    """
    if cfg.attn_impl == "auto":
        return jax.default_backend() == "tpu"
    return cfg.attn_impl == "pallas"


def _flash_block(sq: int) -> int | None:
    """Largest q/kv block (at most 256) that tiles ``sq`` and that the TPU
    compiler can lay out — a multiple of 8 or the whole length — or None
    when there is none (e.g. ``sq=300`` would need 4-row blocks)."""
    bq = min(256, sq)
    while sq % bq:
        bq //= 2
    return bq if bq == sq or bq % 8 == 0 else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_causal(q, k, v, block, chunk):
    """Causal attention through the flash kernel.  The kernel has a forward
    pass only, so the gradient is ``chunked_attention``'s, recomputed from
    q, k and v (training differentiates through this path)."""
    from ..kernels.flash_attention.ops import flash_attention

    return flash_attention(q, k, v, causal=True, block_q=block, block_k=block)


def _flash_causal_fwd(q, k, v, block, chunk):
    return _flash_causal(q, k, v, block, chunk), (q, k, v)


def _flash_causal_bwd(block, chunk, res, g):
    _, vjp = jax.vjp(
        functools.partial(chunked_attention, causal=True, chunk=chunk), *res
    )
    return vjp(g)


_flash_causal.defvjp(_flash_causal_fwd, _flash_causal_bwd)


def attention_block(
    p,
    cfg,
    x,                       # [B, S, d]
    positions,               # [B, S]
    *,
    causal: bool = True,
    rope: bool = True,
    cache=None,              # optional dict(k, v, len) — decode/prefill cache
):
    """Full attention block; returns (out, new_cache).

    When :func:`_use_pallas` holds (on a TPU by default) the no-cache causal
    path runs the flash-attention kernel (gradient from ``chunked_attention``;
    lengths :func:`_flash_block` cannot tile stay on ``chunked_attention``)
    and single-token decode — scalar or
    per-slot vector cache lengths, dense or paged — the split-KV decode
    kernels.  Chunked prefill with an offset (cached prefill and catch-up
    chunks) has no kernel and always runs ``chunked_attention``, the jnp
    path the kernels are checked against in tests/test_kernels.py.
    """
    q, k, v = attention_qkv(p, cfg, x, positions, rope=rope)
    if cache is not None and "table" in cache:
        # Paged decode: K/V live in a shared block pool addressed through a
        # per-row page table.  The caller pre-computes the physical write
        # target — ``write_block``/``write_off`` per row, with block id == P
        # (out of range) meaning "do not write" (masked slot / exhausted
        # pool) — and ``len`` is the ATTEND length (it already counts the
        # token being written, where one is).  Drop-mode scatter keeps the
        # whole thing one fused batched op.
        assert x.shape[1] == 1, "paged cache supports single-token decode"
        wb, wo = cache["write_block"], cache["write_off"]
        kc = cache["k"].at[wb, wo].set(
            k[:, 0].astype(cache["k"].dtype), mode="drop"
        )
        vc = cache["v"].at[wb, wo].set(
            v[:, 0].astype(cache["v"].dtype), mode="drop"
        )
        if _use_pallas(cfg):
            from ..kernels.decode_attention.ops import (
                paged_decode_attention as _pdk,
            )

            out = _pdk(q[:, 0], kc, vc, cache["table"], cache["len"])[:, None]
        else:
            out = paged_decode_attention(
                q, kc, vc, cache["table"], cache["len"]
            )
        new_cache = dict(cache, k=kc, v=vc)
        b, s = x.shape[:2]
        out = out.reshape(b, s, cfg.num_heads * cfg.head_dim) @ p["wo"]
        return out, new_cache
    if cache is None:
        block = _flash_block(q.shape[1])
        if _use_pallas(cfg) and causal and block is not None:
            out = _flash_causal(q, k, v, block, cfg.attn_chunk)
        else:
            out = chunked_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk)
        new_cache = None
    else:
        # Write new K/V at cache['len']: prefill writes S entries from 0,
        # decode writes one entry at len.  ``len`` may be a scalar (uniform
        # batch: dry-run cells) or a per-slot [B] vector (continuous-batching
        # engine; decode only).
        start = cache["len"]
        if jnp.ndim(start) == 0:
            kc = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), start, axis=1
            )
            vc = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), start, axis=1
            )
        elif x.shape[1] == 1:
            bidx = jnp.arange(x.shape[0])
            kc = cache["k"].at[bidx, start].set(k[:, 0].astype(cache["k"].dtype))
            vc = cache["v"].at[bidx, start].set(v[:, 0].astype(cache["v"].dtype))
        else:
            # Ragged chunk write (chunked catch-up refill): every row writes
            # its S new entries at its own offset; positions past the cache
            # end are dropped (rows already caught up write only into their
            # garbage-beyond-len region, which stays garbage).
            bidx = jnp.arange(x.shape[0])[:, None]
            pos = start[:, None] + jnp.arange(x.shape[1])[None, :]
            kc = cache["k"].at[bidx, pos].set(
                k.astype(cache["k"].dtype), mode="drop"
            )
            vc = cache["v"].at[bidx, pos].set(
                v.astype(cache["v"].dtype), mode="drop"
            )
        new_len = jnp.minimum(start + x.shape[1], cache["k"].shape[1])
        if x.shape[1] == 1:
            # The decode kernel takes scalar or per-slot [B] cache lengths,
            # so the ragged continuous-batching path is covered too.
            if _use_pallas(cfg):
                from ..kernels.decode_attention.ops import decode_attention as _dk

                bk = max(1, min(512, kc.shape[1]))
                while kc.shape[1] % bk:
                    bk //= 2
                out = _dk(q[:, 0], kc, vc, new_len, block_k=bk)[:, None]
            else:
                out = decode_attention(q, kc, vc, new_len)
        else:
            out = chunked_attention(
                q, kc, vc, causal=causal, q_offset=start, kv_len=new_len,
                chunk=cfg.attn_chunk,
            )
        new_cache = {"k": kc, "v": vc, "len": new_len}
    b, s = x.shape[:2]
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim) @ p["wo"]
    return out, new_cache


def tree_attention_block(p, cfg, x, positions, k_cache, v_cache, kv_len):
    """Frontier attention: ``A`` speculative queries over a READ-ONLY cache.

    ``x`` is ``[N, A, d]`` — the A candidate tokens of each slot, all sitting
    at absolute position ``kv_len`` (the same ``positions`` row for every
    candidate).  Unlike :func:`attention_block`, the cache is never written:
    each candidate's own K/V ride along as the speculative tail
    (identity tree mask), and the caller decides which candidate — if any —
    to commit later.  Returns ``(out [N, A, d], k_spec, v_spec)``.
    """
    q, k, v = attention_qkv(p, cfg, x, positions)
    if _use_pallas(cfg):
        from ..kernels.decode_attention.ops import tree_decode_attention as _tk

        s = k_cache.shape[1]
        bk = max(1, min(512, s))
        while s % bk:
            bk //= 2
        out = _tk(q, k_cache, v_cache, k, v, kv_len, block_k=bk)
    else:
        out = tree_decode_attention(q, k_cache, v_cache, k, v, kv_len)
    n, a = x.shape[:2]
    out = out.reshape(n, a, cfg.num_heads * cfg.head_dim) @ p["wo"]
    return out, k, v


def paged_tree_attention_block(
    p, cfg, x, positions, pool_k, pool_v, page_table, kv_len
):
    """Frontier attention over a paged prefix (read-only, pool never written).

    Same contract as :func:`tree_attention_block` with the shared prefix
    addressed through a per-row page table.
    """
    q, k, v = attention_qkv(p, cfg, x, positions)
    if _use_pallas(cfg):
        from ..kernels.decode_attention.ops import (
            paged_tree_decode_attention as _ptk,
        )

        out = _ptk(q, pool_k, pool_v, page_table, k, v, kv_len)
    else:
        out = paged_tree_decode_attention(
            q, pool_k, pool_v, page_table, k, v, kv_len
        )
    n, a = x.shape[:2]
    out = out.reshape(n, a, cfg.num_heads * cfg.head_dim) @ p["wo"]
    return out, k, v


def cross_attention_block(p, cfg, x, enc_kv):
    """Enc-dec cross attention: q from x, K/V precomputed from encoder."""
    b, s, _ = x.shape
    hd, hq = cfg.head_dim, cfg.num_heads
    q = (x @ p["wq"]).reshape(b, s, hq, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(hq, hd)
    out = chunked_attention(
        q, enc_kv["k"], enc_kv["v"], causal=False,
        chunk=min(cfg.attn_chunk, enc_kv["k"].shape[1]),
    )
    return out.reshape(b, s, hq * hd) @ p["wo"]


# ---------------------------------------------------------------------------
# Dense SwiGLU MLP
# ---------------------------------------------------------------------------


def init_mlp(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    std = 0.02
    return {
        "w_gate": (jax.random.normal(k1, (d_model, d_ff)) * std).astype(dtype),
        "w_up": (jax.random.normal(k2, (d_model, d_ff)) * std).astype(dtype),
        "w_down": (jax.random.normal(k3, (d_ff, d_model)) * std).astype(dtype),
    }


def mlp_block(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE: top-k routing with capacity-bounded scatter dispatch.
#
# Dispatch avoids the O(T·E·C) one-hot tensor: token positions inside each
# expert come from a cumsum over the [T, E] assignment matrix, tokens are
# scattered into an [E·C, d] buffer, experts run as one batched matmul
# ([E, C, d] @ [E, d, f] — MXU-shaped, EP-shardable on E), and results gather
# back with gate weighting.  HLO FLOPs ≈ active-expert FLOPs (top-k/E of
# dense), which keeps the roofline "useful compute" ratio honest.
# ---------------------------------------------------------------------------


def init_moe(key, cfg, dtype):
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    std = 0.02
    p = {
        "router": (jax.random.normal(k1, (d, e)) * std).astype(jnp.float32),
        "w_gate": (jax.random.normal(k2, (e, d, f)) * std).astype(dtype),
        "w_up": (jax.random.normal(k3, (e, d, f)) * std).astype(dtype),
        "w_down": (jax.random.normal(k4, (e, f, d)) * std).astype(dtype),
    }
    if cfg.shared_expert_d_ff:
        p["shared"] = init_mlp(k5, d, cfg.shared_expert_d_ff, dtype)
    return p


def moe_block(p, cfg, x):
    """MoE layer.  x: [B, S, d] → (out [B, S, d], aux_loss []).

    Under a mesh with a >1 ``model`` axis the routed experts run inside a
    ``shard_map`` (true expert parallelism): tokens stay sharded over the
    data axes and replicated over ``model``; each model shard dispatches to
    its local experts with *local* capacity and the combine is one psum over
    ``model`` — the same communication class as a Megatron MLP.  Without a
    mesh the local dense-buffer path below runs (smoke tests, CPU search).
    """
    from ..distributed.sharding import ambient_abstract_mesh

    mesh = ambient_abstract_mesh()
    tp = dict(mesh.shape).get("model", 1)
    if tp > 1 and cfg.num_experts % tp == 0:
        out, aux = _moe_block_sharded(p, cfg, x, mesh)
        if "shared" in p:
            out = out + mlp_block(p["shared"], x)
        return out, aux
    return _moe_block_local(p, cfg, x)


def _moe_block_sharded(p, cfg, x, mesh):
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def inner(xb, router, wg, wu, wd):
        bl, sl, _ = xb.shape
        t = bl * sl
        xt = xb.reshape(t, d)
        e_loc = wg.shape[0]
        e_off = jax.lax.axis_index("model") * e_loc

        logits = xt.astype(jnp.float32) @ router                 # [T, E]
        if cfg.num_experts_real is not None and cfg.num_experts_real < e:
            logits = jnp.where(jnp.arange(e) >= cfg.num_experts_real, -1e30, logits)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        density = jnp.mean(
            jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), axis=0
        )
        aux = jnp.sum(density * jnp.mean(probs, axis=0)) * e * cfg.router_aux_weight
        if dp:
            aux = jax.lax.pmean(aux, dp)

        capacity = int(max(1, math.ceil(t * k / e * cfg.capacity_factor)))
        flat_e = expert_idx.reshape(-1)                          # [T*k]
        local = (flat_e >= e_off) & (flat_e < e_off + e_loc)
        local_e = jnp.clip(flat_e - e_off, 0, e_loc - 1)
        onehot = jnp.where(
            local[:, None],
            jax.nn.one_hot(local_e, e_loc, dtype=jnp.int32),
            0,
        )
        pos = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - onehot, local_e[:, None], axis=1
        )[:, 0]
        keep = local & (pos < capacity)
        slot = jnp.where(
            keep, local_e * capacity + jnp.minimum(pos, capacity - 1),
            e_loc * capacity,
        )
        buf = jnp.zeros((e_loc * capacity + 1, d), xb.dtype)
        buf = buf.at[slot].set(jnp.repeat(xt, k, axis=0))
        expert_in = buf[: e_loc * capacity].reshape(e_loc, capacity, d)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, wg))
        h = h * jnp.einsum("ecd,edf->ecf", expert_in, wu)
        expert_out = jnp.einsum("ecf,efd->ecd", h, wd)

        flat_out = jnp.concatenate(
            [expert_out.reshape(e_loc * capacity, d),
             jnp.zeros((1, d), xb.dtype)], axis=0,
        )
        gathered = flat_out[slot].reshape(t, k, d)
        gates = (gate_vals * keep.reshape(t, k)).astype(xb.dtype)
        out = jnp.einsum("tkd,tk->td", gathered, gates)
        out = jax.lax.psum(out, "model")                         # EP combine
        return out.reshape(bl, sl, d), aux

    P = jax.sharding.PartitionSpec
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    out, aux = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P(dp_spec, None, None),
            P(),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(dp_spec, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, aux


def _moe_block_local(p, cfg, x):
    """Single-device reference MoE (dense scatter dispatch)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ p["router"])            # [T, E]
    if cfg.num_experts_real is not None and cfg.num_experts_real < e:
        pad_mask = jnp.arange(e) >= cfg.num_experts_real
        logits = jnp.where(pad_mask, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)            # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Load-balancing auxiliary loss (Switch-style).
    density = jnp.mean(
        jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), axis=0
    )
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * e * cfg.router_aux_weight

    capacity = int(max(1, math.ceil(t * k / e * cfg.capacity_factor)))

    # Position of each (token, slot) within its expert's buffer.
    flat_expert = expert_idx.reshape(-1)                        # [T*k]
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)    # [T*k, E]
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot)       # [T*k, E]
    pos = jnp.take_along_axis(
        pos_in_expert, flat_expert[:, None], axis=1
    )[:, 0]                                                     # [T*k]
    keep = pos < capacity
    slot = flat_expert * capacity + jnp.minimum(pos, capacity - 1)
    slot = jnp.where(keep, slot, e * capacity)                  # overflow bin

    buf = jnp.zeros((e * capacity + 1, d), x.dtype)
    tok_rep = jnp.repeat(xt, k, axis=0)                         # [T*k, d]
    buf = buf.at[slot].set(tok_rep)                             # last-write wins
    expert_in = buf[: e * capacity].reshape(e, capacity, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, p["w_up"])
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])

    flat_out = jnp.concatenate(
        [expert_out.reshape(e * capacity, d), jnp.zeros((1, d), x.dtype)], axis=0
    )
    gathered = flat_out[slot].reshape(t, k, d)
    gates = (gate_vals * keep.reshape(t, k)).astype(x.dtype)
    out = jnp.einsum("tkd,tk->td", gathered, gates).reshape(b, s, d)

    if "shared" in p:
        out = out + mlp_block(p["shared"], x)
    return out, aux
