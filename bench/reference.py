"""Float32 building blocks of the plain reference forwards, and the loop
that runs one over blocks of rows.

Each architecture's reference (``bench/archs/<arch>.py``) is written from
its configuration file with these: ``jax.numpy`` in float32 with
``highest`` matmul precision.  They import nothing of the program, and read
the benchmark's own weights by their names in the parameter tree
(``embed``, ``final_norm``, ``lm_head`` ...).  :func:`by_row_blocks` runs
an architecture's decoder stack layer by layer over blocks of rows, so it
fits beside the weights on one chip, and returns the next-token logits at
each row's last position.

``quant="fp8"`` is the control: every linear layer's operands rounded to
float8 e4m3 with a scale per row of activations and per output column of
weights (the path a later change could be tempted to take); attention and
norms stay float32.
"""

from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def linear(x, w, quant):
    w = w.astype(F32)
    if quant == "fp8":
        x, w = q8(x, -1), q8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, theta):
    """Rotate-half rotary embedding at positions 0..S-1; x [B, S, H, D]."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs            # [S, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, q_chunk):
    """Causal GQA attention; q [B, S, Hq, D], k/v [B, S, Hkv, D].  Query
    head h reads KV head h // (Hq / Hkv)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, d) / math.sqrt(d)
    kpos = jnp.arange(s)
    n = s // q_chunk

    def chunk(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * q_chunk, q_chunk, axis=1)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qi, k, precision=HIGHEST)
        qpos = i * q_chunk + jnp.arange(q_chunk)
        sc = jnp.where(qpos[:, None] >= kpos[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=HIGHEST)
        return o.reshape(b, q_chunk, hq, d)

    out = jax.lax.map(chunk, jnp.arange(n))                   # [n, B, C, Hq, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, hq * d)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "quant", "v_chunk"))
def _head(x, last, norm, head, *, eps, quant, v_chunk):
    h = rms(jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0], norm,
            eps)
    def cols(j):
        w = jax.lax.dynamic_slice_in_dim(head, j * v_chunk, v_chunk, axis=1)
        return linear(h, w, quant)

    out = jax.lax.map(cols, jnp.arange(head.shape[1] // v_chunk))
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1)


def divisor(n: int, at_most: int) -> int:
    """The largest divisor of ``n`` that is at most ``at_most``."""
    c = max(1, min(n, at_most))
    while n % c:
        c -= 1
    return c


def by_row_blocks(weights, config: dict, tokens, lengths,
                  stack: Callable, *, quant, tokens_per_block: int
                  ) -> np.ndarray:
    """Logits ``[n, V]`` (float32) after each row's ``lengths[i]`` tokens.

    ``tokens`` is ``[n, S]``; positions past a row's length do not reach
    its logits (causal attention), so rows keep the program's fixed width
    ``S`` and every call compiles one shape.  ``stack(x, q_chunk)`` applies
    the architecture's decoder layers to a block of embedded rows ``x``
    ``[rows, S, d]``, with attention over query chunks of ``q_chunk``;
    the embedding, the final norm and the LM head are applied here.
    """
    tokens = np.asarray(tokens, np.int32)
    lengths = np.asarray(lengths, np.int32)
    n, s = tokens.shape
    rows = max(1, tokens_per_block // s)
    eps = float(config["rms_norm_eps"])
    # Attention scores of one query chunk stay near 2**27 floats (512 MiB).
    q_chunk = divisor(
        s, max(1, 2 ** 27 // (rows * config["num_attention_heads"] * s))
    )
    v_chunk = divisor(config["vocab_size"], 16384)
    out = []
    for lo in range(0, n, rows):
        tok = tokens[lo: lo + rows]
        last = np.maximum(lengths[lo: lo + rows] - 1, 0)
        pad = rows - tok.shape[0]
        if pad:
            tok = np.concatenate([tok, np.repeat(tok[-1:], pad, 0)])
            last = np.concatenate([last, np.repeat(last[-1:], pad)])
        x = stack(_embed(weights["embed"], jnp.asarray(tok)), q_chunk)
        logits = _head(x, jnp.asarray(last), weights["final_norm"],
                       weights["lm_head"], eps=eps, quant=quant,
                       v_chunk=v_chunk)
        out.append(np.asarray(logits)[: rows - pad])
    return (np.concatenate(out) if out
            else np.zeros((0, config["vocab_size"]), np.float32))


def rel_l2(got, want) -> np.ndarray:
    """Per-row relative L2 error of ``got`` against ``want``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.maximum(
        np.linalg.norm(want, axis=-1), 1e-30
    )
