"""Plain reference forward of the served model, and its lower-precision
control.

Written from the architecture the configuration file names (pre-norm
decoder: RMSNorm, GQA attention with optional q/k/v bias and rotate-half
rotary positions, SwiGLU MLP, untied LM head), in ``jax.numpy`` and
float32 with ``highest`` matmul precision.  It imports nothing of the
program; it reads the benchmark's own weights by their names in the
parameter tree (``embed``, ``blocks.attn.wq`` ...).  It runs layer by layer
over blocks of rows, so it fits beside the weights on one chip, and returns
the next-token logits at each row's last position.

``quant="fp8"`` is the control: every linear layer's operands rounded to
float8 e4m3 with a scale per row of activations and per output column of
weights (the path a later change could be tempted to take); attention and
norms stay float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _linear(x, w, quant):
    w = w.astype(F32)
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """Rotate-half rotary embedding at positions 0..S-1; x [B, S, H, D]."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs            # [S, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, q_chunk):
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


@functools.partial(jax.jit, static_argnames=("arch", "quant", "q_chunk"))
def _layer(x, blocks, i, *, arch, quant, q_chunk):
    hq, hkv, d, theta, eps, bias = arch
    p = jax.tree.map(lambda a: a[i], blocks)
    a = p["attn"]
    b, s, _ = x.shape
    h = _rms(x, p["attn_norm"], eps)
    q, k, v = (_linear(h, a[w], quant) for w in ("wq", "wk", "wv"))
    if bias:
        q, k, v = q + a["bq"].astype(F32), k + a["bk"].astype(F32), \
            v + a["bv"].astype(F32)
    q = _rope(q.reshape(b, s, hq, d), theta)
    k = _rope(k.reshape(b, s, hkv, d), theta)
    v = v.reshape(b, s, hkv, d)
    x = x + _linear(_attention(q, k, v, q_chunk), a["wo"], quant)
    m = p["mlp"]
    h = _rms(x, p["mlp_norm"], eps)
    # SwiGLU over chunks of the hidden width, so no whole float32 copy of a
    # projection is ever live.
    ff = m["w_gate"].shape[1]
    f_chunk = _divisor(ff, 4096)

    def mlp(j, acc):
        cols = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=j * f_chunk,
                                 slice_size=f_chunk, axis=1)
        up = jax.nn.silu(_linear(h, cols(m["w_gate"]), quant)) * _linear(
            h, cols(m["w_up"]), quant)
        down = jax.lax.dynamic_slice_in_dim(m["w_down"], j * f_chunk,
                                            f_chunk, axis=0)
        return acc + _linear(up, down, quant)

    return jax.lax.fori_loop(0, ff // f_chunk, mlp, x)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "quant", "v_chunk"))
def _head(x, last, norm, head, *, eps, quant, v_chunk):
    h = _rms(jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0], norm,
             eps)
    def cols(j):
        w = jax.lax.dynamic_slice_in_dim(head, j * v_chunk, v_chunk, axis=1)
        return _linear(h, w, quant)

    out = jax.lax.map(cols, jnp.arange(head.shape[1] // v_chunk))
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1)


def _divisor(n: int, at_most: int) -> int:
    c = max(1, min(n, at_most))
    while n % c:
        c -= 1
    return c


def last_logits(weights, config: dict, tokens, lengths, *, quant=None,
                tokens_per_block: int = 4096) -> np.ndarray:
    """Logits ``[n, V]`` (float32) after each row's ``lengths[i]`` tokens.

    ``tokens`` is ``[n, S]``; positions past a row's length do not reach
    its logits (causal attention), so rows keep the program's fixed width
    ``S`` and every call compiles one shape.
    """
    tokens = np.asarray(tokens, np.int32)
    lengths = np.asarray(lengths, np.int32)
    n, s = tokens.shape
    rows = max(1, tokens_per_block // s)
    arch = (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], float(config["rope_theta"]),
            float(config["rms_norm_eps"]), bool(config["qkv_bias"]))
    eps = float(config["rms_norm_eps"])
    # Attention scores of one query chunk stay near 2**27 floats (512 MiB).
    q_chunk = _divisor(
        s, max(1, 2 ** 27 // (rows * config["num_attention_heads"] * s))
    )
    v_chunk = _divisor(config["vocab_size"], 16384)
    layers = config["num_hidden_layers"]
    out = []
    for lo in range(0, n, rows):
        tok = tokens[lo: lo + rows]
        last = np.maximum(lengths[lo: lo + rows] - 1, 0)
        pad = rows - tok.shape[0]
        if pad:
            tok = np.concatenate([tok, np.repeat(tok[-1:], pad, 0)])
            last = np.concatenate([last, np.repeat(last[-1:], pad)])
        x = _embed(weights["embed"], jnp.asarray(tok))
        for i in range(layers):
            x = _layer(x, weights["blocks"], jnp.int32(i), arch=arch,
                       quant=quant, q_chunk=q_chunk)
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
