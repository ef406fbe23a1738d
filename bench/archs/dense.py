"""The dense decoder: pre-norm blocks of RMSNorm, GQA attention with
optional q/k/v bias and rotate-half rotary positions, and a SwiGLU MLP;
an untied LM head (Qwen2, Llama, DeepSeek-LLM).

FLOPs of a window (``step_mfu``): 2 per matmul parameter per token, over
the QKV, output and MLP projections of every layer, and the LM head
wherever the program applies it.  Attention's own FLOPs (scores and the
weighted sum of values) and the re-decode of divergent suffixes on refill
(catch-up chunks, which the service does not count) are left out, so the
count is a lower bound of the work done.

* decode: each busy tree row's ``W`` slots decode one token per master
  tick: ``busy_tree_ticks * W`` tokens, each through the layers and the
  head;
* staging prefill: each admitted request is prefilled at the padded
  length the program runs (``max_len``) through the layers, and through
  the head at its last position only.
"""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from bench.reference import (F32, attention, by_row_blocks, divisor, linear,
                             rms, rope)

#: Keys of a configuration file that name a mechanism this block does not
#: have (experts, windowed or mixed layers).  A file holding one describes
#: another architecture: built as a dense model it would agree with the
#: dense reference, and pass as the wrong model.
UNMODELLED = ("num_experts", "n_routed_experts", "num_local_experts",
              "moe_intermediate_size", "first_k_dense_replace",
              "scoring_func", "layer_types", "sliding_window")

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}

if TYPE_CHECKING:
    from bench.system import ModelConfig


def model_config(c: dict) -> ModelConfig:
    """The program's ``ModelConfig`` for a configuration file.

    The one function here that reaches the program: it imports
    :mod:`bench.system` when called, so the reference imports nothing of
    the program."""
    from bench import system

    extra = [k for k in UNMODELLED if k in c]
    if extra:
        raise ValueError(f"configuration {c['name']!r} holds {extra}, which "
                         f"the dense architecture does not model; name its "
                         f"module under the key 'arch'")
    return system.ModelConfig(
        name=c["name"],
        family="dense",
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"],
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        qkv_bias=c["qkv_bias"],
        rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"],
        dtype=system.DTYPES[c["torch_dtype"]],
    )


@functools.partial(jax.jit, static_argnames=("arch", "quant", "q_chunk"))
def _layer(x, blocks, i, *, arch, quant, q_chunk):
    hq, hkv, d, theta, eps, bias = arch
    p = jax.tree.map(lambda a: a[i], blocks)
    a = p["attn"]
    b, s, _ = x.shape
    h = rms(x, p["attn_norm"], eps)
    q, k, v = (linear(h, a[w], quant) for w in ("wq", "wk", "wv"))
    if bias:
        q, k, v = q + a["bq"].astype(F32), k + a["bk"].astype(F32), \
            v + a["bv"].astype(F32)
    q = rope(q.reshape(b, s, hq, d), theta)
    k = rope(k.reshape(b, s, hkv, d), theta)
    v = v.reshape(b, s, hkv, d)
    x = x + linear(attention(q, k, v, q_chunk), a["wo"], quant)
    m = p["mlp"]
    h = rms(x, p["mlp_norm"], eps)
    # SwiGLU over chunks of the hidden width, so no whole float32 copy of a
    # projection is ever live.
    ff = m["w_gate"].shape[1]
    f_chunk = divisor(ff, 4096)

    def mlp(j, acc):
        cols = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=j * f_chunk,
                                 slice_size=f_chunk, axis=1)
        up = jax.nn.silu(linear(h, cols(m["w_gate"]), quant)) * linear(
            h, cols(m["w_up"]), quant)
        down = jax.lax.dynamic_slice_in_dim(m["w_down"], j * f_chunk,
                                            f_chunk, axis=0)
        return acc + linear(up, down, quant)

    return jax.lax.fori_loop(0, ff // f_chunk, mlp, x)


def last_logits(weights, c: dict, tokens, lengths, *, quant=None,
                tokens_per_block: int = 4096):
    """Reference logits ``[n, V]`` after each row's ``lengths[i]`` tokens
    (:func:`bench.reference.by_row_blocks`)."""
    arch = (c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], float(c["rope_theta"]), float(c["rms_norm_eps"]),
            bool(c["qkv_bias"]))

    def stack(x, q_chunk):
        for i in range(c["num_hidden_layers"]):
            x = _layer(x, weights["blocks"], jnp.int32(i), arch=arch,
                       quant=quant, q_chunk=q_chunk)
        return x

    return by_row_blocks(weights, c, tokens, lengths, stack, quant=quant,
                         tokens_per_block=tokens_per_block)


def layer_matmul_params(c: dict) -> int:
    """Matmul parameters of one decoder layer of a configuration file."""
    d, ff, dh = c["hidden_size"], c["intermediate_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * ff


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def window_flops(c: dict, stats: dict, *, wave: int, max_len: int) -> float:
    layers = c["num_hidden_layers"] * layer_matmul_params(c)
    decode_tokens = stats["busy_tree_ticks"] * wave
    return 2.0 * (decode_tokens * (layers + head_params(c))
                  + stats["admissions"] * (max_len * layers + head_params(c)))


def attention_kv_bytes(c: dict, stats: dict) -> int:
    """Every slot of every tick reads each attended position's keys and
    values once per layer: ``ServeStats.attended_positions x layers x 2 x
    kv_heads x head_dim`` elements in the model's dtype."""
    return (stats.get("attended_positions", 0) * c["num_hidden_layers"] * 2
            * c["num_key_value_heads"] * c["head_dim"]
            * DTYPE_BYTES[c["torch_dtype"]])
