"""Model FLOPs of a window, counted from shapes and the service's counts.

Convention (``step_mfu``): 2 FLOPs per matmul parameter per token, over the
QKV, output and MLP projections of every layer, and the LM head wherever
the program applies it.  Attention's own FLOPs (scores and the weighted sum
of values) and the re-decode of divergent suffixes on refill (catch-up
chunks, which the service does not count) are left out, so the count is a
lower bound of the work done.

* decode: each busy tree row's ``W`` slots decode one token per master
  tick: ``busy_tree_ticks * W`` tokens, each through the layers and the
  head;
* staging prefill: each admitted request is prefilled at the padded
  length the program runs (``max_len``) through the layers, and through
  the head at its last position only.
"""

from __future__ import annotations


def layer_matmul_params(c: dict) -> int:
    """Matmul parameters of one decoder layer of a configuration file."""
    d, ff, dh = c["hidden_size"], c["intermediate_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * ff


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def window_flops(c: dict, *, busy_tree_ticks: int, wave: int,
                 admissions: int, max_len: int) -> float:
    layers = c["num_hidden_layers"] * layer_matmul_params(c)
    decode_tokens = busy_tree_ticks * wave
    return 2.0 * (decode_tokens * (layers + head_params(c))
                  + admissions * (max_len * layers + head_params(c)))
