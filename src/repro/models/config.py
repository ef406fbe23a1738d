"""Model configuration for every architecture family in the assignment.

One dataclass covers dense / MoE / SSM / hybrid / VLM-stub / enc-dec; the
family switch selects the block composition.  Configs for the 10 assigned
architectures live in ``repro.configs``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | vlm | encdec
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None        # default d_model // num_heads

    # --- MoE ---
    num_experts: int = 0                  # routed experts
    num_experts_per_tok: int = 0
    moe_d_ff: int = 0                     # per-expert hidden size
    shared_expert_d_ff: int = 0           # fused shared-experts hidden size
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # EP divisibility padding: experts >= num_experts_real are dead (router
    # logits masked to -inf); set by launch/cells._pad_experts.
    num_experts_real: Optional[int] = None

    # --- SSM (Mamba-2 / SSD) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_kernel: int = 4
    ssd_chunk: int = 256

    # --- hybrid (Zamba2-style) ---
    attn_every: int = 0                   # shared attn block every k SSM blocks

    # --- VLM stub ---
    num_patches: int = 0                  # precomputed patch embeds prepended

    # --- enc-dec (Whisper) ---
    num_encoder_layers: int = 0
    encoder_seq: int = 0                  # precomputed frame embeds (stub)

    # --- misc ---
    qkv_bias: bool = False
    rope_theta: float = 1e4
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    # Attention implementation: 'auto' picks the Pallas kernels on a TPU
    # backend and the chunked online-softmax jnp path elsewhere (see
    # layers._use_pallas); 'pallas' or 'xla' forces one.  The SSD scan kernel
    # runs only under an explicit 'pallas': it does not lower for the TPU.
    attn_impl: str = "auto"
    attn_chunk: int = 1024
    remat: bool = True
    # scan_layers=False unrolls the layer loop (used by the dry-run roofline
    # extrapolation; XLA cost analysis counts while-bodies once).
    scan_layers: bool = True
    # Chunked cross-entropy: peak logits memory = B*loss_chunk*V instead of
    # B*S*V.  0 = unchunked.
    loss_chunk: int = 0
    # prefill computes logits for the last position only (serving does not
    # need the rest) — saves a [B,S,V] matmul.
    prefill_logits_last_only: bool = False
    # Megatron-style sequence parallelism: residual stream sharded over
    # (seq × model-axis) at block boundaries, turning TP all-reduces into
    # reduce-scatter + all-gather pairs (half the wire bytes) and sharding
    # the norms.  No-op outside a mesh or when seq doesn't divide.
    seq_shard_activations: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // max(self.num_heads, 1))

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic decode: SSM / hybrid only (per assignment rules)."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Approximate parameter count (for 6·N·D roofline bookkeeping)."""
        d, L, V = self.d_model, self.num_layers, self.vocab_size
        hd = self.head_dim
        n_embed = V * d * (1 if self.tie_embeddings else 2)
        total = n_embed
        if self.family in ("dense", "moe", "vlm"):
            attn = d * (self.num_heads * hd) + 2 * d * (self.num_kv_heads * hd) \
                + (self.num_heads * hd) * d
            if self.family == "moe":
                ffn = 3 * d * self.moe_d_ff * self.num_experts \
                    + 3 * d * self.shared_expert_d_ff + d * self.num_experts
            else:
                ffn = 3 * d * self.d_ff
            total += L * (attn + ffn + 2 * d)
        elif self.family == "ssm":
            di, H, N = self.d_inner, self.ssm_heads, self.ssm_state
            blk = d * di * 2 + d * 2 * N + d * H + di * d \
                + self.conv_kernel * (di + 2 * N) + 3 * H + di
            total += L * (blk + d)
        elif self.family == "hybrid":
            di, H, N = self.d_inner, self.ssm_heads, self.ssm_state
            blk = d * di * 2 + d * 2 * N + d * H + di * d \
                + self.conv_kernel * (di + 2 * N) + 3 * H + di
            shared_attn = d * (self.num_heads * hd) + 2 * d * (self.num_kv_heads * hd) \
                + (self.num_heads * hd) * d + 3 * d * self.d_ff + 2 * d
            total += L * (blk + d) + shared_attn
        elif self.family == "encdec":
            attn = d * (self.num_heads * hd) + 2 * d * (self.num_kv_heads * hd) \
                + (self.num_heads * hd) * d
            ffn = 3 * d * self.d_ff
            total += self.num_encoder_layers * (attn + ffn + 2 * d)
            total += L * (2 * attn + ffn + 3 * d)   # self + cross attention
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: routed top-k + shared only)."""
        if self.family != "moe":
            return self.param_count()
        d, L = self.d_model, self.num_layers
        hd = self.head_dim
        attn = d * (self.num_heads * hd) + 2 * d * (self.num_kv_heads * hd) \
            + (self.num_heads * hd) * d
        ffn = 3 * d * self.moe_d_ff * self.num_experts_per_tok \
            + 3 * d * self.shared_expert_d_ff + d * self.num_experts
        n_embed = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return n_embed + L * (attn + ffn + 2 * d)


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Tiny same-family config for CPU smoke tests."""
    base = dict(
        num_layers=2,
        d_model=64,
        num_heads=4,
        num_kv_heads=max(1, min(cfg.num_kv_heads, 2)),
        head_dim=16,
        d_ff=128,
        vocab_size=256,
        dtype=jnp.float32,
        attn_chunk=64,
        remat=False,
    )
    if cfg.family == "moe":
        base.update(num_experts=4, num_experts_per_tok=2, moe_d_ff=64,
                    shared_expert_d_ff=64 if cfg.shared_expert_d_ff else 0)
    if cfg.family in ("ssm", "hybrid"):
        base.update(ssm_state=16, ssm_head_dim=16, ssd_chunk=16)
    if cfg.family == "hybrid":
        base.update(attn_every=2)
    if cfg.family == "vlm":
        base.update(num_patches=8)
    if cfg.family == "encdec":
        base.update(num_encoder_layers=2, encoder_seq=16)
    base.update(overrides)
    return dataclasses.replace(cfg, name=cfg.name + "-smoke", **base)
