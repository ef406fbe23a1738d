"""Unified language model covering all assigned architecture families.

* ``dense`` — pre-norm GQA transformer (llama3 / phi3 / deepseek / qwen2.5)
* ``moe``   — dense attention + routed-expert MLP (+ fused shared experts)
* ``ssm``   — Mamba-2 stack (attention-free)
* ``hybrid``— Mamba-2 stack with one *shared* attention block applied every
              ``attn_every`` layers (Zamba2-style); the shared block has its
              own KV cache per application site
* ``vlm``   — dense backbone with precomputed patch embeddings prepended
              (modality frontend stubbed per the assignment)
* ``encdec``— encoder-decoder (Whisper); conv frontend stubbed with
              precomputed frame embeddings

Layers are stacked and executed with ``lax.scan`` (+ optional remat), which
keeps HLO size and compile time bounded for the 94-layer dry-run cells.
Params are a plain dict pytree; ``abstract_params`` produces allocation-free
ShapeDtypeStructs for ``jit(...).lower()``.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (
    attention_block,
    chunked_attention,
    cross_attention_block,
    init_attention,
    init_mlp,
    init_moe,
    mlp_block,
    moe_block,
    rms_norm,
    tree_attention_block,
)
from .ssm import init_ssm_block, init_ssm_cache, ssm_block

Pytree = Any


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def _init_transformer_block(key, cfg: ModelConfig, cross: bool = False):
    ks = jax.random.split(key, 6)
    p = {
        "attn_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        "attn": init_attention(ks[0], cfg),
        "mlp_norm": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    if cfg.family == "moe":
        p["moe"] = init_moe(ks[1], cfg, cfg.dtype)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.dtype)
    if cross:
        p["cross_norm"] = jnp.ones((cfg.d_model,), cfg.dtype)
        p["cross"] = init_attention(ks[2], cfg)
    return p


def _init_ssm_layer(key, cfg: ModelConfig):
    return {
        "norm": jnp.ones((cfg.d_model,), cfg.dtype),
        "ssm": init_ssm_block(key, cfg, cfg.dtype),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Pytree:
    k_embed, k_blocks, k_head, k_shared, k_enc = jax.random.split(key, 5)
    std = 0.02
    params: dict = {
        "embed": (
            jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model)) * std
        ).astype(cfg.dtype),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size)) * std
        ).astype(cfg.dtype)

    layer_keys = jax.random.split(k_blocks, cfg.num_layers)
    if cfg.family in ("dense", "moe", "vlm"):
        params["blocks"] = jax.vmap(lambda k: _init_transformer_block(k, cfg))(
            layer_keys
        )
    elif cfg.family == "ssm":
        params["blocks"] = jax.vmap(lambda k: _init_ssm_layer(k, cfg))(layer_keys)
    elif cfg.family == "hybrid":
        params["blocks"] = jax.vmap(lambda k: _init_ssm_layer(k, cfg))(layer_keys)
        params["shared_attn"] = _init_transformer_block(k_shared, cfg)
    elif cfg.family == "encdec":
        params["blocks"] = jax.vmap(
            lambda k: _init_transformer_block(k, cfg, cross=True)
        )(layer_keys)
        enc_keys = jax.random.split(k_enc, cfg.num_encoder_layers)
        params["encoder"] = {
            "blocks": jax.vmap(lambda k: _init_transformer_block(k, cfg))(enc_keys),
            "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        }
    else:
        raise ValueError(cfg.family)
    return params


def abstract_params(cfg: ModelConfig) -> Pytree:
    """Allocation-free parameter ShapeDtypeStructs (for the dry-run)."""
    return jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0)
    )


# ---------------------------------------------------------------------------
# Layer-loop execution: lax.scan (default; bounded HLO size / compile time)
# or an unrolled Python loop (dry-run cost extrapolation).
# ---------------------------------------------------------------------------


def _layer_scan(body, carry, xs, cfg: ModelConfig):
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    length = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(length):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


# ---------------------------------------------------------------------------
# Hybrid helpers: the shared attention block and its per-site cache
# ---------------------------------------------------------------------------


def _num_attn_sites(cfg: ModelConfig) -> int:
    if cfg.family != "hybrid" or cfg.attn_every <= 0:
        return 0
    return (cfg.num_layers + cfg.attn_every - 1) // cfg.attn_every


def _shared_attn_apply(shared, cfg, x, positions, site_cache):
    """One application of the shared transformer block (attn + MLP)."""
    h, new_cache = attention_block(
        shared["attn"], cfg, rms_norm(x, shared["attn_norm"], cfg.rms_eps),
        positions, cache=site_cache,
    )
    x = x + h
    x = x + mlp_block(shared["mlp"], rms_norm(x, shared["mlp_norm"], cfg.rms_eps))
    return x, new_cache


# ---------------------------------------------------------------------------
# Block bodies (scan-compatible)
# ---------------------------------------------------------------------------


def _transformer_body(cfg, bp, x, positions, cache, enc_out=None):
    if cfg.seq_shard_activations:
        from ..distributed.sharding import constrain

        x = constrain(x, ("pod", "data"), "model", None)
    h, new_cache = attention_block(
        bp["attn"], cfg, rms_norm(x, bp["attn_norm"], cfg.rms_eps),
        positions, cache=cache,
    )
    x = x + h
    aux = jnp.float32(0.0)
    if enc_out is not None:
        x = x + cross_attention_block(
            bp["cross"], cfg, rms_norm(x, bp["cross_norm"], cfg.rms_eps), enc_out
        )
    if cfg.family == "moe":
        h, aux = moe_block(bp["moe"], cfg, rms_norm(x, bp["mlp_norm"], cfg.rms_eps))
    else:
        h = mlp_block(bp["mlp"], rms_norm(x, bp["mlp_norm"], cfg.rms_eps))
    return x + h, new_cache, aux


def _ssm_body(cfg, bp, x, cache, return_cache=False):
    h, new_cache = ssm_block(
        bp["ssm"], cfg, rms_norm(x, bp["norm"], cfg.rms_eps),
        cache=cache, return_cache=return_cache,
    )
    return x + h, new_cache


# ---------------------------------------------------------------------------
# Forward (training / no-cache)
# ---------------------------------------------------------------------------


def _embed_inputs(params, cfg: ModelConfig, batch) -> tuple[jax.Array, jax.Array]:
    tokens = batch["tokens"]
    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        patches = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
    s = x.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), x.shape[:2])
    return x, positions


def _run_encoder(params, cfg, frames):
    x = frames.astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    def body(x, bp):
        h, _ = attention_block(
            bp["attn"], cfg, rms_norm(x, bp["attn_norm"], cfg.rms_eps),
            positions, causal=False,
        )
        x = x + h
        x = x + mlp_block(bp["mlp"], rms_norm(x, bp["mlp_norm"], cfg.rms_eps))
        return x, None

    fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = _layer_scan(fn, x, params["encoder"]["blocks"], cfg)
    return rms_norm(x, params["encoder"]["final_norm"], cfg.rms_eps)


def _enc_kv(cfg, bp_cross, enc_out):
    b, se, _ = enc_out.shape
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    k = (enc_out @ bp_cross["wk"]).reshape(b, se, hkv, hd)
    v = (enc_out @ bp_cross["wv"]).reshape(b, se, hkv, hd)
    if cfg.qkv_bias:
        k = k + bp_cross["bk"].reshape(hkv, hd)
        v = v + bp_cross["bv"].reshape(hkv, hd)
    return {"k": k, "v": v}


def forward(
    params, cfg: ModelConfig, batch, return_hidden: bool = False
) -> tuple[jax.Array, jax.Array]:
    """Full forward (no cache).  Returns (logits | final hidden, aux_loss)."""
    x, positions = _embed_inputs(params, cfg, batch)

    enc_out = None
    if cfg.family == "encdec":
        enc_out = _run_encoder(params, cfg, batch["frame_embeds"])

    if cfg.family in ("dense", "moe", "vlm", "encdec"):

        def body(carry, bp):
            x, aux = carry
            kv = _enc_kv(cfg, bp["cross"], enc_out) if enc_out is not None else None
            x, _, aux_i = _transformer_body(cfg, bp, x, positions, None, kv)
            return (x, aux + aux_i), None

        fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux), _ = _layer_scan(fn, (x, jnp.float32(0.0)), params["blocks"], cfg)

    elif cfg.family == "ssm":

        def body(x, bp):
            x, _ = _ssm_body(cfg, bp, x, None)
            return x, None

        fn = jax.checkpoint(body) if cfg.remat else body
        x, _ = _layer_scan(fn, x, params["blocks"], cfg)
        aux = jnp.float32(0.0)

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def body(x, xs):
            bp, idx = xs
            is_site = (idx % cfg.attn_every) == 0

            def with_attn(x):
                out, _ = _shared_attn_apply(shared, cfg, x, positions, None)
                return out

            x = jax.lax.cond(is_site, with_attn, lambda x: x, x)
            x, _ = _ssm_body(cfg, bp, x, None)
            return x, None

        fn = jax.checkpoint(body) if cfg.remat else body
        x, _ = _layer_scan(
            fn, x, (params["blocks"], jnp.arange(cfg.num_layers)), cfg
        )
        aux = jnp.float32(0.0)
    else:
        raise ValueError(cfg.family)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if return_hidden:
        return x, aux
    head = params.get("lm_head", None)
    logits = x @ head if head is not None else x @ params["embed"].T
    return logits, aux


def _ce_terms(pred: jax.Array, targets: jax.Array, mask: jax.Array):
    """(Σ nll, Σ mask) over a [B, S, V] fp32 slab."""
    logz = jax.nn.logsumexp(pred, axis=-1)
    gold = jnp.take_along_axis(pred, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask), jnp.sum(mask)


def loss_fn(params, cfg: ModelConfig, batch) -> tuple[jax.Array, dict]:
    """Next-token cross entropy (text positions only for VLM).

    With ``cfg.loss_chunk > 0`` the LM head + CE run chunked over the
    sequence inside a rematerialized scan, bounding peak logits memory to
    ``B × loss_chunk × V`` instead of ``B × S × V``.
    """
    tokens = batch["tokens"]
    mask = batch.get("loss_mask")
    mask_full = (
        jnp.ones_like(tokens[:, 1:], jnp.float32) if mask is None else mask[:, 1:]
    )
    targets = tokens[:, 1:]

    if cfg.loss_chunk <= 0:
        logits, aux = forward(params, cfg, batch)
        if cfg.family == "vlm" and "patch_embeds" in batch:
            logits = logits[:, batch["patch_embeds"].shape[1]:, :]
        pred = logits[:, :-1, :].astype(jnp.float32)
        nll, denom = _ce_terms(pred, targets, mask_full)
        loss = nll / jnp.maximum(denom, 1.0)
        return loss + aux, {"loss": loss, "aux": aux, "tokens": denom}

    hidden, aux = forward(params, cfg, batch, return_hidden=True)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        hidden = hidden[:, batch["patch_embeds"].shape[1]:, :]
    hidden = hidden[:, :-1, :]
    head = params.get("lm_head", None)
    head = head if head is not None else params["embed"].T
    s = hidden.shape[1]
    c = cfg.loss_chunk
    pad = (-s) % c
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask_full = jnp.pad(mask_full, ((0, 0), (0, pad)))
    nc = hidden.shape[1] // c
    hs = jnp.moveaxis(hidden.reshape(hidden.shape[0], nc, c, -1), 1, 0)
    ts = jnp.moveaxis(targets.reshape(targets.shape[0], nc, c), 1, 0)
    ms = jnp.moveaxis(mask_full.reshape(mask_full.shape[0], nc, c), 1, 0)

    @jax.checkpoint
    def chunk(carry, xs):
        h_c, t_c, m_c = xs
        pred = (h_c @ head).astype(jnp.float32)
        nll_c, den_c = _ce_terms(pred, t_c, m_c)
        return (carry[0] + nll_c, carry[1] + den_c), None

    (nll, denom), _ = jax.lax.scan(
        chunk, (jnp.float32(0.0), jnp.float32(0.0)), (hs, ts, ms)
    )
    loss = nll / jnp.maximum(denom, 1.0)
    return loss + aux, {"loss": loss, "aux": aux, "tokens": denom}


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

# Families whose decode cache is pure position-indexed KV — the ones that
# support the ragged right-padded prefill and len-rollback contract (see
# prefill_ragged).  Recurrent caches (ssm/hybrid) and frontend-fed families
# (vlm/encdec) are excluded; every consumer of the contract
# (CachedModelEvaluator, ServingEngine.add_requests, SearchService's
# evaluator default) tests against this one set.
KV_CACHE_FAMILIES = ("dense", "moe")


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int) -> Pytree:
    """Allocate the decode cache (KV / SSM state / enc-dec cross-KV)."""
    L = cfg.num_layers
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    kv = lambda: {
        "k": jnp.zeros((L, batch_size, max_len, hkv, hd), cfg.dtype),
        "v": jnp.zeros((L, batch_size, max_len, hkv, hd), cfg.dtype),
    }
    if cfg.family in ("dense", "moe", "vlm"):
        return {"kv": kv(), "len": jnp.int32(0)}
    if cfg.family == "ssm":
        c = init_ssm_cache(cfg, batch_size)
        return {
            "ssm": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (L,) + x.shape), c
            ),
            "len": jnp.int32(0),
        }
    if cfg.family == "hybrid":
        sites = _num_attn_sites(cfg)
        c = init_ssm_cache(cfg, batch_size)
        return {
            "ssm": jax.tree.map(lambda x: jnp.broadcast_to(x, (L,) + x.shape), c),
            "kv": {
                "k": jnp.zeros((sites, batch_size, max_len, hkv, hd), cfg.dtype),
                "v": jnp.zeros((sites, batch_size, max_len, hkv, hd), cfg.dtype),
            },
            "len": jnp.int32(0),
        }
    if cfg.family == "encdec":
        se = cfg.encoder_seq
        return {
            "kv": kv(),
            "cross": {
                "k": jnp.zeros((L, batch_size, se, hkv, hd), cfg.dtype),
                "v": jnp.zeros((L, batch_size, se, hkv, hd), cfg.dtype),
            },
            "len": jnp.int32(0),
        }
    raise ValueError(cfg.family)


def _step_with_cache(
    params, cfg: ModelConfig, batch, cache, last_positions=None
) -> tuple[jax.Array, Pytree]:
    """Shared prefill/decode path: runs S tokens against the cache.

    ``last_positions`` (``i32[B]``, ragged prefill) gathers the final hidden
    state at each row's own last valid position *before* the unembed, so the
    logits slab stays ``[B, 1, V]`` instead of ``[B, S, V]``.
    """
    x, positions = _embed_inputs(params, cfg, batch)
    cur_len = cache["len"]
    positions = positions + (
        cur_len[:, None] if jnp.ndim(cur_len) == 1 else cur_len
    )
    s = x.shape[1]
    prefill_mode = s > 1

    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        has_cross = cfg.family == "encdec"

        def body(carry, xs):
            x = carry
            if cfg.seq_shard_activations and prefill_mode:
                from ..distributed.sharding import constrain

                x = constrain(x, ("pod", "data"), "model", None)
            if has_cross:
                bp, kc, vc, ck, cv = xs
                enc_kv = {"k": ck, "v": cv}
            else:
                bp, kc, vc = xs
                enc_kv = None
            layer_cache = {"k": kc, "v": vc, "len": cur_len}
            h, new_cache = attention_block(
                bp["attn"], cfg, rms_norm(x, bp["attn_norm"], cfg.rms_eps),
                positions, cache=layer_cache,
            )
            x = x + h
            if enc_kv is not None:
                x = x + cross_attention_block(
                    bp["cross"], cfg,
                    rms_norm(x, bp["cross_norm"], cfg.rms_eps), enc_kv,
                )
            if cfg.family == "moe":
                h, _ = moe_block(
                    bp["moe"], cfg, rms_norm(x, bp["mlp_norm"], cfg.rms_eps)
                )
            else:
                h = mlp_block(bp["mlp"], rms_norm(x, bp["mlp_norm"], cfg.rms_eps))
            return x + h, (new_cache["k"], new_cache["v"])

        xs = (params["blocks"], cache["kv"]["k"], cache["kv"]["v"])
        if has_cross:
            xs = xs + (cache["cross"]["k"], cache["cross"]["v"])
        fn = jax.checkpoint(body) if (cfg.remat and prefill_mode) else body
        x, (ks, vs) = _layer_scan(fn, x, xs, cfg)
        new_cache = dict(cache, kv={"k": ks, "v": vs}, len=cur_len + s)

    elif cfg.family == "ssm":

        def body(x, xs):
            bp, conv, state = xs
            layer_cache = None if prefill_mode else {"conv": conv, "state": state}
            x, nc = _ssm_body(cfg, bp, x, layer_cache, return_cache=True)
            return x, (nc["conv"], nc["state"])

        fn = jax.checkpoint(body) if (cfg.remat and prefill_mode) else body
        x, (convs, states) = _layer_scan(
            fn, x,
            (params["blocks"], cache["ssm"]["conv"], cache["ssm"]["state"]), cfg,
        )
        new_cache = dict(
            cache, ssm={"conv": convs, "state": states}, len=cur_len + s
        )

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        kv_k, kv_v = cache["kv"]["k"], cache["kv"]["v"]

        def body(carry, xs):
            x, kv_k, kv_v = carry
            bp, conv, state, idx = xs
            is_site = (idx % cfg.attn_every) == 0
            site = idx // cfg.attn_every

            def with_attn(op):
                x, kv_k, kv_v = op
                site_cache = {"k": kv_k[site], "v": kv_v[site], "len": cur_len}
                out, nc = _shared_attn_apply(shared, cfg, x, positions, site_cache)
                return (
                    out,
                    kv_k.at[site].set(nc["k"]),
                    kv_v.at[site].set(nc["v"]),
                )

            x, kv_k, kv_v = jax.lax.cond(
                is_site, with_attn, lambda op: op, (x, kv_k, kv_v)
            )
            layer_cache = None if prefill_mode else {"conv": conv, "state": state}
            x, nc = _ssm_body(cfg, bp, x, layer_cache, return_cache=True)
            return (x, kv_k, kv_v), (nc["conv"], nc["state"])

        fn = jax.checkpoint(body) if (cfg.remat and prefill_mode) else body
        (x, kv_k, kv_v), (convs, states) = _layer_scan(
            fn,
            (x, kv_k, kv_v),
            (
                params["blocks"],
                cache["ssm"]["conv"],
                cache["ssm"]["state"],
                jnp.arange(cfg.num_layers),
            ),
            cfg,
        )
        new_cache = dict(
            cache,
            ssm={"conv": convs, "state": states},
            kv={"k": kv_k, "v": kv_v},
            len=cur_len + s,
        )
    else:
        raise ValueError(cfg.family)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if prefill_mode and last_positions is not None:
        idx = jnp.reshape(jnp.asarray(last_positions, jnp.int32), (-1, 1, 1))
        x = jnp.take_along_axis(x, idx, axis=1)
    elif prefill_mode and cfg.prefill_logits_last_only:
        x = x[:, -1:, :]
    head = params.get("lm_head", None)
    logits = x @ head if head is not None else x @ params["embed"].T
    return logits, new_cache


def prefill(params, cfg: ModelConfig, batch, cache) -> tuple[jax.Array, Pytree]:
    """Run the prompt through the model, filling the cache.

    For enc-dec models the encoder runs here and its cross-KV is cached.
    Returns (last-position logits [B, V], cache).
    """
    if cfg.family == "encdec":
        enc_out = _run_encoder(params, cfg, batch["frame_embeds"])

        def per_layer(bp):
            return _enc_kv(cfg, bp["cross"], enc_out)

        cross = jax.vmap(per_layer)(params["blocks"])
        cache = dict(cache, cross=cross)
    logits, cache = _step_with_cache(params, cfg, batch, cache)
    return logits[:, -1, :], cache


def prefill_ragged(
    params, cfg: ModelConfig, tokens, lengths, cache
) -> tuple[jax.Array, Pytree]:
    """Batched ragged prefill: right-padded prompts, per-slot lengths.

    ``tokens`` is ``[B, S]`` with row ``b`` valid up to ``lengths[b]``; one
    forward fills all ``B`` cache slots and the returned logits ``[B, V]``
    are taken at each row's *own* last valid position.  The returned cache
    carries a per-slot ``len`` **vector** — the layout every ragged consumer
    (``decode_step``, the serving engine, ``CachedModelEvaluator``) shares:

    * KV rows at positions ``< len[b]`` are valid; rows at ``>= len[b]`` are
      garbage (computed from pad tokens).  That is safe because attention
      masks ``kv_pos < len`` and every later write lands at position
      ``len[b]`` *before* ``len[b]`` advances past it — garbage is always
      overwritten before it becomes visible.

    Recurrent (SSM / hybrid) caches have no per-position validity to hide
    behind — pad tokens would pollute the state — so only KV-cache families
    take this path.
    """
    if cfg.family not in KV_CACHE_FAMILIES:
        raise ValueError(
            f"prefill_ragged supports KV-cache LM families, not {cfg.family!r}"
        )
    lengths = jnp.asarray(lengths, jnp.int32)
    logits, cache = _step_with_cache(
        params, cfg, {"tokens": tokens}, cache,
        last_positions=jnp.maximum(lengths - 1, 0),
    )
    return logits[:, 0], dict(cache, len=lengths)


def decode_chunk(
    params, cfg: ModelConfig, tokens, target, cache
) -> tuple[jax.Array, Pytree]:
    """Ragged chunked catch-up: advance each row up to ``C`` tokens at once.

    ``tokens`` is ``[B, C]`` holding, for each row, the next ``C`` tokens
    starting at the row's own ``cache['len']``; ``target`` (``i32[B]``) is
    the length each row is catching up *to*.  One forward re-decodes a whole
    chunk of a divergent suffix — batched over rows AND positions — instead
    of ``C`` single-token ``decode_step`` dispatches (the refill while_loop
    this replaces).  Per row:

    * rows with ``len < target`` advance to ``min(len + C, target)``;
    * rows already at target keep their length — their chunk writes land
      beyond ``len`` in the garbage region and stay invisible;
    * returned logits ``[B, V]`` are gathered at ``target - 1 - len``
      (clamped into the chunk), i.e. they are the next-token logits for any
      row that *finishes* its catch-up within this chunk — exactly the rows
      whose logits the caller refreshes.

    Only KV-cache families can take this path (same contract as
    ``prefill_ragged``: positions ``>= len`` are garbage until overwritten).
    """
    if cfg.family not in KV_CACHE_FAMILIES:
        raise ValueError(
            f"decode_chunk supports KV-cache LM families, not {cfg.family!r}"
        )
    cur = jnp.asarray(cache["len"], jnp.int32)
    target = jnp.asarray(target, jnp.int32)
    c = tokens.shape[1]
    gather = jnp.clip(target - 1 - cur, 0, c - 1)
    logits, cache = _step_with_cache(
        params, cfg, {"tokens": tokens}, cache, last_positions=gather
    )
    new_len = jnp.where(cur < target, jnp.minimum(cur + c, target), cur)
    return logits[:, 0], dict(cache, len=new_len)


def decode_frontier(
    params, cfg: ModelConfig, tokens, cache
) -> tuple[jax.Array, Pytree]:
    """Score ``A`` candidate next tokens per row in ONE forward (read-only).

    ``tokens`` is ``[N, A]``: each row's candidate children, all sitting at
    absolute position ``cache['len']`` — they are *alternatives* for the
    same next position, not a sequence.  The shared prefix K/V is read once
    per layer (tree attention with an identity mask over the speculative
    tail: candidate ``i`` attends the prefix plus its own K/V only), and the
    cache is NEVER written.  Returns ``(logits [N, A, V], spec)`` where
    ``spec = {"k": [L, N, A, Hkv, D], "v": ...}`` holds each candidate's own
    K/V entry so the caller can commit the chosen child's row later without
    recomputing it.

    Only KV-cache families qualify (same garbage-region contract as
    ``prefill_ragged``; speculative tails live OUTSIDE the cache entirely).
    """
    if cfg.family not in KV_CACHE_FAMILIES:
        raise ValueError(
            f"decode_frontier supports KV-cache LM families, not {cfg.family!r}"
        )
    tokens = jnp.asarray(tokens)
    n, a = tokens.shape
    x = params["embed"][tokens]
    cur_len = jnp.asarray(cache["len"], jnp.int32)
    positions = jnp.broadcast_to(
        cur_len[:, None] if jnp.ndim(cur_len) == 1 else cur_len, (n, a)
    )

    def body(x, xs):
        bp, kc, vc = xs
        h, ks, vs = tree_attention_block(
            bp["attn"], cfg, rms_norm(x, bp["attn_norm"], cfg.rms_eps),
            positions, kc, vc, cur_len,
        )
        x = x + h
        if cfg.family == "moe":
            h, _ = moe_block(
                bp["moe"], cfg, rms_norm(x, bp["mlp_norm"], cfg.rms_eps)
            )
        else:
            h = mlp_block(bp["mlp"], rms_norm(x, bp["mlp_norm"], cfg.rms_eps))
        return x + h, (ks, vs)

    x, (ks, vs) = _layer_scan(
        body, x, (params["blocks"], cache["kv"]["k"], cache["kv"]["v"]), cfg
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = params.get("lm_head", None)
    logits = x @ head if head is not None else x @ params["embed"].T
    return logits, {"k": ks, "v": vs}


def decode_step(params, cfg: ModelConfig, token, cache) -> tuple[jax.Array, Pytree]:
    """One autoregressive step.  token: [B] or [B, 1] → (logits [B, V], cache).

    ``cache["len"]`` may be a scalar (uniform batch) or a per-slot ``[B]``
    vector (ragged decode: continuous batching, async search slots) — each
    slot writes and attends at its own position, through the Pallas decode
    kernel where ``layers._use_pallas(cfg)`` holds (on a TPU by default).
    """
    token = token.reshape(token.shape[0], 1)
    logits, cache = _step_with_cache(params, cfg, {"tokens": token}, cache)
    return logits[:, -1, :], cache
