#!/usr/bin/env python3
"""Smoke run of the served search path on one TPU chip.

    python chip_smoke.py [--seed N]

Runs in one process on the first TPU device, at the published widths of
Qwen2.5-32B (d_model 5120, 40 query / 8 KV heads of 128, d_ff 27648, vocab
152064, QKV bias, bf16) with the depth cut to 4 layers and random weights
from ``--seed``.  Phases, each of which raises on failure:

1. device — the first JAX device must be a TPU; otherwise exit non-zero
   before anything else runs;
2. kernels — every Pallas kernel of the served path against its ``ref.py``
   oracle on device arrays at the model's widths, then at the head widths
   of every other configured family, at small shapes;
3. gradients — ``jax.grad`` of ``loss_fn`` (one layer) through the flash
   kernel against the jnp attention path; the compiled gradient must hold
   the kernel;
4. logits — cached decode (``prefill_ragged`` + ``decode_step``, kernels
   on) against an f32 ``forward`` over the full prefix;
5. serving — 48 requests submitted to ``SearchService`` and drained on the
   fused ring with the dense, paged and paged-frontier evaluators; the
   compiled serving segment must hold the attention kernels.

Compile and drain seconds are printed as set-up and run times of this
smoke, not as measurements.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.configs import get_config, list_archs  # noqa: E402
from repro.core import SearchSpec  # noqa: E402
from repro.core.evaluators import PagedFrontierModelEvaluator  # noqa: E402
from repro.kernels.decode_attention import ops as da_ops  # noqa: E402
from repro.kernels.decode_attention import ref as da_ref  # noqa: E402
from repro.kernels.flash_attention.ops import flash_attention  # noqa: E402
from repro.kernels.flash_attention.ref import attention_ref  # noqa: E402
from repro.kernels.tree_select.ops import tree_select  # noqa: E402
from repro.kernels.tree_select.ref import tree_select_ref  # noqa: E402
from repro.models import (  # noqa: E402
    decode_step,
    forward,
    init_cache,
    init_params,
    loss_fn,
    num_pages,
    prefill_ragged,
)
from repro.serving import SearchService  # noqa: E402

LAYERS = 4
SPEC = SearchSpec(
    algo="wu_uct", engine="async", batch=16, wave_size=8,
    num_simulations=64, max_depth=8, max_sim_steps=32,
)
TOP_K = 8
MAX_LEN = 256
BLOCK_SIZE = 16
REQUESTS = 48
PROMPT_LENS = (16, 128)
PATHS = ("dense", "paged", "paged_frontier")

# Kernels the compiled serving segment of each evaluator path must hold.
SERVED_KERNELS = {
    "dense": {"tree_select", "decode_attention"},
    "paged": {"tree_select", "paged_decode_attention"},
    "paged_frontier": {
        "tree_select", "paged_decode_attention", "paged_tree_decode_attention",
    },
}

# Attention kernels against their oracles, both fed the same bf16 operands
# and accumulating in f32.  They differ where bf16 rounds the softmax
# weights before the PV matmul (the kernel against a running maximum, the
# oracle against the global one: up to 2^-8 relative per weight, on
# outputs of magnitude <= max|v|, ~4 for unit normal values) and in the
# last bit of the bf16 output (2^-7 relative at most).  So each element
# must agree within ATTN_ATOL + ATTN_RTOL * |ref|.
ATTN_ATOL = 2e-2
ATTN_RTOL = 2.0 ** -7
# tree_select scores the same f32 formula as its oracle; only the order of
# the transcendental evaluations differs: a few f32 ulps.
SELECT_RTOL = 1e-5
# Cached bf16 decode (kernels on) against the f32 forward of the same
# bf16-valued weights, as the per-row relative L2 error of the logits.
# bf16 rounds activations, KV entries and matmul inputs to 8 significant
# bits at every layer, and 4 layers of random weights amplify that to ~2e-2
# (2.249e-2 measured on a v5e, against 2.243e-2 for the plain bf16 forward,
# which is printed beside it).  3e-2 leaves a third of that for other seeds
# and still fails an error in masking, positions or GQA head mapping, which
# scrambles rows (error ~1).  Both paths round alike, so this bounds gross
# errors only; it is not a precision check.
LOGITS_RTOL = 3e-2
# Gradients of loss_fn with the flash kernel forward against the jnp
# attention forward, as the relative L2 error of each parameter's gradient.
# Both differentiate through chunked_attention; they differ only where the
# kernel's bf16 attention output rounds differently (one bf16 ulp, 2^-8
# relative), which the backward carries into every gradient at about that
# size.  Wrong wiring of the kernel's backward (k and v cotangents swapped,
# another call's residuals) gives errors of order 1.
GRAD_RTOL = 5e-2


def require_tpu() -> dict:
    """The first device as JAX reports it; exits unless it is a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX's first device is on platform "
            f"{dev.platform!r}); this smoke runs only on a TPU"
        )
    return {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


def smoke_config(layers: int = LAYERS):
    """Qwen2.5-32B at its published widths, depth cut to ``layers``."""
    return dataclasses.replace(get_config("qwen2.5-32b"), num_layers=layers)


def init_model(cfg, seed: int):
    key = jax.random.PRNGKey(seed)
    return jax.jit(init_params, static_argnums=0)(cfg, key)


def check_kernels(cfg, seed: int, *, slots: int, max_len: int,
                  block_size: int, top_k: int, batch: int) -> dict:
    """Each kernel against its oracle at the served shapes; returns the
    largest absolute difference per kernel."""
    hq, hkv, d, dt = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.dtype
    n_pages = num_pages(max_len, block_size)
    pool = slots * n_pages
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def normal(*shape):
        return jax.random.normal(next(ks), shape, dt)

    q, kc, vc = (normal(slots, hq, d), normal(slots, max_len, hkv, d),
                 normal(slots, max_len, hkv, d))
    lens = jax.random.randint(next(ks), (slots,), 1, max_len + 1)
    qa = normal(slots, top_k, hq, d)
    k_spec, v_spec = normal(slots, top_k, hkv, d), normal(slots, top_k, hkv, d)
    pool_k, pool_v = (normal(pool, block_size, hkv, d),
                      normal(pool, block_size, hkv, d))
    table = jax.random.permutation(next(ks), pool).reshape(slots, n_pages)
    fq, fk, fv = (normal(4, max_len, hq, d), normal(4, max_len, hkv, d),
                  normal(4, max_len, hkv, d))

    runs = {
        "decode_attention": (
            da_ops.decode_attention(q, kc, vc, lens, block_k=max_len),
            lambda: da_ref.decode_attention_ref(q, kc, vc, lens)),
        "paged_decode_attention": (
            da_ops.paged_decode_attention(q, pool_k, pool_v, table, lens),
            lambda: da_ref.paged_decode_attention_ref(
                q, pool_k, pool_v, table, lens)),
        "tree_decode_attention": (
            da_ops.tree_decode_attention(
                qa, kc, vc, k_spec, v_spec, lens, block_k=max_len),
            lambda: da_ref.tree_decode_attention_ref(
                qa, kc, vc, k_spec, v_spec, lens)),
        "paged_tree_decode_attention": (
            da_ops.paged_tree_decode_attention(
                qa, pool_k, pool_v, table, k_spec, v_spec, lens),
            lambda: da_ref.paged_tree_decode_attention_ref(
                qa, pool_k, pool_v, table, k_spec, v_spec, lens)),
        "flash_attention": (
            flash_attention(fq, fk, fv, causal=True, block_q=max_len,
                            block_k=max_len),
            lambda: attention_ref(fq, fk, fv, causal=True)),
    }
    errs = {}
    for name, (out, ref_fn) in runs.items():
        with jax.default_matmul_precision("highest"):
            ref = ref_fn().astype(jnp.float32)
        if out.shape != ref.shape:
            raise AssertionError(f"{name}: shape {out.shape} vs {ref.shape}")
        diff = jnp.abs(out.astype(jnp.float32) - ref)
        if not bool(jnp.all(diff <= ATTN_ATOL + ATTN_RTOL * jnp.abs(ref))):
            raise AssertionError(
                f"{name}: |kernel - ref| exceeds {ATTN_ATOL} + {ATTN_RTOL}"
                f" * |ref| (max difference {float(jnp.max(diff))})"
            )
        errs[name] = float(jnp.max(diff))

    n_c = jnp.floor(jax.random.uniform(next(ks), (batch, top_k)) * 10)
    o_c = jnp.floor(jax.random.uniform(next(ks), (batch, top_k)) * 3)
    v_c = jax.random.normal(next(ks), (batch, top_k))
    valid = jax.random.uniform(next(ks), (batch, top_k)) < 0.7
    valid = valid.at[:, 0].set(True)
    args = (n_c, o_c, v_c, n_c.sum(1) + 1, o_c.sum(1), valid)
    act, score = tree_select(*args)
    _, score_ref = tree_select_ref(*args)
    np.testing.assert_allclose(score, score_ref, rtol=SELECT_RTOL, atol=0,
                               err_msg="tree_select best score")
    taken = jnp.where(valid, 1.0, 0.0)[jnp.arange(batch), act]
    if not bool(jnp.all(taken == 1.0)):
        raise AssertionError("tree_select picked an invalid action")
    finite = jnp.isfinite(score_ref)
    errs["tree_select"] = float(
        jnp.max(jnp.where(finite, jnp.abs(score - score_ref), 0.0))
    )
    return errs


def check_cached_logits(cfg, params, seed: int, *, prompt_lens, steps: int,
                        max_len: int) -> dict:
    """Cached decode against an f32 forward over the full prefix.

    Prompts of ``prompt_lens`` are prefilled ragged into one cache and
    decoded ``steps`` further tokens; the logits after every token are
    compared with the f32 forward's (``highest`` matmul precision, jnp
    attention) at the same position.  Returns the largest per-row relative
    L2 error of the cached path and, for scale, of the plain bf16 forward.
    """
    rng = np.random.default_rng(seed)
    lens = np.asarray(prompt_lens, np.int32)
    n, total = len(lens), int(lens.max()) + steps
    toks = rng.integers(1, cfg.vocab_size, size=(n, total), dtype=np.int32)
    padded = np.where(np.arange(max_len)[None] < lens[:, None],
                      np.pad(toks, ((0, 0), (0, max_len - total))), 0)

    logits, cache = jax.jit(prefill_ragged, static_argnums=1)(
        params, cfg, jnp.asarray(padded), jnp.asarray(lens),
        init_cache(cfg, n, max_len),
    )
    step = jax.jit(decode_step, static_argnums=1)
    got = [logits]
    for t in range(steps):
        fed = jnp.asarray(toks[np.arange(n), lens + t])
        logits, cache = step(params, cfg, fed, cache)
        got.append(logits)
    got = jnp.stack(got, axis=1).astype(jnp.float32)          # [n, steps+1, V]

    pos = jnp.asarray(lens[:, None] - 1 + np.arange(steps + 1)[None])

    @functools.partial(jax.jit, static_argnums=0)
    def plain(c, params, tokens, pos):
        with jax.default_matmul_precision("highest"):
            full, _ = forward(params, c, {"tokens": tokens})
        return jnp.take_along_axis(full, pos[:, :, None], axis=1)

    def rel_err(x):
        x = x.astype(jnp.float32)
        norm = functools.partial(jnp.linalg.norm, axis=-1)
        return norm(x - want) / norm(want)

    xla = dataclasses.replace(cfg, attn_impl="xla")
    want = plain(dataclasses.replace(xla, dtype=jnp.float32), params,
                 jnp.asarray(toks), pos)
    rel = rel_err(got)
    errs = {
        "cached": float(jnp.max(rel)),
        "plain_bf16": float(jnp.max(rel_err(
            plain(xla, params, jnp.asarray(toks), pos)))),
    }
    if not errs["cached"] <= LOGITS_RTOL:
        raise AssertionError(
            f"cached decode logits vs f32 forward: relative L2 error "
            f"{errs['cached']} > {LOGITS_RTOL} (per row and step: "
            f"{np.asarray(rel).round(4)})"
        )
    return errs


def kernels_in(text: str) -> set:
    """Names of the Pallas kernels in compiled HLO text."""
    return {
        m.group(1) for m in re.finditer(
            r'%([A-Za-z_]+)(?:\.\d+)? = [^\n]*'
            r'custom_call_target="tpu_custom_call"',
            text,
        )
    }


def served_kernels(svc) -> set:
    """Names of the Pallas kernels in the compiled fused serving segment."""
    return kernels_in(svc.compiled_segment_text())


def check_kernel_widths(cfg, seed: int, **shapes) -> dict:
    """:func:`check_kernels` at the head widths (query heads, KV heads, head
    dim) of every configured family other than ``cfg``'s."""
    seen = {(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)}
    errs = {}
    for arch in list_archs():
        c = get_config(arch)
        widths = (c.num_heads, c.num_kv_heads, c.head_dim)
        if c.num_heads == 0 or widths in seen:
            continue
        seen.add(widths)
        errs["x".join(map(str, widths))] = max(
            check_kernels(c, seed, **shapes).values()
        )
    return errs


def check_gradients(cfg, seed: int, *, batch: int, seq: int) -> tuple:
    """``jax.grad`` of ``loss_fn`` with ``cfg``'s attention against the jnp
    path, on one layer at ``cfg``'s widths, with respect to the layer's
    weights (the ones whose gradient flows through attention; the embedding
    and LM head gradients would not fit twice beside the weights on one
    chip).  Returns the largest relative L2 error over those gradients and
    the kernels in the compiled gradient."""
    cfg = dataclasses.replace(cfg, num_layers=1)
    params = init_model(cfg, seed)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, seq),
                                0, cfg.vocab_size)

    @functools.partial(jax.jit, static_argnums=0)
    def grads(c, params, tokens):
        def loss(blocks):
            return loss_fn(dict(params, blocks=blocks), c,
                           {"tokens": tokens})[0]

        return jax.grad(loss)(params["blocks"])

    @jax.jit
    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30)

    compiled = grads.lower(cfg, params, tokens).compile()
    kernels = kernels_in(compiled.as_text())
    got = compiled(params, tokens)
    want = grads(dataclasses.replace(cfg, attn_impl="xla"), params, tokens)
    errs = [float(e) for e in jax.tree.leaves(jax.tree.map(rel, got, want))]
    if not all(np.isfinite(errs)):
        raise AssertionError(f"loss_fn gradient is not finite: {errs}")
    err = max(errs)
    if not err <= GRAD_RTOL:
        raise AssertionError(
            f"loss_fn gradient through the kernel vs the jnp path: relative "
            f"L2 error {err} > {GRAD_RTOL}"
        )
    return err, kernels


def make_service(cfg, params, path: str, *, spec, top_k: int, max_len: int,
                 block_size: int) -> SearchService:
    kw = dict(top_k=top_k, max_len=max_len, block_size=block_size)
    if path == "dense":
        return SearchService(cfg, params, spec, **kw)
    if path == "paged":
        return SearchService(cfg, params, spec, paged=True, **kw)
    if path == "paged_frontier":
        blocks = spec.batch * spec.wave_size * num_pages(max_len, block_size)
        ev = PagedFrontierModelEvaluator(
            cfg, params, top_k=top_k, block_size=block_size, num_blocks=blocks,
        )
        return SearchService(cfg, params, spec, paged=True, evaluator=ev, **kw)
    raise ValueError(f"unknown evaluator path {path!r}")


def serve_requests(svc: SearchService, prompts, *, top_k: int) -> dict:
    """Warm up on one request (compiles the staging and segment programs),
    then drain ``prompts`` through the fused ring and check every result."""
    t0 = time.perf_counter()
    svc.submit(prompts[0])
    svc.drain()
    setup_s = time.perf_counter() - t0
    ids = [svc.submit(p) for p in prompts]
    t0 = time.perf_counter()
    results = svc.drain()
    run_s = time.perf_counter() - t0

    stats = svc.stats
    if stats.completed != stats.submitted:
        raise AssertionError(
            f"completed {stats.completed} of {stats.submitted} requests"
        )
    for i in ids:
        row = results[i]
        if not 0 <= int(row.action) < top_k:
            raise AssertionError(f"request {i}: action {int(row.action)} "
                                 f"outside [0, {top_k})")
        if not float(np.sum(row.root_n)) > 0:
            raise AssertionError(f"request {i}: root was never visited")
    return {"requests": len(ids), "setup_s": setup_s, "run_s": run_s,
            "ticks": stats.ticks, "host_rounds": stats.host_rounds}


def make_prompts(n: int, lo: int, hi: int, vocab: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, vocab, size=int(rng.integers(lo, hi + 1))).tolist()
        for _ in range(n)
    ]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = require_tpu()
    print(f"device: {device['kind']} x{device['count']} "
          f"({device['platform']})", flush=True)
    print(f"compile cache: {use_compile_cache()}", flush=True)

    cfg = smoke_config()
    slots = SPEC.batch * SPEC.wave_size
    t0 = time.perf_counter()
    errs = check_kernels(cfg, args.seed, slots=slots, max_len=MAX_LEN,
                         block_size=BLOCK_SIZE, top_k=TOP_K, batch=SPEC.batch)
    print(f"kernels vs ref.py (max abs diff): {json.dumps(errs)} "
          f"[{time.perf_counter() - t0:.1f} s with compile]", flush=True)
    t0 = time.perf_counter()
    errs = check_kernel_widths(cfg, args.seed, slots=8, max_len=64,
                               block_size=16, top_k=4, batch=2)
    print(f"kernels vs ref.py at other families' widths (Hq x Hkv x D: max "
          f"abs diff): {json.dumps(errs)} [{time.perf_counter() - t0:.1f} s "
          "with compile]", flush=True)

    t0 = time.perf_counter()
    err, kernels = check_gradients(cfg, args.seed, batch=2, seq=MAX_LEN)
    if "flash_attention" not in kernels:
        raise AssertionError(
            f"compiled loss_fn gradient lacks flash_attention (holds "
            f"{sorted(kernels)})"
        )
    print(f"loss_fn gradient (1 layer) vs jnp attention, max relative L2 "
          f"error: {err} (limit {GRAD_RTOL}), kernels {sorted(kernels)} "
          f"[{time.perf_counter() - t0:.1f} s with compile]", flush=True)

    t0 = time.perf_counter()
    params = init_model(cfg, args.seed)
    jax.block_until_ready(params)
    print(f"set-up: {cfg.name} x{cfg.num_layers} layers initialised in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)

    t0 = time.perf_counter()
    errs = check_cached_logits(cfg, params, args.seed,
                               prompt_lens=(16, 57, 100, 128), steps=4,
                               max_len=MAX_LEN)
    print(f"logits vs f32 forward, max relative L2 error: cached decode "
          f"{errs['cached']} (limit {LOGITS_RTOL}), plain bf16 forward "
          f"{errs['plain_bf16']} [{time.perf_counter() - t0:.1f} s with "
          "compile]", flush=True)

    prompts = make_prompts(REQUESTS, *PROMPT_LENS, cfg.vocab_size, args.seed)
    for path in PATHS:
        svc = make_service(cfg, params, path, spec=SPEC, top_k=TOP_K,
                           max_len=MAX_LEN, block_size=BLOCK_SIZE)
        out = serve_requests(svc, prompts, top_k=TOP_K)
        kernels = served_kernels(svc)
        missing = SERVED_KERNELS[path] - kernels
        if missing:
            raise AssertionError(
                f"{path}: compiled serving segment lacks kernels "
                f"{sorted(missing)} (holds {sorted(kernels)})"
            )
        print(f"serve {path}: {out['requests']}/{out['requests']} requests, "
              f"{out['ticks']} ticks, {out['host_rounds']} host rounds, "
              f"kernels {sorted(kernels)}; set-up {out['setup_s']:.1f} s "
              f"(compile + 1-request drain), run {out['run_s']:.1f} s "
              "(smoke timings, not measurements)", flush=True)
        del svc

    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
