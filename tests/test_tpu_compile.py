"""The served path's Pallas kernels compile for a TPU v5e, at real widths.

Interpret mode (every other kernel test) accepts kernels the TPU compiler
refuses: contractions it cannot lower, tiles it cannot lay out, more VMEM
than a kernel may use.  These tests compile each kernel for a described
``v5e:2x2`` chip (no chip attached: the TPU compiler runs on the host) at
Qwen2.5-32B's widths — 40 query / 8 KV heads of 128, bf16 — and the shapes
``chip_smoke.py`` serves: 16 trees x 8 slots, 256-token caches, top-8
frontiers, 16-token pages.  The attention kernels are also compiled at the
head widths of every other configured family (head dims of 64 and 112,
groups of 1 to 16), and ``jax.grad`` of the loss at Qwen2.5-32B's widths,
which runs the flash kernel forward.  The slot-cache refill is compiled at
the deep-sessions cell's cache to check that it moves rows by slice.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""

import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, list_archs
from repro.core.evaluators import CachedModelEvaluator, SlotColumn
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_fwd,
    paged_decode_attention_fwd,
)
from repro.kernels.decode_attention.tree_decode_attention import (
    paged_tree_decode_attention_fwd,
    tree_decode_attention_fwd,
)
from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.kernels.tree_select.tree_select import tree_select_fwd
from repro.models import init_params, loss_fn
from repro.models.layers import _flash_block

SLOTS, TREES, MAX_LEN, TOP_K, PAGE = 128, 16, 256, 8, 16
POOL = SLOTS * MAX_LEN // PAGE


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except (RuntimeError, ValueError, ImportError, NotImplementedError) as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """Entries compiled for a described chip cannot be read back without
    one; keep the persistent cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _cases(sharding, arch="qwen2.5-32b"):
    cfg = get_config(arch)
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    f32, i32 = jnp.float32, jnp.int32
    cache = s(SLOTS, MAX_LEN, hkv, d)
    pool = s(POOL, PAGE, hkv, d)
    table = s(SLOTS, MAX_LEN // PAGE, dtype=i32)
    lens = s(SLOTS, dtype=i32)
    spec = s(SLOTS, TOP_K, hkv, d)
    return {
        "tree_select": (
            tree_select_fwd,
            (s(TREES, TOP_K, dtype=f32),) * 3 + (s(TREES, dtype=f32),) * 2
            + (s(TREES, TOP_K, dtype=jnp.bool_),),
        ),
        "decode_attention": (
            decode_attention_fwd, (s(SLOTS, hq, d), cache, cache, lens),
        ),
        "paged_decode_attention": (
            paged_decode_attention_fwd,
            (s(SLOTS, hq, d), pool, pool, table, lens),
        ),
        "tree_decode_attention": (
            tree_decode_attention_fwd,
            (s(SLOTS, TOP_K, hq, d), cache, cache, spec, spec, lens),
        ),
        "paged_tree_decode_attention": (
            paged_tree_decode_attention_fwd,
            (s(SLOTS, TOP_K, hq, d), pool, pool, table, spec, spec, lens),
        ),
        "flash_attention": (
            functools.partial(flash_attention_fwd, block_q=MAX_LEN,
                              block_k=MAX_LEN),
            (s(4, MAX_LEN, hq, d), s(4, MAX_LEN, hkv, d),
             s(4, MAX_LEN, hkv, d)),
        ),
    }


@pytest.mark.parametrize("kernel", [
    "tree_select", "decode_attention", "paged_decode_attention",
    "tree_decode_attention", "paged_tree_decode_attention", "flash_attention",
])
def test_kernel_compiles_for_v5e(kernel, one_chip, no_compile_cache):
    fn, args = _cases(one_chip)[kernel]
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args
    ).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert f"%{kernel}" in text


ATTENTION_KERNELS = [
    "decode_attention", "paged_decode_attention", "tree_decode_attention",
    "paged_tree_decode_attention", "flash_attention",
]


def _other_widths():
    """One family per distinct attention width other than Qwen2.5-32B's."""
    smoke = get_config("qwen2.5-32b")
    seen, archs = {(smoke.num_heads, smoke.num_kv_heads, smoke.head_dim)}, []
    for arch in list_archs():
        c = get_config(arch)
        widths = (c.num_heads, c.num_kv_heads, c.head_dim)
        if c.num_heads and widths not in seen:
            seen.add(widths)
            archs.append(arch)
    return archs


@pytest.mark.parametrize("arch", _other_widths())
@pytest.mark.parametrize("kernel", ATTENTION_KERNELS)
def test_attention_kernel_compiles_at_family_widths(kernel, arch, one_chip,
                                                    no_compile_cache):
    fn, args = _cases(one_chip, arch)[kernel]
    text = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args
    ).compile().as_text()
    assert f"%{kernel}" in text


@pytest.mark.parametrize("seq", [256, 300])
def test_loss_gradient_compiles_for_v5e(seq, one_chip, no_compile_cache,
                                        monkeypatch):
    """Training differentiates through the attention kernels' path: the
    flash kernel (forward only) must sit under a gradient the compiler
    accepts.  ``seq=300`` has no tileable flash block and stays on jnp."""
    # Trace as on a TPU backend: kernels on, interpret mode off.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    cfg = dataclasses.replace(get_config("qwen2.5-32b"), num_layers=1)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(functools.partial(init_params, cfg),
                       jax.random.PRNGKey(0)),
    )
    tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32, sharding=one_chip)
    grad = jax.jit(
        jax.grad(lambda p, t: loss_fn(p, cfg, {"tokens": t})[0])
    )
    text = grad.lower(params, tokens).compile().as_text()
    jax.clear_caches()
    assert ("%flash_attention" in text) == (_flash_block(seq) is not None)


_SHAPE = re.compile(
    r"\b(?:bf16|f16|f32|s8|s16|s32|u8|u16|u32|pred)\[([\d,]*)\]"
)
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
IN_PLACE = {
    "parameter", "get-tuple-element", "bitcast", "tuple", "while",
    "dynamic-update-slice",
}


def _large_outputs(text, limit):
    """``(instruction, opcode, root opcode of the computation it calls)`` of
    every instruction outside a fusion whose output holds an array of more
    than ``limit`` elements, from compiled HLO text."""
    roots, fused, comp, found = {}, set(), None, []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{\s*$", line)
        if head and " = " not in line:
            comp = head.group(1)
            continue
        inst = re.match(r"\s*(ROOT )?%(\S+) = (.*)$", line)
        if not inst:
            continue
        rest = inst.group(3)
        op = _OPCODE.search(" " + rest)
        op = op.group(1) if op else ""
        if inst.group(1):
            roots[comp] = op
        called = re.search(r"calls=%([\w.-]+)", rest)
        called = called.group(1) if called else None
        if op == "fusion":
            fused.add(called)
        dims = _SHAPE.findall(rest[: rest.find(op + "(")])
        if any(math.prod(map(int, filter(None, d.split(",")))) > limit
               for d in dims):
            found.append((comp, inst.group(2), op, called))
    return [
        (name, op, roots.get(called))
        for comp, name, op, called in found if comp not in fused
    ]


def test_refill_moves_slot_rows_by_slice_for_v5e(one_chip, no_compile_cache):
    """The dense evaluator's refill at the deep-sessions cell's shapes (4
    layers of ``[128, 384, 8, 128]`` bf16 K and V, 16 trees x 8 slots,
    logits ``[128, 152064]``), one slot column at a time as the batched
    engine runs it: the compiled program has no ``mini-gather-slice`` (the
    TPU gather's copy of the whole operand) and no output larger than a
    column of the cache other than an in-place ``dynamic-update-slice``."""
    trees, width, max_len = 16, 8, 384
    cfg = dataclasses.replace(get_config("qwen2.5-32b"), num_layers=4)
    ev = CachedModelEvaluator(cfg, None, top_k=TOP_K)
    n = trees * width

    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    kv = s(cfg.num_layers, n, max_len, cfg.num_kv_heads, cfg.head_dim)
    aux = {
        "tokens": s(n, max_len, dtype=jnp.int32),
        "len": s(n, dtype=jnp.int32),
        "pol": {"cache": {"kv": {"k": kv, "v": kv}},
                "logits": s(n, cfg.vocab_size)},
        "rew": (),
    }

    def refill(aux):
        def column(j, aux):
            rows = SlotColumn(j, width)
            sub = ev._take_rows(aux, rows)
            sub = jax.tree.map(lambda x: x + jnp.ones((), x.dtype), sub)
            return ev._put_rows(aux, rows, sub)

        return jax.lax.fori_loop(0, width, column, aux)

    text = jax.jit(refill, donate_argnums=0).lower(aux).compile().as_text()
    assert "mini-gather-slice" not in text
    large = _large_outputs(text, math.prod(kv.shape) // width)
    assert any(op == "while" for _, op, _ in large)
    copies = [
        (name, op, root) for name, op, root in large
        if op not in IN_PLACE
        and not (op == "fusion" and root == "dynamic-update-slice")
    ]
    assert not copies, copies
