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
which runs the flash kernel forward.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, list_archs
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
