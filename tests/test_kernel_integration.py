"""Model-level kernel integration: attn_impl='pallas' ≈ 'xla' end to end.

The Pallas kernels (interpret mode on CPU) must be drop-in replacements for
the jnp paths at the full-model level — forward logits and decode steps
agree within f32 tolerance for every family that has a kernelized hot spot.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import (
    decode_step,
    forward,
    init_cache,
    init_params,
    loss_fn,
    prefill,
)
from repro.models.layers import _flash_block


@pytest.mark.parametrize("arch", ["llama3-8b", "mamba2-2.7b", "zamba2-7b"])
def test_pallas_path_matches_xla_forward(arch):
    cfg_x = dataclasses.replace(
        get_reduced(arch), attn_chunk=32, attn_impl="xla"
    )
    cfg_p = dataclasses.replace(cfg_x, attn_impl="pallas")
    params = init_params(cfg_x, jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                     cfg_x.vocab_size)
    }
    lx, _ = jax.jit(lambda p, b: forward(p, cfg_x, b))(params, batch)
    lp, _ = jax.jit(lambda p, b: forward(p, cfg_p, b))(params, batch)
    np.testing.assert_allclose(
        np.asarray(lx, np.float32), np.asarray(lp, np.float32),
        rtol=5e-4, atol=5e-4,
    )


def test_pallas_decode_matches_xla():
    cfg_x = dataclasses.replace(
        get_reduced("llama3-8b"), attn_chunk=32, attn_impl="xla"
    )
    cfg_p = dataclasses.replace(cfg_x, attn_impl="pallas")
    params = init_params(cfg_x, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg_x.vocab_size)

    def run(cfg):
        cache = init_cache(cfg, 2, 32)
        lg, cache = prefill(params, cfg, {"tokens": toks[:, :8]}, cache)
        outs = [np.asarray(lg, np.float32)]
        for i in range(4):
            lg, cache = decode_step(params, cfg, toks[:, 8 + i - 1], cache)
            outs.append(np.asarray(lg, np.float32))
        return outs

    for a, b in zip(run(cfg_x), run(cfg_p)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)


def test_pallas_path_matches_xla_gradient():
    """The flash kernel has no backward pass of its own: ``jax.grad`` through
    the model must still work with it on, and agree with the jnp path."""
    cfg_x = dataclasses.replace(
        get_reduced("llama3-8b"), attn_chunk=32, attn_impl="xla"
    )
    cfg_p = dataclasses.replace(cfg_x, attn_impl="pallas")
    params = init_params(cfg_x, jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                     cfg_x.vocab_size)
    }

    def grads(cfg):
        return jax.jit(jax.grad(lambda p: loss_fn(p, cfg, batch)[0]))(params)

    for gx, gp in zip(jax.tree.leaves(grads(cfg_x)),
                      jax.tree.leaves(grads(cfg_p))):
        np.testing.assert_allclose(
            np.asarray(gx, np.float32), np.asarray(gp, np.float32),
            rtol=5e-4, atol=5e-5,
        )


@pytest.mark.parametrize("sq,block", [
    (1, 1), (100, 100), (256, 256), (512, 256), (1000, 8), (300, None),
])
def test_flash_block_tiles_for_the_tpu(sq, block):
    """Flash blocks tile the length and are a multiple of 8 or the whole
    length (what the TPU compiler lays out); else no kernel."""
    assert _flash_block(sq) == block
