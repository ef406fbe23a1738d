"""Unit tests for the sharding rules and the roofline HLO parser (no
compilation — pure spec/regex logic)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.distributed.sharding import (
    _fsdp_rule,
    abstract_mesh,
    batch_spec,
    param_partition_specs,
)
from repro.launch.dryrun import _shape_bytes, collective_bytes
from repro.launch.mesh import make_test_mesh
from repro.models import abstract_params


@pytest.fixture(scope="module")
def mesh():
    # AbstractMesh stand-in for spec logic (no devices needed).
    return abstract_mesh((16, 16), ("data", "model"))


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("strategy", ["tp", "fsdp"])
def test_every_param_gets_a_valid_spec(arch, strategy, mesh):
    cfg = get_config(arch)
    params = abstract_params(cfg)
    specs = param_partition_specs(cfg, params, mesh, strategy)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    sizes = dict(mesh.shape)
    for leaf, spec in zip(flat_p, flat_s):
        assert isinstance(spec, P)
        assert len(spec) <= len(leaf.shape), (leaf.shape, spec)
        # Every sharded dim must divide evenly.
        for dim, entry in zip(leaf.shape, tuple(spec)):
            if entry is None:
                continue
            parts = 1
            for name in (entry if isinstance(entry, tuple) else (entry,)):
                parts *= sizes[name]
            assert dim % parts == 0, (arch, strategy, leaf.shape, spec)


def test_tp_rules_respect_head_divisibility(mesh):
    """phi3's 40 heads don't divide model=16 → attention replicates."""
    cfg = get_config("phi3-medium-14b")
    params = abstract_params(cfg)
    specs = param_partition_specs(cfg, params, mesh, "tp")
    attn_spec = specs["blocks"]["attn"]["wq"]
    assert all(e is None for e in tuple(attn_spec)), attn_spec
    # llama3's 32 q heads divide → sharded.
    cfg2 = get_config("llama3-8b")
    params2 = abstract_params(cfg2)
    specs2 = param_partition_specs(cfg2, params2, mesh, "tp")
    assert "model" in jax.tree_util.tree_leaves(
        [specs2["blocks"]["attn"]["wq"]],
        is_leaf=lambda x: isinstance(x, P),
    )[0]


def test_fsdp_rule_picks_largest_divisible_dim():
    mesh = abstract_mesh((16, 16), ("data", "model"))
    spec = _fsdp_rule((4096, 14336), mesh, ("data", "model"))
    assert spec == P(None, ("data", "model"))
    # 151936 doesn't divide 256 → falls to the 4096 dim.
    spec = _fsdp_rule((151936, 4096), mesh, ("data", "model"))
    assert spec == P(None, ("data", "model"))
    # nothing divisible → replicate
    spec = _fsdp_rule((7, 13), mesh, ("data", "model"))
    assert spec == P()


def test_batch_spec_fsdp_divisibility():
    mesh = abstract_mesh((16, 16), ("data", "model"))
    assert batch_spec(mesh, "fsdp", 256) == P(("data", "model"))
    # Single-axis specs: pre-0.5 PartitionSpec does not normalize a 1-tuple
    # entry to the bare name, so compare against the bare-name form the code
    # produces.
    assert batch_spec(mesh, "fsdp", 32) == P("data")   # fallback
    assert batch_spec(mesh, "tp", 256) == P("data")


# ---------------------------------------------------------------------------
# HLO collective parser
# ---------------------------------------------------------------------------


def test_shape_bytes():
    assert _shape_bytes("bf16[8,128]{1,0}") == 8 * 128 * 2
    assert _shape_bytes("f32[2,3,4]") == 24 * 4
    assert _shape_bytes("(f32[4], bf16[8])") == 16 + 16
    assert _shape_bytes("pred[]") == 1  # scalar


def test_collective_bytes_parsing():
    hlo = """
  %ag = bf16[1024,512]{1,0} all-gather(%x), replica_groups=[16,16]<=[256], dimensions={0}
  %ar.1 = f32[256]{0} all-reduce(%y), replica_groups=[1,256]<=[256], to_apply=%add
  %rs = bf16[64]{0} reduce-scatter(%z), replica_groups=[32,8]<=[256]
  %cp = f32[128]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %not_a_collective = f32[9] add(%a, %b)
"""
    out = collective_bytes(hlo)
    ag = 1024 * 512 * 2
    assert abs(out["all-gather"] - ag * 15 / 16) < 1
    assert abs(out["all-reduce"] - 2 * 256 * 4 * 255 / 256) < 1
    assert abs(out["reduce-scatter"] - 64 * 2 * 7) < 1
    assert out["collective-permute"] == 128 * 4
    assert out["counts"]["all-gather"] == 1
    assert out["counts"]["all-reduce"] == 1
