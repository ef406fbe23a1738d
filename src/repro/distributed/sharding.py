"""Sharding rules: logical axes → mesh axes for params, state and batches.

Mesh axes (launch/mesh.py): ``('data', 'model')`` single-pod and
``('pod', 'data', 'model')`` multi-pod.  ``pod`` behaves as an outer data
axis for training (and as the wave/root-parallel axis for search).

Rules of thumb implemented here:

* vocab/d_ff/expert/head dims → ``model`` (TP / EP) when divisible, else
  replicate (the divisibility fallback matters for phi3/qwen2.5's 40 heads
  and whisper's 12 — see EXPERIMENTS.md §Perf for the padding hillclimb);
* batch → ``(pod, data)``;
* AdamW fp32 state (m, v, master) is additionally sharded over ``data`` on
  its largest divisible axis — ZeRO-style: DP replicas each own a slice of
  optimizer memory;
* MCTS tree statistics are replicated; wave slots shard over ``(pod, data)``;
* batched multi-root search (core/batched_search.py) shards its leading
  tree-batch axis ``B`` over ``(pod, data)`` — each DP replica owns a slice
  of the forest and its wave slots (see :func:`constrain_search_batch`);
* the batched *async* engine (core/batched_async_search.py) additionally
  flattens its slot ticks to one ``[B·W]`` rollout batch; the same
  :func:`constrain_search_batch` hook shards that axis (and the future
  policy/value model forward pass riding it) over ``(pod, data)``.
"""

from __future__ import annotations

import re
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig

Pytree = Any


def _mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(mesh.shape)


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def logical_spec(mesh, *axes) -> P:
    """PartitionSpec with axes not present in the mesh dropped."""
    names = set(mesh.axis_names)

    def keep(a):
        if a is None:
            return None
        if isinstance(a, tuple):
            kept = tuple(x for x in a if x in names)
            return kept if kept else None
        return a if a in names else None

    return P(*(keep(a) for a in axes))


def ambient_abstract_mesh():
    """The ambient abstract mesh (empty outside a mesh context)."""
    return jax.sharding.get_abstract_mesh()


def abstract_mesh(axis_sizes, axis_names):
    """A device-free ``AbstractMesh`` with every axis ``Auto``-typed."""
    return jax.sharding.AbstractMesh(
        tuple(axis_sizes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


def use_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def constrain(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint against the ambient abstract mesh (no-op
    outside a mesh context, so model code stays mesh-agnostic)."""
    mesh = ambient_abstract_mesh()
    if not mesh.axis_names:  # unset mesh
        return x
    spec = logical_spec(mesh, *axes)
    # Drop axes that don't divide the corresponding dim.
    sizes = _mesh_axis_sizes(mesh)
    fixed = []
    for dim, a in zip(x.shape, spec):
        if a is None:
            fixed.append(None)
            continue
        parts = 1
        for name in (a if isinstance(a, tuple) else (a,)):
            parts *= sizes[name]
        fixed.append(a if dim % parts == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*fixed))


def constrain_search_batch(pytree: Pytree) -> Pytree:
    """Shard the leading tree-batch axis of every leaf over ``(pod, data)``.

    This is the ``constrain`` hook for both batched search engines
    (:func:`repro.core.batched_search.run_search_batched` and
    :func:`repro.core.batched_async_search.run_async_search_batched`): slot
    tables and per-node state buffers all lead with the ``B`` axis — and the
    async engine's flattened ``[B·W]`` slot-tick batch leads with ``B·W`` —
    so one constraint rule covers the whole pytree.  A no-op outside a mesh
    context, and for leaves whose leading dim does not divide the data axes.
    """

    def one(x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return x
        return constrain(x, ("pod", "data"), *([None] * (x.ndim - 1)))

    return jax.tree.map(one, pytree)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------


def _tp_ok(dim: int, mesh, axis: str = "model") -> bool:
    sizes = _mesh_axis_sizes(mesh)
    return axis in sizes and dim % sizes[axis] == 0


def _param_rule(cfg: ModelConfig, path: str, shape: tuple, mesh) -> P:
    tp = "model"
    hd = cfg.head_dim

    def heads_shardable(n_heads):
        return _tp_ok(n_heads, mesh)

    # --- embeddings / head ---
    if path.endswith("embed"):
        return logical_spec(mesh, tp, None) if _tp_ok(shape[0], mesh) else P()
    if path.endswith("lm_head"):
        return logical_spec(mesh, None, tp) if _tp_ok(shape[1], mesh) else P()

    # --- attention ---
    if re.search(r"(attn|cross)/w[qkvo]$", path) or re.search(r"(attn|cross)/b[qkv]$", path):
        n_heads = cfg.num_heads if re.search(r"w[qo]|bq", path) else cfg.num_kv_heads
        if not heads_shardable(n_heads):
            return P()  # replicate: attention falls back to pure DP
        if path.endswith("wo"):
            return logical_spec(mesh, tp, None)
        if re.search(r"b[qkv]$", path):
            return logical_spec(mesh, tp)
        return logical_spec(mesh, None, tp)

    # --- dense MLP / shared expert ---
    if re.search(r"(mlp|shared)/w_(gate|up)$", path):
        return logical_spec(mesh, None, tp) if _tp_ok(shape[-1], mesh) else P()
    if re.search(r"(mlp|shared)/w_down$", path):
        return logical_spec(mesh, tp, None) if _tp_ok(shape[-2], mesh) else P()

    # --- MoE routed experts: EP over the expert dim ---
    if re.search(r"moe/w_(gate|up|down)$", path):
        return (
            logical_spec(mesh, tp, None, None)
            if _tp_ok(shape[-3], mesh)
            else P()
        )
    if path.endswith("router"):
        return P()

    # --- Mamba-2 ---
    if re.search(r"ssm/in_[xz]$", path):
        return logical_spec(mesh, None, tp) if _tp_ok(shape[-1], mesh) else P()
    if re.search(r"ssm/in_dt$", path):
        return logical_spec(mesh, None, tp) if _tp_ok(shape[-1], mesh) else P()
    if re.search(r"ssm/conv_x$", path):
        return logical_spec(mesh, None, tp) if _tp_ok(shape[-1], mesh) else P()
    if re.search(r"ssm/(A_log|dt_bias|D|norm)$", path):
        return logical_spec(mesh, tp) if _tp_ok(shape[-1], mesh) else P()
    if re.search(r"ssm/out$", path):
        return logical_spec(mesh, tp, None) if _tp_ok(shape[-2], mesh) else P()
    # in_B / in_C / conv_B / conv_C / norms / everything else: replicate.
    return P()


def _paths_and_leaves(tree: Pytree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        key = "/".join(
            str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
            for p in path
        )
        yield key, leaf
    return


def _fsdp_rule(shape: tuple, mesh, axes: tuple[str, ...]) -> P:
    """ZeRO-3/FSDP: shard the largest divisible dim over all given axes.

    Compute-time behavior under GSPMD: weights are all-gathered per layer
    (cheap — parameter bytes) instead of activations being all-reduced
    (expensive at large batch·seq) — the classic TP→FSDP trade for models
    that fit one chip's memory after sharding.
    """
    sizes = _mesh_axis_sizes(mesh)
    total = 1
    for a in axes:
        total *= sizes.get(a, 1)
    best, best_dim = None, 0
    for i, dim in enumerate(shape):
        if dim % total == 0 and dim > best_dim:
            best, best_dim = i, dim
    if best is None:
        return P()
    entries = [None] * len(shape)
    entries[best] = axes if len(axes) > 1 else axes[0]
    return P(*entries)


def param_partition_specs(
    cfg: ModelConfig, abstract_params: Pytree, mesh, strategy: str = "tp"
) -> Pytree:
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    all_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    specs = []
    for path, leaf in flat:
        key = "/".join(
            str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
            for p in path
        )
        shape = leaf.shape
        stacked = key.startswith(("blocks/", "encoder/blocks/"))
        tail = shape[1:] if stacked else shape
        if strategy == "fsdp":
            spec = _fsdp_rule(tail, mesh, all_axes)
        else:
            spec = _param_rule(cfg, key, tail, mesh)
        specs.append(P(None, *spec) if stacked else spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(
    cfg: ModelConfig, abstract_params: Pytree, mesh: Mesh, strategy: str = "tp"
) -> Pytree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        param_partition_specs(cfg, abstract_params, mesh, strategy),
        is_leaf=lambda x: isinstance(x, P),
    )


def _zero_shard(spec: P, shape: tuple, mesh) -> P:
    """Extend a TP spec with ZeRO sharding over the data axes: partition the
    largest still-unsharded, divisible dim over ('pod','data')."""
    dp = data_axes(mesh)
    if not dp:
        return spec
    used = set()
    for a in spec:
        for name in (a if isinstance(a, tuple) else (a,)):
            used.add(name)
    if used & set(dp):  # already data-sharded (fsdp strategy)
        return spec
    sizes = _mesh_axis_sizes(mesh)
    dp_total = 1
    for a in dp:
        dp_total *= sizes[a]
    entries = list(spec) + [None] * (len(shape) - len(spec))
    best, best_dim = None, 0
    for i, (dim, a) in enumerate(zip(shape, entries)):
        if a is None and dim % dp_total == 0 and dim > best_dim:
            best, best_dim = i, dim
    if best is None:
        return spec
    entries[best] = dp if len(dp) > 1 else dp[0]
    return P(*entries)


def opt_state_shardings(
    cfg: ModelConfig,
    abstract_params: Pytree,
    mesh: Mesh,
    abstract_opt: Pytree,
    strategy: str = "tp",
) -> Pytree:
    """AdamW state: param spec + ZeRO partition over data axes."""
    pspecs = param_partition_specs(cfg, abstract_params, mesh, strategy)

    def for_moment(spec_tree, leaf_tree):
        return jax.tree.map(
            lambda s, l: NamedSharding(mesh, _zero_shard(s, l.shape, mesh)),
            spec_tree,
            leaf_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    from ..training.optimizer import AdamWState

    return AdamWState(
        step=NamedSharding(mesh, P()),
        m=for_moment(pspecs, abstract_opt.m),
        v=for_moment(pspecs, abstract_opt.v),
        master=for_moment(pspecs, abstract_opt.master),
    )


def batch_spec(mesh, strategy: str = "tp", global_batch: int | None = None) -> P:
    if strategy == "fsdp":
        # Batch shards over ALL axes when divisible (single-pod: 256 = 16·16).
        axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
        sizes = _mesh_axis_sizes(mesh)
        total = 1
        for a in axes:
            total *= sizes[a]
        if global_batch is None or global_batch % total == 0:
            return P(axes)
    dp = data_axes(mesh)
    return P(dp if len(dp) > 1 else (dp[0] if dp else None))


def batch_shardings(mesh: Mesh, batch_abstract: Pytree) -> Pytree:
    spec = batch_spec(mesh)
    return jax.tree.map(lambda _: NamedSharding(mesh, spec), batch_abstract)
