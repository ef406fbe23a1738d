"""The system under test: a ``SearchService`` built from a cell's files.

This is the only module of the benchmark that imports the program
(``repro``); an architecture's module (``bench/archs/``) builds the
program's ``ModelConfig`` through it.  The weights are the benchmark's own,
made here from the seed in the layout the program's ``init_params``
declares, so the reference can use them without taking anything the
program made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SearchSpec
from repro.core.batched_search import batched_select
from repro.core.evaluators import CachedModelEvaluator, PagedCachedModelEvaluator
from repro.models import init_params
from repro.models.config import ModelConfig
from repro.serving import SearchService

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

#: Standard deviation of every random matrix and bias (the program's own
#: initializer uses the same).
WEIGHT_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (more than 32 bits hold)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF
    )


def make_weights(cfg: ModelConfig, seed: int):
    """Random weights in the program's parameter layout, made on the device
    in one jitted call: ones for norm scales, normal(0, 0.02) otherwise, in
    the configuration's dtype."""
    shapes = jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0)
    )
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(paths))
        leaves = []
        for k, (path, s) in zip(keys, paths):
            name = str(path[-1].key)
            if name.endswith("norm"):
                leaves.append(jnp.ones(s.shape, s.dtype))
            else:
                leaves.append(
                    jax.random.normal(k, s.shape, s.dtype)
                    * jnp.asarray(WEIGHT_STD, s.dtype)
                )
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make(seed_key(seed))


def build_service(cfg: ModelConfig, weights, cell: dict) -> SearchService:
    """The cell's ``SearchService`` on its evaluator path, with every size
    stated (so nothing is read from elsewhere to size a pool)."""
    spec = SearchSpec(**cell["search"])
    top_k, max_len = cell["top_k"], cell["max_len"]
    path = cell["evaluator"]
    kw = dict(
        top_k=top_k, max_len=max_len,
        ring_capacity=cell["ring_capacity"],
        ticks_per_segment=cell["ticks_per_segment"],
    )
    if path == "dense":
        ev = CachedModelEvaluator(cfg, weights, top_k=top_k)
        return SearchService(cfg, weights, spec, evaluator=ev, **kw)
    if path != "paged":
        raise ValueError(f"unknown evaluator path {path!r}")
    paged = dict(block_size=cell["block_size"], num_blocks=cell["num_blocks"])
    ev = PagedCachedModelEvaluator(cfg, weights, top_k=top_k, **paged)
    return SearchService(cfg, weights, spec, evaluator=ev, paged=True,
                         **paged, **kw)


def tree_snapshot(svc: SearchService) -> dict:
    """The search trees as the timed path left them (host copies of
    ``children``, ``pending``, ``N``, ``O``, ``V``, each ``[B, M, ...]``);
    ``acts`` ``[B, M]``, the child the program's own selection takes at
    every node of them, through ``batched_select``, the ``tree_select``
    kernel call the refill makes, at the cell's ``[B, A]``; and
    ``inner_nodes``, how many nodes have a child."""
    eng = svc._engine
    tree = svc._carry[0]

    # Traced afresh for each service, as the service's own programs are, so
    # that it selects as the service's programs were built to.
    @jax.jit
    def select_every_node(tree):
        def at(m):
            nodes = jnp.full((tree.batch_size,), m, jnp.int32)
            return batched_select(tree, nodes, eng.cfg.policy,
                                  eng.use_kernel)[0]

        return jax.lax.map(at, jnp.arange(tree.capacity))

    out = {k: np.asarray(getattr(tree, k))
           for k in ("children", "pending", "N", "O", "V")}
    out["acts"] = np.asarray(select_every_node(tree)).T
    out["inner_nodes"] = int((out["children"] >= 0).any(-1).sum())
    return out


def slot_sample(svc: SearchService, k: int, rng: np.random.Generator) -> dict:
    """Tokens, lengths, stored next-token logits and decode ticks since
    their last refill (``steps``) of ``k`` evaluator slots, as the timed path
    left them: the longest slot, then slots that have decoded at least two
    tokens since their refill (a step that lost the first token's keys and
    values shows in the second's logits), then the rest, each group in an
    order drawn from ``rng``.

    Each slot's stored logits are what the served program computed for the
    token prefix it holds."""
    carry = svc._carry
    aux = carry[7]
    lens = np.asarray(svc.evaluator.aux_len(aux))
    steps = np.asarray(carry[1].steps).reshape(-1)
    live = np.flatnonzero(lens > 0)
    if live.size == 0:
        return {"slot": np.zeros((0,), np.int32),
                "tokens": np.zeros((0, svc.max_len), np.int32),
                "len": np.zeros((0,), np.int32),
                "logits": np.zeros((0, svc.cfg.vocab_size), np.float32),
                "steps": np.zeros((0,), np.int32)}
    longest = live[np.argmax(lens[live])]
    rest = live[live != longest]
    decoded = steps[rest] >= 2
    order = np.concatenate([rng.permutation(rest[decoded]),
                            rng.permutation(rest[~decoded])])
    idx = np.sort(np.concatenate([[longest], order[: max(0, k - 1)]]))
    sel = jnp.asarray(idx.astype(np.int32))
    return {
        "slot": idx,
        "tokens": np.asarray(aux["tokens"][sel]),
        "len": lens[idx],
        "logits": np.asarray(
            svc.evaluator.aux_last_logits(aux)[sel].astype(jnp.float32)
        ),
        "steps": steps[idx],
    }
