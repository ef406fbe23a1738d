"""Batched multi-root WU-UCT: ``B`` independent searches in lockstep.

The wave engine in :mod:`wu_uct` parallelizes rollouts *within* one search;
this engine parallelizes *across* searches — ``B`` independent root states
(many users, many game positions, or an Ensemble-UCT root committee) advance
through selection → expansion → simulation → completion together on one
accelerator.

Design:

* the forest is a :class:`repro.core.batched_tree.BatchedTree` — every SoA
  buffer carries a leading ``[B, ...]`` axis and path walks are lockstep
  masked ``while_loop``\\ s;
* per traversal level, the child statistics of all ``B`` current nodes are
  gathered into dense ``[B, A]`` tables and scored by **one** call into the
  fused Pallas ``tree_select`` kernel (score + masked argmax in a single
  VMEM pass) — the kernel supports all four tree policies, so batched
  baselines (UCT / TreeP / TreeP-VC) ride the same hot path;
* RNG streams are carried per tree and split exactly like the single-tree
  engine splits its stream, so with ``use_kernel`` either on or off this
  engine is *bit-compatible* with ``jax.vmap`` of :func:`wu_uct.run_search`
  (tested in ``tests/test_batched_search.py``);
* the batch axis shards over the ``('pod', 'data')`` mesh axes — pass
  :func:`repro.distributed.sharding.constrain_search_batch` as ``constrain``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..envs.base import Environment
from ..kernels.tree_select.ops import tree_select
from ..kernels.tree_select.ref import tree_select_ref
from . import batched_tree as btree
from .batched_tree import BatchedTree, init_batched_tree
from .evaluators import Evaluator, RolloutEvaluator
from .policies import PolicyConfig, gather_children_tables
from .wu_uct import (
    KIND_EXPAND,
    KIND_SIM,
    KIND_TERMINAL,
    SearchConfig,
    SearchResult,
)

Pytree = Any


class _BatchedSlots(NamedTuple):
    kind: jax.Array       # i32[B, W]
    stop_node: jax.Array  # i32[B, W]
    sim_node: jax.Array   # i32[B, W]
    act: jax.Array        # i32[B, W]


def _canonical_keys(rngs: jax.Array) -> jax.Array:
    """Accept typed PRNG key arrays or raw uint32 key data."""
    if jnp.issubdtype(rngs.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(rngs)
    return rngs


def _split_each(rngs: jax.Array, num: int) -> tuple[jax.Array, ...]:
    """Per-tree ``jax.random.split(rng, num)`` — mirrors the single engine's
    stream structure exactly so vmap-equivalence holds."""
    ks = jax.vmap(lambda k: jax.random.split(k, num))(rngs)
    return tuple(ks[:, i] for i in range(num))


def batched_select(
    tree: BatchedTree,
    nodes: jax.Array,
    pol: PolicyConfig,
    use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Best child action of each tree's current node via one fused [B, A]
    kernel call.  Returns ``(act[B], any_valid[B])``."""
    n_c, o_c, v_c, vl_c, n_p, o_p, valid = gather_children_tables(tree, nodes)
    select = tree_select if use_kernel else tree_select_ref
    act, _ = select(
        n_c, o_c, v_c, n_p, o_p, valid, vl_c,
        kind=pol.kind, beta=pol.beta, r_vl=pol.r_vl, n_vl=pol.n_vl,
    )
    return act.astype(jnp.int32), jnp.any(valid, axis=1)


# ---------------------------------------------------------------------------
# Selection — all B trees traverse in lockstep; one kernel call per level.
# ---------------------------------------------------------------------------


def traverse_batched(
    tree: BatchedTree,
    rngs: jax.Array,
    cfg: SearchConfig,
    use_kernel: bool = True,
) -> jax.Array:
    """Walk every tree from its root by the configured tree policy."""
    width = min(cfg.max_width, tree.num_actions)
    b = jnp.arange(tree.batch_size)

    def cond(carry):
        _, _, stopped = carry
        return jnp.any(jnp.logical_not(stopped))

    def body(carry):
        nodes, rng, stopped = carry
        active = jnp.logical_not(stopped)
        new_rng, k_coin = _split_each(rng, 2)
        rng = jnp.where(active[:, None], new_rng, rng)

        kids = tree.children[b, nodes]                       # [B, A]
        n_tried = jnp.sum((kids >= 0).astype(jnp.int32), axis=1)
        is_leaf = n_tried == 0
        at_depth = tree.depth[b, nodes] >= cfg.max_depth
        is_term = tree.terminal[b, nodes]
        not_full = n_tried < width
        coin = jax.vmap(jax.random.uniform)(k_coin) < cfg.expand_coin
        stop = is_leaf | at_depth | is_term | (not_full & coin)

        best, any_valid = batched_select(tree, nodes, cfg.policy, use_kernel)
        stop = stop | jnp.logical_not(any_valid)
        nxt = jnp.where(stop, nodes, tree.children[b, nodes, best])
        nodes = jnp.where(active, nxt, nodes).astype(jnp.int32)
        return nodes, rng, stopped | stop

    nodes0 = jnp.zeros((tree.batch_size,), jnp.int32)
    stopped0 = jnp.zeros((tree.batch_size,), jnp.bool_)
    nodes, _, _ = jax.lax.while_loop(cond, body, (nodes0, rngs, stopped0))
    return nodes


def _expansion_actions(
    tree: BatchedTree, nodes: jax.Array, rngs: jax.Array, cfg: SearchConfig
) -> jax.Array:
    """Per-tree untried-action choice (Algorithm 7, uniform prior)."""
    b = jnp.arange(tree.batch_size)
    kids = tree.children[b, nodes]
    if cfg.deterministic_expansion:
        return jnp.argmax(kids < 0, axis=1).astype(jnp.int32)
    tried = kids >= 0
    logits = jnp.where(tried, -jnp.inf, 0.0)
    g = jax.vmap(lambda k: jax.random.gumbel(k, (tree.num_actions,)))(rngs)
    return jnp.argmax(logits + g, axis=1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# In-flight statistics (per stat_mode) — masked batched variants live in
# :mod:`repro.core.batched_tree`; these wrappers unpack the search config.
# ---------------------------------------------------------------------------


def _mark_in_flight(
    tree: BatchedTree, nodes: jax.Array, cfg: SearchConfig, mask: jax.Array
) -> BatchedTree:
    return btree.mark_in_flight(
        tree, nodes, mask, stat_mode=cfg.stat_mode, r_vl=cfg.policy.r_vl
    )


def _settle(
    tree: BatchedTree,
    nodes: jax.Array,
    rets: jax.Array,
    cfg: SearchConfig,
    mask: jax.Array,
) -> BatchedTree:
    return btree.settle(
        tree, nodes, rets, mask,
        stat_mode=cfg.stat_mode, gamma=cfg.gamma, r_vl=cfg.policy.r_vl,
    )


# ---------------------------------------------------------------------------
# Wave phases
# ---------------------------------------------------------------------------


def _phase1_select(
    tree: BatchedTree, rngs: jax.Array, cfg: SearchConfig, use_kernel: bool
) -> tuple[BatchedTree, _BatchedSlots, jax.Array]:
    """Sequentially select W slots per tree (in-flight stats in between);
    all B trees fill slot j simultaneously."""
    B = tree.batch_size
    W = cfg.wave_size
    width = min(cfg.max_width, tree.num_actions)
    b = jnp.arange(B)

    def slot_body(j, carry):
        tree, rng, slots = carry
        rng, k_t, k_e = _split_each(rng, 3)
        nodes = traverse_batched(tree, k_t, cfg, use_kernel)

        kids = tree.children[b, nodes]
        n_tried = jnp.sum((kids >= 0).astype(jnp.int32), axis=1)
        is_term = tree.terminal[b, nodes]
        at_depth = tree.depth[b, nodes] >= cfg.max_depth
        needs_expand = (
            jnp.logical_not(is_term)
            & jnp.logical_not(at_depth)
            & (n_tried < width)
        )
        act = _expansion_actions(tree, nodes, k_e, cfg)

        tree, child, expanded = btree.reserve_children(
            tree, nodes, act, mask=needs_expand
        )
        kind = jnp.where(
            is_term, KIND_TERMINAL, jnp.where(expanded, KIND_EXPAND, KIND_SIM)
        ).astype(jnp.int32)
        sim_node = jnp.where(expanded, child, nodes).astype(jnp.int32)

        # Incomplete update as soon as the rollout is initiated (Alg. 1);
        # terminal hits settle immediately with return 0.
        tree = _mark_in_flight(tree, sim_node, cfg, mask=jnp.ones((B,), jnp.bool_))
        tree = _settle(tree, sim_node, jnp.zeros((B,), jnp.float32), cfg, mask=is_term)

        slots = _BatchedSlots(
            kind=slots.kind.at[:, j].set(kind),
            stop_node=slots.stop_node.at[:, j].set(nodes),
            sim_node=slots.sim_node.at[:, j].set(sim_node),
            act=slots.act.at[:, j].set(act),
        )
        return tree, rng, slots

    slots0 = _BatchedSlots(
        kind=jnp.zeros((B, W), jnp.int32),
        stop_node=jnp.zeros((B, W), jnp.int32),
        sim_node=jnp.zeros((B, W), jnp.int32),
        act=jnp.zeros((B, W), jnp.int32),
    )
    tree, rngs, slots = jax.lax.fori_loop(0, W, slot_body, (tree, rngs, slots0))

    sorted_stops = jnp.sort(slots.stop_node, axis=1)
    dups = jnp.sum(
        (sorted_stops[:, 1:] == sorted_stops[:, :-1]).astype(jnp.float32),
        axis=1,
    )
    return tree, slots, dups


def _phase2_work(
    env: Environment,
    cfg: SearchConfig,
    tree: BatchedTree,
    slots: _BatchedSlots,
    rngs: jax.Array,
    constrain: Optional[Callable[[Pytree], Pytree]] = None,
    evaluator: Optional[Evaluator] = None,
):
    """Expansion env-step + simulation rollout for all B × W slots at once —
    the compute that shards over the ('pod', 'data') mesh axes."""
    W = cfg.wave_size
    evaluator = evaluator if evaluator is not None else RolloutEvaluator(env)
    keys = jax.vmap(lambda k: jax.random.split(k, W))(rngs)   # [B, W, ...]

    def per_tree(states_b, terminal_b, kinds, stop_nodes, sim_nodes, acts, kb):
        def one_slot(kind, stop_node, sim_node, act, key):
            parent_state = jax.tree.map(lambda x: x[stop_node], states_b)
            child_state, r_edge, done_child = env.step(parent_state, act)
            is_exp = kind == KIND_EXPAND
            start_state = jax.tree.map(
                lambda a, b: jnp.where(is_exp, a, b),
                child_state,
                jax.tree.map(lambda x: x[sim_node], states_b),
            )
            start_done = jnp.where(is_exp, done_child, terminal_b[sim_node])
            ret = evaluator.rollout(cfg, start_state, start_done, key)
            return child_state, r_edge, done_child, ret

        return jax.vmap(one_slot)(kinds, stop_nodes, sim_nodes, acts, kb)

    args = (
        tree.states, tree.terminal,
        slots.kind, slots.stop_node, slots.sim_node, slots.act, keys,
    )
    if constrain is not None:
        args = constrain(args)
    out = jax.vmap(per_tree)(*args)
    if constrain is not None:
        out = constrain(out)
    return out  # (child_states[B,W,...], r_edge[B,W], done_child[B,W], ret[B,W])


def _phase3_settle(
    tree: BatchedTree,
    cfg: SearchConfig,
    slots: _BatchedSlots,
    child_states: Pytree,
    r_edge: jax.Array,
    done_child: jax.Array,
    rets: jax.Array,
) -> BatchedTree:
    """Master-side completion: write expansion results + complete updates."""
    W = cfg.wave_size

    def slot_body(j, tree):
        kind = slots.kind[:, j]
        sim_node = slots.sim_node[:, j]
        st = jax.tree.map(lambda x: x[:, j], child_states)
        tree = btree.finalize_children(
            tree, sim_node, st, r_edge[:, j], done_child[:, j],
            mask=kind == KIND_EXPAND,
        )
        tree = _settle(tree, sim_node, rets[:, j], cfg, mask=kind != KIND_TERMINAL)
        return tree

    return jax.lax.fori_loop(0, W, slot_body, tree)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def run_search_batched(
    env: Environment,
    cfg: SearchConfig,
    root_states: Pytree,
    rngs: jax.Array,
    constrain: Optional[Callable[[Pytree], Pytree]] = None,
    use_kernel: bool = True,
    evaluator: Optional[Evaluator] = None,
) -> SearchResult:
    """Run ``B`` independent searches; every field of the returned
    :class:`SearchResult` carries a leading ``[B]`` axis.

    ``root_states`` is a pytree whose leaves lead with ``[B]``; ``rngs`` is
    ``jax.random.split(key, B)`` (one independent stream per tree).
    """
    if cfg.num_simulations % cfg.wave_size != 0:
        raise ValueError("num_simulations must be divisible by wave_size")
    num_waves = cfg.num_simulations // cfg.wave_size
    capacity = cfg.num_simulations + cfg.wave_size + 1
    rngs = _canonical_keys(rngs)
    B = rngs.shape[0]
    tree = init_batched_tree(root_states, capacity, env.num_actions)

    def wave_body(i, carry):
        tree, rng, dup_acc, max_o = carry
        rng, k_sel, k_sim = _split_each(rng, 3)
        tree, slots, dups = _phase1_select(tree, k_sel, cfg, use_kernel)
        max_o = jnp.maximum(max_o, tree.O[:, 0])
        child_states, r_edge, done_child, rets = _phase2_work(
            env, cfg, tree, slots, k_sim, constrain, evaluator
        )
        tree = _phase3_settle(
            tree, cfg, slots, child_states, r_edge, done_child, rets
        )
        return tree, rng, dup_acc + dups, max_o

    tree, _, dup_acc, max_o = jax.lax.fori_loop(
        0, num_waves, wave_body,
        (tree, rngs, jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.float32)),
    )

    root_n, root_v = btree.root_action_stats(tree)
    return SearchResult(
        action=btree.best_root_action(tree),
        root_n=root_n,
        root_v=root_v,
        tree_size=tree.size,
        dup_selections=dup_acc / num_waves,
        max_o=max_o,
        overflowed=tree.overflowed,
        ticks=jnp.full((B,), num_waves, jnp.int32),
    )


def make_batched_searcher(
    env: Environment,
    cfg: SearchConfig,
    constrain: Optional[Callable[[Pytree], Pytree]] = None,
    jit: bool = True,
    use_kernel: bool = True,
    evaluator: Optional[Evaluator] = None,
):
    """Build ``search(root_states[B], rngs[B]) -> SearchResult[B]``."""
    fn = functools.partial(
        run_search_batched, env, cfg, constrain=constrain,
        use_kernel=use_kernel, evaluator=evaluator,
    )
    return jax.jit(fn) if jit else fn
