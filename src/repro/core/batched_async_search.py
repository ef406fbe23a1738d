"""Batched async-slot WU-UCT: ``B`` independent async searches, one program.

:mod:`batched_search` batches the *wave* engine (barrier per wave); this
module batches :func:`repro.core.async_search.run_async_search` — the engine
that reproduces the paper's master–worker interleaving, where rollouts settle
at different ticks and a freed slot is refilled immediately.  ``B`` trees ×
``W`` async slots advance inside one jitted ``lax.while_loop``:

* **slot ticks** are vmapped over the flat ``[B·W]`` axis, so every busy
  slot's environment step forms a single batch — exactly the shape a future
  policy/value-network forward pass wants (one model call per master tick);
* **refills** route selection through the fused Pallas ``tree_select``
  kernel as ``[B, A]`` scoring calls (:func:`batched_search.traverse_batched`);
* **bookkeeping** uses the masked batched ``_mark_in_flight`` / ``_settle``
  variants in :mod:`batched_tree` — because settles land at different ticks
  per tree, every update carries a per-tree mask;
* **RNG streams** are carried per tree with the same split structure as the
  single engine, so the output is *bit-identical* to
  ``jax.vmap(run_async_search)`` (tested in
  ``tests/test_batched_async_search.py``).  The win over plain ``vmap`` is
  structural: ``vmap`` of the single engine turns every per-slot
  ``lax.cond`` into a select over the whole tree pytree (O(B·M) memory
  traffic per slot refill), while this engine performs masked row updates.

The engine is exposed two ways:

* :func:`run_async_search_batched` — the one-shot API: admit a batch of
  roots, run every tree to its simulation budget, return ``SearchResult[B]``;
* :class:`BatchedAsyncEngine` — the *persistent* form the serving layer
  drives: the same master tick, but the carry outlives any single request.
  When a tree settles (its ``t_done`` hits the budget) the engine's
  :meth:`~BatchedAsyncEngine.step` freezes that row; the host then splices a
  queued request into the row **mid-stream** via
  :meth:`~BatchedAsyncEngine.admit` — fresh tree, fresh per-tree RNG lane,
  fresh evaluator slot caches (``Evaluator.admit_aux``: dense KV re-prefill
  + cache splice, or paged page-table splice + refcount fan-out) — while the
  other ``B-1`` rows keep searching.  Because every per-row computation
  (traversal scoring, top-k, the Pallas ``[B, A]`` kernel, per-tree RNG
  splits) is row-independent, an admitted request's search is equivalent to
  the same request served in a fresh batch (``tests/test_serving_continuous``
  asserts visit-mass parity).

The flat ``[B·W]`` slot axis and the ``[B]`` tree axis both shard over the
``('pod', 'data')`` mesh axes — pass
:func:`repro.distributed.sharding.constrain_search_batch` as ``constrain``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..envs.base import Environment
from . import batched_tree as btree
from .async_search import EXPAND, FREE, SIM, tick_snapshot
from .evaluators import (
    CATCH_UP,
    REFILL_CACHE,
    Evaluator,
    RolloutEvaluator,
    SlotColumn,
)
from .batched_search import (
    _canonical_keys,
    _expansion_actions,
    _mark_in_flight,
    _settle,
    _split_each,
    traverse_batched,
)
from .batched_tree import init_batched_tree
from .wu_uct import SearchConfig, SearchResult

Pytree = Any

# The stages of a master tick and of the in-loop serving round, as
# ``jax.named_scope``s: each name reaches the ``op_name`` metadata of the ops
# it covers, so a device trace can be timed by stage (a fusion takes the
# scope of its root op).  The evaluator's two refill stages are named in
# :mod:`repro.core.evaluators`.
SELECT = "select"            # tree policy: traversal, expansion, in-flight marks
DECODE = "decode"            # the evaluator's tick over all B·W slots
SETTLE = "settle"            # finalize expanded children, back up returns
SERVE_ROUND = "serve_round"  # in-loop harvest, eviction and ring admission
TICK_SCOPES = (SELECT, REFILL_CACHE, CATCH_UP, DECODE, SETTLE, SERVE_ROUND)


class _BatchedAsyncSlots(NamedTuple):
    kind: jax.Array          # i32[B, W]  FREE / EXPAND / SIM
    sim_node: jax.Array      # i32[B, W]  node being evaluated
    act: jax.Array           # i32[B, W]  expansion action (EXPAND phase)
    state: Pytree            # pytree[B, W, ...] current rollout env state
    rollout_done: jax.Array  # bool[B, W]
    acc: jax.Array           # f32[B, W]  discounted return accumulator
    disc: jax.Array          # f32[B, W]
    steps: jax.Array         # i32[B, W]  simulation steps taken


class RequestRing(NamedTuple):
    """Device-resident staging buffer of pre-prefilled requests.

    A fixed-capacity circular queue the host fills *between* jitted
    segments (:meth:`BatchedAsyncEngine.stage`) and the fused serving loop
    drains *inside* the ``while_loop`` (:meth:`BatchedAsyncEngine
    .serve_segment`): when a tree settles mid-segment, its row is re-seeded
    from the ring head without returning to Python.  ``aux`` holds the
    evaluator's staged per-request resources (dense: prefilled KV rows +
    root logits; paged: a page table whose pool pages are already written
    and held at refcount 1 by the ring).
    """

    req_id: jax.Array   # i32[C]   host-assigned id, -1 = empty slot
    states: Pytree      # pytree[C, ...] root env states
    rng: jax.Array      # u32[C, K] canonical per-request RNG lanes
    head: jax.Array     # i32[]    index of the oldest staged request
    count: jax.Array    # i32[]    staged-but-not-admitted requests
    aux: Pytree         # evaluator ring staging (see init_ring_aux)


class Completions(NamedTuple):
    """Device-side completion buffer one :meth:`serve_segment` fills.

    ``count`` rows are valid; each is the :class:`SearchResult` snapshot of
    one request taken at the tick its tree settled, tagged with the
    ``req_id`` the host staged it under, and with ``settle_tick``, the
    number of the segment's ticks run when the row was found settled (its
    ``ticks`` counts back to the tick it was admitted at).  Capacity is
    ``B + ring_capacity`` — everything in flight plus everything staged can
    complete within one segment, so a segment can never overflow its own
    buffer.
    """

    req_id: jax.Array      # i32[C_out]
    action: jax.Array      # i32[C_out]
    root_n: jax.Array      # f32[C_out, A]
    root_v: jax.Array      # f32[C_out, A]
    tree_size: jax.Array   # i32[C_out]
    max_o: jax.Array       # f32[C_out]
    overflowed: jax.Array  # bool[C_out]
    ticks: jax.Array       # i32[C_out]
    settle_tick: jax.Array  # i32[C_out]
    count: jax.Array       # i32[]


def _freeze_done(alive: jax.Array, new: Pytree, old: Pytree) -> Pytree:
    """Per-tree carry select — the masking ``vmap`` applies to a batched
    ``while_loop`` body, done by hand.  Every leaf leads with ``[B]``."""
    return jax.tree.map(
        lambda a, b: jnp.where(
            alive.reshape(alive.shape + (1,) * (a.ndim - 1)), a, b
        ),
        new,
        old,
    )


class BatchedAsyncEngine:
    """``B``-tree async-slot WU-UCT with a carry that outlives requests.

    The master tick (``refill → tick → settle``) is identical to the
    one-shot :func:`run_async_search_batched` program — that function is a
    thin wrapper over this class, and the vmap-oracle bit-equivalence tests
    pin the tick.  What the class adds is slot-level request lifecycle:

    * :meth:`init_carry` — build the loop carry, optionally with some rows
      born *idle* (``active=False`` rows start with ``t_done == T``, so
      :meth:`step` freezes them until something is admitted);
    * :meth:`step` / :meth:`run_segment` — one / up to ``n`` frozen-masked
      master ticks (settled trees' slots are masked FREE so they stop
      feeding the evaluator);
    * :meth:`settled` / :meth:`result` — which rows finished their budget,
      and the ``SearchResult[B]`` snapshot to harvest them from;
    * :meth:`admit` — splice fresh requests into settled rows: tree reset
      (`init_batched_tree` rows scattered in), slot pool reset, per-tree RNG
      lane overwrite, counters zeroed, and the evaluator's
      ``admit_aux`` re-seeds the rows' ``W`` slot caches (ragged re-prefill
      + dense cache splice, or paged page-table splice + refcount fan-out —
      the shared :mod:`repro.serving.admission` path);
    * :meth:`evict` — release a settled row's evaluator-side resources
      (paged caches return their pages to the pool) without admitting a
      replacement.

    ``admit``/``evict``/``result`` are eager-boundary methods (the serving
    layer calls them between jitted segments); ``step``/``run_segment`` are
    pure and jit-safe.
    """

    def __init__(
        self,
        env: Environment,
        cfg: SearchConfig,
        batch: int,
        *,
        evaluator: Optional[Evaluator] = None,
        constrain: Optional[Callable[[Pytree], Pytree]] = None,
        use_kernel: bool = True,
    ):
        self.env = env
        self.cfg = cfg
        self.B = int(batch)
        self.W = cfg.wave_size
        self.T = cfg.num_simulations
        self.width = min(cfg.max_width, env.num_actions)
        self.capacity = cfg.num_simulations + cfg.wave_size + 1
        self.evaluator = (
            evaluator if evaluator is not None else RolloutEvaluator(env)
        )
        self.constrain = constrain
        self.use_kernel = use_kernel
        self._bidx = jnp.arange(self.B)
        # The single engine ignores deterministic_expansion (Algorithm 7).
        self._exp_cfg = cfg._replace(deterministic_expansion=False)

    # ------------------------------------------------------------------
    # Slot pool
    # ------------------------------------------------------------------
    def _slot_rows0(self, root_states, rows: int) -> _BatchedAsyncSlots:
        """Fresh slot-pool rows (all FREE) for ``rows`` trees."""
        proto = self.evaluator.init_state(
            jax.tree.map(lambda x: x[0], root_states), (rows, self.W)
        )
        return _BatchedAsyncSlots(
            kind=jnp.zeros((rows, self.W), jnp.int32),
            sim_node=jnp.zeros((rows, self.W), jnp.int32),
            act=jnp.zeros((rows, self.W), jnp.int32),
            state=proto,
            rollout_done=jnp.zeros((rows, self.W), jnp.bool_),
            acc=jnp.zeros((rows, self.W), jnp.float32),
            disc=jnp.ones((rows, self.W), jnp.float32),
            steps=jnp.zeros((rows, self.W), jnp.int32),
        )

    def _set_slot(
        self, slots: _BatchedAsyncSlots, j, mask, **kw
    ) -> _BatchedAsyncSlots:
        """Write slot column ``j`` for trees where ``mask`` holds."""
        B = self.B
        upd = {}
        for f in slots._fields:
            v = getattr(slots, f)
            if f in kw:
                if f == "state":
                    v = jax.tree.map(
                        lambda b, x: b.at[:, j].set(
                            jnp.where(
                                mask.reshape((B,) + (1,) * (x.ndim - 1)),
                                x,
                                b[:, j],
                            )
                        ),
                        v,
                        kw[f],
                    )
                else:
                    v = v.at[:, j].set(jnp.where(mask, kw[f], v[:, j]))
            upd[f] = v
        return _BatchedAsyncSlots(**upd)

    # ------------------------------------------------------------------
    # Master tick
    # ------------------------------------------------------------------
    def _refill(self, carry):
        """Fill each tree's FREE slots with fresh selections — slot ``j`` of
        all ``B`` trees fills simultaneously, one [B, A] kernel call per
        traversal level."""
        B, W, T, cfg = self.B, self.W, self.T, self.cfg
        bidx = self._bidx

        def body(j, c):
            tree, slots, rng, t_launch, t_done, aux, fr_hits = c
            with jax.named_scope(SELECT):
                rng, k_t, k_e = _split_each(rng, 3)
                want = (slots.kind[:, j] == FREE) & (t_launch < T)

                nodes = traverse_batched(tree, k_t, cfg, self.use_kernel)
                kids = tree.children[bidx, nodes]
                n_tried = jnp.sum((kids >= 0).astype(jnp.int32), axis=1)
                is_term = tree.terminal[bidx, nodes]
                at_depth = tree.depth[bidx, nodes] >= cfg.max_depth
                needs_exp = (
                    jnp.logical_not(is_term)
                    & jnp.logical_not(at_depth)
                    & (n_tried < self.width)
                )
                act = _expansion_actions(tree, nodes, k_e, self._exp_cfg)
                tree, child, reserved = btree.reserve_children(
                    tree, nodes, act, mask=want & needs_exp
                )
                needs_exp = needs_exp & reserved
                sim_node = jnp.where(needs_exp, child, nodes).astype(jnp.int32)
                tree = _mark_in_flight(tree, sim_node, cfg, mask=want)

                # Terminal hit: settle instantly, slot stays FREE (the paper
                # counts it as a completed simulation with return 0).
                tree = _settle(
                    tree, sim_node, jnp.zeros((B,), jnp.float32), cfg,
                    mask=want & is_term,
                )
                parent_state = btree.get_state(tree, nodes)
                slots = self._set_slot(
                    slots,
                    j,
                    want,
                    kind=jnp.where(
                        is_term, FREE, jnp.where(needs_exp, EXPAND, SIM)
                    ).astype(jnp.int32),
                    sim_node=sim_node,
                    act=act,
                    state=parent_state,
                    rollout_done=tree.terminal[bidx, sim_node],
                    acc=jnp.zeros((B,), jnp.float32),
                    disc=jnp.ones((B,), jnp.float32),
                    steps=jnp.zeros((B,), jnp.int32),
                )
                t_launch = t_launch + want.astype(jnp.int32)
                t_done = t_done + (want & is_term).astype(jnp.int32)
            # Re-sync the evaluator's slot caches: slot column j of every
            # tree lives at flat row b·W + j of the aux pool, a strided
            # column the evaluator slices rather than gathers.
            aux, hit = self.evaluator.refill_aux(
                cfg, aux, SlotColumn(j, W), parent_state,
                want & jnp.logical_not(is_term),
            )
            fr_hits = fr_hits + hit.astype(jnp.int32)
            return tree, slots, rng, t_launch, t_done, aux, fr_hits

        return jax.lax.fori_loop(0, W, body, carry)

    def _tick(self, slots: _BatchedAsyncSlots, rng, aux):
        """Advance every busy slot by one env step — vmapped over the flat
        [B·W] axis, forming one rollout batch (the future model-forward
        hook); shards over ('pod', 'data') via ``constrain``."""
        B, W = self.B, self.W
        keys = jax.vmap(lambda k: jax.random.split(k, W))(rng)   # [B, W, ...]

        def flat(x):
            return x.reshape((B * W,) + x.shape[2:])

        args = (
            flat(slots.kind), flat(slots.act),
            jax.tree.map(flat, slots.state),
            flat(slots.rollout_done), flat(slots.acc), flat(slots.disc),
            flat(slots.steps), flat(keys),
        )
        if self.constrain is not None:
            args = self.constrain(args)
        # aux stays outside `constrain`: model-cache leaves lead with the
        # layer axis, not the slot axis the hook shards.
        with jax.named_scope(DECODE):
            out, aux = self.evaluator.tick(self.cfg, *args, aux)
        if self.constrain is not None:
            out = self.constrain(out)
        out = jax.tree.map(lambda x: x.reshape((B, W) + x.shape[1:]), out)
        new_state, r_edge, done_edge, acc, disc, steps, rollout_done = out
        slots = slots._replace(
            state=new_state, acc=acc, disc=disc, steps=steps,
            rollout_done=rollout_done,
        )
        return slots, r_edge, done_edge, aux

    def _settle_finished(self, carry, r_edge, done_edge):
        """EXPAND→SIM transitions (finalize child) + completed rollouts."""
        cfg = self.cfg

        def body(j, c):
            tree, slots, t_done = c
            kind_j = slots.kind[:, j]
            is_exp = kind_j == EXPAND

            # EXPAND slots: their env step just produced the child state.
            st = jax.tree.map(lambda x: x[:, j], slots.state)
            tree = btree.finalize_children(
                tree, slots.sim_node[:, j], st, r_edge[:, j], done_edge[:, j],
                mask=is_exp,
            )
            kind2 = jnp.where(is_exp, SIM, kind_j).astype(jnp.int32)
            steps2 = jnp.where(is_exp, 0, slots.steps[:, j]).astype(jnp.int32)

            # SIM slots finished (episode done or step cap): complete update.
            fin = (kind2 == SIM) & (
                slots.rollout_done[:, j] | (steps2 >= cfg.max_sim_steps)
            )
            tree = _settle(tree, slots.sim_node[:, j], slots.acc[:, j], cfg,
                           mask=fin)
            slots = slots._replace(
                kind=slots.kind.at[:, j].set(
                    jnp.where(fin, FREE, kind2).astype(jnp.int32)
                ),
                steps=slots.steps.at[:, j].set(steps2),
            )
            return tree, slots, t_done + fin.astype(jnp.int32)

        with jax.named_scope(SETTLE):
            return jax.lax.fori_loop(0, self.W, body, carry)

    def alive(self, carry) -> jax.Array:
        """bool[B] — trees still short of their simulation budget."""
        return carry[4] < self.T          # t_done, per tree

    def settled(self, carry) -> jax.Array:
        """bool[B] — trees whose search finished (harvest/admit targets)."""
        return carry[4] >= self.T

    def _master_iter(self, carry):
        tree, slots, rng, t_launch, t_done, ticks, max_o, aux, fr_hits = carry
        rng, k_tick = _split_each(rng, 2)
        tree, slots, rng, t_launch, t_done, aux, fr_hits = self._refill(
            (tree, slots, rng, t_launch, t_done, aux, fr_hits)
        )
        max_o = jnp.maximum(max_o, tree.O[:, 0])
        attended = self.evaluator.attended_positions(aux)
        slots, r_edge, done_edge, aux = self._tick(slots, k_tick, aux)
        tree, slots, t_done = self._settle_finished(
            (tree, slots, t_done), r_edge, done_edge
        )
        return (
            tree, slots, rng, t_launch, t_done, ticks + 1, max_o, aux, fr_hits
        ), attended

    def step(self, carry):
        """One master tick with finished trees frozen — the same per-lane
        masking ``vmap`` would apply to the single engine's while_loop.
        Returns ``(carry, attended)``: the new carry, and the key/value
        positions the tick's decode step attended over all ``B·W`` slots
        (``Evaluator.attended_positions``; 0 without a model cache).

        The evaluator aux rides outside the freeze: its leaves don't lead
        with ``[B]`` (model caches lead with the layer axis), and a finished
        tree's cache drift is unobservable — its slots are frozen, so
        nothing it decodes ever reaches the tree again.

        Finished trees' slot kinds are masked to FREE for the iteration so
        their dead slots stop FEEDING the evaluator: with a dense cache the
        drift was merely unobservable waste, but with a shared paged pool a
        dead tree's slots would keep allocating copy-on-write blocks every
        tick and starve the live trees.  Tree-side writes were already
        masked (``want`` is false once ``t_launch >= T``), slot outputs are
        frozen from ``carry``, and the RNG split structure is untouched, so
        the vmap-oracle bit-equivalence is preserved.  The same property
        makes settled rows safe *admission targets*: a frozen row's state is
        exactly its state at settle time, so the serving layer can harvest
        and overwrite it between any two ticks.
        """
        alive = self.alive(carry)
        slots_in = carry[1]
        masked = slots_in._replace(
            kind=jnp.where(alive[:, None], slots_in.kind, FREE).astype(
                jnp.int32
            )
        )
        new, attended = self._master_iter((carry[0], masked) + carry[2:])
        # aux rides outside the freeze (above); the per-tree frontier-hit
        # counter rides after it and freezes with a plain where — its hits
        # are already masked by ``want``, so dead lanes never advance.
        return _freeze_done(alive, new[:-2], carry[:-2]) + (
            new[-2], jnp.where(alive, new[-1], carry[-1]),
        ), attended

    # ------------------------------------------------------------------
    # Request lifecycle (the serving layer's surface)
    # ------------------------------------------------------------------
    def init_carry(self, root_states, rngs, active=None):
        """Build the master-loop carry for ``B`` root states.

        ``rngs`` is ``jax.random.split(key, B)``.  ``active`` (bool[B],
        optional) marks rows that carry a real request; inactive rows are
        born settled (``t_launch == t_done == T``) so :meth:`step` freezes
        them — they hold placeholder state until :meth:`admit` splices a
        request in.  Callers with idle paged rows should :meth:`evict` them
        after init so their placeholder prefill pages return to the pool.
        """
        B, W, T = self.B, self.W, self.T
        rngs = _canonical_keys(rngs)
        tree0 = init_batched_tree(
            root_states, self.capacity, self.env.num_actions
        )
        if active is None:
            start = jnp.zeros((B,), jnp.int32)
        else:
            start = jnp.where(jnp.asarray(active), 0, T).astype(jnp.int32)
        return (
            tree0, self._slot_rows0(root_states, B), rngs,
            start, start,
            jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.float32),
            self.evaluator.init_aux(root_states, (B, W)),
            jnp.zeros((B,), jnp.int32),
        )

    def admit(self, carry, rows, root_states, rngs):
        """Splice fresh requests into settled rows, mid-stream.

        ``rows`` is ``i32[R]`` (distinct, settled or idle); ``root_states``
        leaves lead with ``[R]``; ``rngs`` is ``jax.random.split(key, R)``.
        Resets the rows' trees, slot pools, RNG lanes and counters, and
        re-seeds their evaluator slot caches via ``Evaluator.admit_aux``
        (dense: one ragged re-prefill + slot-axis cache splice; paged:
        release + page-table splice + refcount fan-out to the ``W``
        siblings).  Rows not in ``rows`` are untouched — their searches
        continue across the splice.
        """
        tree, slots, rng, t_launch, t_done, ticks, max_o, aux, fr_hits = carry
        rows = jnp.asarray(rows, jnp.int32)
        r = rows.shape[0]
        tree_new = init_batched_tree(
            root_states, self.capacity, self.env.num_actions
        )
        tree = jax.tree.map(
            lambda f, n: f.at[rows].set(n), tree, tree_new
        )
        slots = jax.tree.map(
            lambda f, n: f.at[rows].set(n),
            slots, self._slot_rows0(root_states, r),
        )
        zero = jnp.zeros((r,), jnp.int32)
        return (
            tree, slots, rng.at[rows].set(_canonical_keys(rngs)),
            t_launch.at[rows].set(zero), t_done.at[rows].set(zero),
            ticks.at[rows].set(zero),
            max_o.at[rows].set(jnp.zeros((r,), jnp.float32)),
            self.evaluator.admit_aux(self.cfg, aux, rows, root_states, self.W),
            fr_hits.at[rows].set(zero),
        )

    def evict(self, carry, rows):
        """Release settled rows' evaluator-side resources without admitting.

        Paged evaluators return the rows' pages to the shared pool (their
        slots are frozen FREE, so nothing dereferences the dropped tables);
        dense evaluators are a no-op — an idle dense row costs nothing
        beyond its preallocated HBM.  Tree/slot/RNG state is left in place:
        :meth:`result` stays readable until the row is re-admitted.
        """
        rows = jnp.asarray(rows, jnp.int32)
        aux = self.evaluator.evict_aux(carry[7], rows, self.W)
        return carry[:7] + (aux,) + carry[8:]

    def run_segment(self, carry, num_ticks: int):
        """Up to ``num_ticks`` master ticks; stops early when all settled.

        Returns ``(carry, ticks_run, busy_tree_ticks, attended)`` — the
        occupancy numerator/denominator the serving layer turns into its
        slot-idle fraction (a settled row's ``W`` slots idle for the rest of
        the segment; ``busy_tree_ticks`` counts row-ticks that searched),
        and the key/value positions the segment's decode steps attended.
        """
        def cond(c):
            carry, t, _, _ = c
            return (t < num_ticks) & jnp.any(self.alive(carry))

        def body(c):
            carry, t, busy, att = c
            busy = busy + jnp.sum(self.alive(carry).astype(jnp.int32))
            carry, seen = self.step(carry)
            return carry, t + 1, busy, att + seen

        zero = jnp.int32(0)
        return jax.lax.while_loop(cond, body, (carry, zero, zero, zero))

    def result(self, carry) -> SearchResult:
        """``SearchResult[B]`` snapshot (meaningful on settled rows)."""
        tree = carry[0]
        root_n, root_v = btree.root_action_stats(tree)
        return SearchResult(
            action=btree.best_root_action(tree),
            root_n=root_n,
            root_v=root_v,
            tree_size=tree.size,
            dup_selections=jnp.zeros((self.B,), jnp.float32),
            max_o=carry[6],
            overflowed=tree.overflowed,
            ticks=carry[5],
        )

    # ------------------------------------------------------------------
    # Device-resident serving ring (the fused poll round)
    # ------------------------------------------------------------------
    def init_ring(self, proto_root_states, capacity: int) -> RequestRing:
        """Empty :class:`RequestRing` with room for ``capacity`` requests.

        ``proto_root_states`` (leaves leading with any batch axis) supplies
        only shapes/dtypes for the per-request root-state buffers.
        """
        cap = int(capacity)
        if cap < 1:
            raise ValueError(f"ring capacity must be >= 1, got {capacity}")
        states = jax.tree.map(
            lambda x: jnp.zeros(
                (cap,) + jnp.shape(jnp.asarray(x))[1:], jnp.asarray(x).dtype
            ),
            proto_root_states,
        )
        kd = jax.random.key_data(jax.random.PRNGKey(0))
        return RequestRing(
            req_id=jnp.full((cap,), -1, jnp.int32),
            states=states,
            rng=jnp.zeros((cap,) + kd.shape, kd.dtype),
            head=jnp.int32(0),
            count=jnp.int32(0),
            aux=self.evaluator.init_ring_aux(self.cfg, proto_root_states, cap),
        )

    def stage(self, carry, ring: RequestRing, root_states, rngs, req_ids):
        """Stage ``R`` requests at the ring tail (host-side, between
        segments; the serving layer calls it with ``R == 1`` so the jitted
        graph keeps one fixed shape).

        The evaluator's ``stage_ring_aux`` pre-prefills the requests into
        the ring's staging buffers — paged evaluators allocate their pool
        pages *now*, from the live carry refcounts (held at refcount 1 by
        the ring until admission), which is why the carry is threaded
        through.  The caller must guarantee ``count + R <= capacity``.
        """
        cap = ring.req_id.shape[0]
        req_ids = jnp.asarray(req_ids, jnp.int32)
        r = req_ids.shape[0]
        slots = (ring.head + ring.count + jnp.arange(r, dtype=jnp.int32)) % cap
        states = jax.tree.map(
            lambda buf, x: buf.at[slots].set(x), ring.states, root_states
        )
        aux, ring_aux = self.evaluator.stage_ring_aux(
            self.cfg, carry[7], ring.aux, slots, root_states
        )
        ring = ring._replace(
            req_id=ring.req_id.at[slots].set(req_ids),
            states=states,
            rng=ring.rng.at[slots].set(_canonical_keys(rngs)),
            count=ring.count + r,
            aux=ring_aux,
        )
        return carry[:7] + (aux,) + carry[8:], ring

    def _admit_from_ring(self, carry, ring: RequestRing, row_req, slot, mask):
        """Re-seed rows where ``mask`` holds from ring slots ``slot`` — the
        traceable counterpart of :meth:`admit` (masked select instead of
        scatter, evaluator splice via ``admit_aux_from_ring``)."""
        tree, slots_, rng, t_launch, t_done, ticks, max_o, aux, fr_hits = carry
        roots = jax.tree.map(lambda x: x[slot], ring.states)
        tree = _freeze_done(
            mask,
            init_batched_tree(roots, self.capacity, self.env.num_actions),
            tree,
        )
        slots_ = _freeze_done(mask, self._slot_rows0(roots, self.B), slots_)
        zero = jnp.zeros((self.B,), jnp.int32)
        aux, ring_aux = self.evaluator.admit_aux_from_ring(
            self.cfg, aux, ring.aux, slot, mask, self.W
        )
        carry = (
            tree, slots_,
            jnp.where(mask[:, None], ring.rng[slot], rng),
            jnp.where(mask, zero, t_launch),
            jnp.where(mask, zero, t_done),
            jnp.where(mask, zero, ticks),
            jnp.where(mask, 0.0, max_o),
            aux,
            jnp.where(mask, zero, fr_hits),
        )
        row_req = jnp.where(mask, ring.req_id[slot], row_req)
        return carry, ring._replace(aux=ring_aux), row_req

    def _serve_round(self, carry, ring: RequestRing, row_req, comp, t):
        """One in-loop harvest + admit round (traceable), after ``t`` ticks
        of the segment.

        Settled rows holding a request (``row_req >= 0``) append their
        :meth:`result` snapshot to the completion buffer and release their
        evaluator resources (``evict_aux_to_ring``); then as many settled
        rows as the ring holds requests are re-seeded from the ring head in
        row order, and the ring pointers advance.
        """
        cap = ring.req_id.shape[0]
        ccap = comp.req_id.shape[0]
        settled = self.settled(carry)

        done = settled & (row_req >= 0)
        rank = jnp.cumsum(done.astype(jnp.int32)) - 1
        dst = jnp.where(done, comp.count + rank, ccap)
        res = self.result(carry)
        comp = Completions(
            req_id=comp.req_id.at[dst].set(row_req, mode="drop"),
            action=comp.action.at[dst].set(res.action, mode="drop"),
            root_n=comp.root_n.at[dst].set(res.root_n, mode="drop"),
            root_v=comp.root_v.at[dst].set(res.root_v, mode="drop"),
            tree_size=comp.tree_size.at[dst].set(res.tree_size, mode="drop"),
            max_o=comp.max_o.at[dst].set(res.max_o, mode="drop"),
            overflowed=comp.overflowed.at[dst].set(
                res.overflowed, mode="drop"
            ),
            ticks=comp.ticks.at[dst].set(res.ticks, mode="drop"),
            settle_tick=comp.settle_tick.at[dst].set(
                jnp.full((self.B,), t, jnp.int32), mode="drop"
            ),
            count=comp.count + jnp.sum(done.astype(jnp.int32)),
        )
        aux = self.evaluator.evict_aux_to_ring(carry[7], done, self.W)
        carry = carry[:7] + (aux,) + carry[8:]
        row_req = jnp.where(done, -1, row_req)

        take = jnp.cumsum(settled.astype(jnp.int32)) - 1
        do_admit = settled & (take < ring.count)
        slot = (ring.head + jnp.clip(take, 0, cap - 1)) % cap
        carry, ring, row_req = self._admit_from_ring(
            carry, ring, row_req, slot, do_admit
        )
        n_adm = jnp.sum(do_admit.astype(jnp.int32))
        ring = ring._replace(
            head=(ring.head + n_adm) % cap, count=ring.count - n_adm
        )
        return carry, ring, row_req, comp

    def serve_segment(self, carry, ring: RequestRing, row_req, num_ticks: int):
        """Up to ``num_ticks`` master ticks with harvest + ring admission
        *inside* the loop — the fused poll round.

        ``row_req`` is ``i32[B]``, the request id each row is serving
        (``-1`` = idle).  Each iteration first runs a harvest/admit round
        (gated behind a ``cond`` so tick cost is untouched while nothing is
        settled), then one frozen-masked master tick.  A final round after
        the loop harvests rows that settled on the last tick.  Exits early
        when every row is idle and the ring is empty.  Returns
        ``(carry, ring, row_req, completions, ticks_run, busy_tree_ticks,
        attended)`` (see :meth:`run_segment`).
        """
        ccap = self.B + ring.req_id.shape[0]
        proto = self.result(carry)

        def buf(x):
            return jnp.zeros((ccap,) + x.shape[1:], x.dtype)

        comp = Completions(
            req_id=jnp.full((ccap,), -1, jnp.int32),
            action=buf(proto.action), root_n=buf(proto.root_n),
            root_v=buf(proto.root_v), tree_size=buf(proto.tree_size),
            max_o=buf(proto.max_o), overflowed=buf(proto.overflowed),
            ticks=buf(proto.ticks), settle_tick=buf(proto.ticks),
            count=jnp.int32(0),
        )

        def maybe_round(carry, ring, row_req, comp, t):
            settled = self.settled(carry)
            want = jnp.any(settled & (row_req >= 0)) | (
                (ring.count > 0) & jnp.any(settled)
            )
            with jax.named_scope(SERVE_ROUND):
                return jax.lax.cond(
                    want,
                    self._serve_round,
                    lambda c, g, q, m, _: (c, g, q, m),
                    carry, ring, row_req, comp, t,
                )

        def cond(c):
            carry, ring, row_req, _, t, _, _ = c
            more = jnp.any(self.alive(carry)) | (ring.count > 0)
            return (t < num_ticks) & more

        def body(c):
            carry, ring, row_req, comp, t, busy, att = c
            carry, ring, row_req, comp = maybe_round(
                carry, ring, row_req, comp, t
            )
            busy = busy + jnp.sum(self.alive(carry).astype(jnp.int32))
            carry, seen = self.step(carry)
            return carry, ring, row_req, comp, t + 1, busy, att + seen

        zero = jnp.int32(0)
        carry, ring, row_req, comp, t, busy, att = jax.lax.while_loop(
            cond, body, (carry, ring, row_req, comp, zero, zero, zero),
        )
        # Harvest rows that settled on the loop's last tick without paying
        # a masked tick for them (admission here also primes the next
        # segment's first tick).
        carry, ring, row_req, comp = maybe_round(carry, ring, row_req, comp, t)
        return carry, ring, row_req, comp, t, busy, att

    # ------------------------------------------------------------------
    # One-shot runs (the pre-existing API)
    # ------------------------------------------------------------------
    def run(self, root_states, rngs, trace_ticks: int = 0):
        """Admit ``B`` roots, run every tree to budget, return results."""
        init = self.init_carry(root_states, rngs)
        if trace_ticks > 0:
            def scan_body(carry, _):
                alive = self.alive(carry)
                new, _ = self.step(carry)
                ev_len = self.evaluator.aux_len(new[7])
                if ev_len is not None:
                    ev_len = ev_len.reshape(self.B, self.W)
                return new, tick_snapshot(
                    new, alive, ev_len, self.evaluator.aux_blocks(new[7]),
                    frontier_hits=new[8],
                )

            final, trace = jax.lax.scan(
                scan_body, init, None, length=trace_ticks
            )
            return self.result(final), trace
        final = jax.lax.while_loop(
            lambda c: jnp.any(self.alive(c)), lambda c: self.step(c)[0], init
        )
        return self.result(final)


def run_async_search_batched(
    env: Environment,
    cfg: SearchConfig,
    root_states: Pytree,
    rngs: jax.Array,
    constrain: Optional[Callable[[Pytree], Pytree]] = None,
    use_kernel: bool = True,
    trace_ticks: int = 0,
    evaluator: Optional[Evaluator] = None,
) -> SearchResult:
    """Run ``B`` independent async-slot searches; every field of the returned
    :class:`SearchResult` carries a leading ``[B]`` axis.

    ``root_states`` is a pytree whose leaves lead with ``[B]``; ``rngs`` is
    ``jax.random.split(key, B)``.  With ``trace_ticks > 0`` returns
    ``(SearchResult, AsyncTickTrace)`` with a ``[K, B, ...]`` trace (see
    :func:`repro.core.async_search.run_async_search`).  ``evaluator`` owns
    the flat ``[B·W]`` slot stepping — with
    :class:`repro.core.evaluators.ModelEvaluator`, every master tick is one
    batched model forward over all in-flight slots.
    """
    rngs = _canonical_keys(rngs)
    engine = BatchedAsyncEngine(
        env, cfg, rngs.shape[0],
        evaluator=evaluator, constrain=constrain, use_kernel=use_kernel,
    )
    return engine.run(root_states, rngs, trace_ticks)


def make_batched_async_searcher(
    env: Environment,
    cfg: SearchConfig,
    constrain: Optional[Callable[[Pytree], Pytree]] = None,
    jit: bool = True,
    use_kernel: bool = True,
    evaluator: Optional[Evaluator] = None,
):
    """Build ``search(root_states[B], rngs[B]) -> SearchResult[B]``."""
    fn = functools.partial(
        run_async_search_batched, env, cfg,
        constrain=constrain, use_kernel=use_kernel, evaluator=evaluator,
    )
    return jax.jit(fn) if jit else fn
