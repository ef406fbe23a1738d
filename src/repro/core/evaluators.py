"""Evaluators: the pluggable leaf-evaluation side of parallel MCTS.

"On Effective Parallelization of Monte Carlo Tree Search" frames parallel
MCTS as two separable concerns — tree statistics (the master's bookkeeping,
which WU-UCT keeps principled via ``O_s``) and leaf evaluation (the expensive
expansion/simulation work farmed out to workers).  This module owns the
second concern: every engine in :mod:`repro.core` drives its in-flight slots
through an :class:`Evaluator` instead of hard-wiring ``env.policy`` /
``env.step`` into its loop body.

Two implementations ship:

* :class:`RolloutEvaluator` — the classic random/scripted-policy rollout
  (``env.policy`` chooses simulation actions; ``env.step`` advances).  This
  is a *bit-identical* port of the per-slot stepping that previously lived
  as ``wu_uct.rollout_return`` and ``async_search.slot_tick_step``.
* :class:`ModelEvaluator` — policy/value-LM evaluation over the token
  environment (:mod:`repro.envs.token_env`): all in-flight slots of a master
  tick are scored by **one** batched model forward (``models.forward``)
  instead of three per-slot forwards hidden inside ``env.policy`` +
  ``env.step``.  Plugged into the async engines' flat ``[B·W]`` tick batch,
  this realizes the ROADMAP follow-up: every master tick feeds one model
  forward pass.
* :class:`CachedModelEvaluator` — the same contract with a per-slot KV
  decode cache carried in the engines' slot-aux state, so the one forward
  per master tick is a single batched ``models.decode_step`` (O(1) in
  prefix length) instead of a full-prefix ``models.forward`` (O(depth)).
  Slot refills roll the cache back to the common prefix with the newly
  assigned tree path and re-decode only the divergent suffix.

The evaluator contract (``init_state`` / ``tick`` / ``rollout`` / ``value``
plus the slot-aux hooks ``init_aux`` / ``refill_aux``) is identical across
implementations, so engines stay evaluator-agnostic and
:func:`repro.core.api.build_searcher` can swap them freely.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp

from ..envs.base import Environment

Pytree = Any

# Slot phases, shared with the async engines (async_search re-exports them).
FREE, EXPAND, SIM = 0, 1, 2

# ``jax.named_scope``s of the two refill stages of a master tick (the engine
# names the others, see ``batched_async_search.TICK_SCOPES``).
REFILL_CACHE = "refill_cache"  # slot-cache row gather and scatter
CATCH_UP = "catch_up"          # re-decode of each refilled slot's suffix


def slot_accounting(gamma, kind, nxt, state, r, done, rollout_done, acc, disc,
                    steps):
    """Per-slot discounted-return bookkeeping after one environment step.

    The one accounting rule every evaluator must apply identically for the
    engines' vmap bit-equivalence to hold: only live SIM slots accumulate,
    FREE slots freeze their state, EXPAND slots report the edge transition.
    Shape-polymorphic (scalar per-slot or leading batch axes) so the same
    code serves ``RolloutEvaluator._one_step`` and the batched
    ``ModelEvaluator.tick``.
    """
    is_sim = kind == SIM
    live = is_sim & jnp.logical_not(rollout_done)
    acc = acc + jnp.where(live, disc * r, 0.0)
    disc = jnp.where(live, disc * gamma, disc)
    steps = steps + jnp.where(kind != FREE, 1, 0)
    busy = kind != FREE
    new_state = jax.tree.map(
        lambda a_, b_: jnp.where(
            busy.reshape(busy.shape + (1,) * (a_.ndim - busy.ndim)), a_, b_
        ),
        nxt,
        state,
    )
    rollout_done = jnp.where(
        kind == EXPAND, done, rollout_done | (is_sim & done)
    )
    return new_state, r, done, acc, disc, steps, rollout_done


def _flat_slot_rows(rows, w: int) -> jax.Array:
    """Flat aux rows ``[R·w]`` covering tree rows' ``w`` sibling slots.

    Slot ``j`` of tree ``b`` lives at flat aux row ``b·w + j`` — the layout
    both async engines address ``refill_aux`` with; admission/eviction hooks
    expand their per-tree ``rows`` through this.
    """
    rows = jnp.asarray(rows, jnp.int32)
    return (
        rows[:, None] * w + jnp.arange(w, dtype=jnp.int32)[None, :]
    ).reshape(-1)


@dataclasses.dataclass(frozen=True)
class SlotColumn:
    """Slot ``index`` of every tree: flat aux rows ``arange(N // width) *
    width + index``, in that order.

    The batched engine refills one column of its ``[B, W]`` slot grid at a
    time.  The flat slot axis is tree-major, so the column is a strided
    slice of a free ``[N // W, W]`` reshape: :func:`take_slots` reads it
    with one dynamic slice and :func:`put_slots` writes it back in place.
    The equivalent index array lowers on the TPU to a gather that first
    slices the whole operand along its other axes (the whole KV cache, for
    every refilled column).  ``index`` may be traced; ``width`` is static.
    """

    index: Any
    width: int


SlotRows = Union[jax.Array, SlotColumn]


def _slot_grid(x, width: int, axis: int) -> jax.Array:
    """``x`` with slot axis ``axis`` split into ``[N // width, width]``."""
    n = x.shape[axis]
    return x.reshape(x.shape[:axis] + (n // width, width) + x.shape[axis + 1:])


def take_slots(x, rows: SlotRows, axis: int = 0) -> jax.Array:
    """Rows ``rows`` of ``x``'s slot axis ``axis`` (an ``i32[R]`` index
    array, or a :class:`SlotColumn`)."""
    if isinstance(rows, SlotColumn):
        return jax.lax.dynamic_index_in_dim(
            _slot_grid(x, rows.width, axis), rows.index, axis + 1,
            keepdims=False,
        )
    return x[(slice(None),) * axis + (rows,)]


def put_slots(x, rows: SlotRows, y, axis: int = 0) -> jax.Array:
    """``x`` with the rows :func:`take_slots` reads replaced by ``y``."""
    if isinstance(rows, SlotColumn):
        grid = _slot_grid(x, rows.width, axis)
        y = jnp.expand_dims(jnp.asarray(y, x.dtype), axis + 1)
        if x.ndim - axis > 2:
            grid = jax.lax.dynamic_update_slice_in_dim(
                grid, y, rows.index, axis + 1
            )
        else:
            # The slot axis is one of the two minor axes the TPU tiles in
            # memory; writing one row of every tile at a dynamic offset is
            # slower there than rewriting the whole array under a mask.
            hit = jnp.arange(rows.width) == rows.index
            grid = jnp.where(
                hit.reshape((rows.width,) + (1,) * (x.ndim - axis - 1)),
                y, grid,
            )
        return grid.reshape(x.shape)
    return x.at[(slice(None),) * axis + (rows,)].set(y)


class Evaluator:
    """Protocol for environment/model evaluation inside a search engine.

    Engines call four methods; ``cfg`` is the engine's ``SearchConfig``
    (only ``gamma`` / ``max_sim_steps`` / ``value_mix`` are read):

    * ``init_state(example_state, prefix)`` — allocate zeroed per-slot env
      state buffers with leading ``prefix`` axes (the async slot pools);
    * ``tick(cfg, kind, act, state, rollout_done, acc, disc, steps, keys,
      aux)`` — advance a whole batch of in-flight slots by one environment
      step.  Leading axis is *all* in-flight slots of a master tick: ``[W]``
      for the single async engine, the flat ``[B·W]`` for the batched one.
      Returns ``((new_state, r, done, acc, disc, steps, rollout_done),
      aux)``;
    * ``rollout(cfg, state, already_done, rng)`` — full discounted
      simulation return from one state (the wave engines vmap this per
      slot);
    * ``value(state)`` — bootstrap value ``V(s)`` for truncated rollouts.

    **Slot aux** is evaluator-owned per-slot state the async engines carry
    *alongside* the env-state slot pools but never write into the tree (the
    KV decode cache of :class:`CachedModelEvaluator` — node states must stay
    compact).  Engines thread it unconditionally; the default hooks make it
    an empty pytree so stateless evaluators cost nothing:

    * ``init_aux(root_states, prefix)`` — build the flat ``[N]`` aux pool
      (``N = prod(prefix)``; ``root_states`` leaves lead with
      ``prefix[:-1]`` and broadcast over the trailing slot axis);
    * ``refill_aux(cfg, aux, rows, new_state, mask)`` — re-sync aux rows
      ``rows`` with the freshly assigned ``new_state`` (leaves lead with
      ``[R]``) where ``mask`` (``bool[R]``) holds.  ``rows`` is either flat
      ``i32[R]`` indices or a :class:`SlotColumn` (the batched engine's
      slot ``j`` of every tree, ``R = N // W``); implementations read and
      write rows only through :func:`take_slots` / :func:`put_slots` and
      take ``R`` from ``mask``.  Returns ``(aux, hits)`` where ``hits``
      (``bool[R]``) flags rows served entirely from a speculative frontier
      cache — no model forward dispatched (always ``False`` for evaluators
      without a frontier cache; the engines surface the count in trace
      mode as ``frontier_hits``);
    * ``aux_len(aux)`` — the per-slot cache depth vector for trace-mode
      invariant checking (``None`` when the evaluator carries no cache).
    """

    env: Optional[Environment] = None

    def weights(self) -> Pytree:
        """Arrays the evaluator closes over (model weights), or ``None``.

        A jitted caller passes them in as an argument and traces under
        :meth:`bound`; closed over, they would be baked into the compiled
        program as constants (a copy of every weight per compile).
        """
        return None

    @contextlib.contextmanager
    def bound(self, weights: Pytree):
        """Trace with ``weights`` (the jitted caller's argument) standing in
        for :meth:`weights`."""
        del weights
        yield

    def init_aux(self, root_states: Pytree, prefix: tuple) -> Pytree:
        del root_states, prefix
        return ()

    def refill_aux(self, cfg, aux, rows, new_state, mask):
        del cfg, rows, new_state
        return aux, jnp.zeros(jnp.shape(mask), jnp.bool_)

    def admit_aux(self, cfg, aux, rows, root_states, w):
        """Re-seed the slot caches of freshly admitted *tree* rows.

        The engine-side half of continuous batching: when the serving layer
        splices a new request into settled tree row ``b``, flat aux rows
        ``b·w .. b·w + w - 1`` must be rebuilt from the request's root state
        (``rows`` is ``i32[R]`` tree rows; ``root_states`` leaves lead with
        ``[R]``; ``w`` is the engine's slot count per tree).  Cached
        evaluators re-prefill the roots and splice the rows in via the
        shared :mod:`repro.serving.admission` path; stateless evaluators
        need nothing.  Called at an eager boundary (between jitted
        segments), so paged implementations may surface pool exhaustion.
        """
        del cfg, rows, root_states, w
        return aux

    def evict_aux(self, aux, rows, w):
        """Release aux resources held by settled tree rows ``rows``.

        Paged caches return the rows' pages to the shared pool; evaluators
        without pooled resources need nothing (a dense row's HBM is
        preallocated either way).
        """
        del rows, w
        return aux

    # -- device-resident serving ring (traceable admit/evict variants) --
    def init_ring_aux(self, cfg, proto_root_states, capacity: int) -> Pytree:
        """Empty per-request staging buffers for a ``capacity``-slot ring.

        The fused serving loop (``BatchedAsyncEngine.serve_segment``) admits
        requests *inside* the jitted ``while_loop``; anything the eager
        ``admit_aux`` would compute per admission (prefilled KV, root
        logits, a page table) must instead be staged here ahead of time by
        :meth:`stage_ring_aux`.  Evaluators without per-request resources
        stage nothing.
        """
        del cfg, proto_root_states, capacity
        return ()

    def stage_ring_aux(self, cfg, aux, ring_aux, slots, root_states):
        """Pre-compute ring slots ``slots``'s admission resources.

        Runs at an eager boundary (host staging between segments) but must
        be traceable with fixed shapes — the serving layer jits it once per
        request shape.  Returns ``(aux, ring_aux)``: paged evaluators
        allocate pool pages from the live ``aux`` refcounts (the ring holds
        them at refcount 1 until admission), so the slot aux is threaded
        through.
        """
        del cfg, slots, root_states
        return aux, ring_aux

    def admit_aux_from_ring(self, cfg, aux, ring_aux, slot, mask, w):
        """Traceable twin of :meth:`admit_aux`: splice staged ring slots
        ``slot`` (``i32[B]``) into the rows where ``mask`` (``bool[B]``)
        holds — a masked select over pre-staged buffers instead of a fresh
        prefill, so it runs *inside* the fused serving ``while_loop``.
        Returns ``(aux, ring_aux)`` — consumed ring slots are cleared so a
        later re-staging never double-frees their resources.
        """
        del cfg, slot, mask, w
        return aux, ring_aux

    def evict_aux_to_ring(self, aux, mask, w):
        """Traceable twin of :meth:`evict_aux` over a row *mask* instead of
        row indices: release evaluator resources of every tree row where
        ``mask`` (``bool[B]``) holds.  Must never raise under trace — paged
        implementations latch ``oom`` instead.
        """
        del mask, w
        return aux

    def aux_len(self, aux) -> Optional[jax.Array]:
        del aux
        return None

    def attended_positions(self, aux) -> jax.Array:
        """Key/value positions the next decode step over ``aux`` attends,
        summed over every slot (``i32[]``; 0 without a model cache)."""
        del aux
        return jnp.int32(0)

    def aux_last_logits(self, aux) -> Optional[jax.Array]:
        """Most recent per-slot policy logits ``[N, V]``, when the evaluator
        surfaces them on slot-aux (policy-prior groundwork; the frontier
        cache reads the same slab).  ``None`` for logit-free evaluators."""
        del aux
        return None

    def aux_blocks(self, aux) -> Optional[jax.Array]:
        """Pool blocks currently allocated (paged caches only) — trace-mode
        snapshots it so benchmarks can read the peak working set."""
        del aux
        return None

    def init_state(self, example_state: Pytree, prefix: tuple) -> Pytree:
        """Zeroed per-slot state buffers shaped ``prefix + leaf.shape``."""
        return jax.tree.map(
            lambda x: jnp.zeros(
                tuple(prefix) + jnp.shape(x), jnp.asarray(x).dtype
            ),
            example_state,
        )

    def tick(self, cfg, kind, act, state, rollout_done, acc, disc, steps, keys,
             aux=()):
        raise NotImplementedError

    def value(self, state: Pytree) -> jax.Array:
        return jnp.float32(0.0)

    def has_value(self) -> bool:
        """Whether :meth:`value` is a real estimator; gates the rollout's
        truncation bootstrap and ``value_mix`` blending (a zero-constant
        value must not rescale returns)."""
        return False

    def rollout(self, cfg, state, already_done, rng) -> jax.Array:
        """Default full rollout: tick a single SIM slot until done/step cap.

        Implementations with a cheaper native rollout (the classic env
        rollout) override this; model-backed evaluators get it for free —
        under the wave engines' slot ``vmap`` the per-step forward becomes a
        batched forward over all slots.
        """

        def cond(c):
            _, done, _, _, _, steps = c
            return jnp.logical_not(done[0]) & (steps[0] < cfg.max_sim_steps)

        def body(c):
            st, done, acc, disc, rng, steps = c
            rng, k = jax.random.split(rng)
            (st, _, _, acc, disc, steps, done), _ = self.tick(
                cfg,
                jnp.full((1,), SIM, jnp.int32),
                jnp.zeros((1,), jnp.int32),
                st, done, acc, disc, steps, k[None],
            )
            return st, done, acc, disc, rng, steps

        init = (
            jax.tree.map(lambda x: x[None], state),
            jnp.asarray(already_done, jnp.bool_)[None],
            jnp.zeros((1,), jnp.float32),
            jnp.ones((1,), jnp.float32),
            rng,
            jnp.zeros((1,), jnp.int32),
        )
        st, done, acc, disc, _, _ = jax.lax.while_loop(cond, body, init)
        ret = acc[0]
        if self.has_value():
            final = jax.tree.map(lambda x: x[0], st)
            ret = ret + disc[0] * jnp.where(done[0], 0.0, self.value(final))
            if cfg.value_mix > 0.0:
                v0 = jnp.where(already_done, 0.0, self.value(state))
                ret = (1.0 - cfg.value_mix) * ret + cfg.value_mix * v0
        return ret


# ---------------------------------------------------------------------------
# RolloutEvaluator — today's env.policy behavior, bit-identical.
# ---------------------------------------------------------------------------


class RolloutEvaluator(Evaluator):
    """Classic rollout evaluation: ``env.policy`` acts, ``env.step`` advances.

    The per-slot stepping and discounted-return accounting are verbatim the
    code that previously lived inside the engines, so every engine's default
    behavior (and RNG stream) is unchanged.
    """

    def __init__(self, env: Environment):
        self.env = env

    def _one_step(self, gamma: float) -> Callable:
        """Per-slot one-env-step transition (the parallel part of a master
        tick) — shared by the single engine (vmapped over ``[W]``) and the
        batched engine (vmapped over the flat ``[B·W]`` axis)."""
        env = self.env

        def one(kind, act, state, rollout_done, acc, disc, steps, key):
            pol_act = env.policy(key, state)
            a = jnp.where(kind == EXPAND, act, pol_act)
            nxt, r, done = env.step(state, a)
            return slot_accounting(
                gamma, kind, nxt, state, r, done, rollout_done, acc, disc,
                steps,
            )

        return one

    def tick(self, cfg, kind, act, state, rollout_done, acc, disc, steps, keys,
             aux=()):
        out = jax.vmap(self._one_step(cfg.gamma))(
            kind, act, state, rollout_done, acc, disc, steps, keys
        )
        return out, aux

    def rollout(self, cfg, state, already_done, rng) -> jax.Array:
        """Discounted simulation return with optional value bootstrap/mixing
        (paper Fig. 1(a) "simulation"; App. D truncation bootstrap)."""
        env = self.env

        def cond(carry):
            _, done, _, _, _, steps = carry
            return jnp.logical_not(done) & (steps < cfg.max_sim_steps)

        def body(carry):
            state, done, acc, disc, rng, steps = carry
            rng, k = jax.random.split(rng)
            a = env.policy(k, state)
            nxt, r, d = env.step(state, a)
            acc = acc + disc * r
            disc = disc * cfg.gamma
            return nxt, done | d, acc, disc, rng, steps + 1

        init = (
            state,
            jnp.asarray(already_done, jnp.bool_),
            jnp.float32(0.0),
            jnp.float32(1.0),
            rng,
            jnp.int32(0),
        )
        final_state, done, acc, disc, _, _ = jax.lax.while_loop(
            cond, body, init
        )

        if env.value_fn is not None:
            # Truncation bootstrap: R_simu = Σ γ^i r_i + γ^T V(s_T) (App. D).
            acc = acc + disc * jnp.where(done, 0.0, env.value_fn(final_state))
            if cfg.value_mix > 0.0:
                v0 = jnp.where(already_done, 0.0, env.value_fn(state))
                acc = (1.0 - cfg.value_mix) * acc + cfg.value_mix * v0
        return acc

    def value(self, state: Pytree) -> jax.Array:
        if self.env.value_fn is None:
            return jnp.float32(0.0)
        return self.env.value_fn(state)

    def has_value(self) -> bool:
        return self.env.value_fn is not None


# ---------------------------------------------------------------------------
# ModelEvaluator — one batched policy/value LM forward per master tick.
# ---------------------------------------------------------------------------


class ModelEvaluator(Evaluator):
    """LM-backed evaluation over :mod:`repro.envs.token_env` state batches.

    The token environment's per-slot ``step`` runs one forward for the
    rollout policy plus two inside the transition (policy top-K + reward
    log-prob).  This evaluator instead runs **one** forward over the whole
    in-flight slot batch per tick and derives all three quantities from the
    same logits: the top-K table (action decoding), the sampled simulation
    action, and the reward log-prob (when the reward model is the policy
    model; a distinct reward model adds exactly one more forward).

    Paired with ``engine='async'`` searchers, whose master tick advances all
    ``[W]`` (or flat ``[B·W]``) slots at once, this yields exactly one model
    forward per master tick — asserted by ``tests/test_facade.py`` with a
    traced call counter, and measured by ``benchmarks/bench_model_eval.py``.

    Transitions apply :func:`repro.envs.token_env.apply_token` — the same
    transition core the env's ``step`` uses — so a search with this
    evaluator explores the same MDP by construction.
    """

    def __init__(
        self,
        model_cfg,
        params,
        *,
        top_k: int,
        eos_token: int = 0,
        reward_cfg=None,
        reward_params=None,
        forward_fn: Optional[Callable] = None,
        value_fn: Optional[Callable] = None,
    ):
        if forward_fn is None:
            from ..models import forward as forward_fn  # circular-safe
        self.model_cfg = model_cfg
        self.params = params
        self.top_k = top_k
        self.eos_token = eos_token
        self.reward_cfg = reward_cfg if reward_cfg is not None else model_cfg
        self.reward_params = reward_params
        self.forward_fn = forward_fn
        self.value_fn = value_fn

    def weights(self) -> Pytree:
        return (self.params, self.reward_params)

    @contextlib.contextmanager
    def bound(self, weights: Pytree):
        saved = self.weights()
        self.params, self.reward_params = weights
        try:
            yield
        finally:
            self.params, self.reward_params = saved

    def _position_logits(self, params, cfg, tokens, lengths) -> jax.Array:
        """Logits at each slot's current position — ONE forward for [N]."""
        logits, _ = self.forward_fn(params, cfg, {"tokens": tokens})
        pos = jnp.maximum(lengths - 1, 0)
        return jnp.take_along_axis(logits, pos[:, None, None], axis=1)[:, 0]

    def _transition(self, cfg, kind, act, state, rollout_done, acc, disc,
                    steps, keys, pol_logits, rew_logits):
        """Logits → (action, token, reward) → env transition → accounting.

        The piece shared with :class:`CachedModelEvaluator`: everything
        after the logits are in hand is identical, so cached and uncached
        evaluation explore the same MDP by construction.
        """
        n = state.length.shape[0]
        idx = jnp.arange(n)
        top_vals, top_idx = jax.lax.top_k(pol_logits, self.top_k)
        ranks = jax.vmap(jax.random.categorical)(keys, top_vals)
        a = jnp.where(kind == EXPAND, act, ranks).astype(jnp.int32)
        token = top_idx[idx, jnp.clip(a, 0, self.top_k - 1)]
        logp = jax.nn.log_softmax(rew_logits.astype(jnp.float32))[idx, token]

        # The env's own transition core, applied to the whole slot batch.
        # Deferred import: token_env pulls in the models stack, which a
        # model-free `import repro.core` must not pay for.
        from ..envs.token_env import apply_token

        nxt, r, done = apply_token(state, token, logp, self.eos_token)
        out = slot_accounting(
            cfg.gamma, kind, nxt, state, r, done, rollout_done, acc, disc,
            steps,
        )
        return out, token

    def init_aux(self, root_states: Pytree, prefix: tuple) -> Pytree:
        """Per-slot ``last_logits`` slab — the logits each tick computes are
        kept on aux instead of discarded after value extraction."""
        del root_states
        n = 1
        for p in prefix:
            n *= int(p)
        return {
            "last_logits": jnp.zeros((n, self.model_cfg.vocab_size),
                                     jnp.float32)
        }

    def aux_last_logits(self, aux) -> Optional[jax.Array]:
        if isinstance(aux, dict) and "last_logits" in aux:
            return aux["last_logits"]
        return None

    def tick(self, cfg, kind, act, state, rollout_done, acc, disc, steps, keys,
             aux=()):
        # --- the one batched forward of this master tick -------------------
        pol = self._position_logits(
            self.params, self.model_cfg, state.tokens, state.length
        )
        if self.reward_params is None:
            rew = pol
        else:
            rew = self._position_logits(
                self.reward_params, self.reward_cfg, state.tokens, state.length
            )
        out, _ = self._transition(
            cfg, kind, act, state, rollout_done, acc, disc, steps, keys, pol,
            rew,
        )
        if isinstance(aux, dict) and "last_logits" in aux:
            aux = dict(
                aux,
                last_logits=pol.astype(aux["last_logits"].dtype),
            )
        return out, aux

    def value(self, state: Pytree) -> jax.Array:
        if self.value_fn is None:
            return jnp.float32(0.0)
        return self.value_fn(state)

    def has_value(self) -> bool:
        return self.value_fn is not None


# ---------------------------------------------------------------------------
# CachedModelEvaluator — one batched decode step per master tick.
# ---------------------------------------------------------------------------


class CachedModelEvaluator(ModelEvaluator):
    """:class:`ModelEvaluator` with a per-slot KV decode cache in slot aux.

    The uncached evaluator re-runs a **full-prefix** forward for every slot
    on every master tick — O(depth) work per tick.  This evaluator carries
    the ``models.init_cache`` layout (the same cache contract the serving
    engine uses) per slot inside the async engines' aux state, so a master
    tick costs **one batched ``decode_step``** over all ``[B·W]`` in-flight
    slots — O(1) in prefix length, routed through the Pallas
    ``decode_attention`` kernel via the per-slot ragged ``cache['len']``
    vector.

    Aux layout (flat slot axis ``N``; model-cache leaves carry ``N`` on axis
    1 under their layer-stacked axis, evaluator-side leaves on axis 0):

    * ``tokens  i32[N, S]`` — the tokens fed into the cache (valid ``< len``);
    * ``len     i32[N]``    — tokens processed per slot (== the slot's
      prefix depth; the engines' trace mode snapshots it via
      :meth:`aux_len` for invariant tests);
    * ``pol/rew`` — per model: the KV cache (sans ``len``) plus the stored
      logits ``[N, V]`` at each slot's current position (``rew`` is empty
      when the reward model *is* the policy model).

    **Prefix-aware refill** (:meth:`refill_aux`): when a slot settles and is
    handed a new tree path, the path *is* the token prefix — the cache rolls
    ``len`` back to the common prefix with the tokens it already processed
    and re-decodes only the divergent suffix (a data-dependent
    ``while_loop`` of decode steps; a disjoint prefix degenerates to the
    token-by-token re-prefill fallback).  The last prompt token is always
    re-decoded so the stored logits are the new position's logits.

    Garbage-row contract (shared with ``models.prefill_ragged`` and the
    serving engine): KV rows at positions ``>= len`` are invalid; attention
    masks them and every write lands at position ``len`` before ``len``
    moves past it, so they are overwritten before ever becoming visible.
    This rollback story needs position-indexed cache rows, hence KV-cache
    families only (a recurrent SSM state cannot be rolled back).

    Async engines only: the wave engines evaluate rollouts per slot without
    aux plumbing (``build_searcher`` enforces this).
    """

    def __init__(
        self,
        model_cfg,
        params,
        *,
        top_k: int,
        eos_token: int = 0,
        reward_cfg=None,
        reward_params=None,
        value_fn: Optional[Callable] = None,
        decode_fn: Optional[Callable] = None,
        prefill_fn: Optional[Callable] = None,
        chunk_fn: Optional[Callable] = None,
        refill_chunk: int = 8,
    ):
        super().__init__(
            model_cfg, params, top_k=top_k, eos_token=eos_token,
            reward_cfg=reward_cfg, reward_params=reward_params,
            value_fn=value_fn,
        )
        if decode_fn is None:
            from ..models import decode_step as decode_fn  # circular-safe
        if prefill_fn is None:
            from ..models import prefill_ragged as prefill_fn
        if chunk_fn is None:
            from ..models import decode_chunk as chunk_fn
        if refill_chunk < 1:
            raise ValueError(f"refill_chunk must be >= 1, got {refill_chunk}")
        self.decode_fn = decode_fn
        self.prefill_fn = prefill_fn
        self.chunk_fn = chunk_fn
        self.refill_chunk = refill_chunk
        from ..models import KV_CACHE_FAMILIES

        cfgs = [model_cfg] + ([self.reward_cfg] if reward_params is not None
                              else [])
        for c in cfgs:
            if c.family not in KV_CACHE_FAMILIES:
                raise ValueError(
                    "CachedModelEvaluator needs a rollback-able KV cache; "
                    f"family {c.family!r} carries recurrent state "
                    "(use ModelEvaluator)"
                )

    # -- aux structure helpers ---------------------------------------------

    def _branches(self):
        """(aux key, params, cfg) per model the cache tracks."""
        out = [("pol", self.params, self.model_cfg)]
        if self.reward_params is not None:
            out.append(("rew", self.reward_params, self.reward_cfg))
        return out

    @jax.named_scope(REFILL_CACHE)
    def _take_rows(self, aux, rows):
        def branch(b):
            if b == ():
                return ()
            return {
                "cache": jax.tree.map(
                    lambda x: take_slots(x, rows, 1), b["cache"]
                ),
                "logits": take_slots(b["logits"], rows),
            }

        return {
            "tokens": take_slots(aux["tokens"], rows),
            "len": take_slots(aux["len"], rows),
            "pol": branch(aux["pol"]),
            "rew": branch(aux["rew"]),
        }

    @jax.named_scope(REFILL_CACHE)
    def _put_rows(self, aux, rows, sub):
        def branch(b, sb):
            if b == ():
                return ()
            return {
                "cache": jax.tree.map(
                    lambda x, y: put_slots(x, rows, y, 1),
                    b["cache"], sb["cache"],
                ),
                "logits": put_slots(b["logits"], rows, sb["logits"]),
            }

        return {
            "tokens": put_slots(aux["tokens"], rows, sub["tokens"]),
            "len": put_slots(aux["len"], rows, sub["len"]),
            "pol": branch(aux["pol"], sub["pol"]),
            "rew": branch(aux["rew"], sub["rew"]),
        }

    def _advance(self, aux, token, fed):
        """Feed one token per slot through the cached models.

        Every slot decodes (ONE batched ``decode_step`` per model); only
        ``fed`` slots commit — their ``len`` advances and their stored
        logits refresh.  Non-fed slots' K/V writes land at their own
        position ``len`` (the garbage region) and are overwritten before
        ``len`` ever moves past them.
        """
        idx = jnp.arange(token.shape[0])
        s_max = aux["tokens"].shape[-1]
        length = aux["len"]
        safe = jnp.minimum(length, s_max - 1)
        prev = aux["tokens"][idx, safe]
        tokens = aux["tokens"].at[idx, safe].set(jnp.where(fed, token, prev))

        out = dict(
            tokens=tokens,
            len=jnp.where(fed, length + 1, length),
            pol=(), rew=(),
        )
        for key, params, cfg in self._branches():
            b = aux[key]
            logits, cache = self.decode_fn(
                params, cfg, token, dict(b["cache"], len=safe)
            )
            cache.pop("len")
            out[key] = {
                "cache": cache,
                "logits": jnp.where(
                    fed[:, None], logits, b["logits"]
                ).astype(b["logits"].dtype),
            }
        return out

    # -- evaluator protocol -------------------------------------------------

    def init_aux(self, root_states: Pytree, prefix: tuple) -> Pytree:
        """Prefill every slot's cache with its root prompt — once.

        ``root_states`` leaves lead with ``prefix[:-1]`` (per-tree roots in
        the batched engine); each root broadcasts over the trailing slot
        axis and the flat ``[N]`` pool prefills in ONE ragged batched
        forward (``models.prefill_ragged``).
        """
        from ..models import init_cache

        n = 1
        for p in prefix:
            n *= int(p)
        lead = len(prefix) - 1

        def flat(x):
            x = jnp.expand_dims(x, lead)
            x = jnp.broadcast_to(x, tuple(prefix) + x.shape[lead + 1:])
            return x.reshape((n,) + x.shape[len(prefix):])

        state = jax.tree.map(flat, root_states)
        tokens = jnp.asarray(state.tokens, jnp.int32)
        lengths = jnp.asarray(state.length, jnp.int32)
        s_max = tokens.shape[-1]

        aux = {
            "tokens": tokens, "len": lengths, "pol": (), "rew": (),
        }
        for key, params, cfg in self._branches():
            logits, cache = self.prefill_fn(
                params, cfg, tokens, lengths, init_cache(cfg, n, s_max)
            )
            cache.pop("len")
            aux[key] = {"cache": cache, "logits": logits}
        return aux

    def _rollback_targets(self, sub, new_state, mask):
        """Per-row (start, target, tokens, common) for a refill rollback.

        ``common`` is the (uncapped) shared prefix of the cached tokens and
        the new path's tokens; ``start`` caps it so the final prompt token
        is always re-decoded (the stored logits must be the NEW position's
        logits) — the frontier evaluators compare against the uncapped
        ``common`` to recognize rows whose forced re-decode exists only to
        regenerate logits the frontier cache already holds.  The re-prefill
        fallback is the common == 0 degenerate.  Unmasked rows collapse to
        start == target == their current length (no-op).
        """
        s_max = sub["tokens"].shape[-1]
        pos = jnp.arange(s_max)
        l_new = jnp.asarray(new_state.length, jnp.int32)
        old_len = sub["len"]
        limit = jnp.minimum(old_len, l_new)
        neq = (sub["tokens"] != new_state.tokens) & (pos[None, :] < limit[:, None])
        first = jnp.min(jnp.where(neq, pos[None, :], s_max), axis=1)
        common = jnp.minimum(first, limit)
        start = jnp.minimum(common, jnp.maximum(l_new - 1, 0))
        start = jnp.where(mask, start, old_len)
        target = jnp.where(mask, l_new, old_len)
        tokens = jnp.where(mask[:, None], new_state.tokens, sub["tokens"])
        return start, target, tokens, common

    def refill_aux(self, cfg, aux, rows, new_state, mask):
        del cfg
        sub = self._take_rows(aux, rows)
        r = mask.shape[0]
        s_max = sub["tokens"].shape[-1]
        start, target, tokens, _ = self._rollback_targets(sub, new_state, mask)
        sub = dict(sub, tokens=tokens, len=start)
        sub = self._catch_up(sub, target, r, s_max)
        return self._put_rows(aux, rows, sub), jnp.zeros((r,), jnp.bool_)

    def admit_aux(self, cfg, aux, rows, root_states, w):
        """Mid-stream admission: re-prefill + slot-axis cache splice.

        One ragged batched prefill over the ``R`` admitted roots
        (:mod:`repro.serving.admission`'s shared forward), fanned out to the
        rows' ``w`` sibling slots with a repeat along the cache's slot axis
        — the dense twin of the serving engine's ``add_requests`` splice.
        """
        del cfg
        from ..models import init_cache
        from ..serving.admission import splice_dense_slots

        flat = _flat_slot_rows(rows, w)
        tokens = jnp.asarray(root_states.tokens, jnp.int32)
        lengths = jnp.asarray(root_states.length, jnp.int32)
        r = tokens.shape[0]
        s_max = aux["tokens"].shape[-1]
        out = dict(
            aux,
            tokens=aux["tokens"].at[flat].set(jnp.repeat(tokens, w, axis=0)),
            len=aux["len"].at[flat].set(jnp.repeat(lengths, w, axis=0)),
        )
        for key, params, mcfg in self._branches():
            b = aux[key]
            logits, cache = self.prefill_fn(
                params, mcfg, tokens, lengths, init_cache(mcfg, r, s_max)
            )
            cache.pop("len")
            out[key] = {
                "cache": splice_dense_slots(
                    b["cache"], flat,
                    jax.tree.map(lambda x: jnp.repeat(x, w, axis=1), cache),
                ),
                "logits": b["logits"].at[flat].set(
                    jnp.repeat(logits, w, axis=0)
                ),
            }
        return out

    def init_ring_aux(self, cfg, proto_root_states, capacity: int):
        """Per-request KV staging rows for the device-resident serving ring:
        one prefilled cache row + root logits per staged request, spliced to
        all ``w`` sibling slots at in-loop admission."""
        del cfg
        from ..models import init_cache

        c = int(capacity)
        s_max = int(jnp.shape(proto_root_states.tokens)[-1])
        ring = {
            "tokens": jnp.zeros((c, s_max), jnp.int32),
            "len": jnp.zeros((c,), jnp.int32),
            "pol": (), "rew": (),
        }
        for key, _, mcfg in self._branches():
            cache = init_cache(mcfg, c, s_max)
            cache.pop("len")
            ring[key] = {
                "cache": cache,
                "logits": jnp.zeros((c, mcfg.vocab_size), mcfg.dtype),
            }
        return ring

    def stage_ring_aux(self, cfg, aux, ring_aux, slots, root_states):
        """Prefill the staged requests NOW (host-paced, between segments) so
        in-loop admission is a pure gather — the dense half of ``admit_aux``
        split at the prefill/splice boundary."""
        del cfg
        from ..models import init_cache

        tokens = jnp.asarray(root_states.tokens, jnp.int32)
        lengths = jnp.asarray(root_states.length, jnp.int32)
        r = tokens.shape[0]
        s_max = ring_aux["tokens"].shape[-1]
        out = dict(
            ring_aux,
            tokens=ring_aux["tokens"].at[slots].set(tokens),
            len=ring_aux["len"].at[slots].set(lengths),
        )
        for key, params, mcfg in self._branches():
            rb = ring_aux[key]
            logits, cache = self.prefill_fn(
                params, mcfg, tokens, lengths, init_cache(mcfg, r, s_max)
            )
            cache.pop("len")
            out[key] = {
                "cache": jax.tree.map(
                    lambda b, x: b.at[:, slots].set(x), rb["cache"], cache
                ),
                "logits": rb["logits"].at[slots].set(logits),
            }
        return aux, out

    def admit_aux_from_ring(self, cfg, aux, ring_aux, slot, mask, w):
        """In-loop admission splice: gather the staged ring rows into the
        admitted rows' ``w`` sibling slots with a masked select (the
        traceable twin of ``admit_aux``'s scatter)."""
        del cfg
        src = jnp.repeat(slot, w)
        fm = jnp.repeat(mask, w)
        out = dict(
            aux,
            tokens=jnp.where(
                fm[:, None], ring_aux["tokens"][src], aux["tokens"]
            ),
            len=jnp.where(fm, ring_aux["len"][src], aux["len"]),
        )
        for key, _, _ in self._branches():
            b, rb = aux[key], ring_aux[key]
            cache = jax.tree.map(
                lambda cur, stg: jnp.where(
                    fm.reshape((1, -1) + (1,) * (cur.ndim - 2)),
                    stg[:, src],
                    cur,
                ),
                b["cache"], rb["cache"],
            )
            out[key] = {
                "cache": cache,
                "logits": jnp.where(
                    fm[:, None], rb["logits"][src], b["logits"]
                ),
            }
        return out, ring_aux

    @jax.named_scope(CATCH_UP)
    def _catch_up(self, sub, target, r, s_max):
        """Re-decode each row's divergent suffix in batched ragged chunks.

        One ``models.decode_chunk`` dispatch advances every behind row by up
        to ``refill_chunk`` tokens at its own offset — ``ceil(suffix / C)``
        model calls per refill instead of ``suffix`` single-token decode
        steps (the while_loop of decode_steps this replaces dominated
        shallow-depth ticks; see BENCH_model_eval.json's d8 rows).
        """
        c_sz = min(self.refill_chunk, s_max)
        del r

        def cond(c):
            return jnp.any(c["len"] < target)

        def body(c):
            base = c["len"]
            behind = base < target
            gpos = jnp.minimum(
                base[:, None] + jnp.arange(c_sz)[None, :], s_max - 1
            )
            toks = jnp.take_along_axis(c["tokens"], gpos, axis=1)
            out = dict(c, pol=(), rew=())
            new_len = base
            for key, params, cfg in self._branches():
                b = c[key]
                logits, cache = self.chunk_fn(
                    params, cfg, toks, target, dict(b["cache"], len=base)
                )
                new_len = cache.pop("len")
                # Rows that finish inside this chunk got their final-position
                # logits from the gather; later chunks never touch them.
                fin = behind & (new_len >= target)
                out[key] = {
                    "cache": cache,
                    "logits": jnp.where(
                        fin[:, None], logits, b["logits"]
                    ).astype(b["logits"].dtype),
                }
            out["len"] = new_len
            return out

        return jax.lax.while_loop(cond, body, sub)

    def aux_len(self, aux) -> Optional[jax.Array]:
        return aux["len"]

    def attended_positions(self, aux) -> jax.Array:
        """Every slot decodes and attends ``min(len, max_len - 1) + 1``
        positions (the paged twin counts one more than it reads for a slot
        that feeds no token)."""
        s_max = aux["tokens"].shape[-1]
        return jnp.sum(jnp.minimum(aux["len"], s_max - 1) + 1)

    def aux_last_logits(self, aux) -> Optional[jax.Array]:
        return aux["pol"]["logits"]

    def tick(self, cfg, kind, act, state, rollout_done, acc, disc, steps, keys,
             aux=()):
        if isinstance(aux, tuple) and aux == ():
            raise ValueError(
                "CachedModelEvaluator.tick needs its slot-aux cache "
                "(init_aux); it runs only inside the async engines — build "
                "with SearchSpec(engine='async') / build_searcher, or use "
                "ModelEvaluator for cache-free evaluation"
            )
        pol = aux["pol"]["logits"]
        rew = aux["rew"]["logits"] if aux["rew"] != () else pol
        out, token = self._transition(
            cfg, kind, act, state, rollout_done, acc, disc, steps, keys, pol,
            rew,
        )
        # Exactly the slots whose env state appended a token this tick.
        fed = (kind != FREE) & jnp.logical_not(state.done)
        return out, self._advance(aux, token, fed)


# ---------------------------------------------------------------------------
# PagedCachedModelEvaluator — shared block pool + per-slot page tables.
# ---------------------------------------------------------------------------


class PagedCachedModelEvaluator(CachedModelEvaluator):
    """:class:`CachedModelEvaluator` over a paged (block-sparse) KV layout.

    Dense slot caches give every in-flight slot a private ``[max_len]`` KV
    row — ``B·W`` slots cost ``B·W·max_len`` rows of HBM even though sibling
    slots share their root prompt (and, after refills, long tree prefixes)
    by construction.  This evaluator stores K/V in a shared block pool
    (:func:`repro.models.init_paged_cache`) and addresses it through
    per-slot page tables, so shared prefixes are stored ONCE:

    * :meth:`init_aux` prefills each distinct root prompt once (one ragged
      batched forward over the ``B`` roots, not ``B·W`` slots), scatters the
      dense rows into pool pages, and points all ``W`` sibling slots' tables
      at the same pages (refcount ``W``);
    * decode writes copy-on-write: a slot about to write into a block with
      ``refcount > 1`` first copies it to a freshly allocated private block
      (one drop-mode gather/scatter over the pool), so siblings never see
      each other's divergent suffixes;
    * :meth:`refill_aux` rollback is a page-table edit — suffix pages are
      refcount-decremented back into the free pool
      (:func:`repro.models.release_pages`) and only the divergent suffix
      re-decodes.

    Attention runs through ``models.paged_decode_step`` →
    ``paged_decode_attention`` (the page-table Pallas kernel on TPU, its
    gather-based jnp oracle elsewhere).  Pool exhaustion inside jitted code
    latches the aux ``oom`` counter; :meth:`check_exhausted` (and eager
    ``init_aux``) surface it as
    :class:`repro.models.PagePoolExhaustedError`.

    Aux layout (flat slot axis ``N``; pool leaves are global):

    * ``tokens i32[N, S]`` / ``len i32[N]`` — as the dense evaluator;
    * ``table i32[N, max_pages]`` — pool block id per logical page; entries
      at page indices ``>= ceil(len/block_size)`` are garbage;
    * ``refcount i32[P]`` / ``oom i32[]`` — shared across branches (policy
      and reward models see the same token stream, so one table/refcount
      serves both; each branch owns its own pools);
    * ``pol/rew`` — ``{"k": [L, P, bs, Hkv, D], "v": ..., "logits": [N, V]}``.
    """

    def __init__(
        self,
        model_cfg,
        params,
        *,
        top_k: int,
        block_size: int,
        num_blocks: int,
        eos_token: int = 0,
        reward_cfg=None,
        reward_params=None,
        value_fn: Optional[Callable] = None,
        prefill_fn: Optional[Callable] = None,
        paged_decode_fn: Optional[Callable] = None,
    ):
        super().__init__(
            model_cfg, params, top_k=top_k, eos_token=eos_token,
            reward_cfg=reward_cfg, reward_params=reward_params,
            value_fn=value_fn, prefill_fn=prefill_fn,
        )
        if paged_decode_fn is None:
            from ..models import paged_decode_step as paged_decode_fn
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.paged_decode_fn = paged_decode_fn

    def _maybe_raise(self, oom) -> None:
        """Surface a latched pool-exhaustion counter at an eager boundary."""
        from ..models import PagePoolExhaustedError

        try:
            n = int(oom)
        except (
            TypeError,
            jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError,
        ):
            return
        if n:
            raise PagePoolExhaustedError(
                f"KV block pool exhausted: {n} page allocation(s) failed "
                f"(num_blocks={self.num_blocks}, "
                f"block_size={self.block_size}); grow num_blocks or reduce "
                "concurrent slots"
            )

    def check_exhausted(self, aux) -> None:
        """Raise :class:`PagePoolExhaustedError` if any jitted allocation
        failed since ``init_aux`` (call after a search settles)."""
        self._maybe_raise(aux["oom"])

    # -- aux structure helpers ---------------------------------------------

    @jax.named_scope(REFILL_CACHE)
    def _take_rows(self, aux, rows):
        def branch(b):
            if b == ():
                return ()
            return {
                "k": b["k"], "v": b["v"],
                "logits": take_slots(b["logits"], rows),
            }

        return {
            "tokens": take_slots(aux["tokens"], rows),
            "len": take_slots(aux["len"], rows),
            "table": take_slots(aux["table"], rows),
            "refcount": aux["refcount"],
            "oom": aux["oom"],
            "pol": branch(aux["pol"]),
            "rew": branch(aux["rew"]),
        }

    @jax.named_scope(REFILL_CACHE)
    def _put_rows(self, aux, rows, sub):
        def branch(b, sb):
            if b == ():
                return ()
            return {
                "k": sb["k"], "v": sb["v"],
                "logits": put_slots(b["logits"], rows, sb["logits"]),
            }

        return {
            "tokens": put_slots(aux["tokens"], rows, sub["tokens"]),
            "len": put_slots(aux["len"], rows, sub["len"]),
            "table": put_slots(aux["table"], rows, sub["table"]),
            "refcount": sub["refcount"],
            "oom": sub["oom"],
            "pol": branch(aux["pol"], sub["pol"]),
            "rew": branch(aux["rew"], sub["rew"]),
        }

    def _page_write(self, table, refcount, oom, idx, pos, write):
        """Resolve the physical target for one K/V row write per slot.

        Page bookkeeping per ``write`` slot targeting position ``pos``:

        * ``off == 0`` — the slot is entering a fresh logical page: allocate
          a block and point the table at it;
        * ``off > 0`` and the current block is shared (``refcount > 1``) —
          copy-on-write: allocate, copy the block, decref the shared one;
        * otherwise the slot owns the block exclusively and writes in place.

        Non-write slots never touch the pool (sentinel target, drop-mode
        scatter), so a masked slot can never corrupt a page — shared or
        not.  Allocation failure latches ``oom`` and skips the write.

        Returns ``(table, refcount, oom, wb, off, copy_src, copy_dst)``:
        ``wb`` is the write block per slot (pool size == "no write");
        ``copy_src``/``copy_dst`` drive the per-branch COW pool copy
        (``dst == pool size`` drops).
        """
        from ..models import alloc_blocks

        bs = self.block_size
        p = refcount.shape[0]
        bi = pos // bs
        off = pos % bs
        cur = table[idx, bi]
        cur_c = jnp.clip(cur, 0, p - 1)
        started = off > 0               # page already holds this slot's rows
        shared = refcount[cur_c] > 1
        need_new = write & (~started | shared)
        is_cow = write & started & shared
        blocks, refcount, n_fail = alloc_blocks(refcount, need_new)
        got = need_new & (blocks < p)
        oom = oom + n_fail
        refcount = refcount.at[
            jnp.where(is_cow & got, cur_c, p)
        ].add(-1, mode="drop")
        table = table.at[idx, bi].set(jnp.where(got, blocks, cur))
        ok = write & jnp.where(need_new, got, True)
        wb = jnp.where(ok, jnp.clip(table[idx, bi], 0, p - 1), p)
        copy_src = jnp.where(is_cow & got, cur_c, 0)
        copy_dst = jnp.where(is_cow & got, blocks, p)
        return table, refcount, oom, wb, off, copy_src, copy_dst

    def _advance(self, aux, token, fed):
        """Feed one token per slot: COW resolution → allocation → one batched
        ``paged_decode_step`` per model (bookkeeping in :meth:`_page_write`).
        """
        idx = jnp.arange(token.shape[0])
        s_max = aux["tokens"].shape[-1]
        length = aux["len"]
        safe = jnp.minimum(length, s_max - 1)
        prev = aux["tokens"][idx, safe]
        tokens = aux["tokens"].at[idx, safe].set(jnp.where(fed, token, prev))

        table, refcount, oom, wb, off, copy_src, copy_dst = self._page_write(
            aux["table"], aux["refcount"], aux["oom"], idx, safe, fed
        )
        p = refcount.shape[0]
        att_len = length + jnp.where(wb < p, 1, 0)

        out = dict(
            tokens=tokens,
            len=jnp.where(fed, length + 1, length),
            table=table, refcount=refcount, oom=oom,
            pol=(), rew=(),
        )
        for key, params, cfg in self._branches():
            b = aux[key]
            pk = b["k"].at[:, copy_dst].set(b["k"][:, copy_src], mode="drop")
            pv = b["v"].at[:, copy_dst].set(b["v"][:, copy_src], mode="drop")
            logits, cache = self.paged_decode_fn(
                params, cfg, token,
                {
                    "k": pk, "v": pv, "table": table, "len": att_len,
                    "pos": safe, "write_block": wb, "write_off": off,
                },
            )
            out[key] = {
                "k": cache["k"], "v": cache["v"],
                "logits": jnp.where(
                    fed[:, None], logits, b["logits"]
                ).astype(b["logits"].dtype),
            }
        return out

    # -- evaluator protocol -------------------------------------------------

    def init_aux(self, root_states: Pytree, prefix: tuple) -> Pytree:
        """Prefill each DISTINCT root once; siblings share its pages.

        The ragged batched prefill runs over the ``prod(prefix[:-1])`` roots
        (vs every slot in the dense evaluator), its dense rows scatter into
        sequentially allocated pool pages, and all ``W = prefix[-1]`` slots
        of a root point at the same pages with refcount ``W`` — including
        the last partial page: the first write a slot makes there triggers
        copy-on-write, so sharing is safe from tick zero.
        """
        from ..models import init_cache
        from ..models.paged import num_pages

        n = 1
        for q in prefix:
            n *= int(q)
        w = int(prefix[-1])
        r0 = n // w
        lead = len(prefix) - 1

        def flat(x):
            x = jnp.expand_dims(x, lead)
            x = jnp.broadcast_to(x, tuple(prefix) + x.shape[lead + 1:])
            return x.reshape((n,) + x.shape[len(prefix):])

        state = jax.tree.map(flat, root_states)
        tokens = jnp.asarray(state.tokens, jnp.int32)
        lengths = jnp.asarray(state.length, jnp.int32)
        s_max = tokens.shape[-1]
        bs, p = self.block_size, self.num_blocks
        mp = num_pages(s_max, bs)

        root_tokens = tokens[::w]
        root_len = lengths[::w]
        p_r = (root_len + bs - 1) // bs              # pages per root
        offsets = jnp.cumsum(p_r) - p_r              # sequential block ids
        page_idx = jnp.arange(mp)
        valid = page_idx[None, :] < p_r[:, None]
        dst_raw = offsets[:, None] + page_idx[None, :]
        got = valid & (dst_raw < p)
        dst = jnp.where(got, dst_raw, p).astype(jnp.int32)   # [r0, mp]
        oom = jnp.sum(valid & ~got).astype(jnp.int32)
        refcount = (
            jnp.zeros((p,), jnp.int32)
            .at[dst.reshape(-1)]
            .add(jnp.where(got.reshape(-1), w, 0), mode="drop")
        )
        aux = {
            "tokens": tokens,
            "len": lengths,
            "table": jnp.repeat(dst, w, axis=0),
            "refcount": refcount,
            "oom": oom,
            "pol": (),
            "rew": (),
        }
        for key, params, cfg in self._branches():
            logits, cache = self.prefill_fn(
                params, cfg, root_tokens, root_len,
                init_cache(cfg, r0, mp * bs),
            )
            kv = cache["kv"]

            def to_pool(x):
                l_, _, _, hk, hd = x.shape
                pages = x.reshape(l_, r0 * mp, bs, hk, hd)
                pool = jnp.zeros((l_, p, bs, hk, hd), x.dtype)
                return pool.at[:, dst.reshape(-1)].set(pages, mode="drop")

            aux[key] = {
                "k": to_pool(kv["k"]),
                "v": to_pool(kv["v"]),
                "logits": jnp.repeat(logits, w, axis=0),
            }
        self._maybe_raise(aux["oom"])
        return aux

    def refill_aux(self, cfg, aux, rows, new_state, mask):
        """Rollback = page-table edit; catch-up = batched ragged chunks.

        Suffix pages wholly beyond the common prefix are refcount-released
        (no cache rows rewritten); the retained partial boundary page is
        still shared, so the first catch-up write into it copies-on-write.
        The divergent suffix then re-decodes through the SAME chunked
        ``models.decode_chunk`` path as the dense evaluator
        (:meth:`_paged_catch_up`): the whole suffix's page-allocation
        schedule is resolved up front, the rows' pages are materialized
        dense, and only the written (now-private) pages scatter back.
        """
        del cfg
        from ..models import release_pages

        sub = self._take_rows(aux, rows)
        r = mask.shape[0]
        s_max = sub["tokens"].shape[-1]
        start, target, tokens, _ = self._rollback_targets(sub, new_state, mask)
        bs = self.block_size
        lo = (start + bs - 1) // bs
        hi = (sub["len"] + bs - 1) // bs
        refcount = release_pages(sub["refcount"], sub["table"], lo, hi)
        sub = dict(sub, tokens=tokens, len=start, refcount=refcount)
        sub = self._paged_catch_up(sub, target, r, s_max)
        return self._put_rows(aux, rows, sub), jnp.zeros((r,), jnp.bool_)

    def admit_aux(self, cfg, aux, rows, root_states, w):
        """Mid-stream admission: page release → re-prefill → table splice.

        The rows' slots first return everything they still hold to the pool
        (rows evicted earlier hold nothing — their ``len`` is zero, so the
        release is a no-op and pages are never double-freed).  Each admitted
        root then prefills ONCE (the shared
        :mod:`repro.serving.admission` ragged forward), its dense rows
        scatter into freshly allocated pool pages
        (:func:`repro.serving.admission.splice_pool_pages`), and all ``w``
        sibling slots' tables point at the same pages with refcount ``w`` —
        the same prefix-sharing layout ``init_aux`` builds at cold start.
        Runs at an eager boundary, so exhaustion raises
        :class:`repro.models.PagePoolExhaustedError` immediately.
        """
        del cfg
        from ..models import alloc_blocks, init_cache, release_pages
        from ..serving.admission import splice_pool_pages

        flat = _flat_slot_rows(rows, w)
        tokens = jnp.asarray(root_states.tokens, jnp.int32)
        lengths = jnp.asarray(root_states.length, jnp.int32)
        r = tokens.shape[0]
        bs, p = self.block_size, self.num_blocks
        mp = aux["table"].shape[1]

        hi = (aux["len"][flat] + bs - 1) // bs
        refcount = release_pages(
            aux["refcount"], aux["table"][flat], jnp.zeros_like(hi), hi
        )

        # Fresh page schedule: one block per root page, fanned out to the w
        # sibling slots (alloc_blocks hands out refcount 1; the fan-out adds
        # the other w - 1 sharers).
        p_r = (lengths + bs - 1) // bs
        dst = jnp.full((r, mp), p, jnp.int32)
        oom = aux["oom"]
        for pi in range(mp):
            need = pi < p_r
            blocks, refcount, n_fail = alloc_blocks(refcount, need)
            dst = dst.at[:, pi].set(
                jnp.where(need & (blocks < p), blocks, p)
            )
            oom = oom + n_fail
        refcount = refcount.at[dst.reshape(-1)].add(
            jnp.where((dst < p).reshape(-1), w - 1, 0), mode="drop"
        )

        out = dict(
            aux,
            tokens=aux["tokens"].at[flat].set(jnp.repeat(tokens, w, axis=0)),
            len=aux["len"].at[flat].set(jnp.repeat(lengths, w, axis=0)),
            table=aux["table"].at[flat].set(jnp.repeat(dst, w, axis=0)),
            refcount=refcount,
            oom=oom,
        )
        for key, params, mcfg in self._branches():
            b = aux[key]
            logits, cache = self.prefill_fn(
                params, mcfg, tokens, lengths, init_cache(mcfg, r, mp * bs)
            )
            kv = cache["kv"]
            pk, pv = splice_pool_pages(b["k"], b["v"], kv["k"], kv["v"], dst)
            out[key] = {
                "k": pk, "v": pv,
                "logits": b["logits"].at[flat].set(
                    jnp.repeat(logits, w, axis=0)
                ),
            }
        self._maybe_raise(out["oom"])
        return out

    def evict_aux(self, aux, rows, w):
        """Return settled rows' pages to the pool without admitting.

        Tables drop to the sentinel and ``len`` to zero, so the rows' frozen
        FREE slots never dereference a released block (garbage-table
        entries are clipped + len-masked by the decode path regardless),
        and a later :meth:`admit_aux` release of the same rows is a no-op.
        """
        from ..models import release_pages

        flat = _flat_slot_rows(rows, w)
        bs = self.block_size
        mp = aux["table"].shape[1]
        hi = (aux["len"][flat] + bs - 1) // bs
        refcount = release_pages(
            aux["refcount"], aux["table"][flat], jnp.zeros_like(hi), hi
        )
        return dict(
            aux,
            refcount=refcount,
            table=aux["table"].at[flat].set(
                jnp.full((flat.shape[0], mp), self.num_blocks, jnp.int32)
            ),
            len=aux["len"].at[flat].set(0),
        )

    def init_ring_aux(self, cfg, proto_root_states, capacity: int):
        """Ring staging for the paged evaluator: tokens, a page table and
        root logits per slot.  The KV bytes themselves are NOT staged — a
        staged request's pages live in the shared pool already (written by
        :meth:`stage_ring_aux`, held at refcount 1 by the ring), so in-loop
        admission is a table splice + refcount fan-out."""
        del cfg
        from ..models.paged import num_pages

        c = int(capacity)
        s_max = int(jnp.shape(proto_root_states.tokens)[-1])
        mp = num_pages(s_max, self.block_size)
        ring = {
            "tokens": jnp.zeros((c, s_max), jnp.int32),
            "len": jnp.zeros((c,), jnp.int32),
            "table": jnp.full((c, mp), self.num_blocks, jnp.int32),
            "pol": (), "rew": (),
        }
        for key, _, mcfg in self._branches():
            ring[key] = {
                "logits": jnp.zeros((c, mcfg.vocab_size), mcfg.dtype),
            }
        return ring

    def stage_ring_aux(self, cfg, aux, ring_aux, slots, root_states):
        """Allocate + prefill the staged requests' pool pages now.

        Pages come out of the live slot-aux refcounts (the serving layer
        budgets against them before staging), are written by one ragged
        prefill, and sit at refcount 1 owned by the ring until in-loop
        admission transfers them to the admitted row.  Pool exhaustion
        latches ``oom`` (checked eagerly by the caller after the round) —
        this path must stay traceable.
        """
        del cfg
        from ..models import alloc_blocks, init_cache
        from ..serving.admission import splice_pool_pages

        tokens = jnp.asarray(root_states.tokens, jnp.int32)
        lengths = jnp.asarray(root_states.length, jnp.int32)
        r = tokens.shape[0]
        bs, p = self.block_size, self.num_blocks
        mp = ring_aux["table"].shape[1]

        # Engine invariant: ring slots outside the staged window hold
        # nothing (cleared at admission), so no release is needed here.
        refcount = aux["refcount"]
        p_r = (lengths + bs - 1) // bs
        dst = jnp.full((r, mp), p, jnp.int32)
        oom = aux["oom"]
        for pi in range(mp):
            need = pi < p_r
            blocks, refcount, n_fail = alloc_blocks(refcount, need)
            dst = dst.at[:, pi].set(jnp.where(need & (blocks < p), blocks, p))
            oom = oom + n_fail

        out_ring = dict(
            ring_aux,
            tokens=ring_aux["tokens"].at[slots].set(tokens),
            len=ring_aux["len"].at[slots].set(lengths),
            table=ring_aux["table"].at[slots].set(dst),
        )
        out_aux = dict(aux, refcount=refcount, oom=oom)
        for key, params, mcfg in self._branches():
            b = aux[key]
            logits, cache = self.prefill_fn(
                params, mcfg, tokens, lengths, init_cache(mcfg, r, mp * bs)
            )
            kv = cache["kv"]
            pk, pv = splice_pool_pages(b["k"], b["v"], kv["k"], kv["v"], dst)
            out_aux[key] = dict(b, k=pk, v=pv)
            out_ring[key] = {
                "logits": ring_aux[key]["logits"].at[slots].set(logits),
            }
        return out_aux, out_ring

    def admit_aux_from_ring(self, cfg, aux, ring_aux, slot, mask, w):
        """In-loop paged admission: table splice + refcount fan-out.

        Admission targets are always fully evicted rows (the fused round
        evicts completed rows before admitting), so there is nothing to
        release.  The ring's single page reference transfers to the first
        sibling slot; the fan-out adds the other ``w - 1`` sharers — the
        same prefix-sharing layout ``admit_aux`` builds eagerly.  Consumed
        ring slots drop to the sentinel so a later re-staging of the same
        slot never double-frees.
        """
        del cfg
        src = jnp.repeat(slot, w)
        fm = jnp.repeat(mask, w)
        p = self.num_blocks
        cap = ring_aux["len"].shape[0]
        dst = ring_aux["table"][slot]                       # [B, mp]
        sharers = jnp.where(mask[:, None] & (dst < p), dst, p)
        refcount = aux["refcount"].at[sharers.reshape(-1)].add(
            jnp.where((sharers < p).reshape(-1), w - 1, 0), mode="drop"
        )
        out = dict(
            aux,
            tokens=jnp.where(
                fm[:, None], ring_aux["tokens"][src], aux["tokens"]
            ),
            len=jnp.where(fm, ring_aux["len"][src], aux["len"]),
            table=jnp.where(fm[:, None], ring_aux["table"][src],
                            aux["table"]),
            refcount=refcount,
        )
        for key, _, _ in self._branches():
            out[key] = dict(
                aux[key],
                logits=jnp.where(
                    fm[:, None],
                    ring_aux[key]["logits"][src],
                    aux[key]["logits"],
                ),
            )
        cslot = jnp.where(mask, slot, cap)                  # OOB = untouched
        out_ring = dict(
            ring_aux,
            table=ring_aux["table"].at[cslot].set(p, mode="drop"),
            len=ring_aux["len"].at[cslot].set(0, mode="drop"),
        )
        return out, out_ring

    def evict_aux_to_ring(self, aux, mask, w):
        """Masked traceable eviction: rows where ``mask`` holds return their
        pages to the pool inside the fused loop (``release_pages`` with
        ``hi = 0`` on unmasked rows is a no-op)."""
        from ..models import release_pages

        fm = jnp.repeat(mask, w)
        bs = self.block_size
        hi = jnp.where(fm, (aux["len"] + bs - 1) // bs, 0)
        refcount = release_pages(
            aux["refcount"], aux["table"], jnp.zeros_like(hi), hi
        )
        return dict(
            aux,
            refcount=refcount,
            table=jnp.where(fm[:, None], self.num_blocks, aux["table"]),
            len=jnp.where(fm, 0, aux["len"]),
        )

    @jax.named_scope(CATCH_UP)
    def _paged_catch_up(self, sub, target, r, s_max):
        """Chunked divergent-suffix re-decode over paged rows.

        Page writes no longer interleave with decode steps: every page the
        suffix will touch is resolved FIRST (boundary COW for rows
        re-entering a shared partial page, then one fresh block per whole
        suffix page), which makes all written pages private — so the
        catch-up itself can run as the dense evaluator's batched ragged
        ``decode_chunk`` loop over a dense gather of each row's pages, and
        the written pages scatter back afterwards.  Pages whose allocation
        failed stay masked out of the scatter (shared blocks are never
        corrupted); the failure latches ``oom`` as usual.

        ``sub['len']`` must already hold each row's re-decode start.

        The whole body (boundary COW, page schedule, gather → chunked
        decode → scatter) is gated on any row actually being behind:
        refill_aux runs for every slot every tick, but almost all calls
        are no-ops (nothing settled, or a frontier hit already landed the
        row at its target), and the unconditional bookkeeping alone is
        expensive enough to show up per tick.
        """
        return jax.lax.cond(
            jnp.any(sub["len"] < target),
            lambda op: self._paged_catch_up_behind(op[0], op[1], r, s_max),
            lambda op: op[0],
            (sub, target),
        )

    def _paged_catch_up_behind(self, sub, target, r, s_max):
        from ..models import alloc_blocks

        bs = self.block_size
        p = self.num_blocks
        mp = sub["table"].shape[1]
        idx = jnp.arange(r)
        start = sub["len"]
        behind = start < target

        # Boundary page: rows resuming mid-page COW out of shared blocks.
        bwrite = behind & (start % bs > 0)
        table, refcount, oom, wb, _, copy_src, copy_dst = self._page_write(
            sub["table"], sub["refcount"], sub["oom"], idx,
            jnp.minimum(start, s_max - 1), bwrite,
        )
        page_ok = jnp.ones((r, mp), jnp.bool_).at[
            idx, jnp.clip(start // bs, 0, mp - 1)
        ].set(jnp.where(bwrite, wb < p, True))
        sub = dict(sub, table=table, refcount=refcount, oom=oom)
        for key, _, _ in self._branches():
            b = sub[key]
            sub[key] = dict(
                b,
                k=b["k"].at[:, copy_dst].set(b["k"][:, copy_src], mode="drop"),
                v=b["v"].at[:, copy_dst].set(b["v"][:, copy_src], mode="drop"),
            )

        # Whole-suffix page schedule: one fresh block per page in [lo, hi).
        lo = (start + bs - 1) // bs
        hi = (target + bs - 1) // bs

        def alloc_body(pi, c):
            table, refcount, oom, page_ok = c
            need = behind & (pi >= lo) & (pi < hi)
            blocks, refcount, n_fail = alloc_blocks(refcount, need)
            got = need & (blocks < p)
            table = table.at[:, pi].set(jnp.where(got, blocks, table[:, pi]))
            page_ok = page_ok.at[:, pi].set(
                jnp.where(need, got, page_ok[:, pi])
            )
            return table, refcount, oom + n_fail, page_ok

        table, refcount, oom, page_ok = jax.lax.fori_loop(
            0, mp, alloc_body, (sub["table"], sub["refcount"], sub["oom"],
                                page_ok)
        )
        sub = dict(sub, table=table, refcount=refcount, oom=oom)

        # Dense view → the dense evaluator's chunked catch-up → scatter back.
        t_clip = jnp.clip(table, 0, p - 1)

        def dense(pool):
            out = pool[:, t_clip]                 # [L, R, mp, bs, hkv, hd]
            l_, r_, mp_, bs_, hk, hd = out.shape
            return out.reshape(l_, r_, mp_ * bs_, hk, hd)

        dsub = {"tokens": sub["tokens"], "len": sub["len"],
                "pol": (), "rew": ()}
        for key, _, _ in self._branches():
            b = sub[key]
            dsub[key] = {
                "cache": {"kv": {"k": dense(b["k"]), "v": dense(b["v"])}},
                "logits": b["logits"],
            }
        dsub = self._catch_up(dsub, target, r, s_max)

        pages = jnp.arange(mp)
        changed = (
            behind[:, None]
            & (pages[None, :] >= (start // bs)[:, None])
            & (pages[None, :] < hi[:, None])
            & page_ok
        )
        dst = jnp.where(changed, t_clip, p).reshape(-1)
        out = dict(sub, len=dsub["len"])
        for key, _, _ in self._branches():
            d = dsub[key]["cache"]["kv"]

            def repage(x):
                l_ = x.shape[0]
                return x.reshape(l_, r * mp, bs, *x.shape[3:])

            out[key] = dict(
                sub[key],
                k=sub[key]["k"].at[:, dst].set(repage(d["k"]), mode="drop"),
                v=sub[key]["v"].at[:, dst].set(repage(d["v"]), mode="drop"),
                logits=dsub[key]["logits"],
            )
        return out

    def aux_blocks(self, aux) -> Optional[jax.Array]:
        return jnp.sum(aux["refcount"] > 0)


# ---------------------------------------------------------------------------
# Frontier-speculative expansion: score every candidate child in one forward.
# ---------------------------------------------------------------------------


class _FrontierMixin:
    """Shared frontier-cache logic for the dense and paged evaluators.

    Every tick advance runs through ``models.decode_frontier`` /
    ``paged_decode_frontier``: instead of decoding ONLY the chosen token,
    the slot's ``A = top_k`` candidate children — exactly the action table
    :meth:`ModelEvaluator._transition` decodes ranks against — are scored in
    one tree-batched forward over the shared prefix.  The chosen candidate's
    logits and K/V row commit to the cache (bit-identical to the plain
    decode step), and EXPAND ticks additionally snapshot the whole frontier
    into per-slot aux (``aux['fr']``):

    * ``ptok``/``plen`` — the parent path the frontier was scored FROM;
    * ``cand i32[N, A]`` — the candidate tokens (the transition's top-K);
    * per branch: ``plog`` (the parent position's logits), ``clog [N, A, V]``
      (every candidate's next-position logits) and ``ck``/``cv``
      (``[L, N, A, Hkv, D]``, every candidate's own K/V entry).

    **Refill hits** (:meth:`refill_aux` in the concrete classes): WU-UCT's
    refill assigns the settled slot a tree path that is almost always the
    SAME parent (sibling expansion) or one of its children (deepening) —
    both of which the snapshot already answers:

    * *parent hit* (``len(path) == plen``, path == ptok): restore ``plog``,
      roll ``len`` straight to the target — the standard rollback's forced
      final-token re-decode existed only to regenerate these logits;
    * *child hit* (``len(path) == plen + 1``, last token ∈ ``cand``):
      restore ``clog[rank]`` and commit ``ck``/``cv[rank]`` at position
      ``plen`` — the full refill without any forward.

    Hit rows skip the catch-up loop entirely (zero model dispatches); the
    returned ``hits`` mask feeds the engines' ``frontier_hits`` counter so
    WU-UCT's ``O_s`` accounting is visibly absorbing speculative visits.
    A refill onto a path that diverges from ``ptok`` invalidates the entry.
    """

    def _fr_init(self, aux):
        n, _ = aux["tokens"].shape
        a = self.top_k
        fr = {
            "ptok": jnp.zeros_like(aux["tokens"]),
            "plen": jnp.zeros((n,), jnp.int32),
            "valid": jnp.zeros((n,), jnp.bool_),
            "cand": jnp.zeros((n, a), jnp.int32),
            "pol": (), "rew": (),
        }
        for key, _, cfg in self._branches():
            lg = aux[key]["logits"]
            v = lg.shape[-1]
            fr[key] = {
                "plog": jnp.zeros_like(lg),
                "clog": jnp.zeros((n, a, v), lg.dtype),
                "ck": jnp.zeros(
                    (cfg.num_layers, n, a, cfg.num_kv_heads, cfg.head_dim),
                    cfg.dtype,
                ),
                "cv": jnp.zeros(
                    (cfg.num_layers, n, a, cfg.num_kv_heads, cfg.head_dim),
                    cfg.dtype,
                ),
            }
        return fr

    def init_aux(self, root_states, prefix):
        aux = super().init_aux(root_states, prefix)
        aux["fr"] = self._fr_init(aux)
        return aux

    @jax.named_scope(REFILL_CACHE)
    def _take_rows(self, aux, rows):
        sub = super()._take_rows(aux, rows)
        fr = aux["fr"]

        def br(b):
            if b == ():
                return ()
            return {
                "plog": take_slots(b["plog"], rows),
                "clog": take_slots(b["clog"], rows),
                "ck": take_slots(b["ck"], rows, 1),
                "cv": take_slots(b["cv"], rows, 1),
            }

        sub["fr"] = {
            key: take_slots(fr[key], rows)
            for key in ("ptok", "plen", "valid", "cand")
        }
        sub["fr"].update(pol=br(fr["pol"]), rew=br(fr["rew"]))
        return sub

    @jax.named_scope(REFILL_CACHE)
    def _put_rows(self, aux, rows, sub):
        out = super()._put_rows(aux, rows, sub)
        fr, sfr = aux["fr"], sub["fr"]

        def br(b, sb):
            if b == ():
                return ()
            return {
                "plog": put_slots(b["plog"], rows, sb["plog"]),
                "clog": put_slots(b["clog"], rows, sb["clog"]),
                "ck": put_slots(b["ck"], rows, sb["ck"], 1),
                "cv": put_slots(b["cv"], rows, sb["cv"], 1),
            }

        out["fr"] = {
            key: put_slots(fr[key], rows, sfr[key])
            for key in ("ptok", "plen", "valid", "cand")
        }
        out["fr"].update(
            pol=br(fr["pol"], sfr["pol"]), rew=br(fr["rew"], sfr["rew"])
        )
        return out

    def admit_aux(self, cfg, aux, rows, root_states, w):
        """Admission invalidates the rows' frontier snapshots: they were
        taken against the previous request's tree and must never answer the
        new request's refills.  ``_take_rows``/``_put_rows`` thread ``fr``
        through the base splice, so only the validity bit needs clearing."""
        fr = aux["fr"]
        out = super().admit_aux(cfg, dict(aux, fr=()), rows, root_states, w)
        out["fr"] = dict(
            fr, valid=fr["valid"].at[_flat_slot_rows(rows, w)].set(False)
        )
        return out

    def evict_aux(self, aux, rows, w):
        fr = aux["fr"]
        out = super().evict_aux(dict(aux, fr=()), rows, w)
        out["fr"] = dict(
            fr, valid=fr["valid"].at[_flat_slot_rows(rows, w)].set(False)
        )
        return out

    def stage_ring_aux(self, cfg, aux, ring_aux, slots, root_states):
        """Frontier snapshots are per-slot, not per-request — nothing to
        stage; shield ``fr`` from the base staging path."""
        fr = aux["fr"]
        out_aux, out_ring = super().stage_ring_aux(
            cfg, dict(aux, fr=()), ring_aux, slots, root_states
        )
        return dict(out_aux, fr=fr), out_ring

    def admit_aux_from_ring(self, cfg, aux, ring_aux, slot, mask, w):
        """In-loop admission invalidates the rows' frontier snapshots, same
        as the eager ``admit_aux`` — masked select instead of scatter."""
        fr = aux["fr"]
        out, out_ring = super().admit_aux_from_ring(
            cfg, dict(aux, fr=()), ring_aux, slot, mask, w
        )
        out["fr"] = dict(
            fr, valid=jnp.where(jnp.repeat(mask, w), False, fr["valid"])
        )
        return out, out_ring

    def evict_aux_to_ring(self, aux, mask, w):
        fr = aux["fr"]
        out = super().evict_aux_to_ring(dict(aux, fr=()), mask, w)
        out = dict(out)
        out["fr"] = dict(
            fr, valid=jnp.where(jnp.repeat(mask, w), False, fr["valid"])
        )
        return out

    def _fr_record(self, fr, pre_tokens, length, cand, is_exp):
        """Snapshot the parent path + candidate set on EXPAND rows."""
        exp2 = is_exp[:, None]
        return dict(
            fr,
            ptok=jnp.where(exp2, pre_tokens, fr["ptok"]),
            plen=jnp.where(is_exp, length, fr["plen"]),
            valid=fr["valid"] | is_exp,
            cand=jnp.where(exp2, cand, fr["cand"]),
        )

    def _frontier_hits(self, sub, tokens, new_state, common, mask):
        """Classify each refill row against its frontier snapshot.

        Returns ``(parent_hit, child_hit, crank, pmatch)``; ``crank`` is the
        matched candidate's rank (valid only under ``child_hit``).  Both hit
        kinds require the CACHE to still hold the parent prefix (via the
        uncapped ``common``) *and* the new path to match the snapshot's
        parent path (``pmatch``) — the two can diverge independently after
        intervening refills.
        """
        fr = sub["fr"]
        s_max = tokens.shape[-1]
        r = tokens.shape[0]
        idx = jnp.arange(r)
        pos = jnp.arange(s_max)
        l_new = jnp.asarray(new_state.length, jnp.int32)
        plen = fr["plen"]
        cmp_len = jnp.minimum(plen, l_new)
        pmatch = jnp.logical_not(
            jnp.any(
                (fr["ptok"] != tokens) & (pos[None, :] < cmp_len[:, None]),
                axis=1,
            )
        )
        last = tokens[idx, jnp.clip(l_new - 1, 0, s_max - 1)]
        is_cand = fr["cand"] == last[:, None]
        crank = jnp.argmax(is_cand, axis=1)
        ok = mask & fr["valid"] & pmatch
        parent_hit = ok & (l_new == plen) & (common >= l_new)
        child_hit = (
            ok & (l_new == plen + 1) & jnp.any(is_cand, axis=1)
            & (common >= plen)
        )
        return parent_hit, child_hit, crank, pmatch

    def tick(self, cfg, kind, act, state, rollout_done, acc, disc, steps, keys,
             aux=()):
        if isinstance(aux, tuple) and aux == ():
            raise ValueError(
                "frontier evaluators need their slot-aux cache (init_aux); "
                "they run only inside the async engines — build with "
                "SearchSpec(engine='async') / build_searcher"
            )
        pol = aux["pol"]["logits"]
        rew = aux["rew"]["logits"] if aux["rew"] != () else pol
        out, token = self._transition(
            cfg, kind, act, state, rollout_done, acc, disc, steps, keys, pol,
            rew,
        )
        fed = (kind != FREE) & jnp.logical_not(state.done)
        is_exp = fed & (kind == EXPAND)
        # Only EXPAND rows need the A-wide frontier snapshot; ticks where
        # every fed slot is mid-rollout (the majority — expansions number
        # num_simulations, ticks number far more) take the plain one-token
        # advance and carry the snapshot through untouched.
        aux2 = jax.lax.cond(
            jnp.any(is_exp),
            lambda op: self._advance_frontier(*op),
            lambda op: dict(
                self._advance(op[0], op[1], op[2]), fr=op[0]["fr"]
            ),
            (aux, token, fed, is_exp),
        )
        return out, aux2


class FrontierModelEvaluator(_FrontierMixin, CachedModelEvaluator):
    """:class:`CachedModelEvaluator` with frontier-speculative expansion.

    Tick advances run ``models.decode_frontier`` (tree-batched candidate
    scoring over the dense per-slot cache); refills of the snapshotted
    parent or any of its candidate children are answered from aux with zero
    model forwards.  See :class:`_FrontierMixin` for the cache semantics.
    """

    def __init__(self, model_cfg, params, *, top_k: int, eos_token: int = 0,
                 reward_cfg=None, reward_params=None,
                 value_fn: Optional[Callable] = None,
                 decode_fn: Optional[Callable] = None,
                 prefill_fn: Optional[Callable] = None,
                 chunk_fn: Optional[Callable] = None,
                 refill_chunk: int = 8,
                 frontier_fn: Optional[Callable] = None):
        super().__init__(
            model_cfg, params, top_k=top_k, eos_token=eos_token,
            reward_cfg=reward_cfg, reward_params=reward_params,
            value_fn=value_fn, decode_fn=decode_fn, prefill_fn=prefill_fn,
            chunk_fn=chunk_fn, refill_chunk=refill_chunk,
        )
        if frontier_fn is None:
            from ..models import decode_frontier as frontier_fn
        self.frontier_fn = frontier_fn

    def _advance_frontier(self, aux, token, fed, is_exp):
        """One tree-batched frontier forward advances every slot.

        The chosen candidate's logits and K/V row commit exactly as
        :meth:`CachedModelEvaluator._advance` would have (same math: each
        candidate attends the prefix plus itself); EXPAND rows snapshot the
        full candidate set into ``aux['fr']``.
        """
        idx = jnp.arange(token.shape[0])
        s_max = aux["tokens"].shape[-1]
        length = aux["len"]
        safe = jnp.minimum(length, s_max - 1)
        prev = aux["tokens"][idx, safe]
        tokens = aux["tokens"].at[idx, safe].set(jnp.where(fed, token, prev))

        # The same deterministic top-K table _transition decoded the action
        # against — the fed token is one of these candidates by construction.
        _, cand = jax.lax.top_k(aux["pol"]["logits"], self.top_k)
        rank = jnp.argmax(cand == token[:, None], axis=1)

        fr = self._fr_record(aux["fr"], aux["tokens"], length, cand, is_exp)
        out = dict(
            tokens=tokens,
            len=jnp.where(fed, length + 1, length),
            pol=(), rew=(),
        )
        for key, params, cfg in self._branches():
            b = aux[key]
            clog, spec = self.frontier_fn(
                params, cfg, cand, dict(b["cache"], len=safe)
            )
            chosen = clog[idx, rank]
            rk = rank.reshape(1, -1, 1, 1, 1)
            row_k = jnp.take_along_axis(spec["k"], rk, axis=2)[:, :, 0]
            row_v = jnp.take_along_axis(spec["v"], rk, axis=2)[:, :, 0]
            kv = b["cache"]["kv"]
            kv = {
                "k": kv["k"].at[:, idx, safe].set(row_k),
                "v": kv["v"].at[:, idx, safe].set(row_v),
            }
            out[key] = {
                "cache": dict(b["cache"], kv=kv),
                "logits": jnp.where(
                    fed[:, None], chosen, b["logits"]
                ).astype(b["logits"].dtype),
            }
            fb = fr[key]
            fr[key] = {
                "plog": jnp.where(is_exp[:, None], b["logits"], fb["plog"]),
                "clog": jnp.where(
                    is_exp[:, None, None], clog, fb["clog"]
                ).astype(fb["clog"].dtype),
                "ck": jnp.where(
                    is_exp[None, :, None, None, None], spec["k"], fb["ck"]
                ).astype(fb["ck"].dtype),
                "cv": jnp.where(
                    is_exp[None, :, None, None, None], spec["v"], fb["cv"]
                ).astype(fb["cv"].dtype),
            }
        out["fr"] = fr
        return out

    def refill_aux(self, cfg, aux, rows, new_state, mask):
        del cfg
        sub = self._take_rows(aux, rows)
        r = mask.shape[0]
        s_max = sub["tokens"].shape[-1]
        idx = jnp.arange(r)
        start, target, tokens, common = self._rollback_targets(
            sub, new_state, mask
        )
        parent_hit, child_hit, crank, pmatch = self._frontier_hits(
            sub, tokens, new_state, common, mask
        )
        hit = parent_hit | child_hit
        fr = sub["fr"]
        sub["fr"] = dict(
            fr, valid=jnp.where(mask, fr["valid"] & pmatch, fr["valid"])
        )
        sub = dict(sub, tokens=tokens, len=jnp.where(hit, target, start))
        cpos = jnp.clip(fr["plen"], 0, s_max - 1)
        rk = crank.reshape(1, -1, 1, 1, 1)
        for key, _, _ in self._branches():
            b = sub[key]
            fb = fr[key]
            logits = jnp.where(parent_hit[:, None], fb["plog"], b["logits"])
            logits = jnp.where(
                child_hit[:, None], fb["clog"][idx, crank], logits
            ).astype(b["logits"].dtype)
            row_k = jnp.take_along_axis(fb["ck"], rk, axis=2)[:, :, 0]
            row_v = jnp.take_along_axis(fb["cv"], rk, axis=2)[:, :, 0]
            kv = b["cache"]["kv"]
            ch = child_hit[None, :, None, None]
            kv = {
                "k": kv["k"].at[:, idx, cpos].set(
                    jnp.where(ch, row_k, kv["k"][:, idx, cpos])
                ),
                "v": kv["v"].at[:, idx, cpos].set(
                    jnp.where(ch, row_v, kv["v"][:, idx, cpos])
                ),
            }
            sub[key] = {"cache": dict(b["cache"], kv=kv), "logits": logits}
        sub = self._catch_up(sub, target, r, s_max)
        return self._put_rows(aux, rows, sub), hit


class PagedFrontierModelEvaluator(_FrontierMixin, PagedCachedModelEvaluator):
    """:class:`PagedCachedModelEvaluator` with frontier-speculative expansion.

    Same frontier cache as :class:`FrontierModelEvaluator` over the shared
    block pool: candidate scoring reads the prefix straight from the pages
    (``models.paged_decode_frontier`` — no dense gather), and a child hit
    commits its cached K/V row through the usual page bookkeeping
    (allocation / copy-on-write via ``_page_write``).
    """

    def __init__(self, model_cfg, params, *, top_k: int, block_size: int,
                 num_blocks: int, eos_token: int = 0, reward_cfg=None,
                 reward_params=None, value_fn: Optional[Callable] = None,
                 prefill_fn: Optional[Callable] = None,
                 paged_decode_fn: Optional[Callable] = None,
                 frontier_fn: Optional[Callable] = None):
        super().__init__(
            model_cfg, params, top_k=top_k, block_size=block_size,
            num_blocks=num_blocks, eos_token=eos_token,
            reward_cfg=reward_cfg, reward_params=reward_params,
            value_fn=value_fn, prefill_fn=prefill_fn,
            paged_decode_fn=paged_decode_fn,
        )
        if frontier_fn is None:
            from ..models import paged_decode_frontier as frontier_fn
        self.frontier_fn = frontier_fn

    def _advance_frontier(self, aux, token, fed, is_exp):
        """Frontier forward over the page tables; chosen row commits via the
        standard COW/allocation bookkeeping (:meth:`_page_write`)."""
        idx = jnp.arange(token.shape[0])
        s_max = aux["tokens"].shape[-1]
        length = aux["len"]
        safe = jnp.minimum(length, s_max - 1)
        prev = aux["tokens"][idx, safe]
        tokens = aux["tokens"].at[idx, safe].set(jnp.where(fed, token, prev))

        table, refcount, oom, wb, off, copy_src, copy_dst = self._page_write(
            aux["table"], aux["refcount"], aux["oom"], idx, safe, fed
        )

        _, cand = jax.lax.top_k(aux["pol"]["logits"], self.top_k)
        rank = jnp.argmax(cand == token[:, None], axis=1)

        fr = self._fr_record(aux["fr"], aux["tokens"], length, cand, is_exp)
        out = dict(
            tokens=tokens,
            len=jnp.where(fed, length + 1, length),
            table=table, refcount=refcount, oom=oom,
            pol=(), rew=(),
        )
        for key, params, cfg in self._branches():
            b = aux[key]
            pk = b["k"].at[:, copy_dst].set(b["k"][:, copy_src], mode="drop")
            pv = b["v"].at[:, copy_dst].set(b["v"][:, copy_src], mode="drop")
            clog, spec = self.frontier_fn(
                params, cfg, cand,
                {"k": pk, "v": pv, "table": table, "len": safe},
            )
            chosen = clog[idx, rank]
            rk = rank.reshape(1, -1, 1, 1, 1)
            row_k = jnp.take_along_axis(spec["k"], rk, axis=2)[:, :, 0]
            row_v = jnp.take_along_axis(spec["v"], rk, axis=2)[:, :, 0]
            out[key] = {
                "k": pk.at[:, wb, off].set(row_k, mode="drop"),
                "v": pv.at[:, wb, off].set(row_v, mode="drop"),
                "logits": jnp.where(
                    fed[:, None], chosen, b["logits"]
                ).astype(b["logits"].dtype),
            }
            fb = fr[key]
            fr[key] = {
                "plog": jnp.where(is_exp[:, None], b["logits"], fb["plog"]),
                "clog": jnp.where(
                    is_exp[:, None, None], clog, fb["clog"]
                ).astype(fb["clog"].dtype),
                "ck": jnp.where(
                    is_exp[None, :, None, None, None], spec["k"], fb["ck"]
                ).astype(fb["ck"].dtype),
                "cv": jnp.where(
                    is_exp[None, :, None, None, None], spec["v"], fb["cv"]
                ).astype(fb["cv"].dtype),
            }
        out["fr"] = fr
        return out

    def refill_aux(self, cfg, aux, rows, new_state, mask):
        del cfg
        from ..models import release_pages

        sub = self._take_rows(aux, rows)
        r = mask.shape[0]
        s_max = sub["tokens"].shape[-1]
        idx = jnp.arange(r)
        start, target, tokens, common = self._rollback_targets(
            sub, new_state, mask
        )
        parent_hit, child_hit, crank, pmatch = self._frontier_hits(
            sub, tokens, new_state, common, mask
        )
        fr = sub["fr"]
        plen = fr["plen"]
        bs = self.block_size

        # Hit-aware release: a parent hit keeps the whole target prefix, a
        # child hit keeps the parent prefix (the commit lands at ``plen``).
        keep = jnp.where(
            parent_hit, target, jnp.where(child_hit, plen, start)
        )
        lo = (keep + bs - 1) // bs
        hi = (sub["len"] + bs - 1) // bs
        refcount = release_pages(sub["refcount"], sub["table"], lo, hi)
        sub = dict(sub, refcount=refcount)

        # Child-hit commit target, through the usual page bookkeeping.  A
        # failed allocation (wb == pool size) demotes the row to a miss.
        cpos = jnp.clip(plen, 0, s_max - 1)
        table, refcount, oom, wb, off, copy_src, copy_dst = self._page_write(
            sub["table"], sub["refcount"], sub["oom"], idx, cpos, child_hit
        )
        p = refcount.shape[0]
        committed = child_hit & (wb < p)
        hit = parent_hit | committed
        sub = dict(
            sub, table=table, refcount=refcount, oom=oom, tokens=tokens,
            len=jnp.where(hit, target, start),
        )
        sub["fr"] = dict(
            fr, valid=jnp.where(mask, fr["valid"] & pmatch, fr["valid"])
        )
        rk = crank.reshape(1, -1, 1, 1, 1)
        for key, _, _ in self._branches():
            b = sub[key]
            pk = b["k"].at[:, copy_dst].set(b["k"][:, copy_src], mode="drop")
            pv = b["v"].at[:, copy_dst].set(b["v"][:, copy_src], mode="drop")
            fb = fr[key]
            row_k = jnp.take_along_axis(fb["ck"], rk, axis=2)[:, :, 0]
            row_v = jnp.take_along_axis(fb["cv"], rk, axis=2)[:, :, 0]
            logits = jnp.where(parent_hit[:, None], fb["plog"], b["logits"])
            logits = jnp.where(
                committed[:, None], fb["clog"][idx, crank], logits
            ).astype(b["logits"].dtype)
            sub[key] = dict(
                b,
                k=pk.at[:, wb, off].set(row_k, mode="drop"),
                v=pv.at[:, wb, off].set(row_v, mode="drop"),
                logits=logits,
            )
        sub = self._paged_catch_up(sub, target, r, s_max)
        return self._put_rows(aux, rows, sub), hit
