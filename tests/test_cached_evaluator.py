"""CachedModelEvaluator: decode-cache correctness against the full forward.

Three claim families (ISSUE 5 satellite):

* **logits parity** — the logits a slot sees from its KV-cached
  ``decode_step`` chain equal (fp tolerance) the full-prefix ``forward`` the
  uncached :class:`~repro.core.evaluators.ModelEvaluator` runs, across
  ragged slot depths and after every tick of a chain;
* **prefix-rollback refill** — re-syncing a slot cache onto a new tree path
  via :meth:`refill_aux` (roll ``len`` back to the common prefix, decode
  the divergent suffix) is equivalent to a fresh re-prefill of that path,
  and decodes only the divergent suffix;
* **cache-depth invariant** — inside the real async engines (trace mode),
  every busy slot's ``cache['len']`` equals its token prefix length at
  every master tick, across settle/refill;
* **column refill** — a refill addressed by a :class:`SlotColumn` (the
  batched engine's slot ``j`` of every tree) is bit-identical to one
  addressed by the equivalent index array, on every cached evaluator, and
  the engine's master tick moves slot-cache rows by slice, never by a
  gather or scatter over the whole cache.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import (
    CachedModelEvaluator,
    FrontierModelEvaluator,
    ModelEvaluator,
    PagedCachedModelEvaluator,
    PagedFrontierModelEvaluator,
    SearchSpec,
    build_searcher,
)
from repro.core.batched_async_search import BatchedAsyncEngine
from repro.core.evaluators import EXPAND, FREE, SIM, SlotColumn
from repro.envs.token_env import TokenEnvState, make_token_env
from repro.models import init_params

TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.fixture(scope="module")
def lm():
    cfg = dataclasses.replace(
        get_reduced("llama3-8b"), vocab_size=64, num_layers=2,
        d_model=32, num_heads=2, num_kv_heads=1, head_dim=16, d_ff=64,
    )
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _ragged_states(max_len=16, lengths=(3, 5, 9), seed=7) -> TokenEnvState:
    n = len(lengths)
    toks = jax.random.randint(
        jax.random.PRNGKey(seed), (n, max_len), 2, 60, jnp.int32
    )
    pos = jnp.arange(max_len)
    lengths = jnp.asarray(lengths, jnp.int32)
    return TokenEnvState(
        tokens=jnp.where(pos[None, :] < lengths[:, None], toks, 0),
        length=lengths,
        done=jnp.zeros((n,), jnp.bool_),
    )


def _scfg():
    return SearchSpec(gamma=1.0, max_sim_steps=8).config


# ---------------------------------------------------------------------------
# Logits parity: decode_step chain vs full-prefix forward.
# ---------------------------------------------------------------------------


def test_init_aux_logits_match_full_forward(lm):
    cfg, params = lm
    ev_c = CachedModelEvaluator(cfg, params, top_k=4, eos_token=1)
    ev_u = ModelEvaluator(cfg, params, top_k=4, eos_token=1)
    state = _ragged_states()
    aux = ev_c.init_aux(state, (state.length.shape[0], 1))
    full = ev_u._position_logits(params, cfg, state.tokens, state.length)
    np.testing.assert_allclose(
        np.asarray(aux["pol"]["logits"], np.float32),
        np.asarray(full, np.float32), **TOL,
    )
    np.testing.assert_array_equal(np.asarray(aux["len"]), np.asarray(state.length))


def test_tick_chain_matches_uncached_evaluator(lm):
    """Chain SIM ticks: cached and uncached evaluators must produce the same
    transitions (same sampled tokens given the same keys — their logits agree
    to fp tolerance) and the cached logits must track the full forward."""
    cfg, params = lm
    ev_c = CachedModelEvaluator(cfg, params, top_k=4, eos_token=1)
    ev_u = ModelEvaluator(cfg, params, top_k=4, eos_token=1)
    scfg = _scfg()

    state_c = state_u = _ragged_states()
    n = state_c.length.shape[0]
    aux = ev_c.init_aux(state_c, (n, 1))
    kind = jnp.full((n,), SIM, jnp.int32)
    act = jnp.zeros((n,), jnp.int32)
    def carry0():
        return dict(
            rollout_done=jnp.zeros((n,), jnp.bool_),
            acc=jnp.zeros((n,), jnp.float32),
            disc=jnp.ones((n,), jnp.float32),
            steps=jnp.zeros((n,), jnp.int32),
        )

    cc, cu = carry0(), carry0()
    for step in range(4):
        keys = jax.random.split(jax.random.PRNGKey(step), n)
        (state_c, r_c, d_c, acc, disc, stp, rdone), aux = ev_c.tick(
            scfg, kind, act, state_c, cc["rollout_done"], cc["acc"],
            cc["disc"], cc["steps"], keys, aux,
        )
        cc = dict(rollout_done=rdone, acc=acc, disc=disc, steps=stp)
        (state_u, r_u, d_u, acc, disc, stp, rdone), _ = ev_u.tick(
            scfg, kind, act, state_u, cu["rollout_done"], cu["acc"],
            cu["disc"], cu["steps"], keys,
        )
        cu = dict(rollout_done=rdone, acc=acc, disc=disc, steps=stp)
        np.testing.assert_array_equal(
            np.asarray(state_c.tokens), np.asarray(state_u.tokens),
            err_msg=f"step {step}: cached/uncached sampled different tokens",
        )
        np.testing.assert_allclose(
            np.asarray(r_c, np.float32), np.asarray(r_u, np.float32), **TOL
        )
        # The stored logits equal the full-prefix forward at the new state.
        full = ev_u._position_logits(
            params, cfg, state_c.tokens, state_c.length
        )
        live = ~np.asarray(state_c.done)
        np.testing.assert_allclose(
            np.asarray(aux["pol"]["logits"], np.float32)[live],
            np.asarray(full, np.float32)[live], **TOL,
        )
        np.testing.assert_array_equal(
            np.asarray(aux["len"])[live], np.asarray(state_c.length)[live]
        )


def test_distinct_reward_model_cached(lm):
    """A distinct reward model rides a second cache; rewards must match the
    uncached evaluator's full-forward reward logits."""
    cfg, params = lm
    rew_params = init_params(cfg, jax.random.PRNGKey(9))
    ev_c = CachedModelEvaluator(
        cfg, params, top_k=4, eos_token=1, reward_params=rew_params
    )
    ev_u = ModelEvaluator(
        cfg, params, top_k=4, eos_token=1, reward_params=rew_params
    )
    scfg = _scfg()
    state = _ragged_states()
    n = state.length.shape[0]
    aux = ev_c.init_aux(state, (n, 1))
    kind = jnp.full((n,), SIM, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(3), n)
    common = (jnp.zeros((n,), jnp.bool_), jnp.zeros((n,), jnp.float32),
              jnp.ones((n,), jnp.float32), jnp.zeros((n,), jnp.int32))
    (st_c, r_c, *_), aux = ev_c.tick(
        scfg, kind, jnp.zeros((n,), jnp.int32), state, *common, keys, aux
    )
    (st_u, r_u, *_), _ = ev_u.tick(
        scfg, kind, jnp.zeros((n,), jnp.int32), state, *common, keys
    )
    np.testing.assert_array_equal(np.asarray(st_c.tokens), np.asarray(st_u.tokens))
    np.testing.assert_allclose(
        np.asarray(r_c, np.float32), np.asarray(r_u, np.float32), **TOL
    )


# ---------------------------------------------------------------------------
# Prefix-rollback refill.
# ---------------------------------------------------------------------------


def _run_sim_ticks(ev, scfg, state, aux, steps, seed=11):
    n = state.length.shape[0]
    kind = jnp.full((n,), SIM, jnp.int32)
    rdone = jnp.zeros((n,), jnp.bool_)
    acc = jnp.zeros((n,), jnp.float32)
    disc = jnp.ones((n,), jnp.float32)
    stp = jnp.zeros((n,), jnp.int32)
    for s in range(steps):
        keys = jax.random.split(jax.random.PRNGKey(seed + s), n)
        (state, _, _, acc, disc, stp, rdone), aux = ev.tick(
            scfg, kind, jnp.zeros((n,), jnp.int32), state, rdone, acc, disc,
            stp, keys, aux,
        )
    return state, aux


def test_refill_rollback_matches_fresh_prefill(lm):
    """Roll a deep cache back onto a shallower divergent path: the result
    must equal a fresh init_aux at that path (logits + len)."""
    cfg, params = lm
    ev = CachedModelEvaluator(cfg, params, top_k=4, eos_token=1)
    scfg = _scfg()
    start = _ragged_states(lengths=(4, 4, 4))
    n = 3
    state, aux = _run_sim_ticks(ev, scfg, start, ev.init_aux(start, (n, 1)), 5)

    # New paths: row 0 shares prefix 4 + diverges after 2 rollout tokens;
    # row 1 rolls clean back to the prompt; row 2 a disjoint path (the
    # re-prefill fallback).
    new_tokens = np.asarray(state.tokens).copy()
    new_len = np.asarray([6, 4, 5])
    new_tokens[0, 6:] = 0
    new_tokens[1, 4:] = 0
    new_tokens[2] = 0
    new_tokens[2, :5] = [7, 11, 13, 17, 19]
    new_state = TokenEnvState(
        tokens=jnp.asarray(new_tokens, jnp.int32),
        length=jnp.asarray(new_len, jnp.int32),
        done=jnp.zeros((n,), jnp.bool_),
    )
    rows = jnp.arange(n)
    aux2, _ = ev.refill_aux(scfg, aux, rows, new_state, jnp.ones((n,), jnp.bool_))
    fresh = ev.init_aux(new_state, (n, 1))
    np.testing.assert_array_equal(np.asarray(aux2["len"]), new_len)
    np.testing.assert_allclose(
        np.asarray(aux2["pol"]["logits"], np.float32),
        np.asarray(fresh["pol"]["logits"], np.float32), **TOL,
    )
    # The caches agree wherever rows are valid (< len): decode from both.
    nxt = jnp.asarray([21, 23, 25], jnp.int32)
    l1, _ = ev.decode_fn(params, cfg, nxt, dict(aux2["pol"]["cache"], len=aux2["len"]))
    l2, _ = ev.decode_fn(params, cfg, nxt, dict(fresh["pol"]["cache"], len=fresh["len"]))
    np.testing.assert_allclose(
        np.asarray(l1, np.float32), np.asarray(l2, np.float32), **TOL
    )


@pytest.mark.parametrize("refill_chunk,expect_calls", [(1, 2), (2, 1), (8, 1)])
def test_refill_catches_up_in_chunks(lm, refill_chunk, expect_calls):
    """The rollback catch-up runs ceil(max divergence / refill_chunk)
    batched ``decode_chunk`` calls (counted with a traced callback) — one
    dispatch per chunk, not one per divergent token, and never the full
    re-prefill."""
    cfg, params = lm
    calls = []
    from repro.models import decode_chunk

    def counting_chunk(p, c, t, target, cache):
        jax.debug.callback(lambda: calls.append(1))
        return decode_chunk(p, c, t, target, cache)

    ev = CachedModelEvaluator(
        cfg, params, top_k=4, eos_token=1,
        chunk_fn=counting_chunk, refill_chunk=refill_chunk,
    )
    scfg = _scfg()
    start = _ragged_states(lengths=(10, 10))
    aux = ev.init_aux(start, (2, 1))
    # Row 0: same path, one token shorter (the settle→parent refill shape):
    # only the final prompt token re-decodes.  Row 1: diverges at position 7
    # → max divergence 2 tokens.
    new_tokens = np.asarray(start.tokens).copy()
    new_tokens[0, 9:] = 0
    new_tokens[1, 7] = 61
    new_tokens[1, 9:] = 0
    new_state = TokenEnvState(
        tokens=jnp.asarray(new_tokens, jnp.int32),
        length=jnp.asarray([9, 9], jnp.int32),
        done=jnp.zeros((2,), jnp.bool_),
    )
    calls.clear()
    aux2, _ = ev.refill_aux(
        scfg, aux, jnp.arange(2), new_state, jnp.ones((2,), jnp.bool_)
    )
    jax.effects_barrier()
    assert len(calls) == expect_calls, len(calls)
    np.testing.assert_array_equal(np.asarray(aux2["len"]), [9, 9])
    # The chunked catch-up lands on the same logits a fresh prefill gives.
    fresh = ev.init_aux(new_state, (2, 1))
    np.testing.assert_allclose(
        np.asarray(aux2["pol"]["logits"], np.float32),
        np.asarray(fresh["pol"]["logits"], np.float32), **TOL,
    )


# ---------------------------------------------------------------------------
# Engine integration: cache depth tracks slot depth across settle/refill.
# ---------------------------------------------------------------------------


def _token_search_pieces(lm, max_len=14, top_k=4):
    cfg, params = lm
    env = make_token_env(
        cfg, params, jnp.asarray([3, 5, 7], jnp.int32), max_len=max_len,
        top_k=top_k, eos_token=1,
    )
    ev = CachedModelEvaluator(cfg, params, top_k=top_k, eos_token=1)
    return env, ev


@pytest.mark.parametrize("batch", [0, 3])
def test_cache_len_tracks_slot_depth_under_trace(lm, batch):
    """ISSUE invariant: at every master tick, every busy slot of every
    still-running tree has cache['len'] == its token prefix length — the
    settle/refill rollback machinery never desyncs cache and state."""
    from repro.core.async_search import run_async_search
    from repro.core.batched_async_search import run_async_search_batched

    env, ev = _token_search_pieces(lm)
    spec = SearchSpec(
        algo="wu_uct", engine="async", batch=batch, num_simulations=10,
        wave_size=3, max_depth=5, max_sim_steps=5, max_width=4, gamma=1.0,
    )
    cfg = spec.config
    T = cfg.num_simulations
    trace_bound = 4 * T  # generous static bound
    key = jax.random.PRNGKey(0)
    if batch:
        roots = jax.vmap(env.init)(jax.random.split(key, batch))
        rngs = jax.random.split(jax.random.PRNGKey(1), batch)
        fn = jax.jit(functools.partial(
            run_async_search_batched, env, cfg, trace_ticks=trace_bound,
            evaluator=ev,
        ))
        res, trace = fn(roots, rngs)
        t_done = np.asarray(trace.t_done)            # [K, B]
    else:
        fn = jax.jit(functools.partial(
            run_async_search, env, cfg, trace_ticks=trace_bound, evaluator=ev,
        ))
        res, trace = fn(env.init(key), key)
        t_done = np.asarray(trace.t_done)[:, None]   # [K, 1]

    kind = np.asarray(trace.kind).reshape(t_done.shape[0], t_done.shape[1], -1)
    state_len = np.asarray(trace.state_len).reshape(kind.shape)
    cache_len = np.asarray(trace.cache_len).reshape(kind.shape)
    # alive is [K] for the single engine, [K, B] (per-tree) for the batched.
    alive = np.asarray(trace.alive).reshape(t_done.shape[0], -1)

    assert alive.any() and not alive.all(), "trace bound too tight"
    checked = 0
    for k in range(kind.shape[0]):
        if not alive[k].any():
            break
        for b in range(kind.shape[1]):
            if not alive[k, b % alive.shape[1]] or t_done[k, b] >= T:
                # This tree finished: its slots are frozen while the shared
                # aux keeps ticking, so the invariant only binds live trees.
                continue
            busy = kind[k, b] != FREE
            np.testing.assert_array_equal(
                cache_len[k, b][busy], state_len[k, b][busy],
                err_msg=f"tick {k} tree {b}: cache len != slot prefix len",
            )
            checked += busy.sum()
    assert checked > 0


def test_cached_search_one_prefill_then_decodes_only(lm):
    """The headline claim: after the single root prefill, the whole search
    runs on decode steps — the full-prefix forward is never entered."""
    cfg, params = lm
    from repro.models import decode_step, prefill_ragged

    prefills, decodes = [], []

    def counting_prefill(p, c, t, l, cache):
        jax.debug.callback(lambda: prefills.append(1))
        return prefill_ragged(p, c, t, l, cache)

    def counting_decode(p, c, t, cache):
        jax.debug.callback(lambda: decodes.append(1))
        return decode_step(p, c, t, cache)

    env = make_token_env(
        cfg, params, jnp.asarray([3, 5, 7], jnp.int32), max_len=14,
        top_k=4, eos_token=1,
    )
    ev = CachedModelEvaluator(
        cfg, params, top_k=4, eos_token=1,
        decode_fn=counting_decode, prefill_fn=counting_prefill,
    )
    spec = SearchSpec(
        algo="wu_uct", engine="async", num_simulations=10, wave_size=3,
        max_depth=5, max_sim_steps=5, max_width=4, gamma=1.0,
    )
    search = build_searcher(env, spec, evaluator=ev)
    key = jax.random.PRNGKey(0)
    res = jax.block_until_ready(search(env.init(key), key))
    jax.effects_barrier()
    assert len(prefills) == 1, len(prefills)
    # ≥ one decode per master tick (tick batch) plus refill catch-ups —
    # but O(ticks), never O(ticks·depth).
    assert len(decodes) >= int(res.ticks)
    assert int(res.tree_size) > 1


def test_cached_matches_uncached_end_to_end(lm):
    """Full async searches, cached vs uncached evaluator, same seeds: the
    logits agree to fp tolerance, so every discrete search decision (visits,
    tree shape, chosen action) matches on this seeded case and the value
    statistics agree to fp tolerance."""
    cfg, params = lm
    env, ev = _token_search_pieces(lm)
    ev_u = ModelEvaluator(cfg, params, top_k=4, eos_token=1)
    spec = SearchSpec(
        algo="wu_uct", engine="async", num_simulations=12, wave_size=4,
        max_depth=5, max_sim_steps=5, max_width=4, gamma=1.0,
    )
    key = jax.random.PRNGKey(2)
    root = env.init(key)
    res_c = build_searcher(env, spec, evaluator=ev)(root, key)
    res_u = build_searcher(env, spec, evaluator=ev_u)(root, key)
    for f in ("action", "root_n", "tree_size", "ticks", "overflowed"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res_c, f)), np.asarray(getattr(res_u, f)),
            err_msg=f"field {f}",
        )
    np.testing.assert_allclose(
        np.asarray(res_c.root_v), np.asarray(res_u.root_v), **TOL
    )


def test_cached_evaluator_rejects_wave_engine(lm):
    cfg, params = lm
    env, ev = _token_search_pieces(lm)
    with pytest.raises(ValueError, match="async"):
        build_searcher(env, SearchSpec(algo="wu_uct", engine="wave"),
                       evaluator=ev)


def test_cached_evaluator_rejects_recurrent_families():
    cfg = dataclasses.replace(
        get_reduced("mamba2-2.7b"), vocab_size=64, num_layers=1, d_model=64,
    )
    with pytest.raises(ValueError, match="recurrent"):
        CachedModelEvaluator(cfg, {}, top_k=4)


# ---------------------------------------------------------------------------
# Column refill: SlotColumn rows equal index-array rows, and stay slices.
# ---------------------------------------------------------------------------

_PAGED = dict(block_size=4, num_blocks=96)
COLUMN_EVALUATORS = {
    "dense": lambda c, p: CachedModelEvaluator(c, p, top_k=4, eos_token=1),
    "paged": lambda c, p: PagedCachedModelEvaluator(
        c, p, top_k=4, eos_token=1, **_PAGED
    ),
    "frontier": lambda c, p: FrontierModelEvaluator(
        c, p, top_k=4, eos_token=1
    ),
    "paged_frontier": lambda c, p: PagedFrontierModelEvaluator(
        c, p, top_k=4, eos_token=1, **_PAGED
    ),
}


def _tick_all(ev, scfg, state, aux, kind, act, seed):
    n = state.length.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    (state, *_), aux = ev.tick(
        scfg, jnp.full((n,), kind, jnp.int32), act, state,
        jnp.zeros((n,), jnp.bool_), jnp.zeros((n,), jnp.float32),
        jnp.ones((n,), jnp.float32), jnp.zeros((n,), jnp.int32), keys, aux,
    )
    return state, aux


@pytest.mark.parametrize("j", [0, 2])
@pytest.mark.parametrize("kind", sorted(COLUMN_EVALUATORS))
def test_slot_column_refill_matches_index_rows(lm, kind, j):
    """``refill_aux`` over ``SlotColumn(j, W)`` equals ``refill_aux`` over
    ``arange(B) * W + j`` bit for bit: the whole returned aux and the hit
    mask, with one row masked out and, for the frontier evaluators, a
    parent hit and a child hit among the rest."""
    cfg, params = lm
    ev = COLUMN_EVALUATORS[kind](cfg, params)
    scfg = _scfg()
    b, w = 3, 4
    n = b * w
    roots = _ragged_states(lengths=(4, 6, 5))
    aux = ev.init_aux(roots, (b, w))
    parent = jax.tree.map(lambda x: jnp.repeat(x, w, axis=0), roots)
    # Every slot expands its own child (the frontier snapshot), then rolls
    # out one more token, so the rows of a column hold distinct caches.
    child, aux = _tick_all(
        ev, scfg, parent, aux, EXPAND, jnp.arange(n) % ev.top_k, seed=0
    )
    deeper, aux = _tick_all(
        ev, scfg, child, aux, SIM, jnp.zeros((n,), jnp.int32), seed=1
    )
    rows = jnp.arange(b) * w + j
    # Tree 0 goes back to its parent, tree 1 is masked out, tree 2 moves to
    # its expanded child.
    new_state = jax.tree.map(
        lambda p_, d, c: jnp.stack([p_[rows[0]], d[rows[1]], c[rows[2]]]),
        parent, deeper, child,
    )
    mask = jnp.asarray([True, False, True])

    @functools.partial(jax.jit, static_argnums=2)
    def refill(aux, j, column):
        at = SlotColumn(j, w) if column else jnp.arange(b) * w + j
        return ev.refill_aux(scfg, aux, at, new_state, mask)

    want = refill(aux, jnp.int32(j), False)
    got = refill(aux, jnp.int32(j), True)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    jax.tree.map(np.testing.assert_array_equal, got, want)
    if "frontier" in kind:
        np.testing.assert_array_equal(np.asarray(got[1]), [True, False, True])


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def test_batched_refill_slices_the_slot_cache(lm):
    """The batched engine's master tick (``BatchedAsyncEngine.step``) with
    the dense cached evaluator reads and writes refill rows by slice: no
    gather or scatter takes an operand shaped like the slot cache.  (On the
    TPU a row gather first copies the whole operand, the whole KV cache per
    refilled column.)"""
    env, ev = _token_search_pieces(lm)
    b = 3
    spec = SearchSpec(
        algo="wu_uct", engine="async", batch=b, num_simulations=12,
        wave_size=4, max_depth=5, max_sim_steps=5, max_width=4, gamma=1.0,
    )
    engine = BatchedAsyncEngine(env, spec.config, b, evaluator=ev)
    roots = jax.vmap(env.init)(jax.random.split(jax.random.PRNGKey(2), b))
    carry = engine.init_carry(roots, jax.random.split(jax.random.PRNGKey(1), b))
    cache = carry[-2]["pol"]["cache"]["kv"]["k"]
    grid = cache.shape[:1] + (b, engine.W) + cache.shape[2:]
    eqns = list(_eqns(jax.make_jaxpr(engine.step)(carry).jaxpr))

    def shapes(eqn):
        return [getattr(v.aval, "shape", None) for v in eqn.invars]

    moved = [
        e.primitive.name for e in eqns
        if (e.primitive.name == "gather"
            or e.primitive.name.startswith("scatter"))
        and cache.shape in shapes(e)
    ]
    assert not moved, moved
    # The refill's column slices are there: one write-back per cache leaf.
    writes = [
        e for e in eqns
        if e.primitive.name == "dynamic_update_slice" and grid in shapes(e)
    ]
    assert len(writes) == 2, len(writes)
