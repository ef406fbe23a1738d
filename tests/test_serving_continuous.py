"""Continuous batching for the search service + serving-layer bug sweep.

Tentpole coverage: the persistent :class:`BatchedAsyncEngine` behind
``SearchService.submit/poll/drain/serve`` — a ragged-arrival workload with
more requests than tree rows drains with per-request results, occupancy
counters stay sane, paged pools leak nothing, and (the load-bearing claim)
a request admitted into a recycled row mid-``while_loop`` reaches exactly
the search a fresh batch would have given it.

Satellite coverage: over-long prompt rejection (named error, dense +
paged), ``ServingEngine.run`` slot reuse under request pressure,
``decide``'s invalid-action surfacing, the benchmark-baseline lookup
(env override + warn-once fallback), and the trace-mode occupancy
counters.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import PolicyConfig, SearchConfig, SearchSpec
from repro.core.batched_async_search import run_async_search_batched
from repro.envs import make_bandit_tree
from repro.models import init_params
from repro.serving import (
    InvalidSearchActionError,
    PromptTooLongError,
    SearchService,
    ServeConfig,
    ServingEngine,
)


def _tiny_lm(vocab=64):
    cfg = dataclasses.replace(
        get_reduced("llama3-8b"), vocab_size=vocab, num_layers=1,
        d_model=32, num_heads=2, num_kv_heads=1, head_dim=16, d_ff=64,
    )
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tiny_lm():
    return _tiny_lm()


def _spec(batch=2):
    return SearchSpec(
        algo="wu_uct", engine="async", batch=batch, num_simulations=6,
        wave_size=2, max_depth=3, max_sim_steps=3, max_width=4, gamma=1.0,
    )


def _service(tiny_lm, paged, **kw):
    cfg, params = tiny_lm
    kw.setdefault("ticks_per_round", 4)
    return SearchService(
        cfg, params, _spec(), top_k=4, max_len=12, eos_token=1,
        paged=paged, block_size=4, **kw,
    )


PROMPTS = [[3, 5], [2, 9, 4], [7], [1, 2, 3], [5, 5], [6]]


# ---------------------------------------------------------------------------
# Tentpole: continuous serving through the persistent engine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_ragged_arrival_drains_with_per_request_results(tiny_lm, paged):
    """R = 3*B ragged arrivals all finish, each with its own result row."""
    svc = _service(tiny_lm, paged)
    rows = svc.serve(PROMPTS)
    assert len(rows) == len(PROMPTS)
    for r in rows:
        assert 0 <= int(r.action) < 4
        # A per-request row, not a batch: scalar action, [A] visit counts.
        assert r.action.ndim == 0 and r.root_n.shape == (4,)
        assert float(jnp.sum(r.root_n)) > 0
    st = svc.stats
    assert st.submitted == st.completed == st.admissions == len(PROMPTS)
    assert st.ticks > 0
    assert 0.0 <= st.slot_idle_frac < 1.0
    if paged:
        # Every drained request returned its pages: the pool is whole again.
        aux = svc._carry[7]
        assert int(jnp.sum(np.asarray(aux["refcount"]) > 0)) == 0


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_mid_run_admission_matches_fresh_batch(tiny_lm, paged):
    """A request spliced into a recycled row mid-while_loop must reach the
    same search as a fresh batch seeded with the same key: same action and
    (bit-exact here) the same root visit mass.  This is the engine-parity
    acceptance gate — admission fully re-seeds the row (tree, RNG lane,
    evaluator slot caches), so history cannot bleed into the new search."""
    cfg, params = tiny_lm
    keys = [jax.random.fold_in(jax.random.PRNGKey(42), i) for i in range(4)]
    svc = _service(tiny_lm, paged)
    rows = svc.serve(PROMPTS[:4], keys=keys)  # requests 2,3 admitted mid-run

    oracle = _service(tiny_lm, paged)
    res = oracle._search(oracle._roots(PROMPTS[2:4]), jnp.stack(keys[2:4]))
    for i, b in ((2, 0), (3, 1)):
        fresh = jax.tree.map(lambda x: x[b], res)
        assert int(rows[i].action) == int(fresh.action)
        np.testing.assert_allclose(
            np.asarray(rows[i].root_n), np.asarray(fresh.root_n), atol=1e-6
        )


def test_submit_poll_drain_incremental(tiny_lm):
    """The lower-level API: submit returns ids, poll makes progress,
    results accumulate, and late submissions reuse settled rows."""
    svc = _service(tiny_lm, paged=False)
    ids = [svc.submit(p) for p in PROMPTS[:3]]
    assert ids == [0, 1, 2]
    res = svc.drain()
    assert set(res) == {0, 1, 2}
    # The engine persists: another wave drains into the same carry.
    more = [svc.submit(p) for p in PROMPTS[3:]]
    res = svc.drain()
    assert set(res) == set(ids) | set(more)
    assert svc.stats.completed == len(PROMPTS)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "host_paced"])
def test_compiled_segment_text(tiny_lm, fused):
    """The segment program ``poll`` runs can be compiled on demand for
    inspection, before any request, and serving carries on after it."""
    svc = _service(tiny_lm, paged=False, fused=fused)
    assert "HloModule" in svc.compiled_segment_text()
    rows = svc.serve(PROMPTS[:2])
    assert [0 <= int(r.action) < 4 for r in rows] == [True, True]


def test_continuous_serving_needs_async_engine(tiny_lm):
    cfg, params = tiny_lm
    svc = SearchService(
        cfg, params,
        SearchSpec(algo="wu_uct", engine="wave", batch=2, num_simulations=4,
                   wave_size=2, max_depth=3, max_sim_steps=3, max_width=4,
                   gamma=1.0),
        top_k=4, max_len=12, eos_token=1,
    )
    svc.submit([3, 5])
    with pytest.raises(ValueError, match="async"):
        svc.drain()


# ---------------------------------------------------------------------------
# Satellite: over-long prompts rejected with a named error
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_serving_engine_rejects_over_long_prompt(paged):
    cfg = get_reduced("llama3-8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(
        cfg, params,
        ServeConfig(batch_slots=2, max_len=8, eos_token=1,
                    paged=paged, block_size=4),
    )
    # len == max_len is already too long: the slot must fit the prompt PLUS
    # at least one generated token.
    with pytest.raises(PromptTooLongError, match="max_len"):
        engine.add_requests([[2, 3], list(range(2, 10))])
    # The batch was rejected atomically — no slot was consumed.
    assert not engine.active.any()
    if paged:
        assert engine.blocks_in_use() == 0
    with pytest.raises(ValueError, match="empty"):
        engine.add_requests([[]])
    # In-range prompts still admit afterwards.
    assert engine.add_requests([[2, 3, 4]]) == [0]


def test_search_service_rejects_over_long_prompt(tiny_lm):
    svc = _service(tiny_lm, paged=False)  # max_len=12
    with pytest.raises(PromptTooLongError):
        svc.submit(list(range(2, 14)))
    with pytest.raises(PromptTooLongError):
        svc.search([list(range(2, 14))], jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Satellite: ServingEngine.run slot reuse under request pressure
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_serving_engine_run_reuses_slots(paged):
    """R > batch_slots: freed slots serve later requests, and every
    request's output matches a solo single-slot run of the same prompt
    (greedy decode is deterministic, so any cross-wiring or dropped
    request shows up as a mismatch)."""
    cfg = get_reduced("llama3-8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    sc = ServeConfig(batch_slots=2, max_len=24, eos_token=1,
                     paged=paged, block_size=4)
    rng = np.random.default_rng(3)
    prompts = [
        list(rng.integers(2, cfg.vocab_size, size=n)) for n in (4, 7, 5, 6, 3)
    ]
    engine = ServingEngine(cfg, params, sc)
    outs = engine.run(prompts, max_ticks=200)
    assert all(len(o) > 0 for o in outs)
    for prompt, out in zip(prompts, outs):
        solo = ServingEngine(
            cfg, params, dataclasses.replace(sc, batch_slots=1)
        )
        (ref,) = solo.run([prompt], max_ticks=200)
        assert out == ref
    if paged:
        # Zero leaked pages once every request has finished.
        assert engine.blocks_in_use() == 0
        assert (engine._table == engine.num_blocks).all()


# ---------------------------------------------------------------------------
# Satellite: decide surfaces invalid actions instead of clipping
# ---------------------------------------------------------------------------
def test_decide_surfaces_invalid_action(tiny_lm, monkeypatch):
    svc = _service(tiny_lm, paged=False)
    real = svc._search

    def bad_search(roots, rngs):
        res = real(roots, rngs)
        return res._replace(action=jnp.full_like(res.action, -1))

    monkeypatch.setattr(svc, "_search", bad_search)
    with pytest.raises(InvalidSearchActionError, match="-1"):
        svc.decide([[3, 5]], jax.random.PRNGKey(0))


def test_decide_ignores_padding_rows(tiny_lm, monkeypatch):
    """Out-of-range actions on PADDING rows (beyond the request count)
    must not trip the validation — only real requests are checked."""
    svc = _service(tiny_lm, paged=False)
    real = svc._search

    def pad_bad_search(roots, rngs):
        res = real(roots, rngs)
        return res._replace(action=res.action.at[-1].set(-1))

    monkeypatch.setattr(svc, "_search", pad_bad_search)
    tokens, _ = svc.decide([[3, 5]], jax.random.PRNGKey(0))
    assert len(tokens) == 1


# ---------------------------------------------------------------------------
# Satellite: benchmark-baseline lookup (env override + fallback warning)
# ---------------------------------------------------------------------------
def test_pool_blocks_env_override(tmp_path, monkeypatch):
    from repro.serving import search_service as ss

    base = tmp_path / "BENCH_model_eval.json"
    base.write_text(json.dumps({"rows": [
        {"kind": "batch_ceiling", "ceiling_ratio": 2.0},
        {"kind": "batch_ceiling", "ceiling_ratio": 4.0},
    ]}))
    monkeypatch.setenv(ss.BENCH_BASELINE_ENV, str(base))
    assert ss._bench_baseline_path() == base
    # dense = 4 slots * 4 pages = 16; worst ratio 2.0 -> 16/2*1.25+1 = 11.
    assert ss._prefix_sharing_pool_blocks(4, 32, 8) == 11


def test_pool_blocks_falls_back_with_warning(tmp_path, monkeypatch):
    from repro.serving import search_service as ss

    base = tmp_path / "BENCH_model_eval.json"
    base.write_text(json.dumps({"rows": [{"kind": "other"}]}))
    monkeypatch.setenv(ss.BENCH_BASELINE_ENV, str(base))
    monkeypatch.setattr(ss, "_pool_fallback_warned", False)
    with pytest.warns(UserWarning, match="batch_ceiling"):
        assert ss._prefix_sharing_pool_blocks(4, 32, 8) == 16
    # Warn-once: the second fallback is silent.
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        assert ss._prefix_sharing_pool_blocks(4, 32, 8) == 16


def test_pool_blocks_unparseable_baseline_warns(tmp_path, monkeypatch):
    from repro.serving import search_service as ss

    base = tmp_path / "BENCH_model_eval.json"
    base.write_text("{not json")
    monkeypatch.setenv(ss.BENCH_BASELINE_ENV, str(base))
    with pytest.warns(UserWarning, match="could not parse"):
        assert ss._prefix_sharing_pool_blocks(4, 32, 8) == 16


# ---------------------------------------------------------------------------
# Satellite: trace-mode occupancy counters
# ---------------------------------------------------------------------------
def test_trace_occupancy_counters():
    env = make_bandit_tree(depth=3, num_actions=3, seed=7)
    cfg = SearchConfig(
        num_simulations=8, wave_size=3, max_depth=5, max_sim_steps=4,
        max_width=3, gamma=0.95, policy=PolicyConfig(kind="wu_uct"),
        stat_mode="wu",
    )
    B, K = 3, 60
    roots = jax.vmap(env.init)(jax.random.split(jax.random.PRNGKey(0), B))
    rngs = jax.random.split(jax.random.PRNGKey(1), B)
    fn = jax.jit(functools.partial(
        run_async_search_batched, env, cfg, trace_ticks=K
    ))
    _, trace = fn(roots, rngs)
    busy = np.asarray(trace.busy_slots)
    active = np.asarray(trace.active_trees)
    alive = np.asarray(trace.alive)
    assert busy.shape == (K, B) and active.shape == (K,)
    assert (busy >= 0).all() and (busy <= cfg.wave_size).all()
    # Settled trees count zero busy slots; active_trees is the alive count.
    assert (busy[~alive] == 0).all()
    np.testing.assert_array_equal(active, alive.sum(axis=1))
    # The engine actually worked: some tick had every tree busy.
    assert busy.sum() > 0


# ---------------------------------------------------------------------------
# Device-resident serving ring: the fused poll round
# ---------------------------------------------------------------------------
def _frontier_evaluator(tiny_lm, paged):
    from repro.core.evaluators import (
        FrontierModelEvaluator,
        PagedFrontierModelEvaluator,
    )

    cfg, params = tiny_lm
    if paged:
        return PagedFrontierModelEvaluator(
            cfg, params, top_k=4, eos_token=1, block_size=4, num_blocks=48,
        )
    return FrontierModelEvaluator(cfg, params, top_k=4, eos_token=1)


@pytest.mark.parametrize(
    "mode", ["dense", "paged", "frontier", "paged_frontier"]
)
def test_fused_ring_matches_host_paced_poll(tiny_lm, mode):
    """Every request served through the device-resident loop is
    bit-identical to the PR 8 host-paced poll path.

    Both paths fully re-seed a row at admission (tree, RNG lane, evaluator
    aux) and every per-row computation is row-independent, so WHEN a row
    was admitted relative to the others must not matter — in-loop ring
    admission included.  Dense, paged, and both frontier evaluators.
    """
    paged = mode in ("paged", "paged_frontier")
    kw = {}
    if "frontier" in mode:
        kw["evaluator"] = _frontier_evaluator(tiny_lm, paged)
    keys = [
        jax.random.fold_in(jax.random.PRNGKey(11), i)
        for i in range(len(PROMPTS))
    ]
    rows_fused = _service(tiny_lm, paged, fused=True, **kw).serve(
        PROMPTS, keys=keys
    )
    rows_host = _service(tiny_lm, paged, fused=False, **kw).serve(
        PROMPTS, keys=keys
    )
    for rf, rh in zip(rows_fused, rows_host):
        assert int(rf.action) == int(rh.action)
        np.testing.assert_array_equal(
            np.asarray(rf.root_n), np.asarray(rh.root_n)
        )
        np.testing.assert_allclose(
            np.asarray(rf.root_v), np.asarray(rh.root_v), atol=1e-6
        )
        assert int(rf.ticks) == int(rh.ticks)


def test_ring_churn_zero_leaked_pages(tiny_lm):
    """2x the prompt set through B=2 rows and a 3-slot ring: every pool
    page staged by the ring or held by a slot is back (refcount zero), no
    allocation ever failed, and every page table dropped to the sentinel."""
    svc = _service(tiny_lm, True, ring_capacity=3)
    prompts = PROMPTS + PROMPTS
    rows = svc.serve(prompts)
    assert len(rows) == len(prompts)
    assert svc.stats.completed == len(prompts)
    aux = svc._carry[7]
    p = svc.evaluator.num_blocks
    assert int(jnp.sum(aux["refcount"])) == 0
    assert int(aux["oom"]) == 0
    assert bool(jnp.all(aux["table"] == p))
    assert bool(jnp.all(svc._ring.aux["table"] == p))
    assert bool(jnp.all(svc._ring.aux["len"] == 0))
    assert int(svc._ring.count) == 0
    # The fused path really ran: admissions all flowed through the ring,
    # each request staged before its row took it.
    assert svc.stats.admissions == len(prompts)
    timeline = svc.timeline
    assert sorted(timeline) == list(range(len(prompts)))
    assert all(r.staged <= r.admitted for r in timeline.values())


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "host"])
def test_priority_orders_admission(tiny_lm, fused):
    """submit(priority=...) admits higher priorities first, FIFO within a
    priority class — on both the ring staging and host-paced admission
    paths.  B=1 serializes requests, so completion order IS admission
    order."""
    cfg, params = tiny_lm
    svc = SearchService(
        cfg, params, _spec(batch=1), top_k=4, max_len=12, eos_token=1,
        ticks_per_round=4, fused=fused,
    )
    for i, pri in enumerate([0, 5, 1, 5]):
        svc.submit(PROMPTS[i], priority=pri)
    svc.drain()
    # ids 1 and 3 share the top priority (FIFO between them), then 2, then 0.
    assert list(svc._results.keys()) == [1, 3, 2, 0]
