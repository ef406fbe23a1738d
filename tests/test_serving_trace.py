"""What the search service shows of itself to a trace: ring programs under
their own names, the stages of a master tick as ``named_scope``s in the
compiled ``serve_segment``, the per-request timeline of the fused path, and
the attended-positions counter."""

import re

import jax.numpy as jnp
import pytest

from repro.core.batched_async_search import TICK_SCOPES
from repro.serving import SearchService

from test_serving_continuous import PROMPTS, _service, _spec, _tiny_lm

PROGRAMS = ("serve_segment", "stage", "run_segment", "admit", "evict",
            "result")


@pytest.fixture(scope="module")
def tiny_lm():
    return _tiny_lm()


def _lowered(svc, name):
    """``name``'s program lowered at the arguments the service gives it."""
    svc._ensure_engine()
    w, carry, row = svc._weights, svc._carry, jnp.asarray([0], jnp.int32)
    roots = svc._root_rows([PROMPTS[0]])
    key = jnp.zeros((1, 2), jnp.uint32)
    args = {
        "serve_segment": (svc._serve_fn, (carry, svc._ring, svc._row_req_dev)),
        "stage": (svc._stage_fn, (carry, svc._ring, roots, key, row)),
        "run_segment": (svc._segment, (carry,)),
        "admit": (svc._admit_fn, (carry, row, roots, key)),
        "evict": (svc._evict_fn, (carry, row)),
        "result": (svc._result_fn, (carry,)),
    }
    fn, rest = args[name]
    return fn.lower(w, *rest)


@pytest.mark.parametrize("name", PROGRAMS)
def test_ring_programs_carry_their_names(tiny_lm, name):
    svc = _service(tiny_lm, False)
    assert f"module @jit_{name} " in _lowered(svc, name).as_text()


@pytest.fixture(scope="module")
def segment_op_names(tiny_lm):
    """Every ``op_name`` in the compiled ``serve_segment`` of the dense
    cached evaluator."""
    hlo = _lowered(_service(tiny_lm, False), "serve_segment").compile()
    return set(re.findall(r'op_name="([^"]*)"', hlo.as_text()))


@pytest.mark.parametrize("scope", TICK_SCOPES)
def test_tick_scopes_reach_the_compiled_segment(segment_op_names, scope):
    assert any(scope in name.split("/") for name in segment_op_names)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_request_timeline_is_ordered(tiny_lm, paged):
    """Every answered request: submit <= staged <= admitted <= settled <=
    answered, its ticks in the row are settle - admit, and the first B
    requests, staged before the first segment, are admitted at tick 0."""
    svc = _service(tiny_lm, paged)
    ids = [svc.submit(p) for p in PROMPTS]
    results = svc.drain()
    timeline = svc.timeline
    assert sorted(timeline) == ids
    for rid, r in timeline.items():
        assert r.submit <= r.staged <= r.admitted <= r.settled <= r.answered
        assert r.settle_tick - r.admit_tick == int(results[rid].ticks)
    assert [timeline[i].admit_tick for i in ids[:svc.spec.batch]] == [0, 0]
    assert max(r.settle_tick for r in timeline.values()) <= svc.stats.ticks
    queue = sum(r.admitted - r.submit for r in timeline.values())
    answer = sum(r.answered - r.settled for r in timeline.values())
    assert svc.stats.queue_wait_us == pytest.approx(queue * 1e6, abs=len(ids))
    assert svc.stats.answer_wait_us == pytest.approx(answer * 1e6,
                                                     abs=len(ids))


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "host"])
def test_attended_positions_after_one_tick(tiny_lm, fused):
    """One tick over two freshly admitted rows: every slot is refilled at
    its root (cache length = prompt length) and fed one token, so the
    decode attended each slot's length plus one position, which is the sum
    of the evaluator's cache lengths after the tick."""
    cfg, params = tiny_lm
    svc = SearchService(cfg, params, _spec(), top_k=4, max_len=12,
                        eos_token=1, ticks_per_round=1, ticks_per_segment=1,
                        fused=fused)
    prompts = PROMPTS[1:3]
    for p in prompts:
        svc.submit(p)
    svc.poll()
    assert svc.stats.ticks == 1
    w = svc.spec.wave_size
    lens = svc.evaluator.aux_len(svc._carry[7])
    assert svc.stats.attended_positions == int(jnp.sum(lens))
    assert svc.stats.attended_positions == w * sum(len(p) + 1
                                                   for p in prompts)
