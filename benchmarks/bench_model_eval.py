"""Model-backed evaluation throughput: decode-cached vs prefill-per-tick.

Two claims under test:

* ``ModelEvaluator`` (PR 4): every async master tick evaluates ALL ``[B·W]``
  in-flight slots with **one** batched full-prefix forward — vs the default
  rollout evaluation whose per-slot ``env.policy`` + ``env.step`` lower to
  three forwards per slot step.
* ``CachedModelEvaluator`` (PR 5): that one forward becomes a single
  batched ``decode_step`` against per-slot KV caches carried in the slot
  state — O(1) in prefix length instead of O(depth).  The ``--depth`` sweep
  makes the asymptotics visible: prefill-per-tick cost grows with
  ``max_depth`` (longer prefixes per forward) while the cached per-tick cost
  stays flat, so the speedup widens with depth.  (The early ``d8_B4``
  regression — cached slower than prefill at shallow depth — was refill
  catch-up dispatch: one ``decode_step`` launch per divergent token.  The
  chunked catch-up, one launch per ``refill_chunk`` tokens, removed it.)
* ``PagedCachedModelEvaluator`` (this PR): the dense ``[B·W, max_len]``
  slot caches become a shared block pool + page tables.  Per-tick cost must
  stay flat vs the dense cached rows, and the trace-mode
  ``blocks_in_use`` peak shows the real working set: sibling slots share
  prefix pages (copy-on-write), so the same HBM budget admits strictly more
  slots — the ``paged_ceiling`` rows derive that batch ceiling.

* ``FrontierModelEvaluator`` (this PR): EXPAND ticks score every candidate
  child in one tree-batched ``decode_frontier`` forward and snapshot the
  whole frontier into slot aux, so sibling/child refills are answered with
  ZERO model forwards.  The ``frontier_eval`` rows sweep the candidate
  width ``A`` against a MATCHED cached baseline (same env top-K / tree
  width) and report the absorbed refill hits from the trace counter.

* Continuous batching: a ragged-arrival request workload with
  ``R >> B`` drains through the persistent
  :class:`~repro.serving.SearchService` engine — settled tree rows are
  re-seeded with queued requests mid-``while_loop`` instead of idling until
  the batch's slowest search finishes.  The ``serving_eval`` rows report
  the host-paced poll path (requests/s, slot-idle fraction, host rounds);
  the ``serving_fused`` rows report the device-resident ring path
  (admission/eviction inside the jitted segment — one host sync per
  segment) with its host-round reduction; the
  ``serving_speedup`` rows compare the fused drain against the one-shot
  path serving the same workload in sequential ``B``-sized batches.

Rows: ``prefill_eval_d{d}_B{n}`` / ``cached_eval_d{d}_B{n}`` /
``paged_eval_d{d}_B{n}`` with derived searches/sec and per-tick µs,
``cached_speedup_d{d}_B{n}``, ``paged_ceiling_d{d}_B{n}`` (peak pool blocks
→ max B·W at the dense layout's HBM budget),
``frontier_eval_d{d}_B{n}_A{a}`` / ``frontier_speedup_d{d}_B{n}_A{a}``
(frontier vs matched-width cached decode),
``serving_eval_{mode}_B{n}`` / ``serving_fused_{mode}_B{n}`` /
``serving_speedup_{mode}_B{n}``
(continuous drain of ``R = 3·B`` ragged arrivals — host-paced poll, fused
ring, and fused-vs-sequential-one-shot — dense and paged), plus the PR-4
``rollout_eval`` baseline at the first depth.  Forward/decode counting is
asserted in ``tests/test_facade.py`` / ``tests/test_cached_evaluator.py``;
this file measures the wall-clock consequence.  ``benchmarks/run.py`` dumps
the same measurements machine-readably to ``BENCH_model_eval.json``.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

import functools

import numpy as np

from repro.configs import get_reduced
from repro.core import (
    CachedModelEvaluator,
    FrontierModelEvaluator,
    ModelEvaluator,
    PagedCachedModelEvaluator,
    SearchSpec,
    build_searcher,
)
from repro.envs.token_env import make_token_env
from repro.models import init_params, num_pages

from .common import row, time_fn

BATCH_SIZES = (1, 4)
DEPTHS = (8, 64)
PROMPT = (3, 5, 7)
BLOCK_SIZE = 8
FRONTIER_WIDTHS = (4, 16)


def _tiny_lm(vocab: int = 64):
    cfg = dataclasses.replace(
        get_reduced("llama3-8b"), vocab_size=vocab, num_layers=1,
        d_model=32, num_heads=2, num_kv_heads=1, head_dim=16, d_ff=64,
    )
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def run(
    num_simulations: int = 16,
    wave_size: int = 4,
    batch_sizes: tuple[int, ...] = BATCH_SIZES,
    top_k: int = 4,
    depths: tuple[int, ...] = DEPTHS,
    paged: bool = True,
    frontier_widths: tuple[int, ...] = FRONTIER_WIDTHS,
    serving_batch: int = 4,
    records: list | None = None,
) -> list[str]:
    cfg, params = _tiny_lm()
    prompt = jnp.asarray(PROMPT, jnp.int32)
    rows = []

    def record(name, seconds, B, depth, ticks, kind):
        per_tick = seconds / max(ticks, 1)
        if records is not None:
            records.append({
                "name": name, "kind": kind, "batch": B, "depth": depth,
                "seconds": seconds, "searches_per_sec": B / seconds,
                "ticks": ticks, "us_per_tick": per_tick * 1e6,
            })
        rows.append(
            row(name, seconds,
                f"{B / seconds:.2f} searches/s; {per_tick * 1e6:.0f} us/tick")
        )

    for di, depth in enumerate(depths):
        # Leave room for a full rollout below the deepest expansion.
        max_len = len(PROMPT) + 2 * depth + 2
        env = make_token_env(cfg, params, prompt, max_len=max_len,
                             top_k=top_k, eos_token=1)
        spec = SearchSpec(
            algo="wu_uct", engine="async", num_simulations=num_simulations,
            wave_size=wave_size, max_depth=depth, max_sim_steps=depth,
            max_width=top_k, gamma=1.0,
        )
        model_ev = ModelEvaluator(cfg, params, top_k=top_k, eos_token=1)
        cached_ev = CachedModelEvaluator(cfg, params, top_k=top_k, eos_token=1)

        for B in batch_sizes:
            bspec = spec._replace(batch=B) if B > 1 else spec
            if B > 1:
                roots = jax.vmap(env.init)(
                    jax.random.split(jax.random.PRNGKey(0), B)
                )
                rngs = jax.random.split(jax.random.PRNGKey(1), B)
            else:
                roots = env.init(jax.random.PRNGKey(0))
                rngs = jax.random.PRNGKey(1)

            def bench(search):
                # The first (warmup) call also yields the evaluator's own
                # tick count — different evaluators sample different tokens
                # and so tick different numbers of times.  Shallow-depth
                # searches finish in single-digit ms, where 3-iteration
                # medians were noisy enough to flip speedup rows across
                # runs — 7 iterations keeps the row stable.
                ticks = int(jnp.max(jnp.atleast_1d(search(roots, rngs).ticks)))
                return time_fn(search, roots, rngs, warmup=0, iters=7), ticks

            prefill_search = build_searcher(env, bspec, evaluator=model_ev)
            cached_search = build_searcher(env, bspec, evaluator=cached_ev)

            t_p, ticks_p = bench(prefill_search)
            record(f"prefill_eval_d{depth}_B{B}", t_p, B, depth, ticks_p,
                   "prefill_per_tick")
            t_c, ticks_c = bench(cached_search)
            record(f"cached_eval_d{depth}_B{B}", t_c, B, depth, ticks_c,
                   "cached_decode")
            if records is not None:
                records.append({
                    "name": f"cached_speedup_d{depth}_B{B}",
                    "kind": "speedup", "batch": B, "depth": depth,
                    "speedup": t_p / t_c,
                })
            rows.append(
                row(f"cached_speedup_d{depth}_B{B}", 0.0,
                    f"{t_p / t_c:.2f}x vs prefill-per-tick")
            )

            if paged:
                slots = max(B, 1) * wave_size
                # Dense-equivalent pool for the timing row: same HBM as the
                # dense slot caches, so any speed delta is pure layout cost.
                nb = slots * num_pages(max_len, BLOCK_SIZE)
                paged_ev = PagedCachedModelEvaluator(
                    cfg, params, top_k=top_k, eos_token=1,
                    block_size=BLOCK_SIZE, num_blocks=nb,
                )
                t_g, ticks_g = bench(
                    build_searcher(env, bspec, evaluator=paged_ev)
                )
                record(f"paged_eval_d{depth}_B{B}", t_g, B, depth, ticks_g,
                       "paged_decode")

                # Batch ceiling: the trace-mode blocks_in_use peak is the
                # real paged working set (prefix pages shared COW between
                # siblings + no dead [max_len] tails), so at the HBM budget
                # the dense layout spends on `slots` slots the pool can
                # carry `slots * dense/paged` of them.
                from repro.core.async_search import run_async_search
                from repro.core.batched_async_search import (
                    run_async_search_batched,
                )

                engine = (
                    run_async_search_batched if B > 1 else run_async_search
                )
                fn = jax.jit(functools.partial(
                    engine, env, bspec.config,
                    trace_ticks=4 * num_simulations, evaluator=paged_ev,
                ))
                _, trace = fn(roots, rngs)
                alive = np.asarray(trace.alive)
                alive = alive.reshape(alive.shape[0], -1).any(axis=1)
                peak = int(np.asarray(trace.blocks_in_use)[alive].max())
                dense_pos = slots * max_len
                paged_pos = peak * BLOCK_SIZE
                max_slots = slots * dense_pos // max(paged_pos, 1)
                if records is not None:
                    records.append({
                        "name": f"paged_ceiling_d{depth}_B{B}",
                        "kind": "batch_ceiling", "batch": B, "depth": depth,
                        "slots": slots, "max_len": max_len,
                        "block_size": BLOCK_SIZE, "peak_blocks": peak,
                        "dense_kv_positions": dense_pos,
                        "paged_kv_positions": paged_pos,
                        "max_slots_at_budget": max_slots,
                        "ceiling_ratio": dense_pos / max(paged_pos, 1),
                    })
                rows.append(row(
                    f"paged_ceiling_d{depth}_B{B}", 0.0,
                    f"{peak} blocks peak; {max_slots} slots fit the "
                    f"dense budget ({slots} dense)",
                ))

            # Frontier-speculative expansion: the candidate width A is the
            # env's top_k AND the tree's max_width, so each A gets its own
            # env/spec pair plus a MATCHED cached baseline — comparing a
            # frontier run at A=16 against the top-level cached row at
            # top_k=4 would conflate candidate width with tree shape
            # (wider trees tick more).  The trace run reports how many
            # refills the frontier snapshot absorbed (zero-forward hits).
            from repro.core.async_search import run_async_search
            from repro.core.batched_async_search import (
                run_async_search_batched,
            )

            engine = run_async_search_batched if B > 1 else run_async_search
            for a in frontier_widths:
                if a == top_k:
                    env_a, bspec_a = env, bspec
                    t_base, ticks_base = t_c, ticks_c
                else:
                    env_a = make_token_env(
                        cfg, params, prompt, max_len=max_len, top_k=a,
                        eos_token=1,
                    )
                    spec_a = SearchSpec(
                        algo="wu_uct", engine="async",
                        num_simulations=num_simulations,
                        wave_size=wave_size, max_depth=depth,
                        max_sim_steps=depth, max_width=a, gamma=1.0,
                    )
                    bspec_a = spec_a._replace(batch=B) if B > 1 else spec_a
                    cached_a = CachedModelEvaluator(
                        cfg, params, top_k=a, eos_token=1
                    )
                    t_base, ticks_base = bench(
                        build_searcher(env_a, bspec_a, evaluator=cached_a)
                    )
                frontier_ev = FrontierModelEvaluator(
                    cfg, params, top_k=a, eos_token=1
                )
                t_f, ticks_f = bench(
                    build_searcher(env_a, bspec_a, evaluator=frontier_ev)
                )
                fn = jax.jit(functools.partial(
                    engine, env_a, bspec_a.config,
                    trace_ticks=4 * num_simulations, evaluator=frontier_ev,
                ))
                _, ftrace = fn(roots, rngs)
                hits = int(np.asarray(ftrace.frontier_hits)[-1].sum())
                per_tick = t_f / max(ticks_f, 1)
                if records is not None:
                    records.append({
                        "name": f"frontier_eval_d{depth}_B{B}_A{a}",
                        "kind": "frontier_decode", "batch": B,
                        "depth": depth, "top_k": a, "seconds": t_f,
                        "searches_per_sec": B / t_f, "ticks": ticks_f,
                        "us_per_tick": per_tick * 1e6,
                        "frontier_hits": hits,
                        "expansions": B * num_simulations,
                    })
                    records.append({
                        "name": f"frontier_speedup_d{depth}_B{B}_A{a}",
                        "kind": "frontier_speedup", "batch": B,
                        "depth": depth, "top_k": a,
                        "speedup": t_base / t_f,
                        "cached_seconds": t_base,
                        "cached_ticks": ticks_base,
                    })
                rows.append(row(
                    f"frontier_eval_d{depth}_B{B}_A{a}", t_f,
                    f"{B / t_f:.2f} searches/s; {per_tick * 1e6:.0f} "
                    f"us/tick; {hits} refill hits",
                ))
                rows.append(row(
                    f"frontier_speedup_d{depth}_B{B}_A{a}", 0.0,
                    f"{t_base / t_f:.2f}x vs cached decode at A={a}",
                ))

            if di == 0:
                t_r, ticks_r = bench(build_searcher(env, bspec))
                record(f"rollout_eval_d{depth}_B{B}", t_r, B, depth, ticks_r,
                       "rollout")

    if serving_batch:
        rows += _serving_rows(
            cfg, params, num_simulations=num_simulations,
            wave_size=wave_size, top_k=top_k, depth=depths[0],
            batch=serving_batch, records=records,
        )
    return rows


def _serving_rows(
    cfg, params, *, num_simulations, wave_size, top_k, depth, batch,
    records,
):
    """Continuous-vs-one-shot serving throughput on a ragged workload.

    ``R = 3 * batch`` requests with uneven prompt lengths arrive one per
    searches settle at different ticks, so the one-shot path pays an idle
    tail per ``B``-batch while the persistent engine admits the next
    request into each settled row.  All three serving variants drain the
    same queued-up-front workload (submit all ``R``, then drain — the
    regime the one-shot baseline also gets), so the rows differ only in
    engine pacing, not arrival schedule.  Reported per mode (dense /
    paged KV):

    * ``serving_eval`` — the host-paced poll path (PR 8 behaviour,
      ``fused=False``): requests/s, slot-idle fraction, and its
      ``host_rounds`` (one dispatch + settled-mask sync per
      ``ticks_per_round`` ticks).
    * ``serving_fused`` — the device-resident ring path (``fused=True``,
      ring sized to the workload): requests/s, ``host_rounds`` (one per
      ``ticks_per_segment`` segment — admission/eviction happen inside
      the jitted ``while_loop``) and host rounds per drained request,
      beside the host-paced ``host_rounds`` for the reduction ratio.
    * ``serving_speedup`` — the fused drain vs the same workload in
      sequential one-shot ``B``-batches.

    At this benchmark's toy model scale (~100 µs/tick) host round-trips
    dominate: the fused path's win is that the host syncs once per
    segment instead of once per poll round.  ``host_rounds_per_request``
    is the hardware-independent signal; wall-clock speedup transfers to
    real models where a tick costs milliseconds.
    """
    import time as _time

    from repro.core import SearchSpec
    from repro.serving import SearchService

    max_len = len(PROMPT) + 2 * depth + 2
    spec = SearchSpec(
        algo="wu_uct", engine="async", batch=batch,
        num_simulations=num_simulations, wave_size=wave_size,
        max_depth=depth, max_sim_steps=depth, max_width=top_k, gamma=1.0,
    )
    n_req = 3 * batch
    base_prompts = [(3, 5), (2, 9, 4), (7,), (1, 2, 3), (5, 5), (6, 8, 2, 4)]
    prompts = [list(base_prompts[i % len(base_prompts)]) for i in range(n_req)]
    keys = [jax.random.fold_in(jax.random.PRNGKey(7), i) for i in range(n_req)]
    out = []
    for mode in ("dense", "paged"):
        # Host-paced poll path (PR 8 behaviour): one dispatch + settled
        # sync per ticks_per_round ticks.
        svc = SearchService(
            cfg, params, spec, top_k=top_k, max_len=max_len, eos_token=1,
            paged=(mode == "paged"), block_size=BLOCK_SIZE, fused=False,
        )

        def timed_drain(service):
            # Warm the compiled stage/segment/admit/evict/result programs
            # so the timed drain measures steady-state serving, not
            # compilation, then drain the full queued-up-front workload.
            for i in range(batch):
                service.submit(prompts[i], key=keys[i])
            service.drain()
            st0 = dataclasses.replace(service.stats)
            t0 = _time.perf_counter()
            for i in range(n_req):
                service.submit(prompts[i], key=keys[i])
            res = service.drain()
            dt = _time.perf_counter() - t0
            assert len(res) >= n_req
            return dt, st0, service.stats

        t_cont, st0, st = timed_drain(svc)
        ticks = st.ticks - st0.ticks
        busy = st.busy_tree_ticks - st0.busy_tree_ticks
        idle_frac = 1.0 - busy / max(ticks * batch, 1)
        host_rounds_poll = st.host_rounds - st0.host_rounds

        # Fused device-resident ring path: admission/eviction inside the
        # jitted segment, one host sync per segment.  Ring sized to the
        # workload so the whole queue stages before the first segment.
        fsvc = SearchService(
            cfg, params, spec, top_k=top_k, max_len=max_len, eos_token=1,
            paged=(mode == "paged"), block_size=BLOCK_SIZE, fused=True,
            ring_capacity=n_req, ticks_per_segment=256,
        )
        t_fused, fst0, fst = timed_drain(fsvc)
        host_rounds_fused = fst.host_rounds - fst0.host_rounds

        # One-shot baseline: the same workload in sequential B-batches,
        # each blocking on its slowest search (same compiled program as
        # SearchService.search, warmed by the first chunk).
        one_shot = SearchService(
            cfg, params, spec, top_k=top_k, max_len=max_len, eos_token=1,
            paged=(mode == "paged"), block_size=BLOCK_SIZE,
        )
        chunks = [prompts[i:i + batch] for i in range(0, n_req, batch)]
        one_shot.search(chunks[0], jax.random.PRNGKey(0))
        t0 = _time.perf_counter()
        for ci, chunk in enumerate(chunks):
            jax.block_until_ready(
                one_shot.search(chunk, jax.random.PRNGKey(ci))
            )
        t_seq = _time.perf_counter() - t0

        if records is not None:
            records.append({
                "name": f"serving_eval_{mode}_B{batch}",
                "kind": "serving_eval", "batch": batch, "depth": depth,
                "requests": n_req, "seconds": t_cont,
                "requests_per_sec": n_req / t_cont,
                "slot_idle_frac": idle_frac,
                "admissions": st.admissions - st0.admissions,
                "ticks": ticks,
                "host_rounds": host_rounds_poll,
            })
            records.append({
                "name": f"serving_fused_{mode}_B{batch}",
                "kind": "serving_fused", "batch": batch, "depth": depth,
                "requests": n_req, "seconds": t_fused,
                "requests_per_sec": n_req / t_fused,
                "host_rounds": host_rounds_fused,
                "host_rounds_per_request": host_rounds_fused / n_req,
                "host_paced_host_rounds": host_rounds_poll,
                "host_rounds_reduction": (
                    host_rounds_poll / max(host_rounds_fused, 1)
                ),
            })
            records.append({
                "name": f"serving_speedup_{mode}_B{batch}",
                "kind": "serving_speedup", "batch": batch, "depth": depth,
                "requests": n_req, "speedup": t_seq / t_fused,
                "sequential_seconds": t_seq,
                "fused_seconds": t_fused,
                "host_paced_seconds": t_cont,
            })
        out.append(row(
            f"serving_eval_{mode}_B{batch}", t_cont,
            f"{n_req / t_cont:.2f} req/s; {idle_frac:.3f} slot-idle frac; "
            f"{host_rounds_poll} host rounds",
        ))
        out.append(row(
            f"serving_fused_{mode}_B{batch}", t_fused,
            f"{n_req / t_fused:.2f} req/s; {host_rounds_fused} host rounds "
            f"({host_rounds_poll / max(host_rounds_fused, 1):.1f}x fewer)",
        ))
        out.append(row(
            f"serving_speedup_{mode}_B{batch}", 0.0,
            f"{t_seq / t_fused:.2f}x vs sequential one-shot batches",
        ))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--depth", type=int, nargs="*", default=list(DEPTHS),
        help="max_depth sweep: prefill-per-tick cost grows with depth, "
        "cached decode stays flat",
    )
    ap.add_argument("--batch", type=int, nargs="*", default=list(BATCH_SIZES))
    ap.add_argument("--num-simulations", type=int, default=16)
    ap.add_argument(
        "--paged", dest="paged", action="store_true", default=True,
        help="include paged-evaluator timing + batch-ceiling rows (default)",
    )
    ap.add_argument("--no-paged", dest="paged", action="store_false")
    ap.add_argument(
        "--serving-batch", type=int, default=4,
        help="engine rows B for the continuous-serving rows (0 disables); "
        "the ragged workload is 3*B requests",
    )
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for r in run(
        num_simulations=args.num_simulations,
        batch_sizes=tuple(args.batch),
        depths=tuple(args.depth),
        paged=args.paged,
        serving_batch=args.serving_batch,
    ):
        print(r)


if __name__ == "__main__":
    main()
