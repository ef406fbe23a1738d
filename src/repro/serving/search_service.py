"""Token-search service: many users' search requests, one batched program.

The serving-side consumer of the search front door: a batch of prompt
requests becomes ``B`` root states of one multi-root search
(``repro.core.build_searcher`` with ``spec.batch = B``), so every master
tick of the engine advances all users' searches together — and, with the
default :class:`~repro.core.evaluators.ModelEvaluator`, evaluates all their
in-flight rollout slots in **one** policy-LM forward (the flat ``[B·W]``
batch).  This is the WU-UCT analogue of continuous batching in
:mod:`repro.serving.engine`: throughput comes from batching across requests,
not from parallelizing one request harder.

Two serving shapes:

* :meth:`SearchService.search` / :meth:`~SearchService.decide` — one-shot:
  admit a prompt batch, run it to completion, return.  Settled roots idle
  until the slowest finishes.
* :meth:`SearchService.submit` + :meth:`~SearchService.drain` (or
  :meth:`~SearchService.serve` over a request stream) — continuous: a
  persistent :class:`repro.core.batched_async_search.BatchedAsyncEngine`
  keeps all ``B`` tree rows searching, and whenever a row settles the next
  queued request is spliced into it mid-stream (tree reset, RNG lane, and
  evaluator KV slot caches re-seeded through the shared
  :mod:`repro.serving.admission` path).  :class:`ServeStats` reports the
  occupancy this buys — the slot-idle fraction the one-shot path wastes.
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import json
import os
import time
import warnings
from pathlib import Path
from typing import Iterable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core import SearchResult, SearchSpec, build_searcher
from ..core.api import as_search_config
from ..core.evaluators import CachedModelEvaluator, Evaluator, ModelEvaluator
from ..envs.token_env import TokenEnvState, make_token_env
from ..models import forward
from ..models.config import ModelConfig
from .admission import pages_needed, validate_prompts

#: Environment variable overriding where the committed benchmark baseline
#: (``BENCH_model_eval.json``) is read from for the paged-pool default.
BENCH_BASELINE_ENV = "REPRO_BENCH_BASELINE"

_pool_fallback_warned = False


class InvalidSearchActionError(RuntimeError):
    """A search returned an action outside ``[0, top_k)``.

    Actions are ranks into the policy's top-K table; an out-of-range value
    (e.g. ``-1`` from a search that never visited the root's children) has
    no token to map to.  Surfacing it beats the old behaviour of clipping
    into range, which made a failed search indistinguishable from a
    confident greedy top-1 pick.
    """


def _bench_baseline_path() -> Optional[Path]:
    """Locate the committed ``BENCH_model_eval.json`` baseline.

    Order: the :data:`BENCH_BASELINE_ENV` env var (points at the file), then
    a walk up from this module's directory (the repo-checkout layout), then
    a walk up from the current working directory (installed/site-packages
    layouts running inside a checkout).  Returns ``None`` when nothing is
    found.
    """
    env_path = os.environ.get(BENCH_BASELINE_ENV)
    if env_path:
        p = Path(env_path)
        if p.is_file():
            return p
    seen = set()
    for base in (Path(__file__).resolve().parent, Path.cwd().resolve()):
        for parent in (base, *base.parents):
            if parent in seen:
                continue
            seen.add(parent)
            cand = parent / "BENCH_model_eval.json"
            if cand.is_file():
                return cand
    return None


def _prefix_sharing_pool_blocks(
    slots: int, max_len: int, block_size: int
) -> int:
    """Default paged-pool size informed by measured prefix sharing.

    The dense-equivalent bound ``slots * num_pages`` assumes no page is ever
    shared, but the committed ``paged_ceiling_*`` benchmark rows measure the
    real peak working set of searches with sibling prefix sharing
    (``ceiling_ratio`` = dense positions / peak paged positions).  Size the
    pool to the dense bound shrunk by the WORST measured ratio, plus 25%
    headroom — shallow searches share the least, so the minimum ratio is the
    conservative choice.  When the baseline file cannot be found or parsed
    (see :func:`_bench_baseline_path` for the lookup order), fall back to
    the dense bound and warn once.
    """
    global _pool_fallback_warned
    from ..models import num_pages

    dense = slots * num_pages(max_len, block_size)
    path = _bench_baseline_path()
    ratios = None
    if path is not None:
        try:
            rows = json.loads(path.read_text())["rows"]
            ratios = [
                float(r["ceiling_ratio"])
                for r in rows
                if r.get("kind") == "batch_ceiling" and "ceiling_ratio" in r
            ]
        except (OSError, ValueError, KeyError, TypeError) as e:
            warnings.warn(
                f"could not parse benchmark baseline {path}: {e!r}; "
                "using the dense paged-pool bound",
                stacklevel=2,
            )
            return dense
    if not ratios:
        if not _pool_fallback_warned:
            _pool_fallback_warned = True
            warnings.warn(
                "no BENCH_model_eval.json baseline with batch_ceiling rows "
                f"found (set ${BENCH_BASELINE_ENV} to point at one); using "
                "the dense paged-pool bound",
                stacklevel=2,
            )
        return dense
    ratio = min(ratios)
    if not ratio > 1.0:
        return dense
    shrunk = int(dense / ratio * 1.25) + 1
    return max(1, min(dense, shrunk))


@dataclasses.dataclass
class ServeStats:
    """Occupancy/admission counters for the continuous-serving path.

    ``busy_tree_ticks`` counts (tree row, master tick) pairs where the row
    was actively searching; ``ticks * batch`` is the capacity, so
    :attr:`slot_idle_frac` is the fraction of row-ticks spent idle — the
    quantity slot-level admission exists to minimize (a one-shot batch
    wastes the whole tail where settled roots wait for the slowest).
    """

    batch: int = 0
    submitted: int = 0
    completed: int = 0
    admissions: int = 0
    ticks: int = 0
    busy_tree_ticks: int = 0
    #: Host round-trips into the serving loop: one per :meth:`poll` on the
    #: host-paced path, one per fused ``serve_segment`` on the ring path —
    #: the quantity the device-resident loop exists to shrink.
    host_rounds: int = 0
    #: Key/value positions the decode steps attended, summed over ticks and
    #: over all ``B·W`` slots (``Evaluator.attended_positions``).
    attended_positions: int = 0
    #: Sums over answered requests (fused path) of the host-clock waits of
    #: :class:`RequestTimeline`, in microseconds: submit to admission into
    #: a row, and settle to the answer on the host.
    queue_wait_us: int = 0
    answer_wait_us: int = 0

    @property
    def slot_idle_frac(self) -> float:
        cap = self.ticks * self.batch
        if cap == 0:
            return 0.0
        return 1.0 - self.busy_tree_ticks / cap


class RequestTimeline(NamedTuple):
    """One answered request on the fused path, in ``time.perf_counter``
    seconds, with the ticks of the service's master-tick count at which its
    row was admitted and settled.

    ``submit``, ``staged`` (the ``stage`` dispatch) and ``answered`` (the
    end of the fetch that brought the answer) are read on the host.  The
    device reports ticks only; ``admitted`` and ``settled`` map them to the
    host clock by linear interpolation over the host interval of the
    segment that ran them, from its dispatch to the end of its fetch, so
    they are good to about one tick.
    """

    submit: float
    staged: float
    admitted: float
    settled: float
    answered: float
    admit_tick: int
    settle_tick: int


class SearchService:
    """Batched WU-UCT token search behind a prompt-in / token-out interface.

    ``spec.batch`` fixes the request-slot count (one compiled program);
    shorter request lists are padded with repeats and the padding results
    dropped.  ``evaluator=None`` builds the best evaluator the spec
    supports: a :class:`CachedModelEvaluator` on async engines with a
    KV-cache model family (every master tick costs one batched
    ``decode_step``, not one full-prefix forward), falling back to the
    uncached :class:`ModelEvaluator` otherwise — pass an explicit evaluator
    (e.g. a ``RolloutEvaluator`` over the token env) to switch evaluation
    modes without touching the engine.

    ``ticks_per_round`` paces the continuous path: each :meth:`poll` runs at
    most that many master ticks before the host harvests settled rows and
    admits queued requests (smaller = settled rows idle less, more host
    round-trips).
    """

    def __init__(
        self,
        model_cfg: ModelConfig,
        params,
        spec: SearchSpec,
        *,
        top_k: int = 8,
        max_len: int = 64,
        eos_token: int = 0,
        reward_cfg: Optional[ModelConfig] = None,
        reward_params=None,
        evaluator: Optional[Evaluator] = None,
        paged: bool = False,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        ticks_per_round: int = 8,
        fused: bool = True,
        ring_capacity: Optional[int] = None,
        ticks_per_segment: Optional[int] = None,
    ):
        if spec.batch <= 0:
            raise ValueError("SearchService needs a batched spec (batch > 0)")
        if ticks_per_round < 1:
            raise ValueError(
                f"ticks_per_round must be >= 1, got {ticks_per_round}"
            )
        self.cfg = model_cfg
        self.params = params
        self.spec = spec
        self.top_k = top_k
        self.max_len = max_len
        self.paged = paged
        self.ticks_per_round = ticks_per_round
        self.fused = fused
        self.ring_capacity = (
            int(ring_capacity) if ring_capacity is not None
            else max(1, spec.batch)
        )
        self.ticks_per_segment = (
            int(ticks_per_segment) if ticks_per_segment is not None
            else 8 * ticks_per_round
        )
        if self.ring_capacity < 1:
            raise ValueError(
                f"ring_capacity must be >= 1, got {ring_capacity}"
            )
        if self.ticks_per_segment < 1:
            raise ValueError(
                f"ticks_per_segment must be >= 1, got {ticks_per_segment}"
            )
        # The env's prompt only seeds env.init, which the service bypasses
        # (roots are built from the request prompts directly).
        env = make_token_env(
            model_cfg, params, jnp.zeros((1,), jnp.int32), max_len=max_len,
            top_k=top_k, eos_token=eos_token,
            reward_cfg=reward_cfg, reward_params=reward_params,
        )
        if evaluator is None:
            families = {model_cfg.family} | (
                {reward_cfg.family} if reward_cfg is not None else set()
            )
            from ..models import KV_CACHE_FAMILIES

            cacheable = (
                spec.engine == "async" and families <= set(KV_CACHE_FAMILIES)
            )
            if paged and not cacheable:
                raise ValueError(
                    "paged=True needs an async-engine spec and a KV-cache "
                    f"model family, got engine={spec.engine!r} "
                    f"families={sorted(families)}"
                )
            kwargs = dict(
                top_k=top_k, eos_token=eos_token,
                reward_cfg=reward_cfg, reward_params=reward_params,
            )
            if paged:
                from ..core.evaluators import PagedCachedModelEvaluator

                slots = spec.batch * spec.wave_size
                if num_blocks is None:
                    # Prefix-sharing-aware default: the dense-equivalent
                    # bound shrunk by the measured paged_ceiling_* sharing
                    # ratio (with headroom); see _prefix_sharing_pool_blocks.
                    num_blocks = _prefix_sharing_pool_blocks(
                        slots, max_len, block_size
                    )
                evaluator = PagedCachedModelEvaluator(
                    model_cfg, params, block_size=block_size,
                    num_blocks=num_blocks, **kwargs,
                )
            else:
                ev_cls = CachedModelEvaluator if cacheable else ModelEvaluator
                evaluator = ev_cls(model_cfg, params, **kwargs)
        self.env = env
        self.evaluator = evaluator
        self._search = build_searcher(env, spec, evaluator=evaluator)

        # --- continuous-serving state (built lazily on first submit) ------
        self.stats = ServeStats(batch=spec.batch)
        self._engine = None
        self._carry = None
        # Priority-then-FIFO heap of (-priority, req_id, prompt, key):
        # req_id is monotonic, so equal priorities pop in submission order.
        self._queue: list = []
        self._results: dict = {}           # req_id -> per-request SearchResult
        self._row_req: list = [None] * spec.batch
        self._next_req_id = 0
        self._base_key = jax.random.PRNGKey(0)
        # Fused-path host mirrors (exact: every device-side transition is
        # accounted from the per-round staged/admitted/completed counts).
        self._ring = None
        self._row_req_dev = None
        self._ring_free = self.ring_capacity
        self._inflight = 0
        # Request timelines (fused path): host times of submit and staging
        # until the answer, then one RequestTimeline; and per segment run,
        # (first tick, ticks run, dispatch time, end of fetch).
        self._times: dict = {}
        self._timeline: dict = {}
        self._segments: list = []

    # ------------------------------------------------------------------
    # Root-state packing
    # ------------------------------------------------------------------
    def _root_rows(self, prompts: Sequence[Sequence[int]]) -> TokenEnvState:
        """Pack ``R`` prompts into an ``[R]``-leading root-state batch."""
        validate_prompts(prompts, self.max_len)
        r = len(prompts)
        tokens = np.zeros((r, self.max_len), np.int32)
        lengths = np.zeros((r,), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, : len(p)] = p
            lengths[i] = len(p)
        return TokenEnvState(
            tokens=jnp.asarray(tokens),
            length=jnp.asarray(lengths),
            done=jnp.zeros((r,), jnp.bool_),
        )

    def _roots(self, prompts: Sequence[Sequence[int]]) -> TokenEnvState:
        B = self.spec.batch
        if not prompts:
            raise ValueError("need at least one prompt")
        if len(prompts) > B:
            raise ValueError(f"got {len(prompts)} prompts for batch={B}")
        return self._root_rows(list(prompts) + [prompts[0]] * (B - len(prompts)))

    # ------------------------------------------------------------------
    # One-shot serving
    # ------------------------------------------------------------------
    def search(self, prompts: Sequence[Sequence[int]], key: jax.Array):
        """Run one batched search; returns the ``SearchResult`` (leading
        ``[B]``; rows past ``len(prompts)`` are padding)."""
        roots = self._roots(prompts)
        return self._search(roots, jax.random.split(key, self.spec.batch))

    def decide(self, prompts: Sequence[Sequence[int]], key: jax.Array):
        """Search + decode: the searched next token for every prompt.

        Actions are ranks into the policy's top-K at each prompt's current
        position; one batched forward maps them back to vocabulary ids.  A
        search that returns an out-of-range action (e.g. ``-1``) raises
        :class:`InvalidSearchActionError` — clipping it into range would
        silently serve the greedy top-1 token for a failed search.
        """
        n = len(prompts)
        roots = self._roots(prompts)
        res = self._search(roots, jax.random.split(key, self.spec.batch))
        actions = np.asarray(res.action)
        bad = [
            (i, int(actions[i]))
            for i in range(n)
            if not 0 <= int(actions[i]) < self.top_k
        ]
        if bad:
            raise InvalidSearchActionError(
                f"search returned out-of-range action(s) {bad}; actions are "
                f"ranks into the policy top-{self.top_k} table (the search "
                "may not have completed any simulation from these roots)"
            )
        logits, _ = forward(self.params, self.cfg, {"tokens": roots.tokens})
        pos = jnp.maximum(roots.length - 1, 0)
        at_pos = jnp.take_along_axis(logits, pos[:, None, None], axis=1)[:, 0]
        _, top_idx = jax.lax.top_k(at_pos, self.top_k)
        # Clip only for the gather: rows >= n are padding (never validated,
        # never returned); rows < n were validated in range above.
        ranks = jnp.clip(res.action, 0, self.top_k - 1)
        tokens = jnp.take_along_axis(top_idx, ranks[:, None], axis=1)[:, 0]
        return [int(t) for t in tokens[:n]], res

    # ------------------------------------------------------------------
    # Continuous serving: persistent engine + slot-level admission
    # ------------------------------------------------------------------
    def _ensure_engine(self):
        if self._engine is not None:
            return
        if self.spec.engine != "async":
            raise ValueError(
                "continuous serving (submit/poll/drain/serve) needs an "
                f"async-engine spec, got engine={self.spec.engine!r}"
            )
        from ..core.batched_async_search import BatchedAsyncEngine

        B = self.spec.batch
        engine = BatchedAsyncEngine(
            self.env, as_search_config(self.spec), B,
            evaluator=self.evaluator, use_kernel=self.spec.use_kernel,
        )
        # All rows born idle around a placeholder root; evict immediately so
        # paged placeholders hold no pool pages while waiting for requests.
        roots = self._root_rows([[0]] * B)
        carry = engine.init_carry(
            roots, jax.random.split(jax.random.PRNGKey(0), B),
            active=jnp.zeros((B,), bool),
        )
        carry = engine.evict(carry, jnp.arange(B, dtype=jnp.int32))
        self._engine = engine
        self._carry = carry
        # Every serving program takes the evaluator's weights as its first
        # argument (see Evaluator.weights): closed over, they would be
        # compiled in as constants.
        self._weights = self.evaluator.weights()
        self._segment = self._jit(
            lambda c: engine.run_segment(c, self.ticks_per_round),
            "run_segment",
        )
        self._result_fn = self._jit(engine.result, "result")
        # The service always admits/evicts ONE row per call: `rows` keeps a
        # fixed [1] shape, so these trace exactly once — a variable-size
        # admission batch would recompile the whole splice (prefill included)
        # for every distinct batch size it ever saw.
        self._admit_fn = self._jit(engine.admit, "admit")
        self._evict_fn = self._jit(engine.evict, "evict")
        if self.fused:
            # Device-resident ring: stage() keeps a fixed [1] request shape
            # per call (same single-signature discipline as admit/evict);
            # serve_segment fuses harvest + admission into the while_loop,
            # so the host pays ONE dispatch + ONE sync per segment.
            self._ring = engine.init_ring(roots, self.ring_capacity)
            self._row_req_dev = jnp.full((B,), -1, jnp.int32)
            self._stage_fn = self._jit(engine.stage, "stage")
            self._serve_fn = self._jit(
                lambda c, g, q: engine.serve_segment(
                    c, g, q, self.ticks_per_segment
                ),
                "serve_segment",
            )

    def compiled_segment_text(self) -> str:
        """Compiled HLO text of the segment program :meth:`poll` runs (the
        fused ring's ``serve_segment``, else ``run_segment``), for checking
        which kernels the served path holds."""
        self._ensure_engine()
        if self.fused:
            fn, args = self._serve_fn, (self._carry, self._ring,
                                        self._row_req_dev)
        else:
            fn, args = self._segment, (self._carry,)
        return fn.lower(self._weights, *args).compile().as_text()

    def _jit(self, fn, name: str):
        """``jax.jit`` of ``fn`` taking the evaluator's weights first, named
        ``name`` (the program shows as ``jit_<name>`` in compiled HLO and
        in a device trace's modules)."""
        evaluator = self.evaluator

        def call(weights, *args):
            with evaluator.bound(weights):
                return fn(*args)

        call.__name__ = call.__qualname__ = name
        return jax.jit(call)

    def _free_pool_blocks(self) -> Optional[int]:
        """Free blocks in the paged evaluator's pool (None when dense)."""
        if not self.paged:
            return None
        aux = self._carry[7]
        return int(self.evaluator.num_blocks - jnp.sum(aux["refcount"] > 0))

    def submit(
        self,
        prompt: Sequence[int],
        key: Optional[jax.Array] = None,
        priority: int = 0,
    ):
        """Queue one search request; returns its request id.

        ``key`` seeds the request's tree row (defaults to a fold of the
        service key and the request id).  ``priority`` orders the queue:
        higher values admit first, ties break FIFO by submission order
        (the pre-existing behaviour is the all-zero default).  The request
        runs when a row settles — call :meth:`poll` to make progress or
        :meth:`drain` to block until everything queued has finished.
        """
        validate_prompts([prompt], self.max_len)
        req_id = self._next_req_id
        self._next_req_id += 1
        if self.fused:
            self._times[req_id] = [time.perf_counter()]
        if key is None:
            key = jax.random.fold_in(self._base_key, req_id)
        heapq.heappush(
            self._queue, (-int(priority), req_id, list(prompt), key)
        )
        self.stats.submitted += 1
        return req_id

    def _settled(self) -> np.ndarray:
        """Host copy of the per-row settled mask (ONE device sync)."""
        return np.asarray(self._engine.settled(self._carry))

    def _harvest(self, settled: Optional[np.ndarray] = None) -> dict:
        """Collect results from settled occupied rows; free the rows."""
        carry = self._carry
        if settled is None:
            settled = self._settled()
        done_rows = [
            b for b in range(self.spec.batch)
            if settled[b] and self._row_req[b] is not None
        ]
        fresh = {}
        if done_rows:
            # One device->host transfer for the whole batch; per-request
            # rows are host-side slices.
            res = jax.tree.map(
                np.asarray, self._result_fn(self._weights, carry)
            )
            for b in done_rows:
                req_id = self._row_req[b]
                # Host-side slicing of an already-fetched numpy tree — no
                # device dispatch despite the jax.tree.map spelling.
                # reprolint: disable=JX002
                row = jax.tree.map(lambda x: x[b], res)
                self._results[req_id] = row
                fresh[req_id] = row
                self._row_req[b] = None
                self.stats.completed += 1
            # Return the rows' pages to the pool before anything new is
            # admitted (a no-op for dense caches).  One row per call keeps
            # the jitted evict at a single compiled shape.
            for b in done_rows:
                # Deliberate per-row dispatch: a fixed [1]-shape rows vector
                # keeps the jitted evict at ONE compiled signature (the
                # variable-shape alternative was PR 8's 30x regression), and
                # done_rows is bounded by the small host-side batch B.
                # reprolint: disable=JX002
                row = jnp.asarray([b], jnp.int32)
                self._carry = self._evict_fn(self._weights, self._carry, row)
        return fresh

    def _admit_queued(self, settled: Optional[np.ndarray] = None) -> int:
        """Splice queued requests into free rows (paged: admit-fewer)."""
        if settled is None:
            settled = self._settled()
        free_rows = [
            b for b in range(self.spec.batch)
            if settled[b] and self._row_req[b] is None
        ]
        if not free_rows or not self._queue:
            return 0
        budget = self._free_pool_blocks()
        admitted = 0
        for b in free_rows:
            if not self._queue:
                break
            _, req_id, prompt, key = self._queue[0]
            if budget is not None:
                need = pages_needed(len(prompt), self.evaluator.block_size)
                if need > budget:
                    break  # wait for pages to free (admit in order)
                budget -= need
            heapq.heappop(self._queue)
            # Deliberate per-row admission dispatch (same reasoning as the
            # evict loop in _harvest): fixed [1]-shape rows keep the jitted
            # admit at one compiled signature; issubdtype is metadata-only.
            # reprolint: disable=JX002
            if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
                key = jax.random.key_data(key)
            self._carry = self._admit_fn(
                self._weights, self._carry, jnp.asarray([b], jnp.int32),
                self._root_rows([prompt]), key[None],
            )
            self._row_req[b] = req_id
            admitted += 1
        if admitted and self.paged:
            # admit ran jitted, so pool exhaustion latched instead of
            # raising; surface it here at the eager boundary.
            self.evaluator.check_exhausted(self._carry[7])
        self.stats.admissions += admitted
        return admitted

    def poll(self) -> dict:
        """One serving round; returns the requests that finished in it
        (``{req_id: SearchResult row}``; results also accumulate in
        :attr:`results`).

        Host-paced (``fused=False``): harvest settled rows, admit queued
        requests, advance the engine up to ``ticks_per_round`` master ticks
        — several dispatches and syncs per round.  Fused (the default):
        stage queued requests into the device-resident ring, dispatch ONE
        ``serve_segment`` (up to ``ticks_per_segment`` ticks with harvest +
        admission inside the ``while_loop``), and drain the completion
        buffer — one host round per segment.
        """
        self._ensure_engine()
        if self.fused:
            return self._poll_fused()
        settled = self._settled()
        fresh = self._harvest(settled)
        # Harvest freed rows but left them settled; the same host mask
        # serves admission (one device sync per round, not three).
        self._admit_queued(settled)
        if any(r is not None for r in self._row_req):
            self._carry, t, busy, att = self._segment(
                self._weights, self._carry
            )
            self.stats.ticks += int(t)
            self.stats.busy_tree_ticks += int(busy)
            self.stats.attended_positions += int(att)
        self.stats.host_rounds += 1
        return fresh

    def _poll_fused(self) -> dict:
        """One fused round: refill the ring, run one segment, drain
        completions.  The only device syncs are the paged pool budget (when
        staging) and the single post-segment fetch.  Host spans
        (``serve.stage``, ``serve.dispatch``, ``serve.fetch``,
        ``serve.harvest``) go to the profiler's trace when one runs."""
        budget = self._free_pool_blocks()
        while self._queue and self._ring_free > 0:
            _, req_id, prompt, key = self._queue[0]
            if budget is not None:
                need = pages_needed(len(prompt), self.evaluator.block_size)
                if need > budget:
                    break  # wait for pages to free (admit in order)
                budget -= need
            heapq.heappop(self._queue)
            self._times[req_id].append(time.perf_counter())
            with TraceAnnotation("serve.stage", req_id=req_id):
                # Deliberate per-request staging dispatch: a fixed [1]-shape
                # request keeps the jitted stage at ONE compiled signature
                # (a variable shape recompiles the prefill for every batch
                # size, a 30x slowdown once measured), and the loop is
                # bounded by the small host-side ring capacity.
                # reprolint: disable=JX002
                if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
                    key = jax.random.key_data(key)
                self._carry, self._ring = self._stage_fn(
                    self._weights, self._carry, self._ring,
                    self._root_rows([prompt]), key[None],
                    jnp.asarray([req_id], jnp.int32),
                )
            self._ring_free -= 1
        staged = self.ring_capacity - self._ring_free
        fresh = {}
        if staged > 0 or self._inflight > 0:
            t_dispatch = time.perf_counter()
            with TraceAnnotation("serve.dispatch"):
                out = self._serve_fn(
                    self._weights, self._carry, self._ring, self._row_req_dev
                )
            self._carry, self._ring, self._row_req_dev = out[:3]
            comp, t, busy, att = out[3:]
            oom = self._carry[7]["oom"] if self.paged else 0
            with TraceAnnotation("serve.fetch"):
                comp, t, busy, att, count_after, oom = jax.device_get(
                    (comp, t, busy, att, self._ring.count, oom)
                )
            t_end = time.perf_counter()
            if self.paged:
                self.evaluator._maybe_raise(oom)
            first, t = self.stats.ticks, int(t)
            if t > 0:
                self._segments.append((first, t, t_dispatch, t_end))
            n = int(comp.count)
            with TraceAnnotation("serve.harvest"):
                for i in range(n):
                    req_id = int(comp.req_id[i])
                    # Host-side slicing of the already-fetched completion
                    # buffer (device_get above) — no device dispatch here.
                    # reprolint: disable=JX002
                    row = SearchResult(
                        action=comp.action[i], root_n=comp.root_n[i],
                        root_v=comp.root_v[i], tree_size=comp.tree_size[i],
                        dup_selections=np.float32(0.0), max_o=comp.max_o[i],
                        overflowed=comp.overflowed[i], ticks=comp.ticks[i],
                    )
                    self._results[req_id] = row
                    fresh[req_id] = row
                    settle = first + int(comp.settle_tick[i])
                    self._record(req_id, settle - int(comp.ticks[i]), settle,
                                 t_end)
            admitted = staged - int(count_after)
            self._ring_free = self.ring_capacity - int(count_after)
            self._inflight += admitted - n
            self.stats.admissions += admitted
            self.stats.completed += n
            self.stats.ticks += t
            self.stats.busy_tree_ticks += int(busy)
            self.stats.attended_positions += int(att)
        self.stats.host_rounds += 1
        return fresh

    def _tick_time(self, tick: int) -> float:
        """Host time of the start of master tick ``tick`` (or of the end of
        the last segment run), by linear interpolation over the host
        interval of the segment that ran it."""
        i = bisect.bisect_right(self._segments, tick, key=lambda g: g[0]) - 1
        first, n, t0, t1 = self._segments[i]
        return t0 + (t1 - t0) * (tick - first) / n

    def _record(self, req_id: int, admit: int, settle: int, answered: float):
        submit, staged = self._times.pop(req_id)
        rec = RequestTimeline(
            submit=submit, staged=staged, admitted=self._tick_time(admit),
            settled=self._tick_time(settle), answered=answered,
            admit_tick=admit, settle_tick=settle,
        )
        self._timeline[req_id] = rec
        self.stats.queue_wait_us += round((rec.admitted - submit) * 1e6)
        self.stats.answer_wait_us += round((answered - rec.settled) * 1e6)

    def drain(self, max_rounds: int = 100_000) -> dict:
        """Poll until every submitted request has a result; return them all.

        ``max_rounds`` bounds the loop against a wedged engine (e.g. a
        paged pool too small for even one queued prompt)."""
        self._ensure_engine()
        for _ in range(max_rounds):
            if not self._queue and self._in_flight() == 0:
                break
            before = (len(self._queue), self._in_flight(), self.stats.ticks)
            self.poll()
            after = (len(self._queue), self._in_flight(), self.stats.ticks)
            if after == before:
                raise RuntimeError(
                    f"serving made no progress (queue={after[0]}, "
                    f"in flight={after[1]}); paged pool too small for the "
                    "queued prompts?"
                )
        else:
            raise RuntimeError(f"drain exceeded {max_rounds} rounds")
        if not self.fused:
            # One last harvest: the final segment may have settled rows.
            # (The fused loop harvests in-loop; its completions drained in
            # poll.)
            self._harvest()
        return dict(self._results)

    def _in_flight(self) -> int:
        """Requests past the queue but short of a result (host-side)."""
        if self.fused:
            staged = self.ring_capacity - self._ring_free
            return self._inflight + staged
        return sum(r is not None for r in self._row_req)

    def serve(
        self,
        prompt_stream: Iterable[Sequence[int]],
        keys: Optional[Sequence[jax.Array]] = None,
    ) -> list:
        """Serve a (possibly ragged) request stream to completion.

        Each prompt is submitted and a :meth:`poll` round runs between
        arrivals — requests admit into rows as earlier searches settle, so
        arrival order interleaves with completion order exactly like real
        traffic.  Returns per-request ``SearchResult`` rows in submission
        order.
        """
        ids = []
        for i, prompt in enumerate(prompt_stream):
            key = keys[i] if keys is not None else None
            ids.append(self.submit(prompt, key=key))
            self.poll()
        results = self.drain()
        return [results[i] for i in ids]

    @property
    def results(self) -> dict:
        """All completed requests so far (``{req_id: SearchResult row}``)."""
        return dict(self._results)

    @property
    def timeline(self) -> dict:
        """``{req_id: RequestTimeline}`` of every request answered on the
        fused path so far."""
        return dict(self._timeline)
