"""One run of one cell: set-up, the measured window, the checks, the line.

Set-up (``setup_s``, from process start to the first timed request): the
compile cache, the model the configuration's architecture module
(``bench/archs/``) builds and its weights, made on the device from the
seed, the ``SearchService`` of the cell's evaluator path, and a warm-up
request that compiles (or loads) the ``stage`` and ``serve_segment``
programs the window drives.  The window then drives the cell's traffic through
``SearchService.submit`` and ``poll`` on the fused ring for ``--seconds``.
After it closes: the evaluator slots are sampled and the search trees
copied with the program's selection at each of their nodes, every request
still in flight is waited for (a minute at most), device memory is read,
the service is freed, and the references check the sample, the selections
and every decision.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

import jax
import numpy as np

from . import checks, reference, registry, system, trace, tree_ref
from . import traffic as traffic_mod
from .traffic import SPAN, Window

#: How long answers are waited for after the window closes.
LATE_S = 60.0


class CompileCounter:
    """Programs traced, compiled or loaded from the cache while active."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1


def _keys(seed: int):
    rng = np.random.default_rng([int(seed), 7])
    while True:
        yield rng.integers(0, 2 ** 32, size=2, dtype=np.uint32)


def wait_late(svc, late: dict, limit_s: float = LATE_S,
              after_poll=None) -> list:
    """Poll until every request of ``late`` has its answer or ``limit_s``
    passed; returns their latencies (from their start times).
    ``after_poll()`` runs after each poll."""
    lat = []
    stop = time.perf_counter() + limit_s
    while late and time.perf_counter() < stop:
        fresh = svc.poll()
        t = time.perf_counter()
        for rid in fresh:
            if rid in late:
                lat.append(t - late.pop(rid))
        if after_poll is not None:
            after_poll()
    return lat


def device_info(count: int) -> dict:
    devs = jax.devices()[:count]
    peaks = [d.memory_stats() or {} for d in devs]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(int(p.get("peak_bytes_in_use", 0))
                                 for p in peaks),
    }


def _quantile(xs, q):
    return float(np.quantile(np.asarray(xs, np.float64), q))


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader sees of one traced run."""

    cell: dict
    arch: object             # the configuration's bench/archs module
    stats: dict              # ServeStats counts over the window
    window_s: float
    flops: float
    peak: dict               # the device's row of bench/peaks.json
    trace: object            # bench.trace.Summary, or None


def peak_row(kind: str, root: Path) -> dict:
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


@dataclasses.dataclass
class Served:
    """What one run of a cell left for the checks and the metrics."""

    cell: dict
    arch: object             # the configuration's bench/archs module
    weights: object
    setup_s: float
    window: Window
    stats: dict              # ServeStats counts over the window
    sample: dict             # bench.system.slot_sample at the close
    tree: dict               # bench.system.tree_snapshot at the close
    late_latencies: list     # requests answered after the close
    results: list            # every answer but the warm-up's
    device: dict
    planes: object           # bench.trace.load of the window, or None
    compiles: int            # programs traced or compiled in the window

    @property
    def window_s(self) -> float:
        return self.window.t1 - self.window.t0

    @property
    def unanswered(self) -> int:
        return (self.window.attempted - len(self.window.latencies)
                - len(self.late_latencies))


def serve(cell: dict, seed: int, seconds: float, traced: bool, *,
          t_start: float, root: Path = registry.ROOT) -> Served:
    """Set-up, the window and the wait for late answers; the service is
    freed before this returns, the weights are kept for the reference."""
    counter = CompileCounter()
    arch = registry.arch(cell["config"], root)
    cfg = arch.model_config(cell["config"])
    weights = getattr(arch, "make_weights", system.make_weights)(cfg, seed)
    jax.block_until_ready(weights)
    svc = system.build_service(cfg, weights, cell)
    keys = _keys(seed)
    warm = np.random.default_rng([int(seed), 9]).integers(
        1, cfg.vocab_size, size=cell["warmup_prompt_len"]).tolist()
    warm_id = svc.submit(warm, key=next(keys))
    svc.drain()
    base = dataclasses.asdict(svc.stats)
    gen = traffic_mod.make(cell["traffic"], seed, cfg.vocab_size, seconds,
                           root)
    setup_s = time.perf_counter() - t_start

    counter.active = True
    with trace.DeviceTrace(traced) as tr:
        with SPAN("bench.window"):
            win = gen.drive(svc, keys, seconds)
    counter.active = False
    stats = {k: v - base[k] for k, v in dataclasses.asdict(svc.stats).items()
             if isinstance(v, int)}
    stats["batch"] = svc.stats.batch

    # The slots and trees as the window left them; where no slot had decoded
    # two tokens since its refill (or every row had settled and released its
    # slots), or every tree had just been reset for a new request, as the
    # first later poll that leaves one.  A later poll whose slots are no
    # better (none decoded twice, or none live once the last rows settle)
    # leaves the sample as it was.
    rng = np.random.default_rng([int(seed), 11])
    sample = system.slot_sample(svc, cell["check_slots"], rng)
    tree = system.tree_snapshot(svc)

    def resample():
        nonlocal sample, tree
        if not np.any(sample["steps"] >= 2):
            fresh = system.slot_sample(svc, cell["check_slots"], rng)
            if np.any(fresh["steps"] >= 2) or not sample["slot"].size:
                sample = fresh
        if not tree["inner_nodes"]:
            tree = system.tree_snapshot(svc)

    late = wait_late(svc, dict(win.late), after_poll=resample)
    results = [r for rid, r in svc.results.items() if rid != warm_id]
    device = device_info(cell["chips"])
    del svc
    gc.collect()
    return Served(cell, arch, weights, setup_s, win, stats, sample, tree, late,
                  results, device, tr.planes, counter.count)


@dataclasses.dataclass
class Readings:
    """What the references read of one run, before any limit."""

    want: np.ndarray         # reference logits at the sampled slots
    logit_errs: np.ndarray   # per sampled slot
    select_gaps: np.ndarray  # per tree node with a child to take


def read(served: Served) -> Readings:
    """Run the references over what the run left."""
    cell = served.cell
    search = cell["search"]
    if search["algo"] != "wu_uct":
        raise ValueError(f"bench/tree_ref.py states WU-UCT only, not "
                         f"{search['algo']!r}")
    s = served.sample
    want = served.arch.last_logits(served.weights, cell["config"],
                                   s["tokens"], s["len"])
    return Readings(
        want=want, logit_errs=reference.rel_l2(s["logits"], want),
        select_gaps=tree_ref.select_gaps(served.tree, served.tree["acts"],
                                         search["beta"]),
    )


def compare(served: Served, limits: dict, readings: Readings) -> dict:
    """The numbers compared, each with its limit (bench.checks)."""
    cell = served.cell
    return checks.evaluate(
        limits, logit_errs=readings.logit_errs,
        select_gaps=readings.select_gaps, results=served.results,
        unanswered=served.unanswered, top_k=cell["top_k"],
        num_simulations=cell["search"]["num_simulations"],
    )


def run_cell(name: str, seed: int, seconds: float, traced: bool, *,
             t_start: float, root: Path = registry.ROOT,
             require_chips: bool = True, out=None, err=None) -> int:
    out = out or sys.stdout
    err = err or sys.stderr
    cell = registry.cell(name, root)
    devices = jax.devices()
    if require_chips and (devices[0].platform != "tpu"
                          or len(devices) < cell["chips"]):
        print(f"bench: cell {name} needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=err)
        return 2

    served = serve(cell, seed, seconds, traced, t_start=t_start, root=root)
    readings = read(served)
    verdict = compare(served, cell["limits"], readings)
    win, stats, device = served.window, served.stats, served.device
    window_s = served.window_s
    ttd = np.asarray(win.latencies + served.late_latencies) * 1e3

    units = {m["name"]: m["unit"] for m in cell["end_to_end"]
             + cell["per_layer"]}
    metrics = {}
    summary = None
    if traced:
        summary = trace.reduce(served.planes) if served.planes else None
        spec = cell["search"]
        ctx = Context(
            cell=cell, arch=served.arch, stats=stats, window_s=window_s,
            flops=served.arch.window_flops(
                cell["config"], stats, wave=spec["wave_size"],
                max_len=cell["max_len"]),
            peak=peak_row(device["kind"], root) if require_chips else {},
            trace=summary,
        )
        for m in cell["per_layer"]:
            v = registry.metric_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
    else:
        values = {
            "decisions_per_s": len(win.latencies) / window_s,
            "ttd_p50_ms": _quantile(ttd, 0.5) if ttd.size else math.inf,
            "ttd_p90_ms": _quantile(ttd, 0.9) if ttd.size else math.inf,
            "setup_s": served.setup_s,
        }
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": units[m["name"]]}

    sample = served.sample
    print(f"bench: sampled slots {sample['slot'].tolist()} of lengths "
          f"{sample['len'].tolist()}, non-finite stored logits "
          f"{int(np.sum(~np.isfinite(sample['logits'])))}, decode ticks "
          f"since refill {sample['steps'].tolist()}, tree nodes "
          f"compared {readings.select_gaps.size}", file=err)
    late = np.asarray(win.lateness) * 1e3
    print(f"bench: {name} seed {seed}: window {window_s:.3f} s, "
          f"{len(win.latencies)} decisions in it, {win.attempted} submitted, "
          f"{len(served.late_latencies)} answered after it, setup "
          f"{served.setup_s:.3f} s, programs traced or compiled in the window "
          f"{served.compiles}, counts {json.dumps(stats)}", file=err)
    if late.size:
        print(f"bench: generator lateness ms p50 {np.median(late):.3f} "
              f"max {late.max():.3f}", file=err)
    line = {
        "correct": checks.passed(verdict),
        "attempted": win.attempted,
        "failed": served.unanswered + verdict["decision_faults"]["value"],
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        line["breakdown"] = {"device_ops": summary.device_ops,
                             "idle_gaps": summary.idle_gaps}
    line["checks"] = verdict
    for k, c in verdict.items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)
    return 0
