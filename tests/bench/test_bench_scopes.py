"""Device time by tick stage and by program, idle gaps named after the
program's own host spans (``bench/scopes.py``), and the readers of the
program's tracing counters (attended positions, request waits)."""

import time

import pytest

from bench import harness, registry, scopes, trace

MS = 1e6
STAGES = ("select", "refill_cache", "catch_up", "decode", "settle",
          "serve_round")


def _planes(ops, spans, modules=(), window=(0.0, 100 * MS)):
    """Device ops ``(name, start_ms, dur_ms, program, path)`` and host
    spans ``(name, start_ms, dur_ms)``, as :func:`scopes.load` gives them."""
    device = {
        trace.OP_LINE: [(n, s * MS, d * MS) for n, s, d, _, _ in ops],
        scopes.OP_INFO: [(p, path) for _, _, _, p, path in ops],
        scopes.MODULE_LINE: [(n, s * MS, d * MS) for n, s, d in modules],
    }
    return {
        f"{trace.DEVICE_PREFIX}0": device,
        trace.HOST_PLANE: {"python": [
            (trace.WINDOW_SPAN, window[0], window[1] - window[0])
        ] + [(n, s * MS, d * MS) for n, s, d in spans]},
    }


SEG = "jit(serve_segment)/while/body"
OPS = [
    ("while.1", 0, 60, "serve_segment", "jit(serve_segment)/while"),
    ("fusion.813", 0, 20, "serve_segment",
     f"{SEG}/while/body/closed_call/refill_cache/scatter"),
    ("fusion.2", 20, 5, "serve_segment",
     f"{SEG}/while/body/closed_call/catch_up/while/body/dot_general"),
    ("tree_select", 25, 5, "serve_segment",
     f"{SEG}/while/body/closed_call/select/while/body/pallas_call"),
    ("decode_attention.5", 30, 20, "serve_segment",
     f"{SEG}/decode/while/body/closed_call/pallas_call"),
    ("fusion.9", 50, 5, "serve_segment", f"{SEG}/settle/while/body/add"),
    ("fusion.11", 70, 10, "stage", "jit(stage)/dot_general"),
]


def test_device_time_by_stage_and_program():
    s = scopes.reduce(_planes(OPS, []), STAGES)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.070)
    assert s.scope_s == pytest.approx({
        "refill_cache": 0.020, "catch_up": 0.005, "select": 0.005,
        "decode": 0.020, "settle": 0.005,
        # The while op's own time and the staging prefill: under no stage.
        "": 0.005 + 0.010})
    assert s.program_s == pytest.approx({"serve_segment": 0.060,
                                         "stage": 0.010})
    assert s.uncovered_ops == [["stage:fusion.11", pytest.approx(0.010)],
                               ["serve_segment:while.1",
                                pytest.approx(0.005)]]


def test_program_is_the_module_run_holding_the_op():
    modules = [(0.0, 60.0, "serve_segment"), (65.0, 85.0, "stage")]
    assert [scopes._program_at(modules, t) for t in (0, 59.9, 62, 70, 85)] \
        == ["serve_segment", "serve_segment", "", "stage", ""]


def test_op_names_from_compiled_hlo_text():
    text = (
        'ENTRY %main.1 (p: f32[4]) -> f32[4] {\n'
        '  %fusion.812 = bf16[4,16,384,8,128]{4,3,2,1,0} fusion(%p), '
        'kind=kLoop, calls=%fused_computation.1, metadata={op_type="gather" '
        'op_name="jit(serve_segment)/while/body/refill_cache/gather" '
        'source_file="x.py" source_line=3}\n'
        '  %copy.1914 = f32[4]{0} copy(%p)\n'
        '  ROOT %decode_attention.5 = f32[4]{0} custom-call(%p), '
        'metadata={op_name="jit(serve_segment)/while/body/decode/pallas_call"}'
        '\n}\n')
    assert scopes.hlo_op_names(text) == {
        "fusion.812": "jit(serve_segment)/while/body/refill_cache/gather",
        "decode_attention.5": "jit(serve_segment)/while/body/decode/"
                              "pallas_call"}


def test_stage_is_the_innermost_named_scope():
    path = "jit(serve_segment)/while/body/select/refill_cache/gather"
    assert scopes.stage_of(path, STAGES) == "refill_cache"
    assert scopes.stage_of("jit(stage)/select_n", STAGES) == ""
    assert scopes.program_of("jit_serve_segment(12)") == "serve_segment"
    assert scopes.program_of("jit_stage") == "stage"


def test_idle_gaps_take_the_innermost_span_over_most_of_them():
    ops = [("a", 0, 10, "serve_segment", ""), ("b", 50, 10, "stage", ""),
           ("c", 90, 10, "serve_segment", "")]
    spans = [("bench.poll", 5, 90), ("serve.fetch", 8, 12),
             ("serve.harvest", 20, 2), ("serve.stage", 40, 12),
             ("serve.dispatch", 86, 2), ("bench.submit", 96, 1)]
    s = scopes.reduce(_planes(ops, spans), STAGES)
    # 10-50: fetch 10 ms, harvest 2, stage 10, poll alone 18 -> poll;
    # 60-90: poll alone 26 ms, dispatch 2 -> poll.
    assert s.idle_gaps == [["bench.poll", pytest.approx(0.040)],
                           ["bench.poll", pytest.approx(0.030)]]
    spans[0] = ("bench.poll", 5, 90)
    spans[1] = ("serve.fetch", 8, 35)
    s = scopes.reduce(_planes(ops, spans), STAGES)
    assert s.idle_gaps[0] == ["serve.fetch", pytest.approx(0.040)]
    assert scopes.label_gap(0, 5, spans, scopes._depths(spans)) == \
        "host.other"


def test_trace_reduce_reads_the_same_device_times_from_these_planes():
    planes = _planes(OPS, [("bench.poll", 60, 10), ("serve.fetch", 61, 5)])
    plain = {name: {ln: evs for ln, evs in lines.items()
                    if ln == trace.OP_LINE or name == trace.HOST_PLANE}
             for name, lines in planes.items()}
    a, b = trace.reduce(planes), trace.reduce(plain)
    assert (a.busy_s, a.kernel_s, a.device_ops) == \
        (b.busy_s, b.kernel_s, b.device_ops)


def _context(stats, kernel_s=None, cell=None):
    summary = None
    if kernel_s is not None:
        summary = trace.Summary(window_s=1.0, busy_s=1.0, chips=1,
                                kernel_s=kernel_s, device_ops=[],
                                idle_gaps=[])
    cell = cell or registry.cell("qwen2.5-32b-l4.deep-sessions")
    return harness.Context(
        cell=cell, arch=registry.arch(cell["config"]), stats=stats,
        window_s=1.0, flops=0.0,
        peak={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        trace=summary)


def test_decode_attention_roofline_by_hand():
    read = registry.metric_reader("decode_attention_roofline")
    # 4 layers x K and V x 8 heads x 128 x 2 bytes = 16 KiB a position.
    stats = {"attended_positions": 1_000_000, "completed": 1}
    ctx = _context(stats, {"decode_attention": 0.1})
    assert read(ctx) == pytest.approx(100 * 16384e6 / (819e9 * 0.1))
    assert read(_context(stats, {})) is None
    assert read(_context(stats)) is None


@pytest.mark.parametrize("name,counter", [
    ("queue_wait_ms_mean", "queue_wait_us"),
    ("answer_wait_ms_mean", "answer_wait_us"),
    ("decode_attention_roofline", "attended_positions"),
])
def test_new_readers_on_the_toy_run(toy_root, name, counter):
    """The counters a run of the toy cell leaves give each reader a number;
    a program without them (the parent's) gives none, and no error."""
    c = registry.cell("toy.sessions", toy_root)
    served = harness.serve(c, 7, 1.0, False, t_start=time.perf_counter(),
                           root=toy_root)
    stats = served.stats
    assert stats["completed"] > 0 and stats[counter] > 0
    read = registry.metric_reader(name)
    ctx = _context(stats, {"decode_attention": served.window_s / 10}, c)
    value = read(ctx)
    assert value is not None and value > 0
    if name != "decode_attention_roofline":
        assert value == pytest.approx(
            stats[counter] / stats["completed"] / 1e3)
        assert value < 1e3 * served.window_s + harness.LATE_S * 1e3
    parent = {k: v for k, v in stats.items() if k != counter}
    assert read(_context(parent, {"decode_attention": 1.0}, c)) is None


def test_load_keeps_the_programs_host_spans(toy_root, tmp_path):
    """A profiler trace of fused polls holds the service's ``serve.*`` spans
    on the host, in the same profiler trace as the benchmark's
    ``bench.*``."""
    import glob

    import jax

    from bench import system

    c = registry.cell("toy.sessions", toy_root)
    cfg = registry.arch(c["config"], toy_root).model_config(c["config"])
    svc = system.build_service(cfg, system.make_weights(cfg, 1), c)
    svc.submit([5, 6, 7])
    svc.poll()                                  # compile outside the trace
    svc.submit([3, 4])
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.poll"):
                svc.poll()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    names = {ev[0] for evs in scopes.load(path)[trace.HOST_PLANE].values()
             for ev in evs}
    assert {trace.WINDOW_SPAN, "bench.poll", "serve.stage", "serve.dispatch",
            "serve.fetch", "serve.harvest"} <= names
    assert not {n for n in names if not n.startswith(scopes.SPAN_PREFIXES)}


def _recorded():
    import gzip
    import json
    from pathlib import Path

    data = Path(__file__).resolve().parent / "data"
    return json.loads(gzip.decompress(
        (data / "v5e_deep_sessions_scopes.json.gz").read_bytes()))


@pytest.mark.parametrize("which", ["gap", "tick"])
def test_recorded_tpu_slice_by_stage_and_program(which):
    """20 ms of a traced deep-sessions window on one v5e, its ops mapped to
    their programs by the ``XLA Modules`` line and to their stages by the
    compiled ``serve_segment``'s ``op_name``s: the reduction agrees with a
    10 ns timeline painted op by op; the gap slice's idle gap, where the
    host had fetched a segment's answers and was staging the next request,
    reads ``serve.stage``."""
    from repro.core.batched_async_search import TICK_SCOPES

    rec = _recorded()["slices"][which]
    planes, want = rec["planes"], rec["expected"]
    dev = planes[f"{trace.DEVICE_PREFIX}0"]
    dev[scopes.OP_INFO] = scopes.op_info(dev, rec["op_names"])
    s = scopes.reduce(planes, TICK_SCOPES)
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(want["busy_s_timeline_10ns"], abs=1e-7)
    assert set(s.scope_s) == set(want["scope_s_timeline_10ns"])
    for k, v in want["scope_s_timeline_10ns"].items():
        assert s.scope_s[k] == pytest.approx(v, abs=1e-7), k
    for k, v in want["program_s_timeline_10ns"].items():
        assert s.program_s[k] == pytest.approx(v, abs=1e-7), k
    # The old reduction reads the same device times from these planes.
    old = trace.reduce(planes)
    assert old.busy_s == pytest.approx(s.busy_s, rel=1e-12)
    if which == "gap":
        assert s.idle_gaps[0] == ["serve.stage", pytest.approx(0.008)]
        assert old.idle_gaps[0] == ["bench.poll", pytest.approx(0.008)]
    else:
        assert "refill_cache" in s.scope_s and s.idle_gaps == []


def test_recorded_compiled_hlo_resolves_instructions_without_metadata():
    """An excerpt of the compiled ``serve_segment`` for the v5e: a fusion
    carries its root's ``op_name``; the dynamic-update-slice loops XLA made
    of the refill's scatters carry none, and resolve to the scatter through
    the loop that calls them; the whole-cache copy the loop inserted
    resolves to the segment's ``while`` (no stage)."""
    rec = _recorded()
    names = scopes.hlo_op_names(rec["hlo_excerpt"])
    assert {n: names.get(n) for n in rec["hlo_excerpt_op_names"]} == \
        rec["hlo_excerpt_op_names"]
    stages = {n: scopes.stage_of(p, STAGES) for n, p in names.items()
              if n in rec["hlo_excerpt_op_names"]}
    assert stages == {"fusion.812": "refill_cache",
                      "fusion.868": "refill_cache",
                      "dynamic-update-slice.316": "refill_cache",
                      "copy.1914": "", "decode_attention.5": "decode"}
