"""The reduction from a device trace to busy time, kernel time, top ops
and idle gaps: on hand-made events, and on a small slice recorded from a
TPU v5e trace of the deep-sessions cell (``data/``)."""

import gzip
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6


def _planes(ops, spans, window=(0.0, 100 * MS), chips=1):
    device = {trace.OP_LINE: [(n, s * MS, d * MS) for n, s, d in ops],
              "XLA Modules": [("jit_call(1)", 0.0, 100 * MS)]}
    planes = {f"{trace.DEVICE_PREFIX}{i}": device for i in range(chips)}
    planes[trace.HOST_PLANE] = {"python": [
        (trace.WINDOW_SPAN, window[0], window[1] - window[0])
    ] + [(n, s * MS, d * MS) for n, s, d in spans]}
    return planes


def test_busy_is_the_union_of_op_intervals():
    ops = [("fusion.1", 0, 10), ("decode_attention.3", 5, 10),
           ("tree_select", 30, 5), ("decode_attention.7", 60, 20)]
    s = trace.reduce(_planes(ops, []))
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx((15 + 5 + 20) * 1e-3)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.6)
    assert s.kernel_s["decode_attention"] == pytest.approx(0.030)
    assert s.kernel_s["tree_select"] == pytest.approx(0.005)
    assert s.device_ops[0] == ["decode_attention.7", pytest.approx(0.020)]


def test_events_are_clipped_to_the_window():
    s = trace.reduce(_planes([("a", -10, 20), ("b", 95, 10)], [],
                             window=(0.0, 100 * MS)))
    assert s.busy_s == pytest.approx(15e-3)


def test_idle_gaps_are_named_after_the_host_span_over_them():
    ops = [("a", 0, 10), ("b", 50, 10), ("c", 90, 10)]
    spans = [("bench.poll", 0, 45), ("bench.submit", 45, 3),
             ("bench.wait", 60, 30)]
    s = trace.reduce(_planes(ops, spans))
    assert s.idle_gaps == [["bench.poll", pytest.approx(0.040)],
                           ["bench.wait", pytest.approx(0.030)]]


def test_busy_and_kernels_average_over_chips():
    s = trace.reduce(_planes([("tree_select", 0, 50)], [], chips=2))
    assert s.chips == 2
    assert s.busy_s == pytest.approx(0.05)
    assert s.kernel_s["tree_select"] == pytest.approx(0.05)


def test_no_window_or_no_device_op_reads_nothing():
    planes = _planes([("a", 0, 10)], [])
    del planes[trace.HOST_PLANE]
    assert trace.reduce(planes) is None
    assert trace.reduce(_planes([], [])) is None


def test_recorded_tpu_trace_slice():
    """20 ms of a traced deep-sessions window on one v5e: the whole
    serving segment is one ``while`` op on the ``XLA Ops`` line with the
    tick's ops nested inside it."""
    rec = json.loads(gzip.decompress(
        (DATA / "v5e_deep_sessions_slice.json.gz").read_bytes()))
    want = rec["expected"]
    s = trace.reduce(rec["planes"])
    assert s.chips == 1
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-9)
    # Busy as a 10 ns timeline counts it, independently of the union.
    assert s.busy_s == pytest.approx(want["busy_s_timeline_10ns"], abs=1e-7)
    for k, v in want["kernel_s"].items():
        assert s.kernel_s[k] == pytest.approx(v, rel=1e-9)
    # Self time: the enclosing while op does not hide its body's ops.
    assert not s.device_ops[0][0].startswith("while")
    assert sum(t for _, t in s.device_ops) <= s.busy_s


def test_op_names_from_tpu_event_names():
    name = ("%decode_attention.5 = bf16[128,8,5,128]{3,2,1,0} custom-call("
            "s32[128]{0} %copy-done.216), custom_call_target=\"tpu\"")
    assert trace.op_name(name) == "decode_attention.5"
    assert trace.base_name(name) == "decode_attention"
    assert trace.base_name("tree_select") == "tree_select"


def test_self_time_excludes_nested_ops():
    ops = [("while", 0, 100), ("fusion.1", 10, 30), ("tree_select", 50, 10)]
    s = trace.reduce(_planes(ops, []))
    assert dict(s.device_ops) == pytest.approx(
        {"while": 0.060, "fusion.1": 0.030, "tree_select": 0.010})
    assert s.busy_s == pytest.approx(0.1)
