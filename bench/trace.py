"""Device trace of the measured window, and its reduction to numbers.

:class:`DeviceTrace` records the window with JAX's profiler into a
temporary directory, reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData`` and keeps only plain tuples:
``{plane: {line: [(name, start_ns, duration_ns), ...]}}``.  :func:`reduce`
turns that into the device's busy time (the union of op intervals), the
time of each named kernel, the device ops that took most time, and the idle
gaps between ops, each named after the benchmark's host span (``bench.*``
``TraceAnnotation``) that overlaps it most.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Optional

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"\.\d+$")
_INSTR = re.compile(r"%([^ ]+) = ")


def op_name(name: str) -> str:
    """The HLO instruction name of a device op event, which the TPU trace
    names by the instruction's whole text (``%decode_attention.5 = bf16[...]
    custom-call(...)`` -> ``decode_attention.5``)."""
    m = _INSTR.match(name)
    return m.group(1) if m else name


def base_name(name: str) -> str:
    """An op name without its numeric suffix (``decode_attention.5`` ->
    ``decode_attention``); Pallas kernels keep the ``name`` they were given
    as this base."""
    return _SUFFIX.sub("", op_name(name))


def load(path: str) -> dict:
    """``{plane: {line: [(name, start_ns, duration_ns)]}}`` of one
    ``.xplane.pb``, keeping device planes and the host's ``bench.*``
    spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        keep_host = plane.name == HOST_PLANE
        if not (plane.name.startswith(DEVICE_PREFIX) or keep_host):
            continue
        lines = {}
        for line in plane.lines:
            if keep_host:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in line.events if e.name.startswith(SPAN_PREFIX)]
            elif line.name == OP_LINE:
                evs = [(op_name(e.name), float(e.start_ns),
                        float(e.duration_ns)) for e in line.events]
            else:
                continue
            if evs:
                lines[line.name] = evs
        if lines:
            out[plane.name] = lines
    return out


class DeviceTrace:
    """Context manager that traces its body when ``enabled``; afterwards
    :attr:`planes` holds :func:`load`'s result and the files are gone."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.planes: Optional[dict] = None
        self._dir = None

    def __enter__(self):
        if self.enabled:
            import jax

            self._dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self._dir)
        return self

    def __exit__(self, *exc):
        if not self.enabled:
            return False
        import jax

        try:
            jax.profiler.stop_trace()
            files = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                              recursive=True)
            if files and exc[0] is None:
                self.planes = load(max(files, key=os.path.getsize))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                  # mean over the device planes traced
    chips: int
    kernel_s: dict                 # base op name -> self seconds (chip mean)
    device_ops: list               # [[op name, self seconds]] top 10
    idle_gaps: list                # [[host span, seconds]] 10 longest gaps


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _self_times(intervals):
    """Self time of each ``(name, start, end)`` interval of one line: its
    length less the parts its nested children cover (a ``while`` op spans
    the ops of its body on the same line)."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][1], -intervals[i][2]))
    own = [e - s for _, s, e in intervals]
    stack = []
    for i in order:
        _, s, e = intervals[i]
        while stack and intervals[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, intervals[stack[-1]][2]) - s
        stack.append(i)
    return own


def _window(planes: dict):
    host = planes.get(HOST_PLANE, {})
    spans = [ev for evs in host.values() for ev in evs]
    win = [ev for ev in spans if ev[0] == WINDOW_SPAN]
    if not win:
        return None, spans
    _, s, d = max(win, key=lambda ev: ev[2])
    return (s, s + d), [ev for ev in spans if ev[0] != WINDOW_SPAN]


def reduce(planes: dict, top: int = 10) -> Optional[Summary]:
    """Busy time, kernel times, top ops and idle gaps inside the
    ``bench.window`` span; ``None`` when the trace holds no window or no
    device op."""
    window, spans = _window(planes)
    if window is None:
        return None
    w0, w1 = window
    devices = [p for name, p in planes.items()
               if name.startswith(DEVICE_PREFIX) and OP_LINE in p]
    if not devices:
        return None
    busy_total = 0.0
    kernels: dict = {}
    ops: dict = {}
    gaps = []
    for lines in devices:
        clipped = []
        for name, s, d in lines[OP_LINE]:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 > s0:
                clipped.append((op_name(name), s0, e0))
        for (name, _, _), own in zip(clipped, _self_times(clipped)):
            kernels[base_name(name)] = kernels.get(base_name(name), 0.0) + own
            ops[name] = ops.get(name, 0.0) + own
        merged = _union([(s0, e0) for _, s0, e0 in clipped])
        busy_total += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gaps.append((gs, ge))
    n = len(devices)
    if busy_total <= 0:
        return None

    def label(gs, ge):
        best, name = 0.0, "host.other"
        for sn, ss, sd in spans:
            ov = min(ge, ss + sd) - max(gs, ss)
            if ov > best:
                best, name = ov, sn
        return name

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return Summary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / n * 1e-9,
        chips=n,
        kernel_s={k: v / n * 1e-9 for k, v in kernels.items()},
        device_ops=[[k, v / n * 1e-9] for k, v in
                    sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[label(gs, ge), (ge - gs) * 1e-9] for gs, ge in longest],
    )
