"""Device time by program and by tick stage, and idle gaps named after the
program's own host spans, from a profiler trace of the window.

:func:`load` reads an ``.xplane.pb`` as :func:`bench.trace.load` does, and
keeps besides, for each device op, the program it ran in and its
``op_name`` path, and on the host the program's ``serve.*`` spans beside the
benchmark's ``bench.*``.  :func:`reduce` then gives, inside the
``bench.window`` span:

* ``scope_s``: device self time by the innermost ``jax.named_scope`` of the
  op's path among the names it is given (the stages of a master tick,
  ``repro.core.batched_async_search.TICK_SCOPES``); a fusion carries the
  ``op_name`` of its root, so it counts to its root's stage, and ops under
  no stage count to ``""``;
* ``program_s``: device self time by program (``serve_segment``, ``stage``
  and the other ring programs, named after their ``jit_<name>`` module);
* ``idle_gaps``: the longest gaps between device ops, each named after the
  host span that is the innermost one over the largest part of it (a gap
  in ``bench.poll`` reads ``serve.fetch`` where the host waited there).

The result of :func:`load` is also a valid input of
:func:`bench.trace.reduce`, which reads the same numbers from it as from
its own loader's.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Optional

from . import trace

SPAN_PREFIXES = ("bench.", "serve.")
#: Per device plane, ``(program, op_name path)`` of each ``XLA Ops`` event,
#: in the order of that line.
OP_INFO = "op_info"
MODULE_LINE = "XLA Modules"
_MODULE = re.compile(r"^jit_(.+?)(\(\d+\))?$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\) -> .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)"
                    r"=(\{[^}]*\}|%[\w.\-]+)")


def program_of(module: str) -> str:
    """``jit_serve_segment(12)`` or ``jit_serve_segment`` ->
    ``serve_segment``."""
    m = _MODULE.match(module)
    return m.group(1) if m else module


def hlo_op_names(hlo_text: str) -> dict:
    """``{instruction: op_name}`` of a compiled program's HLO text
    (``SearchService.compiled_segment_text``): the TPU trace names a device
    op by its instruction and carries no ``op_name`` of its own.

    An instruction the compiler made without metadata (a loop expanding a
    scatter, a copy it inserted) takes the ``op_name`` of the instruction
    that calls its computation, and so on up: the expanded scatter's loop
    carries the scatter's name.  One with no such caller keeps none."""
    own, comp_of, caller = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name, rest = m.groups()
        comp_of[name] = comp
        op = _OP_NAME.search(rest)
        if op:
            own[name] = op.group(1)
        for called in _CALLS.findall(rest):
            for c in re.findall(r"%([\w.\-]+)", called):
                caller.setdefault(c, name)

    def resolve(name, seen=()):
        if name in own or name in seen:
            return own.get(name, "")
        up = caller.get(comp_of.get(name))
        return resolve(up, seen + (name,)) if up else ""

    return {name: path for name in comp_of if (path := resolve(name))}


def _program_at(modules: list, t: float) -> str:
    """The program whose module run (``(start, end, program)``, sorted)
    holds time ``t``."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return ""


def op_info(lines: dict, op_names: dict) -> list:
    """``(program, op_name path)`` of each op of a device plane's
    ``XLA Ops`` line: the program is the module run that holds the op on
    the ``XLA Modules`` line, the path ``op_names[program][instruction]``
    (``""`` where not given)."""
    modules = sorted((s, s + d, program_of(n))
                     for n, s, d in lines.get(MODULE_LINE, []))
    info, seen = [], {}
    for name, s, _ in lines[trace.OP_LINE]:
        program = _program_at(modules, s)
        key = (program, op_names.get(program, {}).get(name, ""))
        info.append(seen.setdefault(key, key))
    return info


def load(path: str, op_names: Optional[dict] = None) -> dict:
    """:func:`bench.trace.load` of ``path``, with :data:`OP_INFO` on each
    device plane (:func:`op_info`; ``op_names`` maps each program to its
    :func:`hlo_op_names`) and the host's ``serve.*`` spans kept too."""
    from jax.profiler import ProfileData

    op_names = op_names or {}
    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        host = plane.name == trace.HOST_PLANE
        if not (plane.name.startswith(trace.DEVICE_PREFIX) or host):
            continue
        lines = {}
        for line in plane.lines:
            if host or line.name in (trace.OP_LINE, MODULE_LINE):
                name = trace.op_name if line.name == trace.OP_LINE else str
                evs = [(name(e.name), float(e.start_ns), float(e.duration_ns))
                       for e in line.events
                       if not host or e.name.startswith(SPAN_PREFIXES)]
                if evs:
                    lines[line.name] = evs
        if not host and trace.OP_LINE in lines:
            lines[OP_INFO] = op_info(lines, op_names)
        if lines:
            out[plane.name] = lines
    return out


@dataclasses.dataclass
class ScopeSummary:
    window_s: float
    busy_s: float          # mean over the device planes
    scope_s: dict          # stage ("" for none) -> self seconds (chip mean)
    program_s: dict        # program -> self seconds (chip mean)
    uncovered_ops: list    # [["program:op", self seconds]] top ops, no stage
    idle_gaps: list        # [[host span, seconds]] longest gaps


def stage_of(path: str, stages) -> str:
    """The innermost component of ``path`` that is one of ``stages``."""
    for part in reversed(path.split("/")):
        if part in stages:
            return part
    return ""


def _depths(spans: list) -> list:
    """How many other spans hold each span."""
    return [sum(1 for j, (_, s2, d2) in enumerate(spans)
                if j != i and s2 <= s and s + d <= s2 + d2
                and (s2, d2) != (s, d))
            for i, (_, s, d) in enumerate(spans)]


def label_gap(gs: float, ge: float, spans: list, depths: list) -> str:
    """The span that is the innermost one over the largest part of the gap
    ``[gs, ge)``; ``host.other`` where no span covers any of it."""
    cuts = sorted({gs, ge} | {x for _, s, d in spans for x in (s, s + d)
                              if gs < x < ge})
    time_by = {}
    for a, b in zip(cuts, cuts[1:]):
        best, depth = None, -1
        for (name, s, d), dep in zip(spans, depths):
            if s <= a and b <= s + d and dep > depth:
                best, depth = name, dep
        if best is not None:
            time_by[best] = time_by.get(best, 0.0) + (b - a)
    if not time_by:
        return "host.other"
    return max(time_by, key=time_by.get)


def reduce(planes: dict, stages, top: int = 10) -> Optional[ScopeSummary]:
    """Stage, program and idle-gap times inside the ``bench.window`` span;
    ``None`` when the trace holds no window or no device op."""
    window, spans = trace._window(planes)
    if window is None:
        return None
    w0, w1 = window
    devices = [p for name, p in planes.items()
               if name.startswith(trace.DEVICE_PREFIX) and trace.OP_LINE in p]
    if not devices:
        return None
    stages = set(stages)
    busy = 0.0
    by_scope: dict = {}
    by_program: dict = {}
    uncovered: dict = {}
    gaps = []
    for lines in devices:
        ops = lines[trace.OP_LINE]
        info = lines.get(OP_INFO, [("", "")] * len(ops))
        clipped = []
        for (name, s, d), (program, path) in zip(ops, info):
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 > s0:
                clipped.append(((name, s0, e0), program, path))
        own = trace._self_times([c[0] for c in clipped])
        for ((name, _, _), program, path), t in zip(clipped, own):
            stage = stage_of(path, stages)
            by_scope[stage] = by_scope.get(stage, 0.0) + t
            by_program[program] = by_program.get(program, 0.0) + t
            if not stage:
                key = f"{program}:{name}"
                uncovered[key] = uncovered.get(key, 0.0) + t
        merged = trace._union([(s0, e0) for (_, s0, e0), _, _ in clipped])
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
                 if ge > gs]
    if busy <= 0:
        return None
    n = len(devices)
    depths = _depths(spans)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return ScopeSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy / n * 1e-9,
        scope_s={k: v / n * 1e-9 for k, v in by_scope.items()},
        program_s={k: v / n * 1e-9 for k, v in by_program.items()},
        uncovered_ops=[[k, v / n * 1e-9] for k, v in sorted(
            uncovered.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[label_gap(gs, ge, spans, depths), (ge - gs) * 1e-9]
                   for gs, ge in longest],
    )
