"""From a profiler trace to the per-layer numbers.

A trace is reduced to a flat list of events ``(plane, line, name,
start_ns, dur_ns)``: the harness's own spans and the other host events
(``TraceAnnotation`` and JAX's own host events, on the host clock), and
the ``XLA Modules`` line of each ``/device:TPU:<n>`` plane, one event
per program execution.  The ``XLA Ops`` lines, which hold an event per
op per loop iteration (millions in one dispatch), are not kept: their
self time is summed by op kind while the file is read.

- busy: the union of a device's module intervals inside a window;
- program time: the summed module events of one program;
- idle gaps: the holes in the busy union, each named by the harness
  span it falls in and the innermost host event on that span's thread;
- top device ops: self time (an op's duration less that of the ops
  nested in it on its line), summed by opcode and result shape.

In a recorded trace the device clock is aligned to the host's to about
a millisecond.
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP = re.compile(r"^%\S+ = (.*?) ([a-z][\w-]*)\(")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur


def op_kind(name: str) -> str:
    """``"%copy.7 = s32[28,6]{1,0} copy(...)"`` -> ``"copy s32[28,6]"``."""
    m = _OP.match(_LAYOUT.sub("", name))
    if not m:
        return name
    shape = m[1] if len(m[1]) <= 60 else m[1][:57] + "..."
    return f"{m[2]} {shape}"


def self_times(ops, lo: int, hi: int) -> dict[str, int]:
    """Self time by op kind of one line's ``(name, start, dur)`` ops that
    lie inside ``[lo, hi]``; nesting is read from the times, so the ops
    must come sorted by start (as a trace line holds them)."""
    out: dict[str, int] = {}
    stack: list[list] = []  # [end, kind, dur, nested]

    def close(item):
        out[item[1]] = out.get(item[1], 0) + max(item[2] - item[3], 0)

    for name, start, dur in ops:
        if start < lo or start + dur > hi:
            continue
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += dur
        stack.append([start + dur, op_kind(name), dur, 0])
    while stack:
        close(stack.pop())
    return out


def _counted(events, seen: list):
    """``(name, start, dur)`` of each event, counted into ``seen[0]``."""
    for e in events:
        seen[0] += 1
        yield e.name, int(e.start_ns), int(e.duration_ns)


def read_xplane(logdir: str, span_name: str, keep_ops: bool = False):
    """Events and op self times of the newest ``*.xplane.pb`` under
    ``logdir``; ops are summed inside the ``span_name`` spans' window,
    and their events kept only with ``keep_ops``.  Returns ``(events,
    {plane: {op kind: ns}}, info)``, where ``info`` holds the event count
    of every ``"<plane> <line>"`` and the stats of the device planes."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    planes = list(ProfileData.from_file(paths[-1]).planes)
    events = []
    info: dict = {"lines": {}, "stats": {}}
    for plane in planes:
        device = DEVICE_PLANE.fullmatch(plane.name)
        if device:
            info["stats"][plane.name] = {k: v for k, v in plane.stats}
        for line in plane.lines:
            if device and line.name != MODULES_LINE and not (
                    keep_ops and line.name == OPS_LINE):
                continue
            got = [Event(plane.name, line.name, e.name, int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
            info["lines"][f"{plane.name} {line.name}"] = len(got)
            events += got
    marks = spans(events, span_name)
    ops: dict[str, dict[str, int]] = {}
    if marks:
        lo, hi = marks[0].start, marks[-1].end
        for plane in planes:
            if not DEVICE_PLANE.fullmatch(plane.name):
                continue
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                seen = [0]
                ops[plane.name] = self_times(_counted(line.events, seen),
                                             lo, hi)
                info["lines"][f"{plane.name} {line.name}"] = seen[0]
    return events, ops, info


def save(events: list[Event], path: str) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump([list(e) for e in events], f)


def load(path: str) -> list[Event]:
    with open(path, encoding="utf-8") as f:
        return [Event(*e) for e in json.load(f)]


def device_planes(events) -> list[str]:
    planes = {e.plane for e in events if DEVICE_PLANE.fullmatch(e.plane)}
    return sorted(planes, key=lambda p: int(DEVICE_PLANE.fullmatch(p)[1]))


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy(events, plane: str, lo: int, hi: int) -> list[tuple[int, int]]:
    return union(((e.start, e.end) for e in events if e.plane == plane
                  and e.line == MODULES_LINE), lo, hi)


def busy_ns(events, plane: str, lo: int, hi: int) -> int:
    return sum(e - s for s, e in busy(events, plane, lo, hi))


def program_ns(events, plane: str, program: str, lo: int, hi: int) -> int:
    """Summed time of the module events of ``program`` (by its name, as
    in ``jit_<program>(<id>)``) that start inside ``[lo, hi]``."""
    pat = re.compile(rf"\b(jit_)?{re.escape(program)}\b")
    return sum(e.dur for e in events
               if e.plane == plane and e.line == MODULES_LINE
               and lo <= e.start <= hi and pat.search(e.name))


def holds_program(events, span_name: str, program: str) -> bool:
    """Whether every device plane holds a module event of ``program``
    that starts and ends inside each ``span_name`` span.  The profiler
    drops the trace buffers that the host does not drain in time, and a
    program whose end fell in a dropped buffer has no module event."""
    planes = device_planes(events)
    marks = spans(events, span_name)
    pat = re.compile(rf"\b(jit_)?{re.escape(program)}\b")
    return bool(planes and marks) and all(
        any(e.plane == p and e.line == MODULES_LINE and pat.search(e.name)
            and d.start <= e.start and e.end <= d.end for e in events)
        for p in planes for d in marks)


def spans(events, name: str) -> list[Event]:
    """Host events called ``name`` (the harness's annotations)."""
    return sorted((e for e in events
                   if e.name == name and not DEVICE_PLANE.fullmatch(e.plane)),
                  key=lambda e: e.start)


def top_ops(ops: dict[str, dict[str, int]], n: int = 10) -> list[list]:
    """The ``n`` op kinds with the most self time, in seconds per chip."""
    total: dict[str, int] = {}
    for per in ops.values():
        for k, v in per.items():
            total[k] = total.get(k, 0) + v
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / max(len(ops), 1)] for k, v in ranked]


def idle_gaps(events, plane: str, span: Event, n: int = 10) -> list[list]:
    """The ``n`` longest holes in ``plane``'s busy union inside the
    harness span ``span``, each named ``<span>/<innermost host event>``
    after what the span's thread was doing at the hole's middle."""
    b = busy(events, plane, span.start, span.end)
    edges = [span.start] + [x for iv in b for x in iv] + [span.end]
    holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
             if edges[i + 1] > edges[i]]
    host = [e for e in events if e.plane == span.plane
            and e.line == span.line and e != span]
    out = []
    for s, e in sorted(holes, key=lambda h: h[0] - h[1])[:n]:
        mid = (s + e) // 2
        inner = [h for h in host if h.start <= mid < h.end]
        what = min(inner, key=lambda h: h.dur).name if inner else "host"
        out.append([f"{span.name}/{what}", (e - s) / 1e9])
    return out


class TracedRun:
    """What a per-layer metric reader sees of a ``--trace 1`` run.

    ``dispatches`` are the harness spans of the traced dispatch calls;
    ``lanes_per_chip`` and ``seg_accesses`` size the work of each.
    """

    def __init__(self, events, span_name: str, program: str,
                 lanes_per_chip: int, seg_accesses: int):
        self.events = events
        self.program = program
        self.lanes_per_chip = lanes_per_chip
        self.seg_accesses = seg_accesses
        self.planes = device_planes(events)
        self.dispatches = spans(events, span_name)

    @property
    def window(self) -> tuple[int, int]:
        return self.dispatches[0].start, self.dispatches[-1].end

    def busy_ns(self) -> float:
        """Device busy time in the window, averaged over the chips."""
        lo, hi = self.window
        return (sum(busy_ns(self.events, p, lo, hi) for p in self.planes)
                / len(self.planes))

    def per_dispatch(self, fn) -> list[float]:
        """``fn(plane, span)`` averaged over the chips, per dispatch."""
        return [sum(fn(p, d) for p in self.planes) / len(self.planes)
                for d in self.dispatches]

    @property
    def readable(self) -> bool:
        return bool(self.planes and self.dispatches)
