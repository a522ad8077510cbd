"""Device time by stage scope, and idle time by program span, from a
profiler trace of ladder dispatches.

The program names the parts of its access scan with ``jax.named_scope``
(``repro.obs.scope``): ``<stage>.lookup`` and ``<stage>.fill`` for each
translation stage, ``data``, ``stats``, ``init`` and ``finalize``.  The
names reach the ``op_name`` metadata of every HLO instruction traced
inside them, as path segments such as
``jit(run_systems)/.../while/body/closed_call/vmap(l2_tlb.lookup)/add``.
A TPU op event carries no such stat: its name is the instruction's HLO
text (``%copy.7 = s32[...] copy(...)``).  So the op_name is looked up
by instruction name in the HLO proto of the module that ran the op,
which the profiler writes into its ``/host:metadata`` plane when
``ProfileOptions.enable_hlo_proto`` is on.  Op self time (as in
``trace.self_times``) inside the program's module executions is then
summed by scope and op kind; an op with no scope goes to ``unscoped``.

The program's ``sim.launch``, ``sim.fetch`` and ``sim.unpack`` spans
are profiler annotations on the harness's thread; ``idle_by_span``
splits the device's idle time inside a dispatch among them.

    python3 bench/scopes.py --workload <cell> --seed <n>

runs a cell's set-up, then one dispatch with the profiler off and one
traced with the HLO protos kept, on the chip, and prints one JSON
object: the per-scope device times and their groups per lane and
access, the idle split, the trace seconds of each scope during set-up,
the unpacking's device reads, and what the instrumentation costs.  The
benchmark's harness (``run.py --trace 1``) does not call this module.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import sys
import time

T0 = time.perf_counter()  # the CLI's set-up is timed from here
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(ROOT, ".bench_cache")

if __name__ == "__main__":
    # the paths run.py sets, so that both share one compile cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.environ["TPU_LOG_DIR"] = os.path.join(CACHE, "tpu_logs")
    os.environ["REPRO_OBS_DIR"] = os.path.join(CACHE, "obs")
    _platforms = os.environ.get("JAX_PLATFORMS", "")
    if _platforms and "cpu" not in _platforms.split(","):
        os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace  # noqa: E402

UNSCOPED = "unscoped"
SCOPE = re.compile(r"[a-z0-9_]+\.(?:lookup|fill)|data|stats|init|finalize")
_WRAPPER = re.compile(r"(?:vmap|jvp|transpose|remat|checkpoint|pmap)"
                      r"\((.*)\)")
_INSTRUCTION = re.compile(r"%?([^\s=%]+)")
PROGRAM_SPANS = ("sim.launch", "sim.fetch", "sim.unpack")
# the stage groups of the per-lane device-time metrics
GROUPS = {"tlb": ("l1_tlb", "l2_tlb", "l3_tlb"),
          "victima": ("victima",),
          "walk": ("ptw", "ptw2d", "pom", "restseg", "rev"),
          "cache": ("data",)}
HLO_PROTO_STAT = "Hlo Proto"
METADATA_PLANE = "/host:metadata"


# ------------------------------------------------------------ op_name
def _segments(path: str) -> list[str]:
    """``a/vmap(b/c)/d`` -> ``["a", "vmap(b/c)", "d"]``: the name
    stack's segments, split at slashes outside parentheses."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return out


def scope_of(op_name: str) -> str | None:
    """The innermost scope an HLO ``op_name`` names, with transformation
    wrappers such as ``vmap(...)`` taken off, or None.  A name that XLA
    merged from several (``a;b``) gives the first part's scope."""
    for part in op_name.split(";"):
        found = None
        for seg in _segments(part):
            while (m := _WRAPPER.fullmatch(seg)):
                seg = m[1]
            if SCOPE.fullmatch(seg):
                found = seg
        if found:
            return found
    return None


def group_of(scope: str) -> str | None:
    """The metric group (``GROUPS``) of a scope; None for ``stats``,
    ``init``, ``finalize`` and ``unscoped``."""
    stage = scope.split(".")[0]
    for group, stages in GROUPS.items():
        if stage in stages:
            return group
    return None


def instruction(event_name: str) -> str:
    """``"%copy.7 = s32[2]{0} copy(...)"`` (TPU) or ``"copy.7"`` (CPU)
    -> ``"copy.7"``."""
    m = _INSTRUCTION.match(event_name)
    return m[1] if m else event_name


# ------------------------------------------------------ protobuf walk
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """``(field number, value)`` of the protobuf message in
    ``buf[lo:hi]``: an int for a varint, ``(start, end)`` in ``buf``
    for a length-delimited field, None for a fixed-width one."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _instruction_op_names(buf: bytes, lo: int, hi: int) -> dict[str, str]:
    """``{instruction name: op_name}`` of an ``HloProto`` in buf[lo:hi]
    (hlo_module 1 > computations 3 > instructions 2 > name 1, metadata
    7 > op_name 2)."""
    out = {}
    for f, mod in _fields(buf, lo, hi):
        if f != 1:
            continue
        for g, comp in _fields(buf, *mod):
            if g != 3:
                continue
            for h, ins in _fields(buf, *comp):
                if h != 2:
                    continue
                name = op = None
                for k, v in _fields(buf, *ins):
                    if k == 1:
                        name = _text(buf, v)
                    elif k == 7:
                        for m, w in _fields(buf, *v):
                            if m == 2:
                                op = _text(buf, w)
                if name and op:
                    out[name] = op
    return out


def hlo_op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """``{module name: {instruction name: op_name}}`` from the HLO protos
    in a serialized XSpace's ``/host:metadata`` plane (XSpace planes 1;
    XPlane name 2, event_metadata 4, stat_metadata 5; map entries key 1,
    value 2; XEventMetadata name 2, stats 5; XStat metadata_id 1,
    bytes_value 6).  Empty where the trace was taken without them."""
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        name, events, stat_names = None, [], {}
        for g, v in _fields(xspace, *plane):
            if g == 2:
                name = _text(xspace, v)
                if name != METADATA_PLANE:
                    break  # the name comes before the metadata maps
            elif g == 4:
                events += [w for k, w in _fields(xspace, *v) if k == 2]
            elif g == 5:
                for k, w in _fields(xspace, *v):
                    if k == 2:
                        meta = dict(_fields(xspace, *w))
                        if 1 in meta and 2 in meta:
                            stat_names[meta[1]] = _text(xspace, meta[2])
        if name != METADATA_PLANE:
            continue
        for ev in events:
            ev_name, protos = None, []
            for k, w in _fields(xspace, *ev):
                if k == 2:
                    ev_name = _text(xspace, w)
                elif k == 5:
                    stat = dict(_fields(xspace, *w))
                    if (stat_names.get(stat.get(1)) == HLO_PROTO_STAT
                            and 6 in stat):
                        protos.append(stat[6])
            for p in protos:
                out.setdefault(ev_name, {}).update(
                    _instruction_op_names(xspace, *p))
    return out


# ----------------------------------------------------- device scopes
def self_times_by_op(ops, windows) -> dict[tuple[str, str], int]:
    """Self time (as ``trace.self_times``) of each ``(name, start,
    dur)`` op, sorted by start, that lies inside one of ``windows``:
    sorted, disjoint ``(start, end, module)``.  Keyed by ``(module, op
    name)``, so each distinct name is resolved once afterwards."""
    out: dict[tuple[str, str], int] = {}
    stack: list[list] = []  # [end, key, dur, nested]
    w = 0

    def close(item):
        out[item[1]] = out.get(item[1], 0) + max(item[2] - item[3], 0)

    for name, start, dur in ops:
        while w < len(windows) and windows[w][1] <= start:
            w += 1
        if (w == len(windows) or start < windows[w][0]
                or start + dur > windows[w][1]):
            continue
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += dur
        stack.append([start + dur, (windows[w][2], name), dur, 0])
    while stack:
        close(stack.pop())
    return out


def by_scope(op_ns: dict, names: dict) -> dict[str, dict[str, int]]:
    """``{scope: {op kind: ns}}`` of ``self_times_by_op``'s result, with
    each op's op_name from ``names`` (``hlo_op_names``)."""
    out: dict[str, dict[str, int]] = {}
    for (module, name), ns in op_ns.items():
        op = names.get(module, {}).get(instruction(name))
        scope = (scope_of(op) if op else None) or UNSCOPED
        kinds = out.setdefault(scope, {})
        kind = trace.op_kind(name)
        kinds[kind] = kinds.get(kind, 0) + ns
    return out


def scope_times(path: str, span_name: str, program: str) -> dict:
    """Per device plane of the ``*.xplane.pb`` at ``path``: the module
    time of ``program``'s executions that lie inside the ``span_name``
    spans (``module_ns``, and how many: ``modules``) and their op self
    time ``{scope: {op kind: ns}}`` (``scopes``); ``named`` counts the
    executions whose HLO proto the trace holds."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    names = hlo_op_names(raw)
    planes = list(ProfileData.from_serialized_xspace(raw).planes)
    marks = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
             for p in planes if not trace.DEVICE_PLANE.fullmatch(p.name)
             for ln in p.lines for e in ln.events if e.name == span_name]
    pat = re.compile(rf"\b(jit_)?{re.escape(program)}\b")
    out = {}
    for plane in planes:
        if not trace.DEVICE_PLANE.fullmatch(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if trace.MODULES_LINE not in lines or trace.OPS_LINE not in lines:
            continue
        mods = sorted(
            (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in lines[trace.MODULES_LINE].events
            if pat.search(e.name) and any(
                lo <= e.start_ns and e.start_ns + e.duration_ns <= hi
                for lo, hi in marks))
        ops = ((e.name, int(e.start_ns), int(e.duration_ns))
               for e in lines[trace.OPS_LINE].events)
        out[plane.name] = {
            "modules": len(mods),
            "named": sum(m in names for _, _, m in mods),
            "module_ns": sum(e - s for s, e, _ in mods),
            "scopes": by_scope(self_times_by_op(ops, mods), names)}
    return out


def device_scopes(planes: dict) -> list[list]:
    """``[[scope, seconds, [[op kind, seconds] x 3]], ...]``, most time
    first, averaged over the chips."""
    total: dict[str, dict[str, int]] = {}
    for per in planes.values():
        for scope, kinds in per["scopes"].items():
            t = total.setdefault(scope, {})
            for k, ns in kinds.items():
                t[k] = t.get(k, 0) + ns
    n = max(len(planes), 1)
    rows = []
    for scope, kinds in total.items():
        top = sorted(kinds.items(), key=lambda kv: -kv[1])[:3]
        rows.append([scope, sum(kinds.values()) / 1e9 / n,
                     [[k, ns / 1e9 / n] for k, ns in top]])
    return sorted(rows, key=lambda r: -r[1])


def stage_metrics(planes: dict, lanes_per_chip: int,
                  seg_accesses: int) -> dict:
    """The per-lane device times of the ``GROUPS`` (us per lane and
    access, as ``sim.scan_us_per_lane_step`` divides) and the share of
    op self time no scope claims, in percent; {} without a program."""
    mods = sum(p["modules"] for p in planes.values())
    if not mods:
        return {}
    groups = {g: 0 for g in GROUPS}
    total = unscoped = 0
    for per in planes.values():
        for scope, kinds in per["scopes"].items():
            ns = sum(kinds.values())
            total += ns
            if scope == UNSCOPED:
                unscoped += ns
            elif (g := group_of(scope)) is not None:
                groups[g] += ns
    out = {f"sim.scan.{g}_us_per_lane_step":
           ns / mods / 1e3 / (lanes_per_chip * seg_accesses)
           for g, ns in groups.items()}
    out["sim.scan.unscoped_share"] = 100.0 * unscoped / total if total \
        else None
    return out


# ------------------------------------------------------ idle by span
def idle_by_span(events, plane: str, span, names=PROGRAM_SPANS) -> dict:
    """Idle nanoseconds of ``plane`` inside the harness span ``span``,
    split by the program span of ``names`` on the span's thread that
    they fall in; the rest under ``"none"``."""
    busy = trace.busy(events, plane, span.start, span.end)
    edges = [span.start] + [x for iv in busy for x in iv] + [span.end]
    holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
             if edges[i + 1] > edges[i]]
    inner = [e for e in events if e.plane == span.plane
             and e.line == span.line and e.name in names
             and span.start <= e.start and e.end <= span.end]
    out = {n: 0 for n in names}
    for lo, hi in holes:
        for e in inner:
            out[e.name] += max(0, min(hi, e.end) - max(lo, e.start))
    out["none"] = sum(hi - lo for lo, hi in holes) - sum(out.values())
    return out


# ------------------------------------------------------ measurement
def trace_seconds(jaxpr_trace_s: float) -> dict:
    """The trace seconds of each scope so far (registry counters
    ``sim.trace_s.<scope>``), and the rest of ``jaxpr_trace_s`` (JAX's
    ``jaxpr_trace_duration``) that no scope holds."""
    import repro.obs as obs

    prefix = obs.names.TRACE_S_PREFIX
    per = {k[len(prefix):]: v
           for k, v in obs.REGISTRY.snapshot()["counters"].items()
           if k.startswith(prefix)}
    return {"jaxpr_trace_s": jaxpr_trace_s, "scopes": per,
            "outside_scopes_s": jaxpr_trace_s - sum(per.values())}


def measure(c, metric_names, logdir: str) -> dict:
    """Two more dispatches of the set-up cell ``c`` (``bench.cell.Cell``):
    one with the profiler off, one traced with the HLO protos kept.
    Returns the per-layer metrics ``metric_names`` and the stage metrics
    of the traced one, its device time by scope, its idle time by
    program span, the device reads of the untraced one's unpacking,
    and what tracing costs: both dispatches' wall times, the tracer's
    own time and the read times of the trace."""
    import jax

    import repro.obs as obs
    from bench import cell as cellmod
    from bench import spec

    overhead0 = obs.overhead_s()
    reads0 = obs.REGISTRY.counter(obs.names.CTR_UNPACK_READS)
    c.window(3600.0, max_dispatches=1)
    untraced_s = c.times[-1][1] - c.times[-1][0]
    reads = obs.REGISTRY.counter(obs.names.CTR_UNPACK_READS) - reads0
    overhead = obs.overhead_s() - overhead0

    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = True
    with jax.profiler.trace(logdir, profiler_options=opts):
        c.window(3600.0, max_dispatches=1, annotate=True)
    traced_s = c.times[-1][1] - c.times[-1][0]
    t0 = time.perf_counter()
    events = trace.read_xplane(logdir, cellmod.DISPATCH_SPAN)[0]
    t1 = time.perf_counter()
    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    planes = scope_times(path, cellmod.DISPATCH_SPAN, cellmod.PROGRAM)
    t2 = time.perf_counter()
    shutil.rmtree(logdir, ignore_errors=True)

    run = trace.TracedRun(events, cellmod.DISPATCH_SPAN, cellmod.PROGRAM,
                          c.lanes_per_chip, c.seg)
    metrics = {m: spec.metric(m).read(run) for m in metric_names}
    metrics.update(stage_metrics(planes, c.lanes_per_chip, c.seg))
    idle: dict[str, float] = {}
    for d in run.dispatches if run.readable else ():
        for n, ns in idle_by_span(events, run.planes[0], d).items():
            idle[n] = idle.get(n, 0.0) + ns / 1e9
    return {
        "metrics": metrics,
        "device_scopes": device_scopes(planes),
        "idle_by_span": [[n, s] for n, s in idle.items()],
        "module_s": sum(p["module_ns"] for p in planes.values()) / 1e9,
        "op_self_s": sum(ns for p in planes.values()
                         for kinds in p["scopes"].values()
                         for ns in kinds.values()) / 1e9,
        "named_modules": sum(p["named"] for p in planes.values()),
        "unpack_reads": reads,
        "cost": {"untraced_dispatch_s": untraced_s,
                 "traced_dispatch_s": traced_s,
                 "obs_overhead_s": overhead,
                 "read_xplane_s": t1 - t0, "scope_times_s": t2 - t1},
    }


# --------------------------------------------------------------- CLI
def main(argv=None) -> int:
    from bench import cell as cellmod
    from bench import run, spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    run.require_chips(cell["chips"])
    os.makedirs(CACHE, exist_ok=True)
    c = cellmod.Cell(spec.config(cell["config"]),
                     spec.traffic(cell["traffic"]), args.seed,
                     cell["chips"], CACHE)
    with cellmod.CompileWatch() as watch:
        c.setup()
    out = {"setup_s": time.perf_counter() - T0,
           "trace_s": trace_seconds(watch.secs[cellmod.COMPILE_EVENTS[0]])}
    out.update(measure(c, [m["name"] for m in spec.metrics_of(
        bench, "per_layer", cell["name"])], os.path.join(CACHE,
                                                          "scopes_profile")))
    out["device"] = run.device_info(cell["chips"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
