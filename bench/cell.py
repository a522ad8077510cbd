"""One cell's run: set-up, the timed window, the trace, the check.

Set-up builds ONE ladder runner, ``mmu.make_systems_runner(cfg, plan)``
with the ladder's ``ladder_base_config``, ``ladder_dyn`` and
``plan_mesh``, as ``runner.run_ladder`` builds it, and warms its single
``[S, W, seg_accesses]`` shape (``[S, W, C, seg_accesses]`` for a
traffic file that declares C ``cores``) with one dispatch.  The window
then calls ``run_fn(dyns, traces)`` on fresh segments until ``seconds``
have passed; a dispatch that starts before the deadline finishes.  A
producer thread stacks the next dispatch's segments while the current
one runs.  Every segment starts from cold MMU state: the program has no
entry that carries state from one dispatch to the next.
"""
from __future__ import annotations

import os
import queue
import shutil
import sys
import threading
import time

from bench import gen, trace

DISPATCH_SPAN = "bench.dispatch"
WAIT_SPAN = "bench.wait_segments"
PROGRAM = "run_systems"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileWatch:
    """Seconds JAX spent tracing, lowering and compiling, by event, and
    how many of each happened, from ``jax.monitoring`` durations."""

    def __init__(self):
        self.secs = {e: 0.0 for e in COMPILE_EVENTS}
        self.count = {e: 0 for e in COMPILE_EVENTS}

    def __call__(self, event, duration, **kw):
        if event in self.secs:
            self.secs[event] += duration
            self.count[event] += 1

    def snapshot(self) -> dict:
        return dict(self.count)

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)


def check_config(cfg_file: dict, cores: int = 1) -> tuple:
    """The ladder the file names, checked member by member against the
    program's registry and its core count against the traffic's
    ``cores``; returns (members, program base cfg, dyns)."""
    from bench.reference import lane
    from repro.sim import systems

    ladder = cfg_file["ladder"]
    members = tuple(systems.LADDERS[ladder])
    want = tuple(cfg_file["members"])
    if members != want:
        raise ValueError(f"ladder {ladder!r} has members {members}, the "
                         f"configuration file lists {want}")
    for m in members:
        got = lane.config_dict(systems.config(m))
        ref = lane.config_dict(lane.sim_config(cfg_file["members"][m]))
        diff = sorted(k for k in set(got) | set(ref)
                      if got.get(k) != ref.get(k))
        if diff:
            raise ValueError(f"member {m!r} runs with {diff} unlike its "
                             f"configuration file")
    if systems.mix_cores(members) != cores:
        raise ValueError(f"ladder {ladder!r} runs "
                         f"{systems.mix_cores(members)} cores, the traffic "
                         f"{cores}")
    return (members, systems.ladder_base_config(ladder),
            systems.ladder_dyn(members))


class Cell:
    """Drives one cell of the benchmark in this process."""

    def __init__(self, cfg_file: dict, traffic: dict, seed: int,
                 n_devices: int, scratch: str):
        self.cfg_file, self.traffic = cfg_file, traffic
        self.seed = seed
        self.n_devices = n_devices
        self.scratch = scratch
        self.seg = int(traffic["seg_accesses"])
        self.width = int(traffic["width"])
        self.cores = int(traffic.get("cores", 1))
        self.sources = gen.sources(traffic, seed)
        self.dispatched: list[dict] = []   # numpy traces of each dispatch
        self.results: list[tuple] = []     # (per, extras) of each dispatch
        self.times: list[tuple] = []       # (start, end) of each dispatch
        self.waits: list[float] = []

    # ------------------------------------------------------------ traffic
    def segments(self, k: int) -> dict:
        cols = gen.dispatch_plan(len(self.sources), self.width, k)
        return gen.stack([gen.source_segment(self.sources[w], j)
                          for w, j in cols])

    def _producer(self, q: queue.Queue, stop: threading.Event, k0: int):
        """Stack dispatch ``k0``, ``k0+1``, ... into ``q`` until stopped;
        an error goes into ``q`` in place of the next item."""
        import jax.numpy as jnp

        k = k0
        while not stop.is_set():
            try:
                tr = self.segments(k)
                item = (k, tr, {n: jnp.asarray(v) for n, v in tr.items()})
            except Exception as e:  # handed to the dispatch loop
                item = e
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if isinstance(item, Exception):
                return
            k += 1

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.core import mmu
        from repro.sim import parallel

        self.members, cfg, self.dyns = check_config(self.cfg_file,
                                                    self.cores)
        mesh = self.traffic.get("mesh")
        self.plan = parallel.plan_mesh(
            len(self.members), self.width, n_devices=self.n_devices,
            force=tuple(mesh) if mesh else None, n_cores=self.cores)
        self.run_fn = mmu.make_systems_runner(cfg, self.plan)
        self.lanes_per_chip = ((self.plan.pad_systems // self.plan.sys_dim)
                               * (self.width // self.plan.wl_dim)
                               * (self.cores // self.plan.core_dim))
        log(f"ladder {self.cfg_file['ladder']}: {len(self.members)} systems "
            f"x {self.width} workloads x {self.cores} cores, mesh "
            f"{self.plan.describe()}, "
            f"{self.lanes_per_chip} lanes per chip, {self.seg} accesses "
            f"per segment")
        t0 = time.perf_counter()
        self.run_fn(self.dyns, self.segments(0))
        log(f"warm dispatch (with its compile): "
            f"{time.perf_counter() - t0!r} s")

    # ------------------------------------------------------------ window
    def window(self, seconds: float, max_dispatches: int | None = None,
               annotate: bool = False, watch: CompileWatch | None = None):
        """Dispatch fresh segments until ``seconds`` have passed (or
        ``max_dispatches`` more are done); a later call goes on with the
        dispatches that follow."""
        import jax

        done = len(self.times)
        q: queue.Queue = queue.Queue(maxsize=1)
        stop = threading.Event()
        # dispatch 0 warmed the shape in set-up
        prod = threading.Thread(target=self._producer,
                                args=(q, stop, done + 1), daemon=True)
        before = watch.snapshot() if watch else None
        prod.start()
        try:
            t_start = time.perf_counter()
            deadline = t_start + seconds
            while time.perf_counter() < deadline and (
                    max_dispatches is None
                    or len(self.times) < done + max_dispatches):
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(WAIT_SPAN):
                    item = q.get()
                if isinstance(item, Exception):
                    raise RuntimeError("the segment producer failed") \
                        from item
                _, tr_np, tr = item
                t1 = time.perf_counter()
                if annotate:
                    with jax.profiler.TraceAnnotation(DISPATCH_SPAN):
                        out = self.run_fn(self.dyns, tr)
                else:
                    out = self.run_fn(self.dyns, tr)
                t2 = time.perf_counter()
                self.waits.append(t1 - t0)
                self.times.append((t1, t2))
                self.dispatched.append(tr_np)
                self.results.append(out)
        finally:
            stop.set()
            prod.join()
        if watch:
            self.window_compiles = {k: watch.count[k] - before[k]
                                    for k in before}

    @property
    def wall(self) -> float:
        """The window: first dispatch start to last dispatch end."""
        return self.times[-1][1] - self.times[0][0]

    @property
    def real_lanes(self) -> int:
        """Lanes of a dispatch; a core of a mix is a lane of its own."""
        return len(self.members) * self.width * self.cores

    def accesses(self) -> int:
        return self.real_lanes * self.seg * len(self.times)

    # ------------------------------------------------------------ trace
    def traced_window(self, seconds: float, attempts: int = 3,
                      deadline: float | None = None):
        """Run one dispatch under the profiler; returns the trace's
        events and op self times (``trace.read_xplane``).  One steady
        dispatch is the whole scan, and longer traces overflow the
        profiler's op buffer.  A trace that holds no whole module event
        of the dispatch program, as when the profiler dropped a buffer,
        is logged and the next dispatch traced in its place, up to
        ``attempts`` in all, while one more attempt, as long as the last,
        ends before ``deadline`` (``time.perf_counter``)."""
        import jax

        logdir = os.path.join(self.scratch, "profile")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        for attempt in range(1, attempts + 1):
            shutil.rmtree(logdir, ignore_errors=True)
            t0, began = time.time(), time.perf_counter()
            with jax.profiler.trace(logdir, profiler_options=opts):
                self.window(seconds, max_dispatches=1, annotate=True)
            t1 = time.perf_counter()
            events, ops, info = trace.read_xplane(logdir, DISPATCH_SPAN)
            shutil.rmtree(logdir, ignore_errors=True)
            log(f"trace {attempt}: dispatch and profiler stop "
                f"{t1 - began!r} s, read in {time.perf_counter() - t1!r} s; "
                f"events by line {info['lines']}; device plane stats "
                f"{info['stats']}; libtpu on trace buffers "
                f"{profiler_log_lines(t0)}")
            if trace.holds_program(events, DISPATCH_SPAN, PROGRAM):
                return events, ops
            log(f"trace {attempt} of {attempts} holds no whole {PROGRAM} "
                f"module event inside its dispatch span")
            now = time.perf_counter()
            if deadline is not None and now + (now - began) > deadline:
                break
        raise RuntimeError(f"no trace of {attempt} held the whole "
                           f"{PROGRAM} program")


def profiler_log_lines(since: float) -> list[str]:
    """The lines on trace buffers (how many, how many dropped) that
    libtpu wrote to its logs in ``TPU_LOG_DIR`` since ``since``."""
    logs = os.environ.get("TPU_LOG_DIR", "")
    if not os.path.isdir(logs):
        return []
    out = []
    for name in sorted(os.listdir(logs)):
        path = os.path.join(logs, name)
        if (os.path.islink(path) or not os.path.isfile(path)
                or os.path.getmtime(path) < since):
            continue
        with open(path, encoding="utf-8", errors="replace") as f:
            out += [ln.strip() for ln in f if "trace_buffers" in ln]
    return out
