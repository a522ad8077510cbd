"""Recompile guard: count named XLA compiles under ``jax_log_compiles``.

The one-compile invariant says a whole dyn-gated ladder family fills
through ONE compiled dispatch per (shape, backend).  The guard flips
``jax_log_compiles`` on, which makes jax's lowering logger emit one
``"Compiling jit(<name>) ..."`` record per jit-cache miss — *before*
the persistent-cache lookup, so a lowering is counted even when the XLA
binary comes out of the compilation cache.  That is exactly the event
whose count the invariant bounds; the name tells the ladder dispatch
apart from the tiny eager-op jits (``dynamic_slice``,
``convert_element_type``, ...) that fire around it.

This module deliberately imports nothing from ``repro`` so that
``sim.runner`` can use it without an import cycle.
"""
from __future__ import annotations

import logging
import re
from contextlib import contextmanager
from dataclasses import dataclass, field

# the loggers ``jax_log_compiles`` raises to WARNING: pxla logs one
# "Compiling jit(<name>) ..." record per jit-cache miss (plain jit and
# jit(shard_map) alike), dispatch logs the trace/lower/compile timings
_JAX_COMPILE_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")

_COMPILING = re.compile(r"Compiling jit\((?P<name>[^)]*)\) ")

# the name the sharded ladder dispatch compiles under — the inner
# function built by ``mmu.make_systems_runner`` and wrapped by
# ``parallel.shard_wrap``
DISPATCH_NAME = "run_systems"


@dataclass
class CompileLog:
    """Names of functions compiled while a ``count_compiles`` block ran."""

    names: list = field(default_factory=list)

    def count(self, name: str | None = None) -> int:
        """Total compiles, or compiles of one function name."""
        if name is None:
            return len(self.names)
        return sum(1 for n in self.names if n == name)

    def by_name(self) -> dict:
        out: dict[str, int] = {}
        for n in self.names:
            out[n] = out.get(n, 0) + 1
        return out


class _Capture(logging.Handler):
    def __init__(self, log: CompileLog, on_compile=None):
        super().__init__(level=logging.DEBUG)
        self._log = log
        self._on_compile = on_compile

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILING.match(record.getMessage())
        if m:
            # "Compiling jit(<name>) with global shapes and types ..."
            name = m.group("name")
            self._log.names.append(name)
            if self._on_compile is not None:
                try:
                    self._on_compile(name)
                except Exception:  # telemetry must never kill a compile
                    pass


@contextmanager
def count_compiles(on_compile=None):
    """Context manager yielding a :class:`CompileLog` of jit-cache misses.

    Temporarily enables ``jax_log_compiles`` and attaches a capturing
    handler to jax's compile loggers with propagation off (so user
    terminals are not spammed with WARNING records); both are restored
    on exit.  Nesting is safe — each level sees every compile inside it.
    ``on_compile(name)``, if given, fires per captured compile — the hook
    ``sim.runner`` uses to land every jit-cache miss in the obs trace.
    """
    import jax  # deferred: keep module importable without initializing jax

    log = CompileLog()
    handler = _Capture(log, on_compile)
    prev_flag = jax.config.jax_log_compiles
    loggers = [logging.getLogger(n) for n in _JAX_COMPILE_LOGGERS]
    prev = [(lg.level, lg.propagate) for lg in loggers]
    jax.config.update("jax_log_compiles", True)
    for lg in loggers:
        lg.addHandler(handler)
        if lg.level > logging.WARNING or lg.level == logging.NOTSET:
            lg.setLevel(logging.WARNING)
        lg.propagate = False
    try:
        yield log
    finally:
        for lg, (lvl, prop) in zip(loggers, prev):
            lg.removeHandler(handler)
            lg.setLevel(lvl)
            lg.propagate = prop
        jax.config.update("jax_log_compiles", prev_flag)


def check_ladder_dispatch(members=None, workloads=("rnd", "bc"), n: int = 256,
                          backend: str = "scan", expected: int = 1):
    """Execute a tiny ladder fill and bound its dispatch compile count.

    Builds a ``make_systems_runner`` dispatch for ``members`` (default:
    the first two members of the first discovered family), feeds it two
    same-shape workload chunks, and returns findings if the number of
    ``run_systems`` compiles differs from ``expected``.  This actually
    runs the simulator, so it lives behind ``--pass recompile`` in the
    CLI rather than in the default static sweep.
    """
    import jax
    import numpy as np
    import jax.numpy as jnp

    from repro.core import mmu
    from repro.sim import parallel, systems, trace_gen

    if members is None:
        fam = sorted(systems.discover_ladders().items(),
                     key=lambda kv: -len(kv[1]))[0][1]
        members = list(fam)[:2]
    base = systems.ladder_base_config(members=members)
    dyns = systems.ladder_dyn(members)
    plan = parallel.plan_mesh(len(members), len(workloads))
    run_fn = mmu.make_systems_runner(base, plan, None, backend, None, 1)

    def chunk(seed):
        gens = [trace_gen.generate(w, n=n, seed=seed) for w in workloads]
        tr = {k: jnp.asarray(np.stack([g["trace"][k] for g in gens], axis=1))
              for k in gens[0]["trace"]}
        tr["ipa"] = jnp.asarray(np.broadcast_to(
            np.asarray([g["spec"].ipa for g in gens], np.float32),
            (n, len(gens))))
        return tr

    with count_compiles() as log:
        for seed in (0, 1):  # two same-shape chunks must share one compile
            per, extras = run_fn(dyns, chunk(seed))
            jax.block_until_ready((per, extras))
    got = log.count(DISPATCH_NAME)

    findings = []
    if got != expected:
        findings.append(
            f"RC001 recompile guard: {len(members)}-member ladder "
            f"({backend} backend) compiled '{DISPATCH_NAME}' {got}x over "
            f"two same-shape chunks; the one-compile invariant allows "
            f"exactly {expected} per (shape, backend).  Full compile "
            f"log: {log.by_name()}")
    return findings
