"""``repro.obs`` — unified tracing + metrics for the whole repo.

One process-global span :class:`~repro.obs.tracer.Tracer` (JSONL sink,
see ``tracer``) and one metrics :class:`~repro.obs.registry.Registry`
(counters/gauges/histograms, see ``registry``), behind a module-level
facade so call sites stay one-liners::

    import repro.obs as obs

    with obs.span(obs.names.SPAN_LADDER_FILL, ladder="native") as fill:
        ...
        with obs.span(obs.names.SPAN_DISPATCH, chunk_i=0):
            ...
    rec = obs.report.fill_record(obs.tracer().events, fill.id,
                                 obs.tracer().path)

Work on other threads passes ``parent=fill`` explicitly (thread-local
implicit nesting does not cross threads).  ``python -m repro.obs
report`` reconstructs BENCH records from the JSONL — see ``report``.

Code traced by JAX names its parts with :func:`scope`, which puts a
declared name into the HLO ``op_name`` metadata and counts the part's
trace seconds.

Stdlib-only at import time (jax only inside :func:`scope`, the
``jaxprof`` helpers and the tracer's profiler annotations, which it
opens only once jax is imported), so any layer — ``sim.parallel``
included — may import it without cycles.
"""
from __future__ import annotations

import contextlib
import time

from repro.obs import jaxprof, names, report  # noqa: F401  (re-export)
from repro.obs.registry import Registry
from repro.obs.tracer import Span, Tracer  # noqa: F401  (re-export)

_TRACER: Tracer | None = None
REGISTRY = Registry()


def tracer() -> Tracer:
    """The process-global tracer (created lazily at the env-derived path)."""
    global _TRACER
    if _TRACER is None:
        _TRACER = Tracer()
    return _TRACER


def configure(path: str | None = None) -> Tracer:
    """Point the global tracer at `path` (e.g. from ``--obs-trace``).

    Replaces the singleton; the previous tracer (if any) is closed.
    """
    global _TRACER
    if _TRACER is not None:
        _TRACER.close()
    _TRACER = Tracer(path)
    return _TRACER


# ------------------------------------------------------- span facade

def span(name: str, parent=None, **attrs) -> Span:
    return tracer().span(name, parent=parent, **attrs)


def event(name: str, parent=None, **attrs) -> dict:
    return tracer().event(name, parent=parent, **attrs)


def current() -> Span | None:
    return tracer().current()


def overhead_s() -> float:
    return tracer().overhead_s


@contextlib.contextmanager
def scope(name: str):
    """``jax.named_scope(name)`` around code that JAX traces, so every op
    traced inside carries ``name`` in its HLO ``op_name``; the block's
    host time adds to counter ``sim.trace_s.<name>``.  The body runs
    only while JAX traces it, so the counter holds trace time alone.
    ``name`` must be one of ``names.SCOPE_NAMES``."""
    if name not in names.SCOPE_NAMES:
        raise ValueError(f"scope {name!r} is not declared in "
                         f"repro.obs.names.SCOPE_NAMES")
    import jax

    t0 = time.perf_counter()
    with jax.named_scope(name):
        yield
    REGISTRY.inc(names.TRACE_S_PREFIX + name, time.perf_counter() - t0)


# ---------------------------------------------------- metrics facade

def count(name: str, n=1, emit: bool = False, **attrs):
    """Bump registry counter `name`; with ``emit=True`` also write a
    ``count`` record to the trace.  Tracer-valued `n` is skipped (jit
    tracing) — returns the applied delta or None."""
    applied = REGISTRY.inc(name, n)
    if emit and applied is not None:
        tracer().count(name, applied, **attrs)
    return applied


def gauge(name: str, v):
    return REGISTRY.gauge(name, v)


def observe(name: str, v):
    return REGISTRY.observe(name, v)


def metrics_snapshot(emit: bool = False) -> dict:
    """Registry snapshot; with ``emit=True`` also append it to the trace."""
    snap = REGISTRY.snapshot()
    if emit:
        tracer().metrics(snap)
    return snap
