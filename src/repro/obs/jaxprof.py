"""Device-memory events.

:func:`device_memory_event` snapshots ``Device.memory_stats()`` into a
``device_memory`` trace event where the backend exposes it (TPU/GPU;
CPU returns None and emits nothing).

jax imports are deferred so ``repro.obs`` stays importable — and its
CLI usable on raw JSONL files — without initializing jax.
"""
from __future__ import annotations


def device_memory_event(emit, parent=None):
    """Emit one ``device_memory`` event via `emit` (an ``obs.event``-shaped
    callable) with per-device ``memory_stats()``; returns the stats dict
    or None when no device reports any (CPU backend)."""
    import jax

    stats = {}
    for d in jax.local_devices():
        try:
            s = d.memory_stats()
        except Exception:
            s = None
        if s:
            stats[str(d.id)] = {k: int(v) for k, v in s.items()
                                if isinstance(v, (int, float))}
    if not stats:
        return None
    from repro.obs import names

    emit(names.EV_DEVICE_MEMORY, parent=parent, devices=stats)
    return stats
