"""Where the repository keeps its on-disk caches.

Every default cache lives inside the checkout that holds this package,
so a copy of the repository carries its own caches and never reads
another tree's.  Two caches matter:

- JAX's persistent compilation cache.  ``JAX_COMPILATION_CACHE_DIR``,
  when set, places it (JAX reads that variable itself, and nothing here
  overrides it); otherwise it goes to ``<checkout>/.jax_cache``.
- The simulator's result cache (``sim.runner.CACHE_DIR``), which
  ``REPRO_SIM_CACHE`` overrides; otherwise ``<checkout>/.sim_cache``.

Stdlib-only at import: ``enable_compile_cache`` imports jax when called.
"""
from __future__ import annotations

import os

# src/repro/cachedirs.py -> the checkout root two levels above the package
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_COMPILE_ENV = "JAX_COMPILATION_CACHE_DIR"


def in_checkout(name: str) -> str:
    """Absolute path of ``name`` inside the checkout root."""
    return os.path.join(CHECKOUT, name)


def sim_cache_dir() -> str:
    """The simulator's result-cache directory (env override or default)."""
    return os.environ.get("REPRO_SIM_CACHE") or in_checkout(".sim_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Sim step graphs take minutes to compile, so compiles are shared
    across processes.  Only compiles that take at least 5 s are stored.
    """
    import jax

    if not os.environ.get(_COMPILE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          in_checkout(".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 5)
    return jax.config.jax_compilation_cache_dir
