"""Simulation driver: cached runs over the system registry.

Systems are declared in ``repro.sim.systems``; this module turns
(system, workload) pairs into disk-cached Stats.  Cache writes are
crash-safe (temp file + atomic rename) and unreadable entries are
treated as missing, so an interrupted sweep can never poison later
runs.  ``run_ladder`` fills a whole shape-compatible system ladder
through one compiled shard_map kernel, as a producer/consumer pipeline:
trace generation runs on a background thread pool and overlaps with the
device-meshed simulate calls, which dispatch in fixed-width workload
chunks so every chunk reuses the SAME compiled shape.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import pickle
import tempfile
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro import cachedirs
from repro.analysis import recompile
from repro.core import mmu
from repro.core.mmu import make_systems_runner, simulate, simulate_batch
from repro.kernels import mmu_step
from repro.obs import jaxprof
from repro.sim import parallel, systems, trace_gen

cachedirs.enable_compile_cache()

CACHE_DIR = cachedirs.sim_cache_dir()

# ladder dispatch width: workloads per compiled simulate call.  The last
# chunk pads by repeating its final workload, so EVERY run_ladder call —
# whatever its missing-workload count — compiles exactly one [S, CHUNK]
# shape (the old whole-missing-set dispatch recompiled for each distinct
# count), and trace generation overlaps with the previous chunk's sim.
# REPRO_SIM_CHUNK=auto (the default) derives the width per fill from the
# workload count via ``auto_chunk``; an integer pins it.
_chunk_env = os.environ.get("REPRO_SIM_CHUNK", "auto").strip().lower()
CHUNK: int | None = None if _chunk_env in ("", "auto") else int(_chunk_env)

# auto_chunk ceiling: padded-lane waste shrinks with wider chunks but
# compile time and per-dispatch memory grow; measured schema-2 fills put
# the knee near 8 lanes on this container
CHUNK_MAX = int(os.environ.get("REPRO_SIM_CHUNK_MAX", 8))

# background trace-generation threads for the run_ladder producer pool
GEN_WORKERS = int(os.environ.get("REPRO_GEN_WORKERS", 4))

# perf-trajectory records: one entry per batched ladder fill this process
# ran.  Since schema 5 these are NOT hand-assembled: every fill runs
# under a ``ladder_fill`` obs span tree (trace_gen / chunk_wait /
# dispatch children, xla_compile events) and the record is DERIVED from
# the tracer's events by ``obs.report.fill_record`` — the same function
# ``python -m repro.obs report`` applies to the JSONL file, so the
# artifact is reconstructible bit-exactly offline (and ``--check``
# proves it).  Field meanings: trace_gen_wall_s = consumer-side wait
# (generation NOT hidden behind simulation), trace_gen_true_wall_s =
# producer-side thread time, compile_plus_sim_wall_s = the compiled
# shard_map dispatches; see obs.report.FIELD_SOURCES for the full
# field->source table.  benchmarks/paper.write_sweep_artifact dumps
# them to BENCH_sweep.json so CI can track sweep-throughput regressions.
LADDER_PERF: list[dict] = []


def auto_chunk(n_workloads: int, cap: int | None = None) -> int:
    """Pick the ladder dispatch width from the workload count.

    The fill's wall time is ``n_dispatch * (overhead + chunk *
    lane_cost)``: with one reusable compiled runner per fill, the
    per-dispatch overhead is small against the per-lane sim cost, so
    the measured-cost ordering is (1) fewest dispatches, (2) least
    padded-lane waste — e.g. a 3-workload fill picks chunk=3 (one
    dispatch, zero padding) where the old fixed default of 4 simulated
    a fourth, discarded lane (+33% sim work).  Ties prefer the NARROWER
    chunk (faster compile).  ``cap`` bounds the width (default
    ``CHUNK_MAX``); the chunk count derives from the FULL workload list,
    not the missing count, so partially-cached reruns keep hitting the
    same compiled [S, chunk] shape.
    """
    if n_workloads <= 0:
        raise ValueError(f"no workloads to chunk (n={n_workloads})")
    cap = cap or CHUNK_MAX
    return min(range(1, min(cap, n_workloads) + 1),
               key=lambda c: (math.ceil(n_workloads / c),
                              c * math.ceil(n_workloads / c) - n_workloads,
                              c))


def system_config(system: str):
    """Named preset for an evaluated system (delegates to the registry)."""
    return systems.config(system)


def _sim_config(system: str, overrides: dict | None):
    """The ONE place a run's SimConfig is materialized.

    ``run``, ``run_batch`` and ``run_ladder`` all store under the same
    cache key, so the Stats they produce must not depend on which code
    path filled the entry — any config tweak must happen here.  (The
    per-access ``ipa`` rides in the trace itself, never in the config.)
    """
    cfg = systems.config(system)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def _canon(v):
    """Canonicalize an override value for hashing.

    ``json.dumps`` crashes on dataclasses/NamedTuples (``Lat``) and
    numpy/jnp scalars, and reprs could alias distinct overrides; this
    maps them to stable, tagged JSON-able structures instead.
    """
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        fields = sorted(dataclasses.fields(v), key=lambda f: f.name)
        return {"__dataclass__": type(v).__name__,
                **{f.name: _canon(getattr(v, f.name)) for f in fields}}
    if isinstance(v, tuple) and hasattr(v, "_fields"):  # NamedTuple (Lat)
        return {"__namedtuple__": type(v).__name__,
                **{k: _canon(x) for k, x in sorted(v._asdict().items())}}
    if isinstance(v, (np.generic, np.ndarray)) or isinstance(v, jax.Array):
        a = np.asarray(v)
        return a.item() if a.ndim == 0 else [_canon(x) for x in a.tolist()]
    if isinstance(v, dict):
        return {str(k): _canon(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_canon(x) for x in v]
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    # a repr() fallback would be process-unstable (object addresses) and
    # silently defeat the cache — unknown types must fail loudly
    raise TypeError(
        f"cannot canonicalize override value of type {type(v).__name__}: "
        f"{v!r}")


def _key(system: str, workload: str, n: int, seed: int,
         overrides: dict | None) -> str:
    blob = json.dumps([system, workload, n, seed, _canon(overrides or {})],
                      sort_keys=True)
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def _path(system, workload, n, seed, overrides):
    os.makedirs(CACHE_DIR, exist_ok=True)
    key = _key(system, workload, n, seed, overrides)
    return os.path.join(CACHE_DIR, key + ".pkl")


def _store(path: str, result) -> None:
    """Atomic pickle write: an interrupted run leaves no truncated entry."""
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            pickle.dump(result, f)
        os.replace(tmp, path)
        obs.count(obs.names.CTR_SIM_CACHE_STORE, emit=True)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def _load(path: str):
    """Read a cache entry; unreadable entries count as missing.

    Corrupt bytes from an interrupted legacy write (or stale pickles
    referencing renamed modules) raise a grab-bag of exception types —
    anything short of a successful load means "recompute".
    """
    try:
        with open(path, "rb") as f:
            return pickle.load(f)
    except Exception:
        return None


def _cached(path: str, cache: bool):
    if not cache:
        return None
    got = _load(path) if os.path.exists(path) else None
    # unreadable entries already count as missing in _load; mirror that
    # split into the obs registry (hit = a usable entry came back)
    obs.count(obs.names.CTR_SIM_CACHE_HIT if got is not None
              else obs.names.CTR_SIM_CACHE_MISS, emit=True)
    return got


def _np_stats(st):
    return type(st)(*[np.asarray(x) for x in st])


def _stack_traces(gens, n: int) -> dict:
    keys = set(gens[0]["trace"])
    for g in gens:
        if set(g["trace"]) != keys:
            # a mismatched generator used to surface as a bare KeyError
            # deep in the stacking comprehension — name the workload
            raise ValueError(
                f"workload {g['spec'].name!r} emits trace keys "
                f"{sorted(g['trace'])} but {gens[0]['spec'].name!r} "
                f"emits {sorted(keys)}; every generator in a batched "
                f"run must produce the same trace fields")
    stacked = {
        k: jnp.asarray(np.stack([g["trace"][k] for g in gens], axis=1))
        for k in gens[0]["trace"]
    }
    # multiprogrammed-mix traces already carry per-lane "ipa" (and
    # "core") leaves — stacked above like any other key; only synthesize
    # the per-workload broadcast for plain single-core generators
    if "ipa" not in stacked:
        stacked["ipa"] = jnp.asarray(
            np.broadcast_to(
                np.asarray([g["spec"].ipa for g in gens], np.float32),
                (n, len(gens))))
    return stacked


def run_batch(system: str, workloads=None, n: int = 150_000, seed: int = 0,
              overrides: dict | None = None, cache: bool = True,
              backend: str | None = None, block: int | None = None):
    """Simulate one system over ALL workloads in a single vmapped scan.

    Fills the per-(system, workload) disk cache; returns dict
    workload -> (stats, extras, spec).  ``backend``/``block`` select the
    access-loop implementation (bit-identical; never part of cache keys).
    """
    workloads = workloads or trace_gen.all_workloads()
    if _sim_config(system, overrides).n_cores > 1:
        # multicore: core lanes occupy the batch axis per workload/mix,
        # so batch per-workload via run (same cache keys either way)
        return {w: run(system, w, n=n, seed=seed, overrides=overrides,
                       cache=cache, backend=backend, block=block)
                for w in workloads}
    out = {}
    missing = []
    for w in workloads:
        got = _cached(_path(system, w, n, seed, overrides), cache)
        if got is None:
            missing.append(w)
        else:
            out[w] = got
    if missing:
        gens = trace_gen.generate_many(missing, n=n, seed=seed)
        cfg = _sim_config(system, overrides)
        # overrides may change the composition (e.g. victima=True on
        # radix): let make_step re-derive the stages from the final cfg
        stage_names = None if overrides else systems.get(system).stages
        per, extras = simulate_batch(cfg, _stack_traces(gens, n),
                                     stage_names=stage_names,
                                     backend=backend, block=block)
        for w, g, st, ex in zip(missing, gens, per, extras):
            result = (_np_stats(st), ex, g["spec"])
            _store(_path(system, w, n, seed, overrides), result)
            out[w] = result
    return {w: out[w] for w in workloads}


def run_ladder(ladder: str, workloads=None, n: int = 150_000,
               seed: int = 0, cache: bool = True, members=None,
               chunk: int | None = None, mesh=None,
               backend: str | None = None, block: int | None = None,
               time_shards: int = 1):
    """Fill the cache for a whole system ladder through ONE compiled
    kernel, pipelined over a ("sys", "wl") device mesh.

    All ladder members (e.g. the 28-system native family incl. the
    Fig. 25 L2-cache sizes, or the virt family) are vmapped over their
    Dyn sizing scalars; the system axis is padded to the mesh (see
    ``parallel.plan_mesh``), so any member count works on any device
    count.  The run is a producer/consumer pipeline: trace generation
    for missing workloads runs on a background thread pool while the
    compiled simulate call chews on the previous chunk — ``chunk``
    workloads per dispatch (default ``CHUNK``), the last chunk padded by
    repeating its final workload so every dispatch shares one compiled
    [S, chunk] shape.  Chunking and meshing cannot change results:
    every (system, workload) lane computes independently, so cache
    entries stay byte-compatible with per-system ``run_batch`` results
    (pinned by the multidev tests).  `members` restricts the run to a
    subset of the ladder; `mesh=(sys, wl)` forces the mesh factorization
    (debug).  ``backend``/``block``/``time_shards`` select the access
    loop (scan or pallas; see ``mmu.BACKENDS``) — all bit-identical, so
    cache entries never record the backend.  ``time_shards > 1``
    requires a 1x1 mesh (devices go to the time axis).  Returns dict
    system -> dict workload -> result.
    """
    members = tuple(members or systems.LADDERS[ladder])
    workloads = workloads or trace_gen.all_workloads()
    out = {s: {} for s in members}
    missing = []
    for w in workloads:
        got = {s: _cached(_path(s, w, n, seed, None), cache)
               for s in members}
        # reuse every cached (member, workload) cell as-is; a workload
        # only re-simulates when at least one member's cell is missing
        # (the batched call covers all lanes anyway), and even then the
        # cached cells are neither recomputed nor rewritten below.
        for s, r in got.items():
            if r is not None:
                out[s][w] = r
        if any(r is None for r in got.values()):
            missing.append(w)
    if not missing:
        return out
    cfg = systems.ladder_base_config(ladder, members)
    dyns = systems.ladder_dyn(members)
    # mix-aware dispatch: a multicore family generates [T, W, C]
    # multiprogrammed traces (every "workload" is a mix spec — a plain
    # name is the 1-component mix) and stores per-core result tuples
    n_cores = cfg.n_cores
    # never shrink the dispatch width to the missing count: a
    # partially-cached rerun must reuse the SAME compiled [S, chunk]
    # shape (short groups pad below), and a forced mesh planned for
    # `chunk` must stay valid however few workloads are left — which is
    # also why auto_chunk sees the FULL workload list, never `missing`
    auto = chunk is None and CHUNK is None
    chunk = chunk or CHUNK or auto_chunk(len(workloads))
    if time_shards > 1 and mesh is None:
        mesh = (1, 1)  # devices go to the ("t",) axis instead
    plan = parallel.plan_mesh(len(members), chunk,
                              force=tuple(mesh) if mesh else None,
                              n_cores=n_cores)
    backend = mmu.resolve_backend(backend)
    # ONE runner for all chunks: every chunk dispatches the same
    # [S, chunk] shape, so the shard_map kernel traces/compiles once
    run_fn = make_systems_runner(cfg, plan, backend=backend, block=block,
                                 time_shards=time_shards)
    n_chunks = 0
    # one-compile accounting (schema >= 4): the dispatch graph must
    # compile once for the whole fill.  The time-shard path re-jits its
    # per-round function every dispatch (a known per-chunk retrace), so
    # its count is per-chunk — recorded honestly, not masked.
    dispatch_fn = (recompile.DISPATCH_NAME if time_shards <= 1
                   else "round_fn")
    tr = obs.tracer()
    fill = obs.span(
        obs.names.SPAN_LADDER_FILL,
        ladder=ladder, n_systems=len(members), n_members=len(members),
        n_workloads=len(missing), sim_n=n,
        devices=jax.local_device_count(),
        mesh=([plan.sys_dim, plan.wl_dim, plan.core_dim]
              if plan.core_dim > 1 else [plan.sys_dim, plan.wl_dim]),
        cores=n_cores,
        chunk=chunk, chunk_auto=auto, backend=backend,
        block=(mmu_step.pick_block(n, block)
               if backend == "pallas" else None),
        dispatch_fn=dispatch_fn)

    def _gen(w):
        # producer-side TRUE generation time: runs on a pool worker
        # thread, so the fill parent must be attached explicitly
        with obs.span(obs.names.SPAN_TRACE_GEN, parent=fill, wl=w):
            if n_cores > 1:
                return trace_gen.generate_mix(w, n=n, seed=seed,
                                              n_cores=n_cores)
            return trace_gen.generate(w, n=n, seed=seed)

    with fill:
        with recompile.count_compiles(
                on_compile=lambda name: obs.event(
                    obs.names.EV_COMPILE, parent=fill, fn=name)), \
                ThreadPoolExecutor(
                    max_workers=min(len(missing), GEN_WORKERS)) as pool:
            futs = {w: pool.submit(_gen, w) for w in missing}
            for lo in range(0, len(missing), chunk):
                group = missing[lo:lo + chunk]
                # consumer-side wait: generation NOT hidden behind sim
                with obs.span(obs.names.SPAN_CHUNK_WAIT,
                              workloads=list(group)):
                    gens = [futs[w].result() for w in group]
                # pad the workload axis to the fixed chunk width: padded
                # lanes re-simulate the last workload and are never stored
                padded = gens + [gens[-1]] * (chunk - len(gens))
                # the base composition may contain dyn-gated stages some
                # members lack (radix lanes riding a victima ladder):
                # the runner derives the stages from cfg
                with obs.span(obs.names.SPAN_DISPATCH,
                              chunk_index=n_chunks, workloads=list(group),
                              cores=n_cores):
                    per, extras = run_fn(dyns, _stack_traces(padded, n))
                n_chunks += 1
                for si, s in enumerate(members):
                    for wi, (w, g) in enumerate(zip(group, gens)):
                        if w in out[s]:
                            continue  # pre-existing cell: keep cached bytes
                        if n_cores > 1:
                            # multicore cell: per-core tuples (one Stats/
                            # extras per lane), spec = per-core spec tuple
                            result = (
                                tuple(_np_stats(p) for p in per[si][wi]),
                                tuple(extras[si][wi]), g["spec"])
                        else:
                            result = (_np_stats(per[si][wi]),
                                      extras[si][wi], g["spec"])
                        _store(_path(s, w, n, seed, None), result)
                        out[s][w] = result
        tinfo = getattr(run_fn, "last_time_shard_info", None)
        fill.set(n_chunks=n_chunks,
                 t_shards=tinfo["t_shards"] if tinfo else 1,
                 t_rounds=tinfo["rounds"] if tinfo else None)
        jaxprof.device_memory_event(obs.event)  # no-op on CPU backends
    # the record is DERIVED from the just-closed span tree by the same
    # function the offline CLI uses — see the LADDER_PERF comment above
    LADDER_PERF.append(obs.report.fill_record(tr.events, fill.id, tr.path))
    return out


def run(system: str, workload: str, n: int = 150_000, seed: int = 0,
        overrides: dict | None = None, cache: bool = True,
        backend: str | None = None, block: int | None = None,
        time_shards: int = 1):
    """Simulate one (system, workload). Returns (stats, extras, spec).

    Results are cached on disk — the benchmark harness reruns cheaply.
    ``backend``/``block``/``time_shards`` pick the access-loop
    implementation (bit-identical; never part of cache keys).
    """
    path = _path(system, workload, n, seed, overrides)
    got = _cached(path, cache)
    if got is not None:
        return got

    cfg = _sim_config(system, overrides)
    stage_names = None if overrides else systems.get(system).stages
    if cfg.n_cores > 1:
        # multicore: `workload` is a mix spec (a plain name = the
        # 1-component mix); the per-core lanes ride the vmapped batch
        # axis, and the result is a per-core tuple like run_ladder's
        gen = trace_gen.generate_mix(workload, n=n, seed=seed,
                                     n_cores=cfg.n_cores)
        trace = {k: jnp.asarray(v) for k, v in gen["trace"].items()}
        per, extras = simulate_batch(cfg, trace, stage_names=stage_names,
                                     backend=backend, block=block)
        result = (tuple(_np_stats(s) for s in per), tuple(extras),
                  gen["spec"])
        if cache:
            _store(path, result)
        return result

    gen = trace_gen.generate(workload, n=n, seed=seed)
    trace = {k: jnp.asarray(v) for k, v in gen["trace"].items()}
    trace["ipa"] = jnp.full((len(gen["trace"]["vpn"]),), gen["spec"].ipa,
                            jnp.float32)
    stats, extras = simulate(cfg, trace, stage_names=stage_names,
                             backend=backend, block=block,
                             time_shards=time_shards)
    result = (_np_stats(stats), extras, gen["spec"])
    if cache:
        _store(path, result)
    return result
