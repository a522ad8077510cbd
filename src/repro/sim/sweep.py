"""Populate the simulation result cache for every (system x workload) the
benchmark suite needs.  Run as ``python -m repro.sim.sweep`` (results
land in .sim_cache and benchmarks read them instantly).

Shape-compatible system ladders are discovered from the registry
(``systems.LADDERS``) — e.g. the 28-system native family (radix /
victima / utopia / revelator, L2-TLB sizes incl. CACTI variants, the
Fig. 25 L2-cache sizes, POM and the L3-TLB latency trio) — and filled
by ONE
compiled vmapped call each via ``run_ladder``; the remaining systems
run through the per-system batched path.

CLI: positional system names and/or ``--tags native,ablation`` to
select registry subsets by tag without listing names, e.g.

    python -m repro.sim.sweep --tags utopia
    python -m repro.sim.sweep radix --tags sensitivity

Mesh debugging: ``--devices N`` forces N virtual host devices (sets
``--xla_force_host_platform_device_count`` before the first device
query) and ``--mesh SxW`` (or ``SxWxC`` for multicore families) pins
the ladder ("sys", "wl"[, "core"]) mesh factorization, e.g.

    python -m repro.sim.sweep --devices 4 --mesh 2x2 --tags headline

Multicore: ``--cores C`` selects the registered C-core systems (per-core
private TLBs over the shared contended L3/POM tier; see
docs/architecture.md) and ``--mix bc+rnd+xs`` names a multiprogrammed
co-schedule for them — repeatable, validated against the workload
registry BEFORE anything compiles, and only applied to multicore
families (single-core ladders keep their default workload list):

    python -m repro.sim.sweep --cores 4 --mix bc+rnd+xs --mix dlrm+gen

Backend selection: ``--backend {scan,pallas}`` picks the access-loop
implementation (bit-identical results; pallas runs interpreted on the
CPU and is refused on a TPU, see ``mmu.PALLAS_ON_TPU``) and
``--time-shards N`` splits each trace's time axis into N speculative
blocks resolved to the exact serial carry — it needs a 1x1 ("sys",
"wl") mesh, so it conflicts with ``--mesh`` unless that is 1x1.

Observability: ``--obs-trace PATH`` points the process-global obs
tracer at PATH, so every ladder fill's span tree lands in that JSONL
file (``python -m repro.obs report PATH`` rolls it up; equivalent to
``REPRO_OBS_TRACE=PATH``, which also covers non-sweep entry points like
``benchmarks/run.py``).
"""
from __future__ import annotations

import os
import sys
import time

import repro.obs as obs
from repro.core import mmu
from repro.sim import systems, trace_gen
from repro.sim.runner import run_batch, run_ladder

N = int(os.environ.get("REPRO_SIM_N", 150_000))

# priority order: paper-headline systems first so partial sweeps are useful
SYSTEMS = [
    "radix",
    "victima",
    "utopia",
    "revelator",
    "utopia_victima",
    "revelator_victima",
    "pom",
    "l2tlb_64k",
    "l2tlb_128k",
    "np",
    "victima_virt",
    "isp",
    "pom_virt",
    "l2tlb_3k",
    "l2tlb_8k",
    "l2tlb_16k",
    "l2tlb_32k",
    "l3tlb_64k_15",
    "l3tlb_64k_24",
    "l3tlb_64k_39",
    "l2tlb_8k_real",
    "l2tlb_16k_real",
    "l2tlb_32k_real",
    "l2tlb_64k_real",
    "victima_agnostic",
    "victima_noptwcp",
    "radix_collect",
    "victima_l2_1m",
    "victima_l2_4m",
    "victima_l2_8m",
    "radix_l2_1m",
    "radix_l2_4m",
    "radix_l2_8m",
    "utopia_rs8",
    "utopia_rs32",
    "utopia_virt",
    "revelator_virt",
]


def parse_args(args):
    """Split a CLI arg list into (system names, tags, opts).

    ``--tags native,ablation`` (or ``--tags=...``) selects every system
    carrying any of the given registry tags; positional names add
    individual systems on top.  ``opts`` carries the mesh debug flags —
    ``--mesh SxW`` (forced ("sys", "wl") factorization) and
    ``--devices N`` (forced virtual host device count) — plus the
    access-loop knobs ``--backend {scan,pallas}`` and
    ``--time-shards N``.  All values are validated HERE, before any
    compilation: an unknown backend must die instantly, not after the
    ladder compile (mirroring the --tags fix).
    """
    def _value(val, flag, what="a comma-separated value"):
        # "--tags --foo" used to swallow the next OPTION as a value;
        # flag-like values are always a CLI mistake, so error out
        if val is None or val.startswith("-"):
            raise SystemExit(
                f"{flag} needs {what}"
                + (f", got {val!r}" if val is not None else ""))
        return val

    def _mesh(val, flag):
        parts = _value(val, flag, "a SYSxWL[xCORE] value").split("x")
        if len(parts) not in (2, 3) or not all(p.isdigit() for p in parts):
            raise SystemExit(f"{flag} wants SYSxWL or SYSxWLxCORE "
                             f"(e.g. 2x2 or 1x2x2), got {val!r}")
        return tuple(int(p) for p in parts)

    def _devices(val, flag):
        if not _value(val, flag, "a device count").isdigit() or int(val) < 1:
            raise SystemExit(f"{flag} wants a positive integer, got {val!r}")
        return int(val)

    def _backend(val, flag):
        val = _value(val, flag, "a backend name")
        try:
            # the name only: the platform check initializes jax, which
            # must wait until main has applied --devices
            return mmu.backend_name(val)
        except ValueError as e:
            raise SystemExit(str(e)) from None

    def _tshards(val, flag):
        if not _value(val, flag, "a shard count").isdigit() or int(val) < 1:
            raise SystemExit(f"{flag} wants a positive integer, got {val!r}")
        return int(val)

    def _obs_trace(val, flag):
        return _value(val, flag, "a file path")

    def _cores(val, flag):
        if not _value(val, flag, "a core count").isdigit() or int(val) < 1:
            raise SystemExit(f"{flag} wants a positive integer, got {val!r}")
        return int(val)

    def _mix(val, flag):
        # validate the co-schedule spec's workload names HERE, before
        # anything compiles — same contract as system names and --tags
        val = _value(val, flag, "a workload mix like bc+rnd+xs")
        try:
            trace_gen.parse_mix(val)
        except ValueError as e:
            raise SystemExit(str(e)) from None
        return val

    names, tags = [], []
    opts = {"mesh": None, "devices": None, "backend": None,
            "time_shards": 1, "obs_trace": None, "cores": None, "mix": []}
    it = iter(args or [])
    for a in it:
        if a == "--tags":
            tags += [t for t in _value(next(it, None), "--tags").split(",")
                     if t]
        elif a.startswith("--tags="):
            tags += [t for t in _value(a.split("=", 1)[1], "--tags=")
                     .split(",") if t]
        elif a == "--mesh":
            opts["mesh"] = _mesh(next(it, None), "--mesh")
        elif a.startswith("--mesh="):
            opts["mesh"] = _mesh(a.split("=", 1)[1], "--mesh=")
        elif a == "--devices":
            opts["devices"] = _devices(next(it, None), "--devices")
        elif a.startswith("--devices="):
            opts["devices"] = _devices(a.split("=", 1)[1], "--devices=")
        elif a == "--backend":
            opts["backend"] = _backend(next(it, None), "--backend")
        elif a.startswith("--backend="):
            opts["backend"] = _backend(a.split("=", 1)[1], "--backend=")
        elif a == "--time-shards":
            opts["time_shards"] = _tshards(next(it, None), "--time-shards")
        elif a.startswith("--time-shards="):
            opts["time_shards"] = _tshards(a.split("=", 1)[1],
                                           "--time-shards=")
        elif a == "--obs-trace":
            opts["obs_trace"] = _obs_trace(next(it, None), "--obs-trace")
        elif a.startswith("--obs-trace="):
            opts["obs_trace"] = _obs_trace(a.split("=", 1)[1],
                                           "--obs-trace=")
        elif a == "--cores":
            opts["cores"] = _cores(next(it, None), "--cores")
        elif a.startswith("--cores="):
            opts["cores"] = _cores(a.split("=", 1)[1], "--cores=")
        elif a == "--mix":
            opts["mix"].append(_mix(next(it, None), "--mix"))
        elif a.startswith("--mix="):
            opts["mix"].append(_mix(a.split("=", 1)[1], "--mix="))
        elif a.startswith("-"):
            raise SystemExit(
                f"unknown option {a!r} (only --tags/--mesh/--devices/"
                f"--backend/--time-shards/--obs-trace/--cores/--mix)")
        else:
            names.append(a)
    if opts["time_shards"] > 1 and opts["mesh"] is not None \
            and any(d != 1 for d in opts["mesh"]):
        raise SystemExit(
            f"--time-shards needs a 1x1 ('sys', 'wl') mesh (devices go "
            f"to the 't' axis), got --mesh "
            f"{'x'.join(str(d) for d in opts['mesh'])}")
    return names, tags, opts


def main(selected=None):
    selected, tags, opts = parse_args(selected)
    if opts["obs_trace"]:
        obs.configure(opts["obs_trace"])
    if opts["devices"]:
        # mesh debugging: force N virtual CPU devices.  This only works
        # BEFORE the first jax device query initializes the backend —
        # importing repro.sim.* touches no devices, so setting it here
        # (not in runner) is early enough.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={opts['devices']}"
        ).strip()
    # validate CLI names/tags BEFORE any simulation: a typo used to burn
    # the full ladder compile and then die with a KeyError mid-sweep
    unknown = sorted(set(selected) - set(systems.REGISTRY))
    if unknown:
        raise SystemExit(
            f"unknown system(s): {', '.join(unknown)}; registered: "
            f"{', '.join(sorted(systems.REGISTRY))}")
    all_tags = {t for s in systems.REGISTRY.values() for t in s.tags}
    bad_tags = sorted(set(tags) - all_tags)
    if bad_tags:
        raise SystemExit(
            f"unknown tag(s): {', '.join(bad_tags)}; known: "
            f"{', '.join(sorted(all_tags))}")
    for t in tags:
        selected += [n for n in systems.names(t) if n not in selected]
    if opts["cores"] is not None:
        mc = [n for n, s in systems.REGISTRY.items()
              if "multicore" in s.tags
              and s.config().n_cores == opts["cores"]]
        if not mc:
            known = sorted({s.config().n_cores
                            for s in systems.REGISTRY.values()
                            if "multicore" in s.tags})
            raise SystemExit(
                f"no registered multicore systems with n_cores="
                f"{opts['cores']}; registered core counts: "
                f"{', '.join(map(str, known))}")
        selected += [n for n in mc if n not in selected]
    selected = selected or SYSTEMS
    t00 = time.time()
    done: set[str] = set()
    # batched ladders first: one compilation covers many systems.  A
    # CLI-selected subset only simulates the selected members.
    for ladder, members in systems.LADDERS.items():
        todo = [s for s in members if s in selected]
        if not todo:
            continue
        t0 = time.time()
        # --mix co-schedules apply to multicore families only; every
        # other family keeps its default workload list
        wl = (opts["mix"] or None) if systems.mix_cores(todo) > 1 else None
        run_ladder(ladder, n=N, members=todo, workloads=wl,
                   mesh=opts["mesh"], backend=opts["backend"],
                   time_shards=opts["time_shards"])
        done.update(todo)
        print(f"[sweep] ladder:{ladder:>11s} x all  {time.time()-t0:7.1f}s "
              f"({len(todo)} systems, 1 compile; "
              f"total {time.time()-t00:7.0f}s)", flush=True)
    for sysname in selected:
        if sysname in done:
            continue
        t0 = time.time()
        wl = ((opts["mix"] or None)
              if systems.config(sysname).n_cores > 1 else None)
        run_batch(sysname, n=N, workloads=wl, backend=opts["backend"])
        print(f"[sweep] {sysname:>18s} x all  {time.time()-t0:7.1f}s "
              f"(total {time.time()-t00:7.0f}s)", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or None)
