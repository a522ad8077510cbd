"""Smoke run of the simulator and the serving loop on a TPU.

Drives the normal entry points once at full structure sizes, on random
traces made from fixed seeds, and checks what comes out:

- device:  the first JAX device must be a TPU (no CPU fallback);
- golden:  radix and Victima at the golden config on the golden trace
           must equal ``tests/golden/mmu_stats.json`` bit for bit;
- ladder:  ``runner.run_ladder("radix", ...)`` fills the 28-member
           native family at its Table-3 sizes in one compile; every lane
           must count ``n`` accesses, and the radix and Victima lanes
           must equal per-system ``runner.run`` results bit for bit;
- serve:   ``load.run_load`` replays a Poisson trace through one engine;
           every arrival must be accounted for, no page mapped twice,
           and the VTC hit rate must lie in [0, 1];
- pallas:  ``backend="pallas"`` must be refused up front on the TPU
           (``mmu.PALLAS_ON_TPU``), never interpreted or swapped for scan.

``--chips 4`` runs only the four-chip check instead: the same ladder
fill on a forced 2x2 ("sys", "wl") mesh and on a 1x1 mesh in one
process, which must agree bit for bit.

Everything runs in this one process, which holds the chip(s).  Detail
goes to earlier lines; the last line of standard output is one JSON
object, printed only when every phase passed.  Any failed phase exits
non-zero.  Times are from one run on whatever machine ran the script.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the 2x2 mesh against 1x1
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
LADDER = "radix"
WORKLOADS = ("rnd", "bc")


class SmokeFailure(Exception):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def same_stats(a, b) -> bool:
    import numpy as np

    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


class DispatchSetup:
    """Seconds JAX spent tracing, lowering and compiling ``run_systems``
    (the ladder dispatch), from ``jax.monitoring`` duration events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.secs = {e: 0.0 for e in self.EVENTS}

    def __call__(self, event, duration, **kw):
        if event in self.secs and "run_systems" in str(kw.get("fun_name")):
            self.secs[event] += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)

    @property
    def total(self) -> float:
        return sum(self.secs.values())


def phase_device(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    log("device", f"platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    require(d.platform == "tpu", f"first device is {d.platform!r}, not tpu")
    require(len(devs) >= chips, f"--chips {chips} but {len(devs)} devices")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_golden():
    import dataclasses

    import jax.numpy as jnp
    from golden_trace import (GOLDEN_CFG, GOLDEN_SYSTEMS, golden_trace,
                              stats_to_jsonable)
    from repro.core.mmu import simulate

    with open(os.path.join(HERE, "tests", "golden", "mmu_stats.json"),
              encoding="utf-8") as f:
        want = json.load(f)
    tr = {k: jnp.asarray(v) for k, v in golden_trace().items()}
    for name, overrides in GOLDEN_SYSTEMS.items():
        stats, _ = simulate(dataclasses.replace(GOLDEN_CFG, **overrides), tr)
        got = stats_to_jsonable(stats)
        bad = sorted(k for k in want[name] if got.get(k) != want[name][k])
        require(not bad, f"{name} differs from the golden Stats in {bad}")
        log("golden", f"{name}: {len(want[name])} Stats fields equal the "
            f"snapshot (n_demand_ptw={got['n_demand_ptw']}, "
            f"sum_trans_cyc={got['sum_trans_cyc']})")


def _fill(runner, n: int, **kw):
    """One ``run_ladder`` fill of the native family; returns
    (results, fill record, dispatch set-up seconds)."""
    with DispatchSetup() as setup:
        out = runner.run_ladder(LADDER, workloads=WORKLOADS, n=n, **kw)
    rec = runner.LADDER_PERF[-1]
    require(rec["dispatch_compiles"] == 1 and rec["one_compile"] is True,
            f"dispatch compiled {rec['dispatch_compiles']}x, "
            f"one_compile={rec['one_compile']}")
    for s, per in out.items():
        for w, (st, _ex, _spec) in per.items():
            require(int(st.n_access) == n,
                    f"{s}/{w} counted {int(st.n_access)} accesses, not {n}")
    return out, rec, setup


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_ladder(n: int):
    from repro.sim import runner, systems

    members = systems.LADDERS[LADDER]
    t0 = time.perf_counter()
    out, rec, setup = _fill(runner, n)
    wall = time.perf_counter() - t0
    log("ladder", f"{LADDER} family: {len(members)} systems x "
        f"{len(WORKLOADS)} workloads, n={n}, mesh={rec['mesh']}, "
        f"chunk={rec['chunk']}, dispatch_compiles="
        f"{rec['dispatch_compiles']}, one_compile={rec['one_compile']}")
    sim_s = rec["compile_plus_sim_wall_s"] - setup.total
    lanes = len(members) * len(WORKLOADS)
    log("ladder", "dispatch set-up s (trace, lower, compile): "
        + ", ".join(f"{v!r}" for v in setup.secs.values()))
    log("ladder", f"dispatch s excluding set-up: {sim_s!r}; "
        f"simulated accesses/s: {lanes * n / sim_s!r} "
        f"({lanes} lanes x {n}); fill wall s: {wall!r}")
    log("ladder", f"peak_bytes_in_use: {_peak_bytes()}")
    for s in ("radix", "victima"):
        for w in WORKLOADS:
            t0 = time.perf_counter()
            ref = runner.run(s, w, n=n, cache=False)
            require(same_stats(out[s][w][0], ref[0]),
                    f"ladder lane {s}/{w} differs from runner.run")
            log("ladder", f"{s}/{w}: ladder lane == runner.run bit for bit "
                f"(n_demand_ptw={int(ref[0].n_demand_ptw)}, "
                f"{time.perf_counter() - t0!r} s with its compile)")


def phase_serve():
    from repro.serve import engine, load

    cfg = engine.EngineConfig(n_pool_pages=192)
    trace = load.poisson_trace(2.0, 300, cfg, seed=17)
    # run_load raises at the first tick whose state maps a page twice
    rec = load.run_load(trace, cfg, lanes=1, run="chip_smoke",
                        arrival="poisson", rate=2.0)
    n = len(trace)
    queued = n - rec["admitted"]
    in_flight = rec["admitted"] - rec["retired"]
    log("serve", f"{n} arrivals over {rec['n_ticks']} ticks: "
        f"admitted={rec['admitted']} (after {rec['rejected']} rejected "
        f"attempts) retired={rec['retired']} in_flight={in_flight} "
        f"queued={queued} pool_stall={rec['pool_stall']}")
    require(rec["n_arrivals"] == n, f"record has {rec['n_arrivals']} "
            f"arrivals, trace has {n}")
    require(queued >= 0 and in_flight >= 0,
            "more admitted than arrived, or more retired than admitted")
    log("serve", f"no page mapped twice: engine.pages_consistent held "
        f"on the device after each of the {rec['n_ticks']} ticks")
    hit = rec["vtc_hit_rate"]
    require(0.0 <= hit <= 1.0, f"VTC hit rate {hit} outside [0, 1]")
    log("serve", f"vtc_hit_rate={hit} decode_p50_s={rec['decode_p50_s']} "
        f"decode_p99_s={rec['decode_p99_s']} "
        f"throughput_rps={rec['throughput_rps']}")


def phase_pallas():
    from repro.core import mmu

    try:
        mmu.resolve_backend("pallas")
    except ValueError as e:
        require(str(e) == mmu.PALLAS_ON_TPU, f"unexpected refusal: {e}")
        log("pallas", "refused up front: " + str(e))
        return
    raise SmokeFailure("backend='pallas' was accepted on the TPU")


def phase_mesh4(n: int):
    import numpy as np

    from repro.sim import parallel, runner, systems

    members = systems.LADDERS[LADDER]
    plan = parallel.plan_mesh(len(members), len(WORKLOADS), force=(2, 2))
    mesh = parallel.build_mesh(plan)
    per_blk = plan.pad_systems // plan.sys_dim
    for i, j in np.ndindex(mesh.devices.shape):
        log("mesh4", f"sys block {i} (systems {i * per_blk}.."
            f"{(i + 1) * per_blk - 1}) x wl block {j} ({WORKLOADS[j]}) "
            f"-> {mesh.devices[i, j]}")
    outs = {}
    for shape in ((2, 2), (1, 1)):
        t0 = time.perf_counter()
        out, rec, setup = _fill(runner, n, cache=False, mesh=shape)
        require(rec["mesh"] == list(shape), f"fill ran on {rec['mesh']}")
        log("mesh4", f"mesh {shape}: devices={rec['devices']} "
            f"dispatch_compiles={rec['dispatch_compiles']} set-up s "
            f"{setup.total!r}, dispatch s excluding set-up "
            f"{rec['compile_plus_sim_wall_s'] - setup.total!r}, fill wall s "
            f"{time.perf_counter() - t0!r}")
        outs[shape] = out
    for s in members:
        for w in WORKLOADS:
            require(same_stats(outs[(2, 2)][s][w][0], outs[(1, 1)][s][w][0]),
                    f"{s}/{w} differs between the 2x2 and 1x1 meshes")
    log("mesh4", f"2x2 == 1x1 bit for bit on all {len(members)} x "
        f"{len(WORKLOADS)} lanes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2-mesh check on four chips")
    # 5,000 keeps the run well inside 20 minutes: on one v5e the 56-lane
    # radix fill simulated about 1,500 accesses/s
    ap.add_argument("--n", type=int, default=5_000,
                    help="accesses per workload in the ladder fills")
    args = ap.parse_args(argv)

    phase = "device"
    try:
        device = phase_device(args.chips)
        phase = "import"
        sys.path[:0] = [os.path.join(HERE, "src"), os.path.join(HERE, "tests")]
        import repro.obs as obs
        from repro.sim import runner

        with tempfile.TemporaryDirectory() as tmp:
            # a fresh result cache and trace: nothing is read that this
            # process did not compute
            runner.CACHE_DIR = os.path.join(tmp, "sim_cache")
            obs.configure(os.path.join(tmp, "trace.jsonl"))
            if args.chips == 4:
                phases = [("mesh4", lambda: phase_mesh4(args.n))]
            else:
                phases = [("golden", phase_golden),
                          ("ladder", lambda: phase_ladder(args.n)),
                          ("serve", phase_serve),
                          ("pallas", phase_pallas)]
            for phase, fn in phases:
                t0 = time.perf_counter()
                fn()
                log(phase, f"passed in {time.perf_counter() - t0!r} s")
            obs.configure()
    except Exception:  # any failure ends the run with a non-zero exit
        traceback.print_exc()
        print(f"FAILED in phase {phase}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
