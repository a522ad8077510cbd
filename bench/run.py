"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
one steady dispatch, and a breakdown of device time and idle gaps.
Detail goes to standard error; the last line of standard output is one
JSON object.  A run whose first device is not a TPU, or that sees fewer
devices than the cell asks for, exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(ROOT, ".bench_cache")
# a traced dispatch is taken again only if it would end by then, which
# leaves time for the reference check inside a run's 360 s
TRACE_BY_S = 300.0

if __name__ == "__main__":
    # fixed paths inside the checkout, set before JAX is imported: the
    # compile cache must not move between runs, libtpu logs nowhere
    # else, and the CPU backend (the reference's) stays available
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.environ["TPU_LOG_DIR"] = os.path.join(CACHE, "tpu_logs")
    os.environ["REPRO_OBS_DIR"] = os.path.join(CACHE, "obs")
    _platforms = os.environ.get("JAX_PLATFORMS", "")
    if _platforms and "cpu" not in _platforms.split(","):
        os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cell as cellmod  # noqa: E402
from bench import check, spec, trace  # noqa: E402

log = cellmod.log


def device_info(n: int) -> dict:
    import jax

    devs = jax.devices()[:n]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def require_chips(n: int) -> None:
    """Exit (no result) unless JAX's first device is a TPU that the
    peaks table knows and at least ``n`` devices are visible."""
    import jax

    with open(os.path.join(spec.BENCH, "peaks.json"), encoding="utf-8") as f:
        peaks = json.load(f)["devices"]
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"the first device is {devs[0].platform!r}, not a "
                         f"TPU: no result")
    if len(devs) < n:
        raise SystemExit(f"the cell asks for {n} chips, JAX sees "
                         f"{len(devs)}: no result")
    if devs[0].device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind "
                         f"{devs[0].device_kind!r} in peaks.json")


def traced_metrics(c, events, ops, readers) -> tuple[dict, dict, dict]:
    run = trace.TracedRun(events, cellmod.DISPATCH_SPAN, cellmod.PROGRAM,
                          c.lanes_per_chip, c.seg)
    if not run.readable:
        raise RuntimeError("the trace holds no device plane or no "
                           "dispatch span")
    metrics = {}
    for m, reader in readers:
        v = reader.read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    lo, hi = run.window
    busy = {"busy_s": run.busy_ns() / 1e9, "window_s": (hi - lo) / 1e9}
    gaps = []
    for d in run.dispatches:
        gaps += trace.idle_gaps(events, run.planes[0], d)
    breakdown = {"device_ops": trace.top_ops(ops),
                 "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10]}
    return metrics, busy, breakdown


def execute(bench: dict, name: str, seed: int, seconds: float,
            traced: bool) -> dict:
    """One run of cell ``name``; returns the result object."""
    cell = spec.cell(bench, name)
    cfg_file = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    group = "per_layer" if traced else "end_to_end"
    wanted = spec.metrics_of(bench, group, cell["name"])
    readers = [(m, spec.metric(m["name"])) for m in wanted] \
        if traced else []

    import jax

    require_chips(cell["chips"])
    t_import = time.perf_counter() - T0
    os.makedirs(CACHE, exist_ok=True)

    c = cellmod.Cell(cfg_file, traffic, seed, cell["chips"], CACHE)
    with cellmod.CompileWatch() as watch:
        c.setup()
        setup_s = time.perf_counter() - T0
        log(f"set-up {setup_s!r} s: start-up and imports {t_import!r} s; "
            "trace, lower, compile "
            + ", ".join(f"{watch.secs[e]!r} s ({watch.count[e]}x)"
                        for e in cellmod.COMPILE_EVENTS))
        if traced:
            events, ops = c.traced_window(seconds,
                                          deadline=T0 + TRACE_BY_S)
        else:
            c.window(seconds, watch=watch)
            log(f"compiles inside the window (trace, lower, compile): "
                f"{list(c.window_compiles.values())}")
    device = device_info(cell["chips"])
    log(f"window: {len(c.times)} dispatches in {c.wall!r} s; dispatch s "
        f"{[b - a for a, b in c.times]}; waits for the segment producer s "
        f"{c.waits}")

    if traced:
        metrics, busy, breakdown = traced_metrics(c, events, ops, readers)
        device.update(busy)
    else:
        values = {"sim_accesses_per_s": c.accesses() / c.wall,
                  "peak_hbm_gb": device["memory_peak_bytes"] / 1e9,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in wanted if m["name"] in values}
    # the program's compiled state goes before the reference runs
    c.run_fn = None
    jax.clear_caches()

    t0 = time.perf_counter()
    ref = check.Reference(cfg_file, c.members)
    nums = check.run_check(c, ref, int(cfg_file["check_per_member"]),
                           log=log)
    log(f"reference check took {time.perf_counter() - t0!r} s")
    checks = {k: {"value": nums[k], "limit": lim}
              for k, lim in check.LIMITS.items()}
    for k, v in checks.items():
        print(f"check {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    out = {"correct": all(v["value"] <= v["limit"] for v in checks.values()),
           "attempted": c.real_lanes * len(c.times),
           "failed": nums["lanes_short"], "metrics": metrics,
           "device": device}
    if traced:
        out["breakdown"] = breakdown
    out["check"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = execute(spec.load(), args.workload, args.seed, args.seconds,
                  bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
