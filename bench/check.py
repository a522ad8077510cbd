"""Whether the timed dispatches were right: a sample of their lanes,
drawn from the seed, against the per-lane reference.

Each sampled lane-segment (one system of one dispatch column, and of
one core where the traffic runs multiprogrammed mixes) is run
again by ``bench.reference`` on the host CPU, alone, on its member's own
static config (its own table sizes and stage composition: no ladder
allocation, no per-lane sizing scalars, no stage gates) and the very
segment the dispatch read.  Every Stats field and every extra must be
equal, bit for bit: the simulator's outputs are integer counts and
float32 sums of integer cycles, exact below 2**24, so the limit is 0
mismatched lanes.
"""
from __future__ import annotations

import multiprocessing
import os
import time
from concurrent.futures import ProcessPoolExecutor

import numpy as np

LIMITS = {"lanes_mismatched": 0, "lanes_short": 0}
# reference processes at once: each member's lane traces and compiles
# its own program, so members run in parallel on the host's CPU cores
WORKERS = max(1, min(12, (os.cpu_count() or 2) - 1))


def sample(n_members: int, n_dispatches: int, width: int, per_member: int,
           seed: int, cores: int = 1) -> list[tuple[int, ...]]:
    """``per_member`` distinct (dispatch, column[, core]) cells for every
    member, drawn from the seed: [(member, dispatch, column[, core]),
    ...]; the core only where ``cores`` > 1."""
    rng = np.random.default_rng([int(seed), 0xC4EC])
    cells = n_dispatches * width * cores
    out = []
    for s in range(n_members):
        for c in rng.choice(cells, size=min(per_member, cells),
                            replace=False):
            k, rest = divmod(int(c), width * cores)
            lane = divmod(rest, cores) if cores > 1 else (rest,)
            out.append((s, k, *lane))
    return out


def _val(x) -> np.ndarray:
    a = np.asarray(x)
    return a if a.dtype.kind in "iub" else a.astype(np.float64)


def program_lane(stats, extras) -> dict:
    """One lane of the program's output as {name: value}."""
    flat = dict(stats._asdict())
    flat.update(extras)
    return flat


def diff(got: dict, want: dict) -> list[str]:
    """Names of the fields that differ (or exist on one side only)."""
    return sorted(k for k in set(got) | set(want)
                  if k not in got or k not in want
                  or not np.array_equal(_val(got[k]), _val(want[k])))


def _cpu_only() -> None:
    """Worker start-up: the reference never touches an accelerator."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def _member_lanes(overrides: dict, acc: str, traces: list) -> tuple:
    """One member's lanes in a worker: ([(outputs, activity), ...],
    seconds of its first lane, trace and compile in)."""
    import jax
    import jax.numpy as jnp

    from bench.reference import lane

    t0 = time.perf_counter()
    run = lane.lane_fn(lane.sim_config(overrides), getattr(jnp, acc))
    res, first_s = [], 0.0
    for tr in traces:
        out = jax.device_get(run(tr))
        res.append((lane.outputs(out), lane.activity(out)))
        first_s = first_s or time.perf_counter() - t0
    return res, first_s


class Reference:
    """The cell's ladder members as the reference runs them: each on its
    own static config, one member to a task, in ``workers`` CPU
    processes.  ``acc`` names the dtype of the cycle sums."""

    def __init__(self, cfg_file: dict, members, acc: str = "float32",
                 workers: int = WORKERS):
        self.overrides = [cfg_file["members"][m] for m in members]
        self.acc = acc
        self.workers = workers
        self.first_s = 0.0  # the slowest member's first lane

    def lanes(self, jobs) -> list[tuple[dict, dict]]:
        """``[(member, trace), ...]`` -> ``[(outputs, activity), ...]``."""
        if not jobs:
            return []
        rows: dict[int, list[int]] = {}
        for i, (s, _) in enumerate(jobs):
            rows.setdefault(s, []).append(i)
        out: list = [None] * len(jobs)
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=min(self.workers, len(rows)),
                                 mp_context=ctx,
                                 initializer=_cpu_only) as pool:
            futs = {s: pool.submit(_member_lanes, self.overrides[s],
                                   self.acc, [jobs[i][1] for i in idx])
                    for s, idx in rows.items()}
            for s, fut in futs.items():
                res, first_s = fut.result()
                self.first_s = max(self.first_s, first_s)
                for i, r in zip(rows[s], res):
                    out[i] = r
        return out


def column(traces: dict, *lane: int) -> dict:
    """Lane ``(column[, core])`` of a dispatch's [T, W(, C)] traces."""
    return {k: np.ascontiguousarray(v[(slice(None), *lane)])
            for k, v in traces.items()}


def at(rows, lane):
    """``rows[w]``, or ``rows[w][c]``: one lane of a member's outputs."""
    for i in lane:
        rows = rows[i]
    return rows


def _leaves(rows):
    """Every Stats of a member's row, through the per-core lists."""
    for x in rows:
        if isinstance(x, list):
            yield from _leaves(x)
        else:
            yield x


def run_check(cell, ref: Reference, per_member: int, log=print) -> dict:
    """Compare the cell's window against the reference; returns the
    compared numbers ``{name: value}`` (limits in ``LIMITS``)."""
    short = sum(int(st.n_access) != cell.seg
                for per, _ in cell.results for st in _leaves(per))
    picks = sample(len(cell.members), len(cell.results), cell.width,
                   per_member, cell.seed, cell.cores)
    jobs = [(s, column(cell.dispatched[k], *lane))
            for s, k, *lane in picks]
    want = ref.lanes(jobs)
    bad = 0
    active: dict[str, dict[str, int]] = {}
    for (s, k, *lane), (ref_out, act) in zip(picks, want):
        per, extras = cell.results[k]
        d = diff(program_lane(at(per[s], lane), at(extras[s], lane)),
                 ref_out)
        if d:
            bad += 1
            if bad <= 5:
                log(f"lane {cell.members[s]} / dispatch {k} column "
                    f"{lane[0]}{' core %d' % lane[1] if lane[1:] else ''}: "
                    f"differs from the reference in {d}")
        tot = active.setdefault(cell.members[s], dict.fromkeys(act, 0))
        for name, n in act.items():
            tot[name] += n
    log("what the checked lanes exercised past the L2 TLB, summed per "
        "member (zero counts left out): "
        + "; ".join(f"{m} " + " ".join(f"{k}={n}" for k, n in t.items()
                                       if n)
                    for m, t in active.items()))
    log(f"checked {len(picks)} sampled lane-segments of "
        f"{cell.real_lanes * len(cell.results)} against the reference; "
        f"the slowest member's first lane, with its trace and compile, "
        f"took {ref.first_s!r} s")
    return {"lanes_mismatched": bad, "lanes_short": short,
            "lanes_checked": len(picks)}
