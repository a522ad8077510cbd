"""The harness end to end at a tiny size on the CPU: set-up, window and
check of a three-member ladder, and the check failing on each fault of
the timed path that a one-chip cell can have, and on the control."""
import dataclasses
import json

import numpy as np
import pytest

from bench import cell as cellmod
from bench import check, spec

TINY = dict(l2tlb_sets=4, l2tlb_ways=4, l1d4_sets=2, l1d4_ways=2,
            l1d2_sets=2, l1d2_ways=2, l2_sets=64, l2_ways=8, l3_sets=64,
            l3_ways=8, n_pages4=1 << 12, n_pages2=1 << 8, n_pagesh=1 << 8,
            n_feat=1 << 10, pom_sets=16, pom_ways=4)
MEMBERS = {"radix": dict(TINY), "victima": dict(TINY, victima=True),
           "l2tlb_3k": dict(TINY, l2tlb_sets=8)}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A bench directory with a tiny three-member ladder, and the
    program's registry made to agree with it."""
    from repro.sim import systems

    d = tmp_path_factory.mktemp("bench")
    for sub in ("configs", "traffic", "metrics"):
        (d / sub).mkdir()
    traffic = spec.traffic("all11.s128")
    traffic.update(width=4, seg_accesses=16, block_accesses=64)
    (d / "traffic" / "tiny.json").write_text(json.dumps(traffic))
    cfg = dict(spec.config("native28"), name="tiny3", members=MEMBERS,
               check_per_member=8)
    (d / "configs" / "tiny3.json").write_text(json.dumps(cfg))
    saved = systems.LADDERS, systems.config
    systems.LADDERS = dict(saved[0], radix=tuple(MEMBERS))
    systems.config = lambda m: lane_cfg(m)
    yield d, cfg, traffic
    systems.LADDERS, systems.config = saved


def lane_cfg(m):
    from repro.core.mmu import SimConfig

    return dataclasses.replace(SimConfig(), **MEMBERS[m])


@pytest.fixture(scope="module")
def ready(tiny):
    d, cfg, traffic = tiny
    c = cellmod.Cell(cfg, traffic, 2**31 + 11, 1, str(d))
    c.setup()
    return c, check.Reference(cfg, c.members, workers=2)


def run_window(c, ref, corrupt=None):
    """A two-dispatch window with ``corrupt`` applied to what each
    dispatch returns, then the check; returns the compared numbers."""
    real = c.run_fn

    def broken(dyns, traces):
        per, extras = real(dyns, traces)
        return corrupt(per, extras) if corrupt else (per, extras)

    c.run_fn = broken
    c.results, c.dispatched, c.times, c.waits = [], [], [], []
    try:
        c.window(600, max_dispatches=2)
    finally:
        c.run_fn = real
    return check.run_check(c, ref, 8, log=lambda m: None)


def sound(nums):
    return all(nums[k] <= lim for k, lim in check.LIMITS.items())


def test_tiny_run_is_correct(ready):
    c, ref = ready
    nums = run_window(c, ref)
    assert len(c.times) == 2 and nums["lanes_checked"] == 3 * 8
    assert sound(nums), nums


def test_a_second_window_goes_on_with_the_next_segments(ready):
    """A traced dispatch taken again after a dropped trace reads the
    segments that follow, not the first window's again."""
    c, ref = ready
    run_window(c, ref)
    c.window(600, max_dispatches=1)
    assert len(c.times) == 3
    for k, tr in enumerate(c.dispatched, start=1):
        want = c.segments(k)
        assert set(tr) == set(want)
        assert all(np.array_equal(tr[n], want[n]) for n in want)


def test_a_trace_without_the_program_is_taken_again_then_fails(ready):
    """The CPU's trace has no TPU plane, so no attempt holds the
    dispatch program: each takes one more dispatch, and a deadline that
    one more attempt would pass stops them."""
    c, _ = ready
    c.results, c.dispatched, c.times, c.waits = [], [], [], []
    with pytest.raises(RuntimeError, match="no trace of 2 held"):
        c.traced_window(600, attempts=2)
    assert len(c.times) == 2
    with pytest.raises(RuntimeError, match="no trace of 1 held"):
        c.traced_window(600, attempts=3, deadline=0.0)
    assert len(c.times) == 3


def test_reference_counts_what_the_lanes_exercised(ready):
    """The 16-entry L2 TLB of the radix and Victima members overflows
    within a segment, so the reference sees evictions, and Victima's
    background walk fires on the Victima member only."""
    from bench.reference import lane

    c, ref = ready
    run_window(c, ref)
    jobs = [(s, check.column(c.dispatched[0], 0)) for s in range(3)]
    acts = [act for _, act in ref.lanes(jobs)]
    assert all(tuple(a) == lane.ACTIVITY for a in acts)
    assert acts[0]["l2tlb_evictions"] > 0 and acts[1]["l2tlb_evictions"] > 0
    assert [a["n_bg_ptw"] > 0 for a in acts] == [False, True, False]


def test_state_unchanged_fails(ready):
    """The scan hands back the state it started from."""
    from repro.core.stages import zero_stats

    c, ref = ready

    def unchanged(per, extras):
        z = type(per[0][0])(*[np.asarray(x) for x in zero_stats()])
        return [[z for _ in row] for row in per], extras

    nums = run_window(c, ref, unchanged)
    assert nums["lanes_short"] > 0 and nums["lanes_mismatched"] > 0


def test_half_batch_left_out_fails(ready):
    c, ref = ready

    def half(per, extras):
        w = len(per[0]) // 2
        return ([row[:w] + row[:w] for row in per],
                [row[:w] + row[:w] for row in extras])

    assert run_window(c, ref, half)["lanes_mismatched"] > 0


def test_answer_altered_fails(ready):
    c, ref = ready

    def altered(per, extras):
        return ([[st._replace(n_demand_ptw=st.n_demand_ptw + 1)
                  for st in row] for row in per], extras)

    assert run_window(c, ref, altered)["lanes_mismatched"] > 0


def test_control_fails(ready):
    """The reference with bfloat16 cycle sums, in the program's place."""
    c, ref = ready
    run_window(c, ref)
    ctl = check.Reference(dict(c.cfg_file), c.members, acc="bfloat16",
                          workers=2)
    picks = check.sample(len(c.members), len(c.results), c.width, 8,
                         c.seed)
    jobs = [(s, check.column(c.dispatched[k], w)) for s, k, w in picks]
    bad = sum(bool(check.diff(a, b)) for (a, _), (b, _) in
              zip(ctl.lanes(jobs), ref.lanes(jobs)))
    assert bad > 0


def test_producer_error_reaches_the_window(ready, monkeypatch):
    c, _ = ready
    real = c.segments

    def failing(k):
        if k >= 2:
            raise ValueError("no segment")
        return real(k)

    monkeypatch.setattr(c, "segments", failing)
    c.results, c.dispatched, c.times, c.waits = [], [], [], []
    with pytest.raises(RuntimeError, match="producer"):
        c.window(600, max_dispatches=5)
    assert len(c.times) == 1


def test_execute_prints_a_whole_result(tiny, monkeypatch):
    """``run.execute`` end to end on the CPU, the chip check skipped."""
    from bench import run

    _, cfg, traffic = tiny
    monkeypatch.setattr(run, "require_chips", lambda n: None)
    monkeypatch.setattr(spec, "config", lambda name: cfg)
    monkeypatch.setattr(spec, "traffic", lambda name: traffic)
    bench = spec.load()
    name = bench["workloads"][0]["name"]
    out = run.execute(bench, name, 2**31 + 5, 0.5, traced=False)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % (3 * 4) == 0
    assert set(out["metrics"]) == {"sim_accesses_per_s", "peak_hbm_gb",
                                   "setup_s"}
    assert out["check"]["lanes_mismatched"] == {"value": 0, "limit": 0}
    json.dumps(out)


def test_single_core_reference_as_recorded(tiny):
    """The reference's outputs on this cell's lanes, every field, equal
    those recorded before the reference learned the core axis."""
    import os
    import zlib

    import jax

    from bench.reference import lane

    _, cfg, traffic = tiny
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "single_core_outputs.json"),
              encoding="utf-8") as f:
        pins = json.load(f)
    assert pins["members"] == MEMBERS
    c = cellmod.Cell(cfg, traffic, 2**31 + 11, 1, "")
    runs = {m: lane.lane_fn(lane.sim_config(ov)) for m, ov in MEMBERS.items()}
    for want in pins["reference"]:
        tr = check.column(c.segments(want["dispatch"]), want["column"])
        out = lane.outputs(jax.device_get(runs[want["member"]](tr)))
        got = {k: zlib.crc32(np.ascontiguousarray(v).tobytes())
               for k, v in out.items()}
        assert got == want["crc32"], want
