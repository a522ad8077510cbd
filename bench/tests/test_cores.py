"""The harness's core axis end to end at a tiny size on the CPU: a
four-member, four-core ladder through set-up, window and check, and the
check failing where the timed path loses the core id, swaps two cores'
answers, or runs the control."""
import dataclasses
import json
import os

import numpy as np
import pytest

from bench import cell as cellmod
from bench import check

TINY = dict(l2tlb_sets=4, l2tlb_ways=4, l1d4_sets=2, l1d4_ways=2,
            l1d2_sets=2, l1d2_ways=2, l2_sets=64, l2_ways=8, l3_ways=8,
            n_pages4=1 << 12, n_pages2=1 << 8, n_pagesh=1 << 8,
            n_feat=1 << 10, pom_ways=4, n_cores=4, shared_tier_stats=True,
            l3_sets=16, pom_sets=4)
MEMBERS = {"radix_4c": dict(TINY), "victima_4c": dict(TINY, victima=True),
           "pom_4c": dict(TINY, pom=True),
           "victima_dramc_4c": dict(TINY, victima=True, dram_cache_sets=16)}
SEED = 2**31 + 13
PER_MEMBER = 4
WIDTH = 3


@pytest.fixture(scope="module")
def tiny4(tmp_path_factory):
    """A bench directory with a tiny four-core ladder, and the program's
    registry made to agree with it."""
    from repro.core.mmu import SimConfig
    from repro.sim import systems

    d = tmp_path_factory.mktemp("bench")
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "mix4.json"), encoding="utf-8") as f:
        traffic = json.load(f)
    traffic.update(width=WIDTH, seg_accesses=16, block_accesses=64)
    cfg = dict(name="tiny4", ladder="radix_4c", members=MEMBERS,
               check_per_member=PER_MEMBER)
    saved = systems.LADDERS, systems.config
    systems.LADDERS = dict(saved[0], radix_4c=tuple(MEMBERS))
    systems.config = lambda m: dataclasses.replace(SimConfig(), **MEMBERS[m])
    yield d, cfg, traffic
    systems.LADDERS, systems.config = saved


class SameLanes(check.Reference):
    """The reference, answering a lane it has run before from memory:
    every window here dispatches the same segments, so only the first
    check pays for the reference's compiles."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.memo = {}

    def lanes(self, jobs):
        keys = [(s, tuple((k, v.tobytes()) for k, v in sorted(tr.items())))
                for s, tr in jobs]
        new = [(k, j) for k, j in zip(keys, jobs) if k not in self.memo]
        for (k, _), r in zip(new, super().lanes([j for _, j in new])):
            self.memo[k] = r
        return [self.memo[k] for k in keys]


@pytest.fixture(scope="module")
def ready4(tiny4):
    d, cfg, traffic = tiny4
    c = cellmod.Cell(cfg, traffic, SEED, 1, str(d))
    c.setup()
    return c, SameLanes(cfg, c.members, workers=4)


def run_window(c, ref, corrupt_in=None, corrupt_out=None, log=None):
    """A two-dispatch window, each dispatch's traces passed through
    ``corrupt_in`` and its outputs through ``corrupt_out``, then the
    check; returns the compared numbers."""
    real = c.run_fn

    def broken(dyns, traces):
        if corrupt_in:
            traces = corrupt_in(traces)
        per, extras = real(dyns, traces)
        return corrupt_out(per, extras) if corrupt_out else (per, extras)

    c.run_fn = broken
    c.results, c.dispatched, c.times, c.waits = [], [], [], []
    try:
        c.window(600, max_dispatches=2)
    finally:
        c.run_fn = real
    return check.run_check(c, ref, PER_MEMBER,
                           log=log or (lambda m: None))


def test_counts_take_the_core_axis(ready4):
    c, _ = ready4
    assert c.cores == 4 and c.real_lanes == 4 * WIDTH * 4
    assert c.lanes_per_chip == 4 * WIDTH * 4
    tr = c.segments(1)
    assert tr["vpn"].shape == (16, WIDTH, 4)
    run_window(c, ready4[1])
    assert c.accesses() == 4 * WIDTH * 4 * 16 * 2
    per, extras = c.results[0]
    assert len(per) == 4 and len(per[0]) == WIDTH and len(per[0][0]) == 4
    assert {"l3_access", "dramc_access"} <= set(extras[3][0][0])


def test_tiny_multicore_run_is_correct(ready4):
    c, ref = ready4
    lines = []
    nums = run_window(c, ref, log=lines.append)
    assert nums == {"lanes_mismatched": 0, "lanes_short": 0,
                    "lanes_checked": 4 * PER_MEMBER}, lines
    act = next(m for m in lines if m.startswith("what the checked lanes"))
    dramc = act.split("victima_dramc_4c ")[1]
    assert "dramc_access=" in dramc and "lanes_port_delayed=" in dramc


def test_core_ids_zeroed_fail(ready4):
    """Every core's accesses dispatched as core 0: the port delay's
    rotating slot goes wrong on cores 1-3."""
    import jax.numpy as jnp

    c, ref = ready4
    nums = run_window(c, ref, corrupt_in=lambda tr: dict(
        tr, core=jnp.zeros_like(tr["core"])))
    assert nums["lanes_mismatched"] > 0


def test_swapped_core_answers_fail(ready4):
    c, ref = ready4

    def swap(per, extras):
        for rows in (per, extras):
            for row in rows:
                for lane in row:
                    lane[0], lane[1] = lane[1], lane[0]
        return per, extras

    assert run_window(c, ref, corrupt_out=swap)["lanes_mismatched"] > 0


def test_state_unchanged_fails(ready4):
    """The scan hands back the state it started from, on every core."""
    from repro.core.stages import zero_stats

    c, ref = ready4

    def unchanged(per, extras):
        z = type(per[0][0][0])(*[np.asarray(x) for x in zero_stats()])
        return [[[z] * len(lanes) for lanes in row] for row in per], extras

    nums = run_window(c, ref, corrupt_out=unchanged)
    assert nums["lanes_short"] == len(c.results) * c.real_lanes
    assert nums["lanes_mismatched"] > 0


def test_half_batch_left_out_fails(ready4):
    """The first mix's answers stand in for the others."""
    c, ref = ready4

    def half(per, extras):
        return ([[row[0]] * len(row) for row in per],
                [[row[0]] * len(row) for row in extras])

    assert run_window(c, ref, corrupt_out=half)["lanes_mismatched"] > 0


def test_control_fails(ready4):
    """The reference with bfloat16 cycle sums, in the program's place,
    on the lanes a window samples."""
    c, ref = ready4
    run_window(c, ref)
    ctl = check.Reference(dict(c.cfg_file), c.members, acc="bfloat16",
                          workers=4)
    picks = check.sample(len(c.members), len(c.results), c.width,
                         PER_MEMBER, c.seed, c.cores)
    assert all(len(p) == 4 for p in picks)
    jobs = [(s, check.column(c.dispatched[k], *lane))
            for s, k, *lane in picks]
    bad = sum(bool(check.diff(a, b)) for (a, _), (b, _) in
              zip(ctl.lanes(jobs), ref.lanes(jobs)))
    assert bad > 0


def test_cores_must_match_the_ladder(tiny4):
    _, cfg, traffic = tiny4
    with pytest.raises(ValueError, match="cores"):
        cellmod.check_config(cfg, 2)
    one = dict(traffic, cores=1)
    with pytest.raises(ValueError, match="mixes"):
        cellmod.Cell(cfg, one, SEED, 1, "")
