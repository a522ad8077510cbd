"""Dynamic-geometry views + path-independent sim cache.

Property tests (deterministic random op sequences — no hypothesis
dependency) pin the core ladder invariant: a structure allocated at its
ladder-maximum shape, operated through a masked view, is BIT-IDENTICAL
to a statically allocated smaller structure:

- assoc.lookup_dyn / insert_lru_dyn   (L2 TLB views, PR 1)
- caches.L2Geom through l2_lookup / l2_insert / l2_retag_to_tlb /
  l2_touch and the access_data / access_pte composite paths (this PR)

Plus the Utopia RestSeg invariants (occupancy never exceeds the live
way count; a RestSeg hit resolves with ZERO walker cycles) and the
runner satellites: run() and run_batch() must write byte-identical
cache entries for the same key, and _key must digest non-JSON override
values (Lat, numpy/jnp scalars) without aliasing.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import assoc, caches
from repro.core.caches import BT_DATA, BT_TLB2, BT_TLB4, L2Geom, Lat

SEED = 20260730


# ------------------------------------------------------------- assoc views


def test_assoc_masked_view_equals_small_static():
    rng = np.random.default_rng(SEED)
    SETS, WAYS = 8, 4
    big = assoc.make(4 * SETS, 2 * WAYS)
    small = assoc.make(SETS, WAYS)
    mask = jnp.int32(SETS - 1)
    ways = jnp.int32(WAYS)
    for t in range(300):
        key = jnp.int32(rng.integers(0, 1 << 20))
        now = jnp.int32(t)
        if rng.random() < 0.5:
            hb, wb, sb = assoc.lookup_dyn(big, key, mask, ways)
            hs, ws, ss = assoc.lookup(small, key)
            assert bool(hb) == bool(hs)
            if bool(hs):
                assert int(wb) == int(ws) and int(sb) == int(ss)
                big = assoc.touch_lru(big, sb, wb, now)
                small = assoc.touch_lru(small, ss, ws, now)
        else:
            en = bool(rng.random() < 0.9)
            big, ev_t_b, ev_v_b = assoc.insert_lru_dyn(
                big, key, now, mask, ways, en)
            small, ev_t_s, ev_v_s = assoc.insert_lru(small, key, now, en)
            assert bool(ev_v_b) == bool(ev_v_s)
            if bool(ev_v_s):
                assert int(ev_t_b) == int(ev_t_s)
    big, small = assoc.rows(big), assoc.rows(small)
    assert np.array_equal(np.asarray(big.tags)[:SETS, :WAYS],
                          np.asarray(small.tags))
    assert np.array_equal(np.asarray(big.valid)[:SETS, :WAYS],
                          np.asarray(small.valid))
    assert np.array_equal(np.asarray(big.meta)[:SETS, :WAYS],
                          np.asarray(small.meta))
    # the view never leaks outside its live geometry
    live = np.zeros_like(np.asarray(big.valid), bool)
    live[:SETS, :WAYS] = True
    assert not np.asarray(big.valid)[~live].any()


def _assert_l2_view_equal(big, small, sets, ways):
    big, small = assoc.rows(big), assoc.rows(small)
    for field in ("tags", "valid", "rrpv", "btype", "reuse"):
        a = np.asarray(getattr(big, field))[:sets, :ways]
        b = np.asarray(getattr(small, field))
        assert np.array_equal(a, b), field
    for field in ("hist_reuse_data", "hist_reuse_tlb",
                  "n_tlb4", "n_tlb2", "n_ntlb"):
        assert np.array_equal(np.asarray(getattr(big, field)),
                              np.asarray(getattr(small, field))), field
    live = np.zeros((big.tags.shape[0], big.tags.shape[1]), bool)
    live[:sets, :ways] = True
    assert not np.asarray(big.valid)[~live].any()


@pytest.mark.parametrize("tlb_aware", [True, False])
def test_l2_cache_masked_view_equals_small_static(tlb_aware):
    """Random l2_insert/l2_lookup/l2_touch/l2_retag_to_tlb sequences:
    the L2Geom view of a 4x-oversized L2 == a statically small L2."""
    rng = np.random.default_rng(SEED + tlb_aware)
    SETS, WAYS = 8, 4
    big = caches.make_l2(4 * SETS, 4 * WAYS)
    small = caches.make_l2(SETS, WAYS)
    geom = L2Geom(set_mask=jnp.int32(SETS - 1), n_ways=jnp.int32(WAYS))
    bts = [BT_DATA, BT_TLB4, BT_TLB2]
    for t in range(400):
        key = jnp.int32(rng.integers(0, 1 << 16))
        bt = bts[rng.integers(0, len(bts))]
        pressure = jnp.bool_(rng.random() < 0.5)
        op = rng.random()
        if op < 0.25:
            hb, wb, sb = caches.l2_lookup(big, key, bt, geom)
            hs, ws, ss = caches.l2_lookup(small, key, bt)
            assert bool(hb) == bool(hs), t
            if bool(hs):
                assert int(wb) == int(ws) and int(sb) == int(ss)
                big = caches.l2_touch(big, sb, wb, pressure, tlb_aware,
                                      True)
                small = caches.l2_touch(small, ss, ws, pressure,
                                        tlb_aware, True)
        elif op < 0.65:
            en = bool(rng.random() < 0.9)
            big = caches.l2_insert(big, key, bt, pressure, tlb_aware, en,
                                   geom)
            small = caches.l2_insert(small, key, bt, pressure, tlb_aware,
                                     en)
        else:
            tlb_bt = BT_TLB2 if bt == BT_TLB2 else BT_TLB4
            big = caches.l2_retag_to_tlb(big, key, tlb_bt, pressure,
                                         tlb_aware, True, geom)
            small = caches.l2_retag_to_tlb(small, key, tlb_bt, pressure,
                                           tlb_aware, True)
    _assert_l2_view_equal(big, small, SETS, WAYS)


def test_hier_access_paths_masked_view_equals_small_static():
    """access_data + access_pte composites (incl. prefetch + background
    traffic + L3 interaction) under an L2Geom view == small static L2."""
    rng = np.random.default_rng(SEED)
    SETS, WAYS = 16, 4
    lat = Lat()
    big = caches.make_hier(l1_sets=4, l1_ways=2, l2_sets=4 * SETS,
                           l2_ways=2 * WAYS, l3_sets=16, l3_ways=4)
    small = caches.make_hier(l1_sets=4, l1_ways=2, l2_sets=SETS,
                             l2_ways=WAYS, l3_sets=16, l3_ways=4)
    geom = L2Geom(set_mask=jnp.int32(SETS - 1), n_ways=jnp.int32(WAYS))
    for t in range(200):
        line = jnp.int32(rng.integers(0, 1 << 14))
        now = jnp.int32(t + 1)
        pressure = jnp.bool_(rng.random() < 0.5)
        if rng.random() < 0.7:
            big, cb = caches.access_data(big, line, now, pressure, True,
                                         lat, geom)
            small, cs = caches.access_data(small, line, now, pressure,
                                           True, lat)
        else:
            big, cb, db = caches.access_pte(big, line, pressure, True,
                                            lat, True, bt=BT_TLB4,
                                            geom=geom)
            small, cs, ds = caches.access_pte(small, line, pressure, True,
                                              lat, True, bt=BT_TLB4)
            assert bool(db) == bool(ds), t
        assert int(cb) == int(cs), t
    _assert_l2_view_equal(big.l2, small.l2, SETS, WAYS)
    assert np.array_equal(np.asarray(big.l3.tags), np.asarray(small.l3.tags))
    assert np.array_equal(np.asarray(big.l1d.tags),
                          np.asarray(small.l1d.tags))


# ------------------------------------------------------- utopia restseg


def test_restseg_masked_view_equals_small_static():
    """The RestSeg migrate/probe path (insert_lru_dyn + lookup_dyn under
    a way limit) over an oversized allocation == a statically small
    RestSeg, and occupancy never exceeds the live way count."""
    rng = np.random.default_rng(SEED)
    SETS, WAYS = 8, 4
    big = assoc.make(SETS, 4 * WAYS)   # ladder-maximum way allocation
    small = assoc.make(SETS, WAYS)
    mask = jnp.int32(SETS - 1)
    ways = jnp.int32(WAYS)
    for t in range(300):
        vpn = jnp.int32(rng.integers(0, 1 << 16))
        now = jnp.int32(t)
        if rng.random() < 0.5:  # probe (+ LRU touch on hit)
            hb, wb, sb = assoc.lookup_dyn(big, vpn, mask, ways)
            hs, ws, ss = assoc.lookup(small, vpn)
            assert bool(hb) == bool(hs), t
            if bool(hs):
                assert int(wb) == int(ws) and int(sb) == int(ss)
                big = assoc.touch_lru(big, sb, wb, now)
                small = assoc.touch_lru(small, ss, ws, now)
        else:  # migration; a conflict demotes the LRU resident
            mig = bool(rng.random() < 0.8)
            big, _, conf_b = assoc.insert_lru_dyn(big, vpn, now, mask,
                                                  ways, mig)
            small, _, conf_s = assoc.insert_lru(small, vpn, now, mig)
            assert bool(conf_b) == bool(conf_s), t
        occupancy = np.asarray(assoc.rows(big).valid).sum(axis=1)
        assert occupancy.max() <= WAYS, t
    big, small = assoc.rows(big), assoc.rows(small)
    assert np.array_equal(np.asarray(big.tags)[:, :WAYS],
                          np.asarray(small.tags))
    assert not np.asarray(big.valid)[:, WAYS:].any()


def _simulate_final_state(cfg, trace, dyn=None):
    from repro.core.mmu import make_state, make_step

    step = make_step(cfg, dyn=dyn)

    @jax.jit
    def run(tr):
        st, _ = jax.lax.scan(step, make_state(cfg), tr)
        return st

    return run(trace)


def test_restseg_migration_invariants():
    """End-to-end utopia run: every RestSeg hit is walk-free (hits +
    demand walks exactly cover the L2-TLB misses), migrations only
    follow walks, conflicts only follow migrations — and under a dyn
    way limit nothing is ever resident outside the live ways."""
    from golden_trace import GOLDEN_CFG, golden_trace
    from repro.core.mmu import simulate
    from repro.core.stages import dyn_of

    cfg = dataclasses.replace(GOLDEN_CFG, utopia=True, restseg4_sets=16,
                              restseg2_sets=8, restseg_ways=4)
    trace = {k: jnp.asarray(v) for k, v in golden_trace(n=2000).items()}
    stats, _ = simulate(cfg, trace)
    hits = int(stats.n_restseg_hit)
    assert hits > 0
    # RestSeg hit => zero walk cycles: walks + hits partition the misses
    assert hits + int(stats.n_demand_ptw) == int(stats.n_l2tlb_miss)
    assert hits + int(stats.n_restseg_miss) == int(stats.n_l2tlb_miss)
    assert int(stats.n_restseg_mig) <= int(stats.n_demand_ptw)
    assert int(stats.n_restseg_conflict) <= int(stats.n_restseg_mig)
    assert int(np.asarray(stats.hist_restseg).sum()) \
        == hits + int(stats.n_restseg_miss)

    # dyn way-limited run: occupancy stays inside the live view
    ways_alloc = dataclasses.replace(cfg, restseg_ways=8)
    st = _simulate_final_state(ways_alloc, trace, dyn=dyn_of(cfg))
    for rs in (st.restseg4, st.restseg2):
        valid = np.asarray(assoc.rows(rs).valid)
        assert valid.sum(axis=1).max() <= cfg.restseg_ways
        assert not valid[:, cfg.restseg_ways:].any()
    assert np.asarray(st.restseg4.valid).any()  # migrations landed


# ------------------------------------------------------- revelator


def test_revelator_speculation_invariants():
    """End-to-end revelator run: speculative hits + mispredicts + demand
    walks exactly cover the L2-TLB misses, mispredicts DO occur under a
    lossy signature (the alias model), every speculative resolution pays
    an overlapped verification walk, and enrollment only follows walks."""
    import dataclasses as dc

    from golden_trace import GOLDEN_CFG, golden_trace
    from repro.core.mmu import simulate

    cfg = dc.replace(GOLDEN_CFG, revelator=True, rev_sets=16, rev_ways=4,
                     rev_sig_bits=10)
    trace = {k: jnp.asarray(v) for k, v in golden_trace(n=2000).items()}
    stats, _ = simulate(cfg, trace)
    hits = int(stats.n_rev_hit)
    mis = int(stats.n_rev_mispred)
    assert hits > 0 and mis > 0
    # speculation resolves without the demand walker: partition holds
    assert hits + mis + int(stats.n_demand_ptw) == int(stats.n_l2tlb_miss)
    # every speculative resolution was verified by a real (overlapped)
    # walk; verification is never free
    assert int(np.asarray(stats.hist_rev_verify).sum()) == hits + mis
    assert float(stats.sum_rev_verify_cyc) > 0
    # enrollment is PTW-CP-gated after demand walks only
    assert int(stats.n_rev_enroll) <= int(stats.n_demand_ptw)
    assert int(stats.n_rev_enroll) > 0


# --------------------------------------------------- path-independent cache


_TINY = dict(
    l2tlb_sets=4, l2tlb_ways=4,
    l1d4_sets=2, l1d4_ways=2, l1d2_sets=2, l1d2_ways=2,
    l2_sets=64, l2_ways=8, l3_sets=64, l3_ways=8,
    n_pages4=1 << 12, n_pages2=1 << 8, n_pagesh=1 << 8, n_feat=1 << 10,
)


def test_run_and_run_batch_write_identical_cache_entries(tmp_path,
                                                         monkeypatch):
    """Fresh-cache run() and run_batch() must produce byte-identical
    entries for the same (system, workload, n, seed, overrides) —
    cached Stats must not depend on which code path filled them."""
    from repro.sim import runner

    n, seed, w = 1500, 3, "bc"
    dir_a, dir_b = str(tmp_path / "a"), str(tmp_path / "b")

    monkeypatch.setattr(runner, "CACHE_DIR", dir_a)
    res_run = runner.run("radix", w, n=n, seed=seed, overrides=_TINY)
    monkeypatch.setattr(runner, "CACHE_DIR", dir_b)
    res_batch = runner.run_batch("radix", workloads=[w], n=n, seed=seed,
                                 overrides=_TINY)[w]

    key = runner._key("radix", w, n, seed, _TINY) + ".pkl"
    with open(os.path.join(dir_a, key), "rb") as f:
        blob_a = f.read()
    with open(os.path.join(dir_b, key), "rb") as f:
        blob_b = f.read()
    assert blob_a == blob_b
    for field, a, b in zip(res_run[0]._fields, res_run[0], res_batch[0]):
        assert np.array_equal(np.asarray(a), np.asarray(b)), field


def test_key_canonicalizes_non_json_overrides():
    from repro.sim import runner

    # NamedTuple / dataclass values must not crash
    k_lat = runner._key("radix", "bc", 10, 0, {"lat": Lat(l2=20)})
    assert k_lat != runner._key("radix", "bc", 10, 0,
                                {"lat": (4, 20, 35, 160)})
    # numpy / jnp scalars hash like the equivalent python numbers
    # (they produce the same replace()d config, so they must share a key)
    assert runner._key("radix", "bc", 10, 0, {"l2_sets": np.int32(64)}) \
        == runner._key("radix", "bc", 10, 0, {"l2_sets": 64})
    assert runner._key("radix", "bc", 10, 0, {"l2_sets": jnp.int32(64)}) \
        == runner._key("radix", "bc", 10, 0, {"l2_sets": 64})
    # distinct values stay distinct
    assert runner._key("radix", "bc", 10, 0, {"l2_sets": 64}) \
        != runner._key("radix", "bc", 10, 0, {"l2_sets": 128})
    # still stable for plain-JSON overrides (legacy keys unchanged)
    assert runner._key("radix", "bc", 10, 0, {"victima": True}) \
        == runner._key("radix", "bc", 10, 0, {"victima": True})


def test_ptw_reduction_zero_baseline_is_zero():
    """A baseline with no demand walks has nothing to reduce: the old
    ``1 - new/max(base, 1)`` returned a large NEGATIVE number instead
    of 0.0 whenever the comparison system did walk."""
    import types

    from repro.core import metrics

    none = types.SimpleNamespace(n_demand_ptw=0)
    some = types.SimpleNamespace(n_demand_ptw=500)
    assert metrics.ptw_reduction(none, some) == 0.0
    assert metrics.ptw_reduction(some, none) == 1.0
    assert metrics.ptw_reduction(some, some) == 0.0
    assert metrics.reduction(100, 25) == 0.75


def test_sweep_rejects_unknown_systems_before_simulating():
    from repro.sim import sweep

    with pytest.raises(SystemExit, match="unknown system"):
        sweep.main(["radix", "definitely_not_a_system"])


_NO_OPTS = {"mesh": None, "devices": None, "backend": None, "time_shards": 1,
            "obs_trace": None, "cores": None, "mix": []}


def test_sweep_parse_args_accepts_both_tag_forms():
    from repro.sim import sweep

    assert sweep.parse_args(["--tags", "native,ablation"]) \
        == ([], ["native", "ablation"], _NO_OPTS)
    assert sweep.parse_args(["--tags=utopia"]) == ([], ["utopia"], _NO_OPTS)
    assert sweep.parse_args(["radix", "--tags", "virt", "pom"]) \
        == (["radix", "pom"], ["virt"], _NO_OPTS)


def test_sweep_parse_args_mesh_and_devices():
    from repro.sim import sweep

    assert sweep.parse_args(["--mesh", "2x2", "--devices", "4"]) \
        == ([], [], {**_NO_OPTS, "mesh": (2, 2), "devices": 4})
    assert sweep.parse_args(["--mesh=4x1", "radix"]) \
        == (["radix"], [], {**_NO_OPTS, "mesh": (4, 1)})
    with pytest.raises(SystemExit, match="SYSxWL"):
        sweep.parse_args(["--mesh", "4"])
    with pytest.raises(SystemExit, match="positive integer"):
        sweep.parse_args(["--devices", "zero"])
    with pytest.raises(SystemExit, match=r"needs a SYSxWL\[xCORE\] value"):
        sweep.parse_args(["--mesh", "--tags"])


def test_sweep_parse_args_rejects_flag_like_tag_values():
    """``--tags --foo`` used to silently swallow the next option as a
    tag list; flag-like values must error out instead."""
    from repro.sim import sweep

    with pytest.raises(SystemExit, match="needs a comma-separated value"):
        sweep.parse_args(["--tags", "--foo"])
    with pytest.raises(SystemExit, match="needs a comma-separated value"):
        sweep.parse_args(["--tags=-foo"])
    with pytest.raises(SystemExit, match="needs a comma-separated value"):
        sweep.parse_args(["--tags"])  # missing value entirely


def test_run_ladder_reuses_cached_member_cells(tmp_path, monkeypatch):
    """A workload with SOME members cached used to re-simulate and
    REWRITE every member's entry; cached cells must be returned as-is
    (neither recomputed nor rewritten — mtime/bytes unchanged) and only
    the missing cells stored."""
    from repro.core.stages import zero_stats
    from repro.sim import runner

    monkeypatch.setattr(runner, "CACHE_DIR", str(tmp_path))
    members, wls, n, seed = ("radix", "victima"), ["bc", "bfs"], 64, 7

    # pre-seed ONE cell with sentinel content the stub cannot produce
    sentinel = ({"marker": "seeded"}, {"extras": 1}, None)
    seeded = runner._path("radix", "bc", n, seed, None)
    runner._store(seeded, sentinel)
    stat0 = os.stat(seeded)
    with open(seeded, "rb") as f:
        bytes0 = f.read()

    calls = []

    def fake_make_systems_runner(cfg, plan, stage_names=None, **kwargs):
        def fake_run(dyns, traces):
            import jax
            S = jax.tree.leaves(dyns)[0].shape[0]
            W = jax.tree.leaves(traces)[0].shape[1]
            calls.append((S, W))
            per = [[zero_stats() for _ in range(W)] for _ in range(S)]
            extras = [[{"stub": True} for _ in range(W)] for _ in range(S)]
            return per, extras
        return fake_run

    monkeypatch.setattr(runner, "make_systems_runner",
                        fake_make_systems_runner)
    out = runner.run_ladder("radix", workloads=wls, n=n, seed=seed,
                            members=members)

    # the seeded cell came back from the cache, not the stub...
    assert out["radix"]["bc"] == sentinel
    # ...its bytes and mtime are untouched...
    stat1 = os.stat(seeded)
    with open(seeded, "rb") as f:
        assert f.read() == bytes0
    assert stat1.st_mtime_ns == stat0.st_mtime_ns
    # ...and the three genuinely missing cells were simulated + stored in
    # ONE dispatch at the auto-tuned chunk width (derived from the FULL
    # workload list, so a partially-cached rerun reuses the same shape)
    assert calls == [(len(members), runner.auto_chunk(len(wls)))]
    for s, w in [("victima", "bc"), ("radix", "bfs"), ("victima", "bfs")]:
        assert out[s][w][1] == {"stub": True}, (s, w)
        assert os.path.exists(runner._path(s, w, n, seed, None)), (s, w)


def test_trace_gen_reports_total_page_count():
    from repro.sim import trace_gen

    gen = trace_gen.generate("bc", n=1000, seed=0)
    assert "n_pages4" not in gen  # renamed: it was the TOTAL page count
    assert gen["n_pages"] > 0
    assert int(np.max(gen["trace"]["vpn"])) < gen["n_pages"]
