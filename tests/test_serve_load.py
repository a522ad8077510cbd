"""Serving load harness + the engine bugs it exposed.

Regression coverage for the production-traffic fixes: admissions/growth
must never alias pages under pool exhaustion, dead slots must stay out
of the translation batch, the pressure signal must decay with the
working set (epoch window, not lifetime counters), the VTC index
geometry must be validated up front (n_clusters=1 remains the valid
ablation), and the harness's BENCH_serve records must re-derive
bit-exactly from the obs trace.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.obs import report
from repro.paged import block_table as btab
from repro.paged import translation_cache as vtc_mod
from repro.serve import engine, load
from repro.sim import parallel


@pytest.fixture
def tr(tmp_path):
    t = obs.configure(str(tmp_path / "trace.jsonl"))
    yield t
    obs.configure()


def _mapped_pages(st):
    """Every physical page reachable from the block tables (host list)."""
    rows = np.asarray(st.bt.directory)
    leaves = np.asarray(st.bt.leaves)
    pages = []
    for r in range(rows.shape[0]):
        for row in rows[r]:
            if row >= 0:
                pages += [int(p) for p in leaves[row] if p >= 0]
    return pages


def _assert_no_aliasing(st):
    pages = _mapped_pages(st)
    assert len(pages) == len(set(pages)), (
        f"physical page mapped twice: {sorted(pages)}")
    # and the free vector agrees with the mapping
    assert int(jnp.sum(st.page_free)) == st.page_free.shape[0] - len(pages)


# ------------------------------------------- pool exhaustion (no alias)


def test_admit_rejects_on_pool_exhaustion_without_aliasing():
    cfg = engine.EngineConfig(n_slots=4, max_blocks_per_req=8,
                              n_pool_pages=8, n_leaf_rows=16,
                              tc_sets=8, tc_ways=2, n_clusters=16)
    st = engine.init(cfg)
    st, ok0 = engine.admit(st, 0, 6)
    assert bool(ok0)
    before = jax.device_get(st)
    # only 2 pages left: a 5-page admission must be rejected ATOMICALLY
    st, ok1 = engine.admit(st, 1, 5)
    assert not bool(ok1)
    assert not bool(st.slot_live[1]) and int(st.slot_len[1]) == 0
    assert int(jnp.sum(st.page_free)) == 2  # nothing leaked
    np.testing.assert_array_equal(np.asarray(st.page_free),
                                  np.asarray(before.page_free))
    _assert_no_aliasing(st)
    # a request that still fits is admitted fine afterwards
    st, ok2 = engine.admit(st, 2, 2)
    assert bool(ok2)
    _assert_no_aliasing(st)
    # degenerate requests are rejected too
    st, ok3 = engine.admit(st, 3, 0)
    assert not bool(ok3)


def test_pages_consistent_flags_aliased_and_free_mapped_pages():
    """The per-tick device invariant run_load checks agrees with the
    host reference on a clean state and catches both faults."""
    cfg = engine.EngineConfig(n_slots=4, max_blocks_per_req=8,
                              n_pool_pages=8, n_leaf_rows=16,
                              tc_sets=8, tc_ways=2, n_clusters=16)
    st = engine.init(cfg)
    assert bool(engine.pages_consistent(st))
    st, ok = engine.admit(st, 0, 3)
    assert bool(ok)
    _assert_no_aliasing(st)
    assert bool(engine.pages_consistent(st))
    row = int(st.bt.directory[0, 0])
    page = int(st.bt.leaves[row, 0])
    aliased = st._replace(bt=st.bt._replace(
        leaves=st.bt.leaves.at[row, 3].set(page)))
    assert not bool(engine.pages_consistent(aliased))
    mapped_and_free = st._replace(page_free=st.page_free.at[page].set(1))
    assert not bool(engine.pages_consistent(mapped_and_free))


def test_decode_grow_stalls_when_pool_exhausted():
    cfg = engine.EngineConfig(n_slots=2, max_blocks_per_req=8,
                              n_pool_pages=4, n_leaf_rows=16,
                              tc_sets=8, tc_ways=2, n_clusters=16)
    st = engine.init(cfg)
    st, ok = engine.admit(st, 0, 4)     # consumes the whole pool
    assert bool(ok) and int(jnp.sum(st.page_free)) == 0
    len0 = int(st.slot_len[0])
    # pos % TOKENS_PER_PAGE == 0 -> the tick wants to grow a page, but
    # none is free: the slot must STALL (src -1, no advance), not map
    # argmax(all-zero) == page 0 on top of request 0's first block
    st, phys, src = engine.decode_translate(st, cfg)
    assert int(src[0]) == -1
    assert int(st.slot_len[0]) == len0
    assert int(st.n_pool_stall) == 1
    _assert_no_aliasing(st)
    assert engine.stats(st, scope="stall_t")["pool_stall"] == 1
    # freeing pages (retirement) unblocks the next tick
    st = engine.retire(st, 0, scope="stall_t")
    st, ok = engine.admit(st, 0, 2)
    st, phys, src = engine.decode_translate(st, cfg)
    assert int(src[0]) >= 0
    _assert_no_aliasing(st)


# ------------------------------------------------- dead-slot masking


def test_dead_slots_never_enter_translation_batch():
    cfg = engine.EngineConfig(n_slots=4, max_blocks_per_req=8,
                              n_pool_pages=64, n_leaf_rows=32,
                              tc_sets=8, tc_ways=2, n_clusters=16)
    st = engine.init(cfg)
    # no live slots: ticks must touch NO VTC state and no pressure window
    for _ in range(10):
        st, phys, src = engine.decode_translate(st, cfg)
        assert all(int(x) == -1 for x in src)
    v = vtc_mod.stats(st.vtc)
    assert v["n_hit_tc"] == v["n_hit_cluster"] == v["n_walk"] == 0
    assert int(st.win_total) == 0 and not bool(st.pressure)


def test_translation_counts_match_per_live_slot_reference():
    """Stats parity pin: with 2 of 4 slots live, the lifetime VTC counter
    total must equal exactly the per-live-slot stream count (3 lanes per
    live slot per tick) — dead slots contribute nothing."""
    cfg = engine.EngineConfig(n_slots=4, max_blocks_per_req=8,
                              n_pool_pages=64, n_leaf_rows=32,
                              tc_sets=8, tc_ways=2, n_clusters=16)
    st = engine.init(cfg)
    st, _ = engine.admit(st, 0, 2)
    st, _ = engine.admit(st, 2, 3)
    ticks = 9
    for _ in range(ticks):
        st, phys, src = engine.decode_translate(st, cfg)
        assert int(src[1]) == -1 and int(src[3]) == -1
        assert int(src[0]) >= 0 and int(src[2]) >= 0
    v = vtc_mod.stats(st.vtc)
    assert v["n_hit_tc"] + v["n_hit_cluster"] + v["n_walk"] == 6 * ticks


def test_translate_batch_valid_mask_is_inert():
    bt = btab.make(2, 64, 16)
    for b in range(4):
        bt = btab.map_block(bt, jnp.int32(0), jnp.int32(b), jnp.int32(b + 9))
    vtc = vtc_mod.make(tc_sets=8, tc_ways=2, n_clusters=16)
    reqs = jnp.array([0, 0], jnp.int32)
    blks = jnp.array([1, 2], jnp.int32)
    valid = jnp.array([True, False])
    v1, b1, phys, src = vtc_mod.translate_batch(
        vtc, bt, reqs, blks, jnp.bool_(False), valid=valid)
    assert int(phys[0]) == 10 and int(src[0]) >= 0
    assert int(phys[1]) == -1 and int(src[1]) == -1
    # the masked lane left EXACTLY the state the unmasked prefix built
    v2, b2, _, _ = vtc_mod.translate(vtc, bt, jnp.int32(0), jnp.int32(1),
                                     jnp.bool_(False))
    for a, b in zip(jax.tree.leaves(v1), jax.tree.leaves(v2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(b1), jax.tree.leaves(b2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- windowed pressure


def test_pressure_decays_after_working_set_shrinks():
    cfg = engine.EngineConfig(n_slots=2, max_blocks_per_req=8,
                              n_pool_pages=64, n_leaf_rows=32,
                              tc_sets=4, tc_ways=2, n_clusters=16,
                              pressure_epoch=8, pressure_thresh=0.15)
    st = engine.init(cfg)
    # phase 1 — churn: admit/tick/retire so every tick translates cold
    # (retirement shoots down the VTC): walk-heavy windows latch pressure
    for _ in range(40):
        st, ok = engine.admit(st, 0, 2)
        assert bool(ok)
        st, _, _ = engine.decode_translate(st, cfg)
        st = engine.retire(st, 0, scope="decay_t")
    assert bool(st.pressure), "walk-heavy churn must latch pressure"
    # phase 2 — the working set shrinks to one hot request: the sampled
    # window sees mostly TC hits and the NEXT epoch boundary must drop
    # pressure, even though the lifetime walk rate stays above threshold
    st, ok = engine.admit(st, 0, 2)
    for _ in range(24):
        st, _, _ = engine.decode_translate(st, cfg)
    assert not bool(st.pressure), "pressure must decay with the workload"
    v = vtc_mod.stats(st.vtc)
    assert v["walk_rate"] > cfg.pressure_thresh, (
        "regression guard is vacuous: lifetime counters would have "
        "decayed on their own")


# ------------------------------------- index-geometry validation


def test_vtc_make_rejects_non_pow2_geometry():
    with pytest.raises(ValueError, match="tc_sets"):
        vtc_mod.make(tc_sets=12, tc_ways=2, n_clusters=16)
    with pytest.raises(ValueError, match="n_clusters"):
        vtc_mod.make(tc_sets=8, tc_ways=2, n_clusters=3)
    with pytest.raises(ValueError, match="tc_ways"):
        vtc_mod.make(tc_sets=8, tc_ways=0, n_clusters=16)


def test_engine_config_rejects_bad_geometry():
    with pytest.raises(ValueError, match="tc_sets"):
        engine.EngineConfig(tc_sets=12)
    with pytest.raises(ValueError, match="n_clusters"):
        engine.EngineConfig(n_clusters=24)
    with pytest.raises(ValueError, match="pressure_epoch"):
        engine.EngineConfig(pressure_epoch=0)
    with pytest.raises(ValueError, match="gate"):
        engine.EngineConfig(gate_freq_min=-1)


def test_n_clusters_one_is_the_valid_ablation():
    bt = btab.make(2, 64, 16)
    for b in range(8):
        bt = btab.map_block(bt, jnp.int32(0), jnp.int32(b), jnp.int32(b + 3))
    vtc = vtc_mod.make(tc_sets=4, tc_ways=2, n_clusters=1)
    for b in list(range(8)) * 2:
        vtc, bt, phys, src = vtc_mod.translate(
            vtc, bt, jnp.int32(0), jnp.int32(b), jnp.bool_(True))
        assert int(phys) == b + 3
    # and the engine runs end-to-end on the ablation config
    cfg = engine.EngineConfig(n_slots=2, max_blocks_per_req=8,
                              n_pool_pages=32, n_leaf_rows=16,
                              tc_sets=8, tc_ways=2, n_clusters=1)
    st = engine.init(cfg)
    st, _ = engine.admit(st, 0, 2)
    for _ in range(4):
        st, phys, src = engine.decode_translate(st, cfg)
    assert int(src[0]) >= 0


# --------------------------------------------------- arrival traces


def test_arrival_traces_respect_mix_and_capacity():
    cfg = engine.EngineConfig(n_slots=4, max_blocks_per_req=8,
                              n_pool_pages=64, n_leaf_rows=32)
    cap = cfg.max_blocks_per_req - 1
    for trace in (load.poisson_trace(2.0, 40, cfg, seed=3),
                  load.diurnal_trace(2.0, 40, cfg, seed=3)):
        assert trace, "a 2 req/tick trace over 40 ticks cannot be empty"
        for r in trace:
            assert 0 <= r.arrive_tick < 40
            assert 1 <= r.prompt_blocks <= cap
            assert r.decode_tokens >= 1
            assert r.kind in load.MIX_WEIGHTS
    # determinism: same seed, same trace
    a = load.poisson_trace(1.0, 20, cfg, seed=5)
    b = load.poisson_trace(1.0, 20, cfg, seed=5)
    assert a == b


def test_length_mix_spans_short_and_long_requests():
    cfg = engine.EngineConfig()
    mix = load.length_mix(cfg)
    blocks = sorted(m[1] for m in mix)
    assert blocks[0] < blocks[-1]  # 4K chat << 500K long-context
    assert blocks[-1] <= cfg.max_blocks_per_req - 1


# ------------------------------------------------------ lane sharding


def test_plan_lane_dim_divisor_rule():
    assert parallel.plan_lane_dim(4, n_devices=1) == 1
    assert parallel.plan_lane_dim(4, n_devices=2) == 2
    assert parallel.plan_lane_dim(4, n_devices=3) == 2
    assert parallel.plan_lane_dim(6, n_devices=4) == 3
    assert parallel.plan_lane_dim(3, n_devices=2) == 1
    with pytest.raises(ValueError):
        parallel.plan_lane_dim(0)


def test_shard_lanes_runs_fn_per_lane():
    fn = jax.vmap(lambda x: x * 2 + 1)
    call = parallel.shard_lanes(fn, 4)
    out = call(jnp.arange(4, dtype=jnp.int32).reshape(4, 1))
    np.testing.assert_array_equal(np.asarray(out).ravel(),
                                  np.array([1, 3, 5, 7]))
    assert jax.local_device_count() % call.mesh_dim == 0


# ------------------------------------------------- harness round trip


def test_run_load_round_trip_bit_exact(tr, tmp_path):
    import json

    from repro.obs.__main__ import main
    cfg = engine.EngineConfig(n_slots=4, max_blocks_per_req=8,
                              n_pool_pages=64, n_leaf_rows=32,
                              tc_sets=8, tc_ways=2, n_clusters=16)
    trace = load.poisson_trace(1.0, 25, cfg, seed=11)
    before = len(load.SERVE_PERF)
    rec = load.run_load(trace, cfg, lanes=1, run="rt_test",
                        arrival="poisson", rate=1.0)
    assert len(load.SERVE_PERF) == before + 1
    assert set(rec) == set(report.SERVE_FIELDS)
    assert rec["run"] == "rt_test" and rec["n_arrivals"] == len(trace)
    assert rec["admitted"] == rec["retired"] == len(trace)
    assert rec["decode_p50_s"] > 0 and rec["decode_p99_s"] >= rec["decode_p50_s"]
    assert rec["throughput_rps"] > 0
    assert 0.0 <= rec["vtc_hit_rate"] <= 1.0
    assert rec["vtc_hit_tc"] + rec["vtc_hit_cluster"] + rec["vtc_walk"] > 0
    # offline reconstruction from the JSONL file is bit-exact
    tr.flush()
    offline = report.serve_record(report.read_trace(tr.path),
                                  trace_file=tr.path)
    assert offline == rec
    # and the CLI check agrees against a written artifact
    art = tmp_path / "BENCH_serve.json"
    art.write_text(json.dumps({"schema": 1, "serve_runs": [rec]}))
    assert main(["report", tr.path, "--check", str(art)]) == 0
    doctored = dict(rec, retired=rec["retired"] + 1)
    art.write_text(json.dumps({"schema": 1, "serve_runs": [doctored]}))
    assert main(["report", tr.path, "--check", str(art)]) == 1


def test_run_load_backpressure_requeues_rejections(tr):
    """A pool-starved engine must reject, re-queue, and still finish
    every request — with the rejections visible in the record."""
    cfg = engine.EngineConfig(n_slots=4, max_blocks_per_req=8,
                              n_pool_pages=14, n_leaf_rows=32,
                              tc_sets=8, tc_ways=2, n_clusters=16)
    reqs = [load.Request(0, 4, 2, "train_4k") for _ in range(6)]
    rec = load.run_load(reqs, cfg, lanes=1, run="bp_test",
                        arrival="burst", rate=6.0)
    assert rec["rejected"] > 0
    assert rec["retired"] == len(reqs)
    assert rec["admitted"] == len(reqs)


def test_run_load_two_lanes(tr):
    cfg = engine.EngineConfig(n_slots=2, max_blocks_per_req=8,
                              n_pool_pages=32, n_leaf_rows=16,
                              tc_sets=8, tc_ways=2, n_clusters=16)
    trace = load.poisson_trace(1.0, 15, cfg, seed=4)
    rec = load.run_load(trace, cfg, lanes=2, run="lanes_test",
                        arrival="poisson", rate=2.0)
    assert rec["lanes"] == 2
    assert rec["retired"] == len(trace)
    assert jax.local_device_count() % rec["mesh"] == 0


# ------------------------------------------------------- gate tuning


def test_tune_gate_maps_box_lower_edges(monkeypatch):
    from repro.core import ptwcp_nn
    from repro.sim import runner
    monkeypatch.setattr(
        runner, "run_batch",
        lambda system, workloads, n: {w: (None, {"feat": w}, None)
                                      for w in workloads})
    monkeypatch.setattr(
        ptwcp_nn, "build_dataset",
        lambda extras: (np.zeros((4, 2)), np.zeros(4)))
    monkeypatch.setattr(ptwcp_nn, "fit_box",
                        lambda X, y: (3, 12, 2, 9))  # clo, chi, flo, fhi
    assert load.tune_gate(n=10) == (2, 3)
    # refit edges beyond the counters' saturation range are clamped
    monkeypatch.setattr(ptwcp_nn, "fit_box",
                        lambda X, y: (99, 120, 50, 90))
    assert load.tune_gate(n=10) == (7, 15)


# ---------------------------------------------------- OB001 closure


def test_ob001_serve_contract_clean():
    from repro.analysis import obs_contract
    assert obs_contract.check_serve_field_sources() == []
    assert obs_contract.check_load_appends() == []


def test_ob001_catches_hand_assembled_serve_record(tmp_path):
    from repro.analysis import obs_contract
    bad = tmp_path / "load.py"
    bad.write_text(
        "import repro.obs as obs\n"
        "from repro.obs import names\n"
        "SERVE_PERF = []\n"
        "def run_load():\n"
        "    with obs.span(names.SPAN_SERVE_RUN, run='x') as run_span:\n"
        "        pass\n"
        "    SERVE_PERF.append({'run': 'x'})\n")
    findings = obs_contract.check_load_appends(str(bad))
    assert findings and "hand-assembled" in findings[0]
