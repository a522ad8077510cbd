"""Pallas access-scan kernel + time-axis sharding: backend bit-identity.

- ``mmu_step.pick_block`` / ``parallel.pick_t_shards`` /
  ``runner.auto_chunk`` unit tests: exact-divisor tiling (padding the
  time axis would simulate phantom accesses), env overrides, rejection
  of empty/absurd inputs;
- ``mmu.resolve_backend`` validation (explicit arg and
  ``REPRO_SIM_BACKEND``) and the sweep CLI's upfront ``--backend`` /
  ``--time-shards`` rejection;
- ``blocked_scan`` == ``lax.scan`` on a toy carry for several block
  sizes, and ``time_shard_scan`` == a serial fold with the hand-off
  resolving in <= t rounds;
- the pallas backend (interpret mode on CPU) produces Stats
  bit-identical to the scan backend for EVERY member of the native and
  virt ladder families (tiny-shrunk configs, one batched call per
  backend), for ``simulate``/``simulate_batch``, and through a
  time-sharded (>= 2 block) run;
- ``run_ladder(backend="pallas")`` writes cache entries byte-identical
  to the scan fill and records backend/block/chunk_auto in LADDER_PERF;
- [multidev] time-sharded simulate on the forced 4-device mesh (blocks
  laid out on the ("t",) axis) still matches the serial scan
  bit-for-bit.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from golden_trace import GOLDEN_CFG, golden_trace
from repro.core import mmu
from repro.kernels import mmu_step
from repro.sim import parallel, systems
from test_parallel import _tiny_registry
from test_systems_registry import _stack_dyns, _tiny_config

multidev = pytest.mark.multidev


# ------------------------------------------------------------- unit: tiling


def test_pick_block_targets_the_grid_sweet_spot():
    # no target: the divisor whose grid length is nearest TARGET_GRID
    assert mmu_step.pick_block(2000) == 250      # grid 8
    assert mmu_step.pick_block(6000) == 750      # grid 8
    assert mmu_step.pick_block(512) == 64        # grid 8
    assert mmu_step.pick_block(149) == 149       # prime: one whole block
    assert mmu_step.pick_block(8) == 1           # grid 8 even when tiny


def test_pick_block_explicit_target_snaps_to_divisor():
    assert mmu_step.pick_block(2000, 100) == 100
    assert mmu_step.pick_block(2000, 99) == 100  # nearest divisor
    assert mmu_step.pick_block(2000, 3) == 4     # tie 2/4 prefers larger
    with pytest.raises(ValueError, match="empty trace"):
        mmu_step.pick_block(0)
    with pytest.raises(ValueError, match=">= 1"):
        mmu_step.pick_block(100, 0)


def test_pick_block_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_BLOCK", "500")
    assert mmu_step.pick_block(2000) == 500
    monkeypatch.setenv("REPRO_PALLAS_BLOCK", "")
    assert mmu_step.pick_block(2000) == 250


def test_pick_t_shards_rounds_down_to_divisor():
    assert parallel.pick_t_shards(600, 4) == 4
    assert parallel.pick_t_shards(600, 7) == 6   # 7 does not divide
    assert parallel.pick_t_shards(149, 4) == 1   # prime: no sharding
    assert parallel.pick_t_shards(600, 1) == 1
    with pytest.raises(ValueError, match="empty trace"):
        parallel.pick_t_shards(0, 2)
    with pytest.raises(ValueError, match=">= 1"):
        parallel.pick_t_shards(600, 0)


def test_auto_chunk_minimizes_dispatches_then_padding():
    from repro.sim import runner

    # 3 workloads: one dispatch, zero padding (the old fixed chunk=4
    # simulated a 4th, discarded lane)
    assert runner.auto_chunk(3) == 3
    assert runner.auto_chunk(1) == 1
    assert runner.auto_chunk(8) == 8
    assert runner.auto_chunk(12) == 6   # 2 dispatches, 0 padding (not 8/4pad)
    assert runner.auto_chunk(20) == 7   # 3 dispatches, 1 padded lane
    assert runner.auto_chunk(11, cap=4) == 4
    with pytest.raises(ValueError, match="no workloads"):
        runner.auto_chunk(0)


# --------------------------------------------------- unit: backend selection


def test_resolve_backend_validates(monkeypatch):
    monkeypatch.delenv("REPRO_SIM_BACKEND", raising=False)
    assert mmu.resolve_backend() == "scan"
    assert mmu.resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError, match="unknown simulation backend"):
        mmu.resolve_backend("fast")
    monkeypatch.setenv("REPRO_SIM_BACKEND", "pallas")
    assert mmu.resolve_backend() == "pallas"
    assert mmu.resolve_backend("scan") == "scan"  # explicit arg wins
    monkeypatch.setenv("REPRO_SIM_BACKEND", "bogus")
    with pytest.raises(ValueError, match="REPRO_SIM_BACKEND"):
        mmu.resolve_backend()


def test_pallas_is_refused_up_front_on_a_tpu(monkeypatch):
    """On a TPU the pallas backend raises with Mosaic's reason instead of
    running interpreted or falling back to scan; naming it stays a pure
    name check that touches no device."""
    monkeypatch.delenv("REPRO_SIM_BACKEND", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError) as e:
        mmu.resolve_backend("pallas")
    assert str(e.value) == mmu.PALLAS_ON_TPU
    assert mmu.resolve_backend("scan") == "scan"
    assert mmu.backend_name("pallas") == "pallas"
    with pytest.raises(ValueError, match="unknown simulation backend"):
        mmu.backend_name("fast")


def test_blocked_scan_runs_only_on_cpu_or_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    tr = jnp.arange(8, dtype=jnp.int32)
    st0 = (jnp.zeros((5,), jnp.int32), jnp.int32(0))
    with pytest.raises(ValueError, match="not on 'gpu'"):
        mmu_step.blocked_scan(_toy_step, st0, tr)


def test_sweep_cli_rejects_bad_backend_and_time_shards():
    """A typo'd --backend must die at parse time, BEFORE any ladder
    compile (mirroring the --tags fix)."""
    from repro.sim.sweep import parse_args

    assert parse_args(["--backend", "pallas"])[2]["backend"] == "pallas"
    assert parse_args(["--time-shards=4"])[2]["time_shards"] == 4
    with pytest.raises(SystemExit, match="unknown simulation backend"):
        parse_args(["--backend", "fast"])
    with pytest.raises(SystemExit, match="backend name"):
        parse_args(["--backend"])
    with pytest.raises(SystemExit, match="positive integer"):
        parse_args(["--time-shards", "0"])
    with pytest.raises(SystemExit, match="1x1"):
        parse_args(["--time-shards", "2", "--mesh", "2x2"])
    # a 1x1 mesh is the one forced factorization time sharding allows
    opts = parse_args(["--time-shards", "2", "--mesh", "1x1"])[2]
    assert opts["time_shards"] == 2 and opts["mesh"] == (1, 1)


# ----------------------------------------------- unit: blocked_scan mechanics


def _toy_step(st, acc, consts=None):
    """Order-dependent toy carry (gather/scatter like the real probes)."""
    tab, tot = st
    idx = acc % tab.shape[0]
    mul = 3 if consts is None else consts["mul"]
    tab = tab.at[idx].set(tab[idx] * mul + acc)
    return (tab, tot + tab[idx]), ()


def test_blocked_scan_matches_lax_scan_across_block_sizes():
    tr = jnp.arange(96, dtype=jnp.int32) * 7 + 1
    st0 = (jnp.zeros((5,), jnp.int32), jnp.int32(0))
    ref, _ = jax.lax.scan(_toy_step, st0, tr)
    for blk in (None, 96, 48, 12, 1):
        got = mmu_step.blocked_scan(_toy_step, st0, tr, block=blk)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), blk


def test_blocked_scan_delivers_consts_and_hoists_closures():
    """Per-call constants ride as kernel inputs, and constants baked
    into the step's CLOSURE (the stage composition does this) are
    hoisted automatically instead of tripping pallas's captured-consts
    error."""
    tr = jnp.arange(48, dtype=jnp.int32)
    st0 = (jnp.zeros((5,), jnp.int32), jnp.int32(0))
    consts = {"mul": jnp.int32(5)}
    ref, _ = jax.lax.scan(lambda s, a: _toy_step(s, a, consts), st0, tr)
    got = mmu_step.blocked_scan(_toy_step, st0, tr, consts=consts, block=12)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    bias = jnp.int32(11)  # captured closure constant, not an input

    def closed_step(st, acc):
        return _toy_step(st, acc + bias)

    ref2, _ = jax.lax.scan(closed_step, st0, tr)
    got2 = mmu_step.blocked_scan(closed_step, st0, tr, block=16)
    for a, b in zip(jax.tree.leaves(ref2), jax.tree.leaves(got2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_time_shard_scan_resolves_carry_handoff():
    tr = jnp.arange(60, dtype=jnp.int32)
    st0 = (jnp.zeros((4,), jnp.int32), jnp.int32(0))

    def block_fn(st, tr_blk):
        st, _ = jax.lax.scan(_toy_step, st, tr_blk)
        return st

    ref = block_fn(st0, tr)
    for t, batch in [(4, "vmap"), (3, "map"), (1, "vmap"), (7, "vmap")]:
        got, info = parallel.time_shard_scan(block_fn, st0, tr, t,
                                             batch=batch)
        assert info["t_shards"] == parallel.pick_t_shards(60, t)
        assert 1 <= info["rounds"] <= info["t_shards"]
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (t, batch)
    with pytest.raises(ValueError, match="batch mode"):
        parallel.time_shard_scan(block_fn, st0, tr, 2, batch="pmap")


# ------------------------------------------ backend bit-identity (families)


def _assert_same_stats(ref, got, ctx):
    for field, a, b in zip(ref._fields, ref, got):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (ctx, field)


def _family_ladder(name_frag):
    """The discovered ladder containing ``name_frag``, tiny-shrunk."""
    members = next(m for m in systems.LADDERS.values() if name_frag in m)
    cfgs = [_tiny_config(s) for s in members]
    return members, systems.dyn_base_config(cfgs), _stack_dyns(cfgs)


@pytest.fixture(scope="module")
def short_traces():
    tr = {k: jnp.asarray(v) for k, v in golden_trace(n=256).items()}
    return tr, {k: jnp.stack([v], axis=1) for k, v in tr.items()}


@pytest.mark.parametrize("anchor", ["radix", "np"])
def test_pallas_backend_matches_scan_on_ladder_family(anchor, short_traces,
                                                      monkeypatch):
    """EVERY member of the native (28-system) and virt (5-system)
    families: one batched scan-backend call vs one batched
    pallas(interpret) call, Stats bit-for-bit.  This drives the full
    stage composition — TLB/assoc/RestSeg/Revelator state and all dyn
    gates — through the resident-state kernel."""
    monkeypatch.delenv("REPRO_SIM_BACKEND", raising=False)
    _, traces = short_traces
    members, base, dyns = _family_ladder(anchor)
    per_s, ex_s = mmu.simulate_systems(base, dyns, traces)
    per_p, ex_p = mmu.simulate_systems(base, dyns, traces,
                                       backend="pallas")
    for si, name in enumerate(members):
        _assert_same_stats(per_s[si][0], per_p[si][0], name)
        assert ex_s[si][0]["l2_access"] == ex_p[si][0]["l2_access"], name
        assert ex_s[si][0]["l2_miss"] == ex_p[si][0]["l2_miss"], name


def test_pallas_backend_matches_scan_simulate_and_batch(short_traces):
    tr, _ = short_traces
    cfg = dataclasses.replace(GOLDEN_CFG, victima=True)
    ref, ex_ref = mmu.simulate(cfg, tr)
    got, ex_got = mmu.simulate(cfg, tr, backend="pallas")
    _assert_same_stats(ref, got, "simulate")
    assert ex_ref["l2_access"] == ex_got["l2_access"]

    traces = {k: jnp.stack([v, v], axis=1) for k, v in tr.items()}
    per_s, _ = mmu.simulate_batch(cfg, traces)
    per_p, _ = mmu.simulate_batch(cfg, traces, backend="pallas")
    for w in range(2):
        _assert_same_stats(per_s[w], per_p[w], ("batch", w))


def test_time_sharded_simulate_matches_serial(short_traces):
    """>= 2 speculative trace blocks, hand-off resolved: bit-identical
    to the serial scan on both backends (256 accesses / 4 shards)."""
    tr, _ = short_traces
    ref, _ = mmu.simulate(GOLDEN_CFG, tr)
    got4, _ = mmu.simulate(GOLDEN_CFG, tr, time_shards=4)
    _assert_same_stats(ref, got4, "t4-scan")
    got2p, _ = mmu.simulate(GOLDEN_CFG, tr, backend="pallas",
                            time_shards=2)
    _assert_same_stats(ref, got2p, "t2-pallas")


def test_time_sharded_systems_requires_1x1_plan(short_traces):
    _, traces = short_traces
    cfgs = [GOLDEN_CFG, dataclasses.replace(GOLDEN_CFG, victima=True)]
    base, dyns = systems.dyn_base_config(cfgs), _stack_dyns(cfgs)
    per_ref, _ = mmu.simulate_systems(base, dyns, traces)
    per_t, _ = mmu.simulate_systems(base, dyns, traces, time_shards=4)
    for si in range(2):
        _assert_same_stats(per_ref[si][0], per_t[si][0], si)
    plan = parallel.plan_mesh(2, 1, n_devices=1, force=(2, 1))
    with pytest.raises(ValueError, match="1x1"):
        mmu.make_systems_runner(base, plan, time_shards=2)


@pytest.mark.parametrize("backend", ["scan", "pallas"])
def test_time_sharded_systems_match_serial_per_lane(backend, short_traces):
    """Two systems x two distinct workloads, time-sharded: each (system,
    workload) lane ends bit-identical to the un-sharded scan dispatch,
    on the scan backend's single S x W lane axis and on the pallas
    backend's nested one."""
    tr, _ = short_traces
    traces = {k: jnp.stack([v, v[::-1]], axis=1) for k, v in tr.items()}
    cfgs = [GOLDEN_CFG, dataclasses.replace(GOLDEN_CFG, victima=True)]
    base, dyns = systems.dyn_base_config(cfgs), _stack_dyns(cfgs)
    per_ref, _ = mmu.simulate_systems(base, dyns, traces)
    per_t, _ = mmu.simulate_systems(base, dyns, traces, backend=backend,
                                    time_shards=2)
    for si in range(2):
        for wi in range(2):
            _assert_same_stats(per_ref[si][wi], per_t[si][wi], (si, wi))
    # the lanes differ, so a lane read from the wrong place would show
    for a, b in ((per_ref[0][0], per_ref[0][1]),
                 (per_ref[0][0], per_ref[1][0])):
        assert any(not np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b))


# --------------------------------------------- runner/perf-record plumbing


def test_run_ladder_pallas_backend_cache_byte_identical(tmp_path,
                                                        monkeypatch):
    """run_ladder(backend='pallas') must write cache entries
    BYTE-identical to the scan fill (the backend is deliberately absent
    from cache keys) and stamp backend/block/chunk_auto into
    LADDER_PERF."""
    from repro.sim import runner

    monkeypatch.setattr(systems, "REGISTRY", _tiny_registry())
    monkeypatch.delenv("REPRO_SIM_BACKEND", raising=False)
    members = ("t_radix", "t_victima")
    wls, n, seed = ["bc", "xs"], 256, 3

    def fill(cache_dir, backend):
        monkeypatch.setattr(runner, "CACHE_DIR", str(cache_dir))
        out = runner.run_ladder("tiny", workloads=wls, n=n, seed=seed,
                                members=members, backend=backend)
        assert set(out) == set(members)
        return out

    out_s = fill(tmp_path / "scan", None)
    out_p = fill(tmp_path / "pallas", "pallas")

    perf = runner.LADDER_PERF[-2:]
    assert [p["backend"] for p in perf] == ["scan", "pallas"]
    assert perf[0]["block"] is None
    assert perf[1]["block"] == mmu_step.pick_block(n)
    assert all(p["chunk_auto"] for p in perf)
    assert all(p["chunk"] == 2 for p in perf)  # auto_chunk(2 workloads)
    assert all(p["t_shards"] == 1 for p in perf)

    for s in members:
        for w in wls:
            key = runner._key(s, w, n, seed, None) + ".pkl"
            blob_s = (tmp_path / "scan" / key).read_bytes()
            blob_p = (tmp_path / "pallas" / key).read_bytes()
            assert blob_s == blob_p, (s, w)
            _assert_same_stats(out_s[s][w][0], out_p[s][w][0], (s, w))


def test_backend_speedup_line_pairs_fills():
    import benchmarks.paper as paper

    fills = [
        {"ladder": "native", "sim_n": 2000, "n_workloads": 3,
         "backend": "scan", "compile_plus_sim_wall_s": 60.0},
        {"ladder": "native", "sim_n": 2000, "n_workloads": 3,
         "backend": "pallas", "block": 250,
         "compile_plus_sim_wall_s": 30.0},
        {"ladder": "virt", "sim_n": 2000, "n_workloads": 3,
         "backend": "scan", "compile_plus_sim_wall_s": 9.0},
    ]
    line = paper.backend_speedup_line(fills)
    assert "native" in line and "2.00x" in line and "block 250" in line
    # one backend only -> nothing to print
    assert paper.backend_speedup_line(fills[:1]) is None
    assert paper.backend_speedup_line([]) is None


# --------------------------------------------------- multidev time sharding


@multidev
def test_time_sharded_simulate_multidev_matches_serial(short_traces):
    """Time-axis sharding on the forced 4-device mesh: 4 speculative
    blocks laid out on the ("t",) axis resolve to the exact serial
    carry."""
    if jax.local_device_count() < 4:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count"
                    "=4 (see the multidev CI job)")
    tr, traces = short_traces
    ref, _ = mmu.simulate(GOLDEN_CFG, tr)
    got, _ = mmu.simulate(GOLDEN_CFG, tr, time_shards=4)
    _assert_same_stats(ref, got, "simulate-t4")

    cfgs = [GOLDEN_CFG, dataclasses.replace(GOLDEN_CFG, victima=True)]
    base, dyns = systems.dyn_base_config(cfgs), _stack_dyns(cfgs)
    per_ref, _ = mmu.simulate_systems(base, dyns, traces)
    per_t, _ = mmu.simulate_systems(base, dyns, traces, time_shards=4)
    for si in range(2):
        _assert_same_stats(per_ref[si][0], per_t[si][0], ("sys-t4", si))
