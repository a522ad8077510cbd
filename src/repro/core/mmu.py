"""MMU translation-pipeline driver (paper §§4-6, Table 3).

The translation path is a statically composed list of stages (see
``repro.core.stages``): L1 TLB -> L2 TLB -> [Victima L2-cache probe] ->
[hardware L3 TLB] -> [POM-TLB] -> page-table walker (radix or 2-D
nested).  ``make_step`` folds the composition into one scan-step; the
static ``SimConfig`` + composition specialize the compiled code path, so
a jitted ``lax.scan`` simulates ~1M accesses in seconds on CPU exactly
like the pre-pipeline monolith (golden-snapshot tested bit-for-bit).

Three entry points share the step:
  simulate         — one (config, trace)
  simulate_batch   — one config, W workloads in lock-step (vmap)
  simulate_systems — S shape-compatible systems x W workloads in one
                     compiled call (vmap over ``Dyn`` sizing scalars) —
                     how the sweep covers a whole size ladder with a
                     single compilation.

Every entry point runs the access loop through one of two BACKENDS
(``REPRO_SIM_BACKEND`` or the ``backend=`` kwarg):

  scan   — the ``jax.lax.scan`` carry loop described above (default);
  pallas — the same step fused into a blocked Pallas kernel
           (``repro.kernels.mmu_step``) that keeps the state carry
           resident across trace blocks.  It runs interpreted on the
           CPU; on a TPU it is refused up front (``PALLAS_ON_TPU``).

Both are bit-identical (tests/test_mmu_kernel.py); ``time_shards``
additionally splits the trace time axis into speculative blocks with
exact carry hand-off (``repro.sim.parallel.time_shard_scan``).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

import repro.obs as obs
# a pure pytree/mesh utility with no repro.core (or sim-sibling) imports,
# so this core module can use it without a layering cycle
from repro.sim import parallel
from repro.core.caches import BT_DATA, access_data
from repro.core.stages import (Dyn, Feats, MMUState, Request, STAGES,
                               SimConfig, Stats, WALK_HIST_BUCKETS,
                               default_stages, dramc_of, fill_order,
                               l2_geom_of, make_state, validate_stages)
from repro.core.stages.fold import accum_stats, collect_feats

__all__ = [
    "BACKENDS", "PALLAS_ON_TPU", "Dyn", "Feats", "MMUState", "SimConfig",
    "Stats", "WALK_HIST_BUCKETS", "backend_name", "make_state",
    "make_step", "make_systems_runner", "resolve_backend",
    "scan_accesses", "simulate", "simulate_batch", "simulate_systems",
]

# access-loop backends: "scan" = lax.scan carry loop, "pallas" = blocked
# resident-state kernel (repro.kernels.mmu_step; interpreted on the CPU)
BACKENDS = ("scan", "pallas")
_BACKEND_ENV = "REPRO_SIM_BACKEND"

# why the pallas backend cannot run on a TPU (found by compiling
# mmu_step.blocked_scan for a described v5e, at tiny and full sizes)
PALLAS_ON_TPU = (
    "backend 'pallas' cannot run on a TPU: Mosaic, the TPU Pallas "
    "compiler, refuses the MMU kernel at every ladder size.  The kernel's "
    "lax.scan over the trace block is not lowered (scan with per-step "
    "inputs raises NotImplementedError), and with that replaced by a "
    "fori_loop the stages' reads of set rows from loaded state lower to "
    "dynamic_slice, an unimplemented primitive in Pallas TPU lowering.  "
    "Use backend='scan'.")


def backend_name(backend: str | None = None) -> str:
    """The requested access-loop backend (kwarg > env > "scan").

    Validates the name only and touches no device, so CLI layers can
    reject a typo BEFORE anything compiles or initializes jax.
    """
    b = backend or os.environ.get(_BACKEND_ENV, "").strip() or "scan"
    if b not in BACKENDS:
        raise ValueError(
            f"unknown simulation backend {b!r} (from "
            f"{'backend=' if backend else _BACKEND_ENV}); "
            f"known: {', '.join(BACKENDS)}")
    return b


def resolve_backend(backend: str | None = None) -> str:
    """The effective access-loop backend for this process's platform.

    ``backend_name`` plus the platform check: "pallas" on a TPU raises
    up front (``PALLAS_ON_TPU``) instead of falling back to anything.
    """
    b = backend_name(backend)
    if b == "pallas" and jax.default_backend() == "tpu":
        raise ValueError(PALLAS_ON_TPU)
    return b


def scan_accesses(step, st0, trace, backend: str | None = None,
                  consts=None, block: int | None = None):
    """Run the per-access ``step`` over ``trace`` on the chosen backend.

    Drop-in for ``lax.scan(step, st0, trace)[0]``.  ``step`` takes
    ``(state, access)`` — or ``(state, access, consts)`` when ``consts``
    is given (the pallas kernel cannot close over traced arrays, so
    per-call constants like stacked ladder ``Dyn`` scalars ride as
    explicit inputs on both backends to keep the call shape uniform).
    """
    if resolve_backend(backend) == "scan":
        body = step if consts is None else (
            lambda ss, acc: step(ss, acc, consts))
        st, _ = jax.lax.scan(body, st0, trace)
        return st
    from repro.kernels import mmu_step  # deferred: pallas import is lazy

    return mmu_step.blocked_scan(step, st0, trace, consts=consts,
                                 block=block)


def make_step(cfg: SimConfig, stage_names=None, dyn: Dyn | None = None):
    """Build the scan-step for this configuration.

    Trace record: dict(vpn=int32 4K-VPN, is2m=bool, line=int32 data line
    id, ipa=float32 — per-trace instructions/access so a vmapped batch of
    workloads shares one compiled step).  `dyn` carries traced sizing
    overrides for ladder-batched runs (vmap it alongside the state).
    """
    names = tuple(stage_names) if stage_names else default_stages(cfg)
    validate_stages(cfg, names)
    stages = [STAGES[n] for n in names]
    fills = [STAGES[n] for n in fill_order(names)]
    pressure_thr = jnp.float32(cfg.pressure_mpki)
    bypass_thr = jnp.float32(cfg.bypass_l2mpki)
    geom = l2_geom_of(dyn)  # dynamic L2-cache view (None = static)
    dramc = dramc_of(cfg, dyn)  # DRAM-cache gate (None = compiled out)

    def step(st: MMUState, acc):
        vpn = acc["vpn"]
        is2m = acc["is2m"]
        ipa = acc.get("ipa", jnp.float32(cfg.ipa))
        now = st.now + 1
        st = st._replace(now=now)
        s0 = st.stats

        instrs = jnp.maximum(s0.n_access.astype(jnp.float32), 1.0) * ipa
        pressure = (s0.n_l2tlb_miss.astype(jnp.float32) * 1000.0
                    > pressure_thr * instrs)
        l2_bypass = (st.hier.n_l2_miss.astype(jnp.float32) * 1000.0
                     >= bypass_thr * instrs)
        vpn2 = vpn >> 9
        vpn_sz = jnp.where(is2m, vpn2, vpn)
        req = Request(
            vpn=vpn, is2m=is2m, line=acc["line"], ipa=ipa, vpn2=vpn2,
            vpn_sz=vpn_sz, key2=(vpn_sz << 1) | is2m.astype(jnp.int32),
            now=now, pressure=pressure, l2_bypass=l2_bypass, dyn=dyn,
        )

        # ---------------- lookup pass: fold the composition
        out: dict = {}
        need = jnp.bool_(True)
        trans = jnp.int32(0)   # cycles up to and including the L2 TLB
        past_l2 = jnp.int32(0)  # cycles past the L2 TLB (Fig 9/22/29)
        for stg in stages:
            with obs.scope(f"{stg.name}.lookup"):
                st, res = stg.lookup(cfg, st, req, need)
            need = need & ~res.hit
            out[stg.name] = res._replace(need=need)
            if stg.past_l2:
                past_l2 = past_l2 + res.cycles
            else:
                trans = trans + res.cycles
        walk_res = out["_walk"] = out[names[-1]]

        # ---------------- fill pass: refills, learning, background walks
        for stg in fills:
            with obs.scope(f"{stg.name}.fill"):
                st = stg.fill(cfg, st, req, out)

        # shared-tier port contention (multicore only): accesses that
        # went past the private L2 TLB contend for the shared L3/POM/
        # walker port.  The rotating-slot queue delay is deterministic
        # per (core, now), so vmapped core lanes stay bit-reproducible
        # and independent of lane evaluation order.
        if cfg.n_cores > 1:
            core = acc.get("core", jnp.int32(0))
            slot = (core + now) % jnp.int32(cfg.n_cores)
            q = jnp.int32(cfg.shared_port_cyc) * slot
            past_l2 = past_l2 + jnp.where(out["l2_tlb"].need, q, 0)

        trans = trans + past_l2

        # ---------------- the data access itself
        with obs.scope(obs.names.SCOPE_DATA):
            hier, dcyc = access_data(st.hier, req.line, now, pressure,
                                     cfg.tlb_aware, cfg.lat, geom, dramc)
        st = st._replace(hier=hier)

        with obs.scope(obs.names.SCOPE_STATS):
            st = st._replace(stats=accum_stats(s0, st, out, walk_res,
                                               trans, past_l2, dcyc))
            if cfg.collect:
                st = collect_feats(cfg, st, req, out, walk_res)
        return st, ()

    return step


def _final_hists(l2):
    """Fold still-resident blocks into the reuse histograms (blocks that
    were never evicted would otherwise be invisible to Figs. 11/24).
    One lane's L2 arrays: every block counts once, whatever its set."""
    bucket = jnp.minimum(l2.reuse, 21)
    is_data = (l2.btype == BT_DATA) & l2.valid
    is_tlb = (l2.btype != BT_DATA) & l2.valid
    hd = l2.hist_reuse_data + jnp.zeros_like(l2.hist_reuse_data).at[
        bucket].add(is_data.astype(jnp.int32))
    ht = l2.hist_reuse_tlb + jnp.zeros_like(l2.hist_reuse_tlb).at[
        bucket].add(is_tlb.astype(jnp.int32))
    return hd, ht


def _finalize(st: MMUState, batch_dims: int = 0, collect: bool = False):
    """Fold a finished state into the per-run output tuple (`batch_dims`
    counts the leading workload/system axes on the state leaves).  The
    feature table and the 4K-page counters leave the device only where
    the extras read them (`collect`): the counters alone are 4 MiB a
    lane."""
    hists = _final_hists
    for _ in range(batch_dims):
        hists = jax.vmap(hists)
    hd, ht = hists(st.hier.l2)
    return (st.stats, st.hier.n_l2_access, st.hier.n_l2_miss, hd, ht,
            st.feats if collect else None, st.pc4 if collect else None,
            (st.hier.n_l3_access, st.hier.n_l3_trans,
             st.hier.n_dramc_access, st.hier.n_dramc_hit))


def _shared_tier_extras(cfg) -> bool:
    """Whether the shared-tier (L3/DRAM-cache) counters surface in extras.
    Gated so single-core extras stay byte-identical to the pre-multicore
    pickles (the sim cache stores extras verbatim)."""
    return (cfg.n_cores > 1 or cfg.dram_cache_sets > 0
            or cfg.shared_tier_stats)


def _extras_of(cfg, l2a, l2m, hd, ht, feats, pc4, shared=None,
               index=lambda x: x):
    """One lane's extras; every value is one ``device_get`` of
    ``index(x)`` (a device read where ``x`` is a device array)."""
    def get(x):
        return jax.device_get(index(x))

    e = {"l2_access": int(get(l2a)), "l2_miss": int(get(l2m)),
         "hist_reuse_data": get(hd), "hist_reuse_tlb": get(ht)}
    if shared is not None and _shared_tier_extras(cfg):
        e["l3_access"] = int(get(shared[0]))
        e["l3_trans"] = int(get(shared[1]))
        e["dramc_access"] = int(get(shared[2]))
        e["dramc_hit"] = int(get(shared[3]))
    if cfg.collect:
        e["feats"] = jax.tree.map(get, feats)
        e["pc4"] = jax.tree.map(get, pc4)
    return e


def simulate(cfg: SimConfig, trace: dict, stage_names=None,
             backend: str | None = None, block: int | None = None,
             time_shards: int | None = None):
    """Run one trace under `cfg`; returns (Stats, extras).

    ``backend`` selects the access-loop implementation (see BACKENDS),
    ``block`` the pallas trace-block size, and ``time_shards > 1``
    splits the trace time axis into speculative blocks resolved to the
    exact serial carry (``parallel.time_shard_scan``) — all three leave
    the Stats bit-identical to the default scan.
    """
    step = make_step(cfg, stage_names)
    t = int(time_shards or 1)
    if t > 1:
        def body(st, tr):
            return scan_accesses(step, st, tr, backend=backend,
                                 block=block)
        st, _ = parallel.time_shard_scan(
            body, make_state(cfg), trace, t,
            batch="map" if resolve_backend(backend) == "pallas"
            else "vmap")
        outs = jax.jit(_finalize, static_argnames="collect")(
            st, collect=cfg.collect)
    else:
        @jax.jit
        def run(tr):
            st = scan_accesses(step, make_state(cfg), tr,
                               backend=backend, block=block)
            return _finalize(st, collect=cfg.collect)

        outs = run(trace)
    stats, l2a, l2m, hd, ht, feats, pc4, shared = outs
    stats = jax.tree.map(lambda x: jax.device_get(x), stats)
    return stats, _extras_of(cfg, l2a, l2m, hd, ht, feats, pc4, shared)


def simulate_batch(cfg: SimConfig, traces: dict, stage_names=None,
                   backend: str | None = None, block: int | None = None):
    """Run W workloads in lock-step: traces leaves are [T, W, ...].

    One compile + one scan of a vmapped step — on a single CPU core this
    is ~an order of magnitude faster than W sequential runs (SIMD across
    the workload lane, per-step dispatch amortized).
    Returns (Stats [W], extras list of per-workload dicts).
    """
    step = make_step(cfg, stage_names)
    W = jax.tree.leaves(traces)[0].shape[1]

    @jax.jit
    def run(tr):
        base = make_state(cfg)
        st0 = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (W,) + x.shape), base)
        st = scan_accesses(
            lambda ss, acc: (jax.vmap(step)(ss, acc)[0], ()), st0, tr,
            backend=backend, block=block)
        return _finalize(st, batch_dims=1, collect=cfg.collect)

    stats, l2a, l2m, hd, ht, feats, pc4, shared = run(traces)
    stats = jax.tree.map(jax.device_get, stats)
    extras = [_extras_of(cfg, l2a, l2m, hd, ht, feats, pc4, shared,
                         index=lambda x, i=i: x[i]) for i in range(W)]
    per = [jax.tree.map(lambda x, i=i: x[i], stats) for i in range(W)]
    return per, extras


def _step_sw(cfg: SimConfig, stage_names):
    """S x W-vmapped scan step with the per-system ``Dyn`` scalars
    delivered as ``consts`` — the shape the pallas backend needs (a
    kernel cannot close over traced arrays, so the system vmap moves
    INSIDE the blocked scan instead of wrapping the kernel call)."""

    def step_sw(ss, acc, dyns):
        def per_sys(ss_s, dd):
            step = make_step(cfg, stage_names, dyn=dd)
            return jax.vmap(step)(ss_s, acc)[0]

        return jax.vmap(per_sys)(ss, dyns), ()

    return step_sw


def _lanes(d: Dyn, tr: dict, lanes: int):
    """One lane axis of S x ``lanes`` lanes, system-major: each system's
    ``Dyn`` scalars repeated per lane, the ``[T, lanes]`` traces tiled
    once per system.  The scan backend runs its step vmapped once over
    this axis: a nested vmap(system) o vmap(workload) hands back the flat
    state arrays of every scatter transposed, and the loop carry then
    pays a copy to restore its layout."""
    s = jax.tree.leaves(d)[0].shape[0]
    d_sw = jax.tree.map(lambda x: jnp.repeat(x, lanes, axis=0), d)
    tr_sw = jax.tree.map(
        lambda x: jnp.tile(x, (1, s) + (1,) * (x.ndim - 2)), tr)
    return d_sw, tr_sw


def _step_lanes(cfg: SimConfig, stage_names, d_sw: Dyn):
    """Scan step over the single lane axis of ``_lanes``."""

    def lane_step(ss, acc, dd):
        return make_step(cfg, stage_names, dyn=dd)(ss, acc)[0]

    return lambda ss, acc: (jax.vmap(lane_step)(ss, acc, d_sw), ())


def _broadcast_state(cfg: SimConfig, lead: tuple[int, ...]) -> MMUState:
    base = make_state(cfg)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, lead + x.shape), base)


def make_systems_runner(cfg: SimConfig, plan, stage_names=None,
                        backend: str | None = None,
                        block: int | None = None,
                        time_shards: int = 1):
    """Build a REUSABLE sharded S x W dispatch for one mesh plan.

    Returns ``run(dyns, traces) -> (per, extras)``.  The shard_map +
    jit wrapper is constructed once, so same-shape calls — e.g.
    ``runner.run_ladder``'s fixed-width workload chunks — trace, lower
    and compile exactly once instead of once per call.

    ``backend`` picks the access-loop implementation per lane (see
    BACKENDS), ``block`` the pallas trace-block size.  ``time_shards >
    1`` splits the trace time axis into speculative blocks resolved to
    the exact serial carry on a ("t",) device mesh
    (``parallel.time_shard_scan``) — it currently requires a 1x1
    ("sys", "wl") plan (the devices go to the time axis instead).  The
    runner records the last hand-off round count on
    ``run.last_time_shard_info``.
    """
    backend = resolve_backend(backend)
    t_shards = int(time_shards or 1)
    if t_shards > 1 and plan.sys_dim * plan.wl_dim != 1:
        raise ValueError(
            f"time sharding needs a 1x1 ('sys', 'wl') plan (devices go "
            f"to the 't' mesh axis), got {plan.describe()}")

    def run_systems(d, tr):
        # derive the lane width from tr: under shard_map this body sees
        # one [S_blk] x [W_blk] (x [C_blk]) mesh block, not the full grid
        leaf = jax.tree.leaves(tr)[0]
        w_blk = leaf.shape[1]
        # multicore: per-core lanes ([T, W, C] traces) ride the vmapped
        # workload axis — flatten to [T, W*C], un-flatten the outputs so
        # the mesh out_specs see a [S, W, C]-leading grid
        c_blk = leaf.shape[2] if leaf.ndim >= 3 else None
        if c_blk is not None:
            tr = jax.tree.map(
                lambda x: x.reshape((x.shape[0], w_blk * c_blk)
                                    + x.shape[3:]), tr)
        lanes = w_blk if c_blk is None else w_blk * c_blk
        s_blk = jax.tree.leaves(d)[0].shape[0]

        def unflatten(outs):
            if c_blk is None:
                return outs
            return jax.tree.map(
                lambda x: x.reshape(x.shape[:1] + (w_blk, c_blk)
                                    + x.shape[2:]), outs)

        if backend == "scan":
            d_sw, tr_sw = _lanes(d, tr, lanes)
            with obs.scope(obs.names.SCOPE_INIT):
                st0 = _broadcast_state(cfg, (s_blk * lanes,))
            st, _ = jax.lax.scan(_step_lanes(cfg, stage_names, d_sw),
                                 st0, tr_sw)
            with obs.scope(obs.names.SCOPE_FINALIZE):
                outs = _finalize(st, batch_dims=1, collect=cfg.collect)
            return unflatten(jax.tree.map(
                lambda x: x.reshape((s_blk, lanes) + x.shape[1:]), outs))
        # pallas: the system vmap moves inside the kernel's inner scan
        # (see _step_sw) so the pallas_call itself is never vmapped
        with obs.scope(obs.names.SCOPE_INIT):
            st0 = _broadcast_state(cfg, (s_blk, lanes))
        st = scan_accesses(_step_sw(cfg, stage_names), st0, tr,
                           backend=backend, consts=d, block=block)
        with obs.scope(obs.names.SCOPE_FINALIZE):
            return unflatten(_finalize(st, batch_dims=2,
                                       collect=cfg.collect))

    if t_shards <= 1:
        dispatch = parallel.shard_wrap(run_systems, plan)
    else:
        def dispatch(dyns, traces):
            S = jax.tree.leaves(dyns)[0].shape[0]
            leaf = jax.tree.leaves(traces)[0]
            W = leaf.shape[1]
            c = leaf.shape[2] if leaf.ndim >= 3 else None
            if c is not None:  # core lanes ride the workload axis
                traces = jax.tree.map(
                    lambda x: x.reshape((x.shape[0], W * c)
                                        + x.shape[3:]), traces)
            lanes = W if c is None else W * c
            fin = jax.jit(_finalize,
                          static_argnames=("batch_dims", "collect"))

            if backend == "scan":  # the same single lane axis
                d_sw, traces = _lanes(dyns, traces, lanes)
                step = _step_lanes(cfg, stage_names, d_sw)
                st, info = parallel.time_shard_scan(
                    lambda st, tr: jax.lax.scan(step, st, tr)[0],
                    _broadcast_state(cfg, (S * lanes,)), traces, t_shards)
                outs = jax.tree.map(
                    lambda x: x.reshape((S, lanes) + x.shape[1:]),
                    fin(st, batch_dims=1, collect=cfg.collect))
            else:
                sw = _step_sw(cfg, stage_names)

                def body(st, tr):
                    return scan_accesses(sw, st, tr, backend=backend,
                                         consts=dyns, block=block)

                st, info = parallel.time_shard_scan(
                    body, _broadcast_state(cfg, (S, lanes)), traces,
                    t_shards, batch="map")
                outs = fin(st, batch_dims=2, collect=cfg.collect)
            run.last_time_shard_info = info
            if c is not None:
                outs = jax.tree.map(
                    lambda x: x.reshape(x.shape[:1] + (W, c)
                                        + x.shape[2:]), outs)
            return outs

    def run(dyns: Dyn, traces: dict):
        S = jax.tree.leaves(dyns)[0].shape[0]
        leaf = jax.tree.leaves(traces)[0]
        W = leaf.shape[1]
        C = leaf.shape[2] if leaf.ndim >= 3 else None
        with obs.span(obs.names.SPAN_SIM_LAUNCH):
            outs = dispatch(dyns, traces)
        stats, l2a, l2m, hd, ht, feats, pc4, shared = outs
        with obs.span(obs.names.SPAN_SIM_FETCH):
            stats = jax.tree.map(jax.device_get, stats)
        with obs.span(obs.names.SPAN_SIM_UNPACK) as unpack:
            reads = [0]

            def extras_at(*ix):
                def index(x):  # each index is then read from the device
                    reads[0] += 1
                    return x[ix]
                return _extras_of(cfg, l2a, l2m, hd, ht, feats, pc4,
                                  shared, index=index)

            if C is None:
                per = [[jax.tree.map(lambda x, s=s, w=w: x[s, w], stats)
                        for w in range(W)] for s in range(S)]
                extras = [[extras_at(s, w) for w in range(W)]
                          for s in range(S)]
            else:
                # multicore: per[s][w] / extras[s][w] are per-core lists
                per = [[[jax.tree.map(
                    lambda x, s=s, w=w, k=k: x[s, w, k], stats)
                    for k in range(C)] for w in range(W)]
                    for s in range(S)]
                extras = [[[extras_at(s, w, k) for k in range(C)]
                           for w in range(W)] for s in range(S)]
            obs.count(obs.names.CTR_UNPACK_READS, reads[0])
            unpack.set(reads=reads[0])
        return per, extras

    run.last_time_shard_info = None
    return run


def simulate_systems(cfg: SimConfig, dyns: Dyn, traces: dict,
                     stage_names=None, plan=None,
                     backend: str | None = None, block: int | None = None,
                     time_shards: int = 1):
    """Run S shape-compatible systems x W workloads in ONE compiled call.

    `cfg` is the ladder's static base config (structures allocated at the
    ladder maximum); `dyns` has [S]-shaped leaves of per-system sizing
    scalars; traces leaves are [T, W, ...] (shared across systems).
    The S x W grid is dispatched over a 2-D ("sys", "wl") device mesh
    via shard_map (repro.sim.parallel): the system axis is padded to a
    mesh multiple (no divisibility precondition) and on a single device
    the 1x1 mesh runs the identical code path as an identity
    partitioning.  `plan` overrides the mesh factorization (see
    ``parallel.plan_mesh``).  ``backend``/``block``/``time_shards``
    forward to ``make_systems_runner``; ``time_shards > 1`` defaults the
    plan to 1x1 (the devices go to the time axis instead).  Returns
    (list[S] of list[W] Stats, extras).  One-shot form of
    ``make_systems_runner`` — callers dispatching the same shapes
    repeatedly should hold on to a runner instead.
    """
    S = jax.tree.leaves(dyns)[0].shape[0]
    W = jax.tree.leaves(traces)[0].shape[1]
    if plan is None:
        plan = (parallel.plan_mesh(S, W, n_devices=1)
                if int(time_shards or 1) > 1 else parallel.plan_mesh(S, W))
    return make_systems_runner(cfg, plan, stage_names, backend=backend,
                               block=block,
                               time_shards=time_shards)(dyns, traces)
