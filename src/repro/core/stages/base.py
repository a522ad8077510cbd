"""Translation-pipeline contract + shared simulation types.

A *stage* models one level of the address-translation path (L1 TLB,
L2 TLB, Victima L2-cache probe, hardware L3 TLB, POM-TLB, page-table
walker).  Stages obey a uniform contract so ``mmu.make_step`` can fold a
statically composed stage list into one scan step (the composition is
resolved at trace time, so ``lax.scan`` compiles to the same specialized
code path as the old hand-written monolith):

  ``lookup(cfg, state, request, need) -> (state, StageResult)``
      Probe the stage for the accesses still unresolved (`need` mask),
      applying any hit-path state updates (LRU touches, RRPV promotion).
      ``StageResult.hit`` marks accesses this stage resolved and
      ``StageResult.cycles`` the latency it charged.

  ``fill(cfg, state, request, out) -> state``
      Post-walk refill/learning pass (TLB refills, PTW-CP counters,
      Victima block installs).  ``out`` maps stage name -> StageResult
      of the lookup phase; fills may publish derived values into their
      own ``info`` dict for later fills / the stats fold (e.g. the L2
      TLB's evicted entry, consumed by Victima's background walk).

The driver ORs cycles into one of two accumulators selected by the
stage's ``past_l2`` flag: latency before/at the L2 TLB vs. latency past
it (the paper's Figs. 9/22/29 metric).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import ptwcp
from repro.core.assoc import Assoc, make, shape
from repro.core.caches import Hier, L2Geom, Lat, make_hier
from repro.core.page_table import PWCs, make_pwcs

WALK_HIST_BUCKETS = 64  # 10-cycle buckets for the Fig.4 PTW latency CDF


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Static simulation configuration (Table 3 defaults)."""

    # --- TLB hierarchy
    l1d4_sets: int = 16   # 64-entry, 4-way (4K pages)
    l1d4_ways: int = 4
    l1d2_sets: int = 8    # 32-entry, 4-way (2M pages)
    l1d2_ways: int = 4
    l1tlb_lat: int = 1
    l2tlb_sets: int = 128  # 1536-entry, 12-way
    l2tlb_ways: int = 12
    l2tlb_lat: int = 12
    # --- optional hardware L3 TLB (0 sets = absent)
    l3tlb_sets: int = 0
    l3tlb_ways: int = 16
    l3tlb_lat: int = 15
    # --- POM-TLB (software L3 TLB resident in memory)
    pom: bool = False
    pom_sets: int = 4096  # 64K entries, 16-way
    pom_ways: int = 16
    # --- Victima
    victima: bool = False
    tlb_aware: bool = True       # TLB-aware SRRIP at the L2 cache
    use_ptwcp: bool = True       # False = insert every candidate (ablation)
    bypass_l2mpki: float = 5.0   # consult PTW-CP only if L2$ MPKI below this
    pressure_mpki: float = 5.0   # "translation pressure" threshold
    # --- Utopia hybrid RestSeg/FlexSeg mapping
    utopia: bool = False
    restseg4_sets: int = 8192    # 4K-page RestSeg: 128K entries, 16-way
    restseg2_sets: int = 256     # 2M-page RestSeg
    restseg_ways: int = 16
    # --- Revelator hash-based speculative translation
    revelator: bool = False
    rev_sets: int = 4096         # signature table: 64K entries, 16-way
    rev_ways: int = 16
    rev_lat: int = 4             # hash + signature probe (near-zero)
    rev_sig_bits: int = 20       # lossy signature width; aliasing between
    #   pages whose hashes share the low rev_sig_bits is the deterministic
    #   stand-in for the paper's frame-allocation mispredictions
    # --- caches
    l1_sets: int = 64
    l1_ways: int = 8
    l2_sets: int = 2048   # 2MB
    l2_ways: int = 16
    l3_sets: int = 2048   # 2MB/core
    l3_ways: int = 16
    lat: Lat = Lat()
    # --- multicore (n_cores=1 is the single-core degenerate case: the
    #     contention model below compiles out entirely, so every existing
    #     system is bit-identical to its pre-multicore self)
    n_cores: int = 1             # independent core lanes (a batch axis:
    #   each core carries its own private L1/L2 TLB + Victima state as a
    #   simulation lane; the shared tier is modeled by static capacity
    #   partitioning — l3/pom/l3tlb/dramc sets divided by n_cores — plus
    #   the rotating shared-port arbitration delay below)
    shared_port_cyc: int = 2     # queueing delay per losing arbitration
    #   slot at the shared tier's port (charged on L2-TLB misses)
    shared_tier_stats: bool = False  # surface shared-L3 / DRAM-cache
    #   occupancy counters in extras (multicore scenario bookkeeping;
    #   off by default so single-core extras stay byte-identical)
    # --- die-stacked DRAM cache below the L3 (0 sets = absent)
    dram_cache_sets: int = 0
    dram_cache_ways: int = 16
    # --- virtualization
    virt: bool = False           # nested paging 2-D walk
    ideal_shadow: bool = False   # I-SP: 1-D shadow walk, free updates
    ntlb_sets: int = 16          # 64-entry nested TLB
    ntlb_ways: int = 4
    # --- bookkeeping
    n_pages4: int = 1 << 21      # 4K-page counter-table entries (masked vpn;
    #   larger footprints alias — counters are advisory predictor state and
    #   XLA-CPU copies of >2M-entry carry arrays dominate sim runtime)
    n_pages2: int = 1 << 14      # 2M-page counter-table entries
    n_pagesh: int = 1 << 14      # host-page counter table (hashed, virt;
    #   small: 10 scatter/gather per virt step — see fused-counter note)
    ipa: float = 3.0             # instructions per traced memory access
    collect: bool = False        # per-page feature collection (Table 2)
    n_feat: int = 1 << 20        # feature-table entries (hashed vpn)


class Dyn(NamedTuple):
    """Traced sizing/latency overrides for ladder-batched simulation.

    A batched sweep allocates structures at the ladder's maximum static
    shape and vmaps the step over these per-system scalars; systems whose
    configs differ only in these fields share one compiled step.
    """

    l2tlb_set_mask: jax.Array  # int32, = live l2tlb sets - 1
    l2tlb_ways: jax.Array      # int32 effective ways
    l2tlb_lat: jax.Array       # int32 probe latency
    l3tlb_lat: jax.Array       # int32 probe latency (unused if no L3 TLB)
    l2_set_mask: jax.Array     # int32, = live L2-cache sets - 1
    l2_ways: jax.Array         # int32 effective L2-cache ways
    victima_en: jax.Array      # bool — Victima stage live on this lane
    #   (lets a radix member ride a victima-composition ladder with the
    #    TLB-block installs and background walks masked off bit-exactly)
    utopia_en: jax.Array       # bool — RestSeg stage live on this lane
    restseg_ways: jax.Array    # int32 effective RestSeg ways
    l3tlb_en: jax.Array        # bool — hardware L3 TLB live on this lane
    pom_en: jax.Array          # bool — POM-TLB live on this lane
    rev_en: jax.Array          # bool — Revelator speculative stage live
    dramc_en: jax.Array        # bool — die-stacked DRAM cache live on
    #   this lane (masks the probe between the L3 and DRAM bit-exactly)


# SimConfig fields a batched ladder may vary across members.  "victima",
# "utopia", "pom", "l3tlb_sets" and "revelator" are special: they are not
# geometry scalars but dyn-*gateable* stage flags (see
# systems.DYN_GATED_STAGES) — lanes lacking the stage mask off all its
# state writes bit-exactly.
DYN_FIELDS = ("l2tlb_sets", "l2tlb_ways", "l2tlb_lat", "l3tlb_lat",
              "l2_sets", "l2_ways", "victima",
              "utopia", "restseg_ways", "l3tlb_sets", "pom", "revelator",
              "dram_cache_sets")


def dyn_of(cfg: SimConfig) -> Dyn:
    """The Dyn scalars equivalent to `cfg`'s static sizing."""
    return Dyn(
        l2tlb_set_mask=jnp.int32(cfg.l2tlb_sets - 1),
        l2tlb_ways=jnp.int32(cfg.l2tlb_ways),
        l2tlb_lat=jnp.int32(cfg.l2tlb_lat),
        l3tlb_lat=jnp.int32(cfg.l3tlb_lat),
        l2_set_mask=jnp.int32(cfg.l2_sets - 1),
        l2_ways=jnp.int32(cfg.l2_ways),
        victima_en=jnp.bool_(cfg.victima),
        utopia_en=jnp.bool_(cfg.utopia),
        restseg_ways=jnp.int32(cfg.restseg_ways),
        l3tlb_en=jnp.bool_(cfg.l3tlb_sets > 0),
        pom_en=jnp.bool_(cfg.pom),
        rev_en=jnp.bool_(cfg.revelator),
        dramc_en=jnp.bool_(cfg.dram_cache_sets > 0),
    )


def l2_geom_of(dyn: "Dyn | None") -> L2Geom | None:
    """The dynamic L2-cache view carried by a request (None = static)."""
    if dyn is None:
        return None
    return L2Geom(set_mask=dyn.l2_set_mask, n_ways=dyn.l2_ways)


def dramc_of(cfg: SimConfig, dyn: "Dyn | None"):
    """The die-stacked DRAM-cache gate for cache-hierarchy accesses.

    ``None`` compiles the probe out entirely — the base config has no
    DRAM cache, so every pre-existing system keeps its exact compiled
    graph.  When the (ladder-maximum) config has one, the gate is a
    traced bool so lanes without it mask the probe off bit-exactly.
    """
    if cfg.dram_cache_sets <= 0:
        return None
    return jnp.bool_(True) if dyn is None else dyn.dramc_en


class Stats(NamedTuple):
    n_access: jax.Array
    n_l1tlb_hit: jax.Array
    n_l2tlb_hit: jax.Array
    n_l2tlb_miss: jax.Array
    n_victima_hit: jax.Array
    n_l3tlb_hit: jax.Array
    n_pom_hit: jax.Array
    n_demand_ptw: jax.Array      # native / guest demand walks
    n_bg_ptw: jax.Array
    n_host_ptw: jax.Array        # virt: demand host walks
    n_ntlb_hit: jax.Array
    n_nvictima_hit: jax.Array    # nested-TLB-block hits in L2 cache
    sum_trans_cyc: jax.Array     # f32
    sum_l2miss_cyc: jax.Array    # f32 — translation cycles past the L2 TLB
    sum_data_cyc: jax.Array      # f32
    sum_walk_cyc: jax.Array      # f32 — demand walk cycles only
    hist_walk: jax.Array         # i32 [WALK_HIST_BUCKETS]
    sum_tlb4_live: jax.Array     # f32 — Σ live TLB blocks (reach, Fig 23)
    sum_tlb2_live: jax.Array     # f32
    # --- Utopia RestSeg (zero for systems without the stage)
    n_restseg_hit: jax.Array      # i32 — probes resolved by a RestSeg
    n_restseg_miss: jax.Array     # i32 — probes that fell through to FlexSeg
    n_restseg_mig: jax.Array      # i32 — pages migrated into a RestSeg
    n_restseg_conflict: jax.Array  # i32 — migrations that demoted a page
    #                                back to FlexSeg (set conflict)
    sum_restseg_cyc: jax.Array    # f32 — Σ RestSeg tag-probe cycles
    hist_restseg: jax.Array       # i32 [WALK_HIST_BUCKETS] — probe-latency
    #                               buckets (same 10-cycle grid as hist_walk)
    # --- Revelator speculation (zero for systems without the stage)
    n_rev_hit: jax.Array          # i32 — correct speculative translations
    n_rev_mispred: jax.Array      # i32 — signature hits that mispredicted
    n_rev_enroll: jax.Array       # i32 — pages enrolled post-walk
    sum_rev_verify_cyc: jax.Array  # f32 — Σ verification-walk cycles
    #                                (overlapped; critical only on mispredict)
    hist_rev_verify: jax.Array    # i32 [WALK_HIST_BUCKETS] — verify-latency
    #                               buckets (same 10-cycle grid as hist_walk)


def zero_stats() -> Stats:
    z = jnp.int32(0)
    f = jnp.float32(0)
    return Stats(
        n_access=z, n_l1tlb_hit=z, n_l2tlb_hit=z, n_l2tlb_miss=z,
        n_victima_hit=z, n_l3tlb_hit=z, n_pom_hit=z, n_demand_ptw=z,
        n_bg_ptw=z, n_host_ptw=z, n_ntlb_hit=z, n_nvictima_hit=z,
        sum_trans_cyc=f, sum_l2miss_cyc=f, sum_data_cyc=f, sum_walk_cyc=f,
        hist_walk=jnp.zeros((WALK_HIST_BUCKETS,), jnp.int32),
        sum_tlb4_live=f, sum_tlb2_live=f,
        n_restseg_hit=z, n_restseg_miss=z, n_restseg_mig=z,
        n_restseg_conflict=z, sum_restseg_cyc=f,
        hist_restseg=jnp.zeros((WALK_HIST_BUCKETS,), jnp.int32),
        n_rev_hit=z, n_rev_mispred=z, n_rev_enroll=z,
        sum_rev_verify_cyc=f,
        hist_rev_verify=jnp.zeros((WALK_HIST_BUCKETS,), jnp.int32),
    )


class Feats(NamedTuple):
    """Per-page features for the Table-2 predictor study (hashed table)."""
    n_access: jax.Array     # uint16
    n_l1_miss: jax.Array    # uint16
    n_l2_miss: jax.Array    # uint16 — L2 TLB misses
    n_walk: jax.Array       # uint16 — unsaturated walk count
    walk_cyc: jax.Array     # float32 — Σ demand-walk cycles (label source)
    is2m: jax.Array         # uint8


def zero_feats(n: int) -> Feats:
    return Feats(
        n_access=jnp.zeros((n,), jnp.uint16),
        n_l1_miss=jnp.zeros((n,), jnp.uint16),
        n_l2_miss=jnp.zeros((n,), jnp.uint16),
        n_walk=jnp.zeros((n,), jnp.uint16),
        walk_cyc=jnp.zeros((n,), jnp.float32),
        is2m=jnp.zeros((n,), jnp.uint8),
    )


class RevTable(NamedTuple):
    """Revelator signature table: hashed VPN -> speculative frame.

    ``tab`` is keyed by a *lossy* multiplicative-hash signature of the
    size-tagged page id (so distinct pages can alias — the deterministic
    misprediction source); ``vpn`` shadows the enrolled page id per way,
    the ground truth the verification walk confirms against.
    """

    tab: Assoc       # tags = lossy signature, meta = LRU stamp
    vpn: jax.Array   # int32, stored like ``tab``'s arrays — enrolled
    #                  key2 per way


def make_rev(n_sets: int, n_ways: int) -> RevTable:
    return RevTable(tab=make(n_sets, n_ways),
                    vpn=jnp.zeros(shape(n_sets, n_ways), jnp.int32))


class MMUState(NamedTuple):
    now: jax.Array
    l1d4: Assoc
    l1d2: Assoc
    l2tlb: Assoc
    l3tlb: Assoc
    pom: Assoc
    pwcs: PWCs
    hier: Hier
    ntlb: Assoc
    restseg4: Assoc  # Utopia 4K-page RestSeg (tags = migrated vpn)
    restseg2: Assoc  # Utopia 2M-page RestSeg (tags = migrated vpn2)
    rev: RevTable    # Revelator signature table (sized 1 when off)
    pc4: ptwcp.PageCounters
    pc2: ptwcp.PageCounters
    pch: ptwcp.PageCounters
    feats: Feats
    stats: Stats


def make_state(cfg: SimConfig) -> MMUState:
    return MMUState(
        now=jnp.int32(0),
        l1d4=make(cfg.l1d4_sets, cfg.l1d4_ways),
        l1d2=make(cfg.l1d2_sets, cfg.l1d2_ways),
        l2tlb=make(cfg.l2tlb_sets, cfg.l2tlb_ways),
        l3tlb=make(max(cfg.l3tlb_sets, 1), cfg.l3tlb_ways),
        pom=make(cfg.pom_sets if cfg.pom else 1, cfg.pom_ways),
        pwcs=make_pwcs(),
        hier=make_hier(cfg.l1_sets, cfg.l1_ways, cfg.l2_sets, cfg.l2_ways,
                       cfg.l3_sets, cfg.l3_ways,
                       max(cfg.dram_cache_sets, 1), cfg.dram_cache_ways),
        ntlb=make(cfg.ntlb_sets if cfg.virt else 1, cfg.ntlb_ways),
        restseg4=make(cfg.restseg4_sets if cfg.utopia else 1,
                      cfg.restseg_ways if cfg.utopia else 1),
        restseg2=make(cfg.restseg2_sets if cfg.utopia else 1,
                      cfg.restseg_ways if cfg.utopia else 1),
        rev=make_rev(cfg.rev_sets if cfg.revelator else 1,
                     cfg.rev_ways if cfg.revelator else 1),
        pc4=ptwcp.make_counters(cfg.n_pages4),
        pc2=ptwcp.make_counters(cfg.n_pages2),
        pch=ptwcp.make_counters(cfg.n_pagesh if cfg.virt else 1),
        feats=zero_feats(cfg.n_feat if cfg.collect else 1),
        stats=zero_stats(),
    )


class Request(NamedTuple):
    """One traced access plus derived keys and step-global signals."""

    vpn: jax.Array       # int32 4K-page vpn
    is2m: jax.Array      # bool — access lands in a 2M-backed region
    line: jax.Array      # int32 data line id
    ipa: jax.Array       # f32 instructions per access
    vpn2: jax.Array      # vpn >> 9 (2M-page id)
    vpn_sz: jax.Array    # size-native page id
    key2: jax.Array      # unified L2 TLB key (page id + size bit)
    now: jax.Array       # logical time (LRU stamp)
    pressure: jax.Array  # bool — translation pressure (L2-TLB MPKI > thr)
    l2_bypass: jax.Array  # bool — L2$ MPKI high: bypass the PTW-CP
    dyn: Dyn | None      # ladder-batched sizing overrides (None = static)


class StageResult(NamedTuple):
    hit: jax.Array            # bool — accesses resolved by this stage
    cycles: jax.Array         # int32 — latency charged by this stage
    info: dict                # stage-specific values for fills/stats
    #                           (fills may publish into their own dict)
    need: Any = None          # bool — still-unresolved mask AFTER this
    #                           stage (filled in by the driver)


def ptwcp_walk_verdict(cfg: SimConfig, st: "MMUState", req: "Request",
                       walk_en):
    """Post-walk PTW-CP verdict shared by fill-time promotion engines
    (Utopia's RestSeg migration, Revelator's enrollment).

    Reads the *freshly trained* counters — callers run after whichever
    fill owns the counter traffic (see ``stages.fill_order``) — and
    applies the standard overrides: ``use_ptwcp=False`` promotes every
    candidate, high L2$ MPKI (``req.l2_bypass``) bypasses the predictor.
    """
    idx4 = req.vpn & (cfg.n_pages4 - 1)
    idx2 = req.vpn2 & (cfg.n_pages2 - 1)
    pred = jnp.where(req.is2m,
                     ptwcp.predict_page(st.pc2, idx2),
                     ptwcp.predict_page(st.pc4, idx4))
    pred = pred if cfg.use_ptwcp else jnp.bool_(True)
    return walk_en & (pred | req.l2_bypass)


class Stage:
    """Base stage: a no-op level.  Subclasses override lookup/fill."""

    name: str = "?"
    past_l2: bool = True  # cycles count toward the past-L2-TLB metric

    def lookup(self, cfg: SimConfig, st: MMUState, req: Request, need):
        return st, StageResult(hit=jnp.bool_(False), cycles=jnp.int32(0),
                               info={})

    def fill(self, cfg: SimConfig, st: MMUState, req: Request,
             out: dict) -> MMUState:
        return st


def hash_h(x: jax.Array, n: int) -> jax.Array:
    """Fibonacci-ish hash for the host-page counter table."""
    return (x * jnp.int32(-1640531535)) & (n - 1)
