"""Cache hierarchy model: L1D (LRU), L2 (SRRIP + Victima TLB blocks), L3 (SRRIP).

The L2 cache is the structure Victima modifies (§5.1 of the paper): each
block carries a *block type* —

    BT_DATA = 0   conventional data block (tag = physical line id)
    BT_TLB4 = 1   TLB block, 8 PTEs for 8 contiguous 4K pages (tag = vpn>>3)
    BT_TLB2 = 2   TLB block for 2M pages                      (tag = vpn2m>>3)
    BT_NTLB = 3   nested TLB block (virt.), 8 host leaf PTEs  (tag = gpn>>3)

Tag matching always requires the block type to match, which models the
paper's TLB-entry bit + disjoint tag layout.  Reuse histograms (paper
Figs. 11 & 24) and live TLB-block counts (Fig. 23 translation reach) are
folded into the cache state and updated on insert/evict.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bench.reference.assoc import (
    RRIP_MAX,
    Assoc,
    insert_lru,
    lookup,
    make,
    set_index,
    srrip_age_and_pick,
    srrip_victim_tlb_aware,
    touch_lru,
)

BT_DATA, BT_TLB4, BT_TLB2, BT_NTLB = 0, 1, 2, 3
REUSE_BUCKETS = 22  # reuse counts 0..20, bucket 21 = ">20" overflow


class L2Cache(NamedTuple):
    tags: jax.Array    # int32 [S, W]
    valid: jax.Array   # bool  [S, W]
    rrpv: jax.Array    # int32 [S, W]
    btype: jax.Array   # int32 [S, W]
    reuse: jax.Array   # int32 [S, W]
    hist_reuse_data: jax.Array  # int32 [REUSE_BUCKETS] — filled on eviction
    hist_reuse_tlb: jax.Array   # int32 [REUSE_BUCKETS]
    n_tlb4: jax.Array  # int32 scalar — live TLB blocks (4K)
    n_tlb2: jax.Array  # int32 scalar — live TLB blocks (2M)
    n_ntlb: jax.Array  # int32 scalar — live nested TLB blocks

    @property
    def n_sets(self) -> int:
        return self.tags.shape[0]


def make_l2(n_sets: int, n_ways: int) -> L2Cache:
    z = jnp.zeros((n_sets, n_ways), jnp.int32)
    return L2Cache(
        tags=z,
        valid=jnp.zeros((n_sets, n_ways), jnp.bool_),
        rrpv=z,
        btype=z,
        reuse=z,
        hist_reuse_data=jnp.zeros((REUSE_BUCKETS,), jnp.int32),
        hist_reuse_tlb=jnp.zeros((REUSE_BUCKETS,), jnp.int32),
        n_tlb4=jnp.int32(0),
        n_tlb2=jnp.int32(0),
        n_ntlb=jnp.int32(0),
    )


def l2_lookup(l2: L2Cache, key: jax.Array, btype):
    s = set_index(key, l2.n_sets)
    hits = l2.valid[s] & (l2.tags[s] == key) & (l2.btype[s] == btype)
    return jnp.any(hits), jnp.argmax(hits), s


def l2_touch(
    l2: L2Cache,
    s: jax.Array,
    w: jax.Array,
    pressure: jax.Array,
    tlb_aware: bool,
    enable,
) -> L2Cache:
    """Hit-promotion per paper Listing 1 `updateOnL2CacheHit`.

    TLB blocks under pressure decrement RRPV by 3, everything else by 1.
    Reuse counter increments (for Figs. 11/24).
    """
    en = jnp.asarray(enable)
    is_tlbish = l2.btype[s, w] != BT_DATA
    dec = jnp.where(is_tlbish & pressure & tlb_aware, 3, 1)
    new_rrpv = jnp.maximum(l2.rrpv[s, w] - dec, 0)
    return l2._replace(
        rrpv=l2.rrpv.at[s, w].set(jnp.where(en, new_rrpv, l2.rrpv[s, w])),
        reuse=l2.reuse.at[s, w].set(l2.reuse[s, w] + en.astype(jnp.int32)),
    )


def _account_evict(l2: L2Cache, s, w, evicting) -> L2Cache:
    """Histogram + live-count bookkeeping for the block being replaced."""
    bt = l2.btype[s, w]
    was_valid = l2.valid[s, w] & evicting
    bucket = jnp.minimum(l2.reuse[s, w], REUSE_BUCKETS - 1)
    is_data = bt == BT_DATA
    one = jnp.int32(1)
    hist_d = l2.hist_reuse_data.at[bucket].add(
        jnp.where(was_valid & is_data, one, 0)
    )
    hist_t = l2.hist_reuse_tlb.at[bucket].add(
        jnp.where(was_valid & ~is_data, one, 0)
    )
    dec = was_valid.astype(jnp.int32)
    return l2._replace(
        hist_reuse_data=hist_d,
        hist_reuse_tlb=hist_t,
        n_tlb4=l2.n_tlb4 - jnp.where(bt == BT_TLB4, dec, 0),
        n_tlb2=l2.n_tlb2 - jnp.where(bt == BT_TLB2, dec, 0),
        n_ntlb=l2.n_ntlb - jnp.where(bt == BT_NTLB, dec, 0),
    )


def l2_insert(
    l2: L2Cache,
    key: jax.Array,
    btype,
    pressure: jax.Array,
    tlb_aware: bool,
    enable,
) -> L2Cache:
    """Insert a block (Listing 1 `insertBlockInL2` + victim selection).

    Inserted TLB blocks under pressure get RRPV=0; everything else the
    standard SRRIP long re-reference interval (RRIP_MAX-1).
    Evicted TLB blocks are dropped (paper §5.1).
    """
    en = jnp.asarray(enable)
    btype = jnp.asarray(btype, jnp.int32)
    s = set_index(key, l2.n_sets)
    row_rrpv, row_valid = l2.rrpv[s], l2.valid[s]
    row_is_tlb = l2.btype[s] != BT_DATA
    if tlb_aware:
        aged, w = srrip_victim_tlb_aware(row_rrpv, row_valid, row_is_tlb,
                                         pressure)
    else:
        aged, w = srrip_age_and_pick(row_rrpv, row_valid)

    l2 = _account_evict(l2, s, w, en)
    ins_is_tlbish = btype != BT_DATA
    ins_rrpv = jnp.where(ins_is_tlbish & pressure & tlb_aware, 0, RRIP_MAX - 1)
    aged = aged.at[w].set(ins_rrpv)
    inc = en.astype(jnp.int32)
    return l2._replace(
        tags=l2.tags.at[s, w].set(jnp.where(en, key, l2.tags[s, w])),
        valid=l2.valid.at[s, w].set(l2.valid[s, w] | en),
        rrpv=l2.rrpv.at[s].set(jnp.where(en, aged, l2.rrpv[s])),
        btype=l2.btype.at[s, w].set(jnp.where(en, btype, l2.btype[s, w])),
        reuse=l2.reuse.at[s, w].set(jnp.where(en, 0, l2.reuse[s, w])),
        n_tlb4=l2.n_tlb4 + jnp.where(btype == BT_TLB4, inc, 0),
        n_tlb2=l2.n_tlb2 + jnp.where(btype == BT_TLB2, inc, 0),
        n_ntlb=l2.n_ntlb + jnp.where(btype == BT_NTLB, inc, 0),
    )


def l2_retag_to_tlb(
    l2: L2Cache,
    key: jax.Array,
    btype,
    pressure: jax.Array,
    tlb_aware: bool,
    enable,
) -> L2Cache:
    """Victima §5.2: transform the cache line holding the fetched leaf PTEs
    into a TLB block, *unless* one already exists for this region.

    (The physical line was inserted by the walk's PTE fetch; lookup by VA
    requires the block to live in set(VA), so the transformation is modeled
    as an insert at set(key) — behaviourally identical.)
    """
    # check for an existing TLB block of this region+type (§5.2 step 2)
    s = set_index(key, l2.n_sets)
    btype_arr = jnp.asarray(btype, jnp.int32)
    exists = jnp.any(
        l2.valid[s] & (l2.tags[s] == key) & (l2.btype[s] == btype_arr)
    )
    return l2_insert(
        l2, key, btype, pressure, tlb_aware,
        jnp.asarray(enable) & ~exists,
    )


# ---------------------------------------------------------------- L3 (SRRIP)


def l3_access(l3: Assoc, key: jax.Array, enable):
    """Probe L3; fill on miss. Returns (l3, hit)."""
    en = jnp.asarray(enable)
    hit, w, s = lookup(l3, key)
    # hit: promote to RRPV 0
    meta_hit = l3.meta.at[s, w].set(jnp.where(hit & en, 0, l3.meta[s, w]))
    l3 = l3._replace(meta=meta_hit)
    # miss: insert with SRRIP
    aged, vw = srrip_age_and_pick(l3.meta[s], l3.valid[s])
    do_ins = en & ~hit
    aged = aged.at[vw].set(RRIP_MAX - 1)
    l3 = Assoc(
        tags=l3.tags.at[s, vw].set(jnp.where(do_ins, key, l3.tags[s, vw])),
        valid=l3.valid.at[s, vw].set(l3.valid[s, vw] | do_ins),
        meta=l3.meta.at[s].set(jnp.where(do_ins, aged, l3.meta[s])),
    )
    return l3, hit


# ---------------------------------------------------------------- hierarchy


class Hier(NamedTuple):
    l1d: Assoc
    l2: L2Cache
    l3: Assoc
    dramc: Assoc            # die-stacked DRAM cache (sized 1 when off)
    # running counters for MPKI-style signals
    n_l2_access: jax.Array  # int32 — demand data accesses reaching L2
    n_l2_miss: jax.Array    # int32
    # shared-tier occupancy counters (multicore scenario bookkeeping)
    n_l3_access: jax.Array   # int32 — demand probes reaching the L3
    n_l3_trans: jax.Array    # int32 — of those, translation-typed
    #                          (walker PTE / TLB-block / POM traffic)
    n_dramc_access: jax.Array  # int32 — L3 misses probing the DRAM cache
    n_dramc_hit: jax.Array     # int32


def make_hier(l1_sets=64, l1_ways=8, l2_sets=2048, l2_ways=16,
              l3_sets=2048, l3_ways=16,
              dramc_sets=1, dramc_ways=16) -> Hier:
    z = jnp.int32(0)
    return Hier(
        l1d=make(l1_sets, l1_ways),
        l2=make_l2(l2_sets, l2_ways),
        l3=make(l3_sets, l3_ways),
        dramc=make(dramc_sets, dramc_ways),
        n_l2_access=z,
        n_l2_miss=z,
        n_l3_access=z,
        n_l3_trans=z,
        n_dramc_access=z,
        n_dramc_hit=z,
    )


class Lat(NamedTuple):
    """Latency constants (cycles), Table 3 + calibration."""

    l1d: int = 4
    l2: int = 16
    l3: int = 35
    dram: int = 160  # full DRAM round trip (beyond L3 probe)
    dramc: int = 58  # die-stacked DRAM-cache hit (beyond L3 probe) —
    #   in-package DRAM, roughly a third of the off-package round trip


def _dramc_probe(h: Hier, line: jax.Array, miss3, lat: Lat, dramc):
    """Probe the die-stacked DRAM cache on an L3 miss (SRRIP, same
    policy as the L3 — it is an ``Assoc`` driven by ``l3_access``).

    ``dramc`` is the live gate (see ``stages.base.dramc_of``): ``None``
    compiles the probe out and this reduces to the plain DRAM path.
    Returns (h, miss_cyc, dram) where
    ``miss_cyc`` is the beyond-L3 cycle term and ``dram`` the accesses
    that still went to main memory.
    """
    if dramc is None:
        return h, jnp.int32(lat.dram), miss3
    gate = jnp.asarray(dramc) & miss3
    dcc, hitd = l3_access(h.dramc, line, gate)
    h = h._replace(
        dramc=dcc,
        n_dramc_access=h.n_dramc_access + gate.astype(jnp.int32),
        n_dramc_hit=h.n_dramc_hit + (gate & hitd).astype(jnp.int32),
    )
    dram = miss3 & ~(gate & hitd)
    miss_cyc = jnp.where(
        gate, jnp.int32(lat.dramc) + jnp.where(hitd, 0, lat.dram),
        jnp.int32(lat.dram))
    return h, miss_cyc, dram


def access_data(h: Hier, line: jax.Array, now: jax.Array,
                pressure: jax.Array, tlb_aware: bool, lat: Lat,
                dramc=None):
    """Demand data access L1D→L2→L3→[DRAM cache]→DRAM with fills.
    Returns (h, cycles).  ``dramc`` gates the die-stacked DRAM-cache
    probe (None = absent, compiled out)."""
    hit1, w1, s1 = lookup(h.l1d, line)
    h = h._replace(l1d=touch_lru(h.l1d, s1, w1, now))

    hit2, w2, s2 = l2_lookup(h.l2, line, BT_DATA)
    go_l2 = ~hit1
    l2c = l2_touch(h.l2, s2, w2, pressure, tlb_aware, go_l2 & hit2)

    go_l3 = go_l2 & ~hit2
    l3c, hit3 = l3_access(h.l3, line, go_l3)
    # fill L2 on L2 miss (from L3 or DRAM)
    l2c = l2_insert(l2c, line, BT_DATA, pressure, tlb_aware, go_l3)
    # stream prefetcher at L2 (Table 3): next-line fill on L2 miss.
    # This is what keeps PT/PTE lines from squatting in the L2 under
    # data-intensive streams (PTW latencies match the paper's Fig. 4).
    nxt = line + 1
    pf_hit, _, _ = l2_lookup(l2c, nxt, BT_DATA)
    l2c = l2_insert(l2c, nxt, BT_DATA, pressure, tlb_aware,
                    go_l3 & ~pf_hit)
    # fill L1D on any L1 miss
    l1c, _, _ = insert_lru(h.l1d, line, now, go_l2)

    # background traffic: the traced stream is one data line per access,
    # but a real core also moves code/stack/auxiliary-heap lines through
    # L2/L3 between traced accesses.  Without it, hot PT lines squat in
    # the L2 forever and baseline PTWs are unrealistically cheap (the
    # paper measures ≈137-cycle PTWs, Fig. 4).  Two pseudo-random
    # untracked lines per access reproduce that pressure; Victima's
    # TLB blocks survive it through the TLB-aware policy — which is the
    # paper's §5.1 motivation verbatim.
    for salt in (jnp.int32(-1640531527), jnp.int32(-2048144789)):
        bg_line = ((now * jnp.int32(-1640531527)) ^ salt) & ((1 << 26) - 1)
        l3c, bg_hit3 = l3_access(l3c, bg_line, True)
        l2c = l2_insert(l2c, bg_line, BT_DATA, pressure, tlb_aware,
                        ~bg_hit3)

    # die-stacked DRAM cache between the L3 and main memory (background
    # lines model pressure only — they never charge latency, so they
    # skip the probe)
    h, miss_cyc, _dram = _dramc_probe(
        h._replace(l3=l3c), line, go_l3 & ~hit3, lat, dramc)

    cycles = jnp.where(
        hit1, lat.l1d,
        jnp.where(hit2, lat.l2, jnp.where(hit3, lat.l3, lat.l3 + miss_cyc)),
    )
    h = h._replace(
        l1d=l1c,
        l2=l2c,
        n_l2_access=h.n_l2_access + go_l2.astype(jnp.int32),
        n_l2_miss=h.n_l2_miss + (go_l3).astype(jnp.int32),
        n_l3_access=h.n_l3_access + go_l3.astype(jnp.int32),
    )
    return h, cycles


def access_pte(h: Hier, line: jax.Array, pressure: jax.Array,
               tlb_aware: bool, lat: Lat, enable, bt: int = BT_DATA,
               dramc=None):
    """Page-table-walker access (starts at L2). Returns (h, cycles, dram).

    `bt` lets POM-TLB lines be typed as TLB blocks so the TLB-aware SRRIP
    prioritizes them (Table 3: POM-TLB uses the §5.1 policy).  ``dramc``
    gates the die-stacked DRAM-cache probe between the L3 and main
    memory (None = absent, compiled out); a DRAM-cache hit counts as
    ``dram=False`` — the walk never left the package."""
    en = jnp.asarray(enable)
    hit2, w2, s2 = l2_lookup(h.l2, line, bt)
    l2c = l2_touch(h.l2, s2, w2, pressure, tlb_aware, en & hit2)
    go_l3 = en & ~hit2
    l3c, hit3 = l3_access(h.l3, line, go_l3)
    l2c = l2_insert(l2c, line, bt, pressure, tlb_aware, go_l3)
    h, miss_cyc, dram = _dramc_probe(
        h._replace(l3=l3c), line, go_l3 & ~hit3, lat, dramc)
    cycles = jnp.where(
        en,
        jnp.where(hit2, lat.l2, jnp.where(hit3, lat.l3, lat.l3 + miss_cyc)),
        0,
    )
    h = h._replace(
        l2=l2c,
        n_l3_access=h.n_l3_access + go_l3.astype(jnp.int32),
        n_l3_trans=h.n_l3_trans + go_l3.astype(jnp.int32),
    )
    return h, cycles, dram
