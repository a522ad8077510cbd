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

from repro.core.assoc import (
    RRIP_MAX,
    Assoc,
    get,
    insert_lru,
    lookup,
    make,
    pick,
    put,
    put_row,
    row,
    set_assoc,
    set_index,
    setv,
    shape,
    srrip_age_and_pick,
    srrip_victim_tlb_aware,
    touch_lru,
)

BT_DATA, BT_TLB4, BT_TLB2, BT_NTLB = 0, 1, 2, 3
REUSE_BUCKETS = 22  # reuse counts 0..20, bucket 21 = ">20" overflow


class L2Geom(NamedTuple):
    """Traced view geometry of a dynamically sized L2 cache.

    A ladder-batched run allocates the L2 at the ladder's maximum static
    shape; each member's live geometry is a set mask plus an effective
    way count.  Because every insert masks its set index and restricts
    victim selection to ways below ``n_ways``, the view is bit-identical
    to a statically allocated (live_sets, n_ways) cache — the invariant
    the ladder-equivalence tests pin.  ``geom=None`` everywhere below
    selects the static path (identical compiled code to pre-Dyn days).
    """

    set_mask: jax.Array  # int32 = live sets - 1
    n_ways: jax.Array    # int32 effective ways


def _l2_set(l2: "L2Cache", key: jax.Array, geom: L2Geom | None):
    return set_index(key, l2.n_sets) if geom is None else key & geom.set_mask


def _way_ok(l2: "L2Cache", geom: L2Geom | None):
    if geom is None:
        return None
    return jnp.arange(l2.n_ways) < geom.n_ways


@set_assoc("tags", "valid", "rrpv", "btype", "reuse")
class L2Cache:
    """The L2 cache: per-block arrays in set-major vector rows, as in
    ``assoc.Assoc``, plus the reuse histograms and live counts."""

    tags: jax.Array    # int32 [S/k, k*W]
    valid: jax.Array   # bool  [S/k, k*W]
    rrpv: jax.Array    # int32 [S/k, k*W]
    btype: jax.Array   # int32 [S/k, k*W]
    reuse: jax.Array   # int32 [S/k, k*W]
    hist_reuse_data: jax.Array  # int32 [REUSE_BUCKETS] — filled on eviction
    hist_reuse_tlb: jax.Array   # int32 [REUSE_BUCKETS]
    n_tlb4: jax.Array  # int32 scalar — live TLB blocks (4K)
    n_tlb2: jax.Array  # int32 scalar — live TLB blocks (2M)
    n_ntlb: jax.Array  # int32 scalar — live nested TLB blocks
    n_ways: int        # static: ways per set


def make_l2(n_sets: int, n_ways: int) -> L2Cache:
    shp = shape(n_sets, n_ways)
    z = jnp.zeros(shp, jnp.int32)
    return L2Cache(
        tags=z,
        valid=jnp.zeros(shp, jnp.bool_),
        rrpv=z,
        btype=z,
        reuse=z,
        hist_reuse_data=jnp.zeros((REUSE_BUCKETS,), jnp.int32),
        hist_reuse_tlb=jnp.zeros((REUSE_BUCKETS,), jnp.int32),
        n_tlb4=jnp.int32(0),
        n_tlb2=jnp.int32(0),
        n_ntlb=jnp.int32(0),
        n_ways=n_ways,
    )


def _l2_match(l2: L2Cache, s: jax.Array, key: jax.Array, btype):
    """Ways of set ``s`` holding a valid ``key`` block of ``btype``."""
    n = l2.n_ways
    return (row(l2.valid, s, n) & (row(l2.tags, s, n) == key)
            & (row(l2.btype, s, n) == btype))


def l2_lookup(l2: L2Cache, key: jax.Array, btype,
              geom: L2Geom | None = None):
    s = _l2_set(l2, key, geom)
    # no way mask needed on probe: inserts never touch ways past the
    # view's limit, so those ways are never valid
    hits = _l2_match(l2, s, key, btype)
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
    n = l2.n_ways
    is_tlbish = get(l2.btype, s, w, n) != BT_DATA
    dec = jnp.where(is_tlbish & pressure & tlb_aware, 3, 1)
    rrpv = get(l2.rrpv, s, w, n)
    new_rrpv = jnp.maximum(rrpv - dec, 0)
    return l2._replace(
        rrpv=put(l2.rrpv, s, w, n, jnp.where(en, new_rrpv, rrpv)),
        reuse=put(l2.reuse, s, w, n,
                  get(l2.reuse, s, w, n) + en.astype(jnp.int32)),
    )


def _account_evict(l2: L2Cache, s, w, evicting) -> L2Cache:
    """Histogram + live-count bookkeeping for the block being replaced."""
    n = l2.n_ways
    bt = get(l2.btype, s, w, n)
    was_valid = get(l2.valid, s, w, n) & evicting
    bucket = jnp.minimum(get(l2.reuse, s, w, n), REUSE_BUCKETS - 1)
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
    geom: L2Geom | None = None,
) -> L2Cache:
    """Insert a block (Listing 1 `insertBlockInL2` + victim selection).

    Inserted TLB blocks under pressure get RRPV=0; everything else the
    standard SRRIP long re-reference interval (RRIP_MAX-1).
    Evicted TLB blocks are dropped (paper §5.1).
    """
    en = jnp.asarray(enable)
    btype = jnp.asarray(btype, jnp.int32)
    s = _l2_set(l2, key, geom)
    way_ok = _way_ok(l2, geom)
    n = l2.n_ways
    row_rrpv, row_valid = row(l2.rrpv, s, n), row(l2.valid, s, n)
    row_btype = row(l2.btype, s, n)
    row_is_tlb = row_btype != BT_DATA
    if tlb_aware:
        aged, w = srrip_victim_tlb_aware(row_rrpv, row_valid, row_is_tlb,
                                         pressure, way_ok)
    else:
        aged, w = srrip_age_and_pick(row_rrpv, row_valid, way_ok)

    l2 = _account_evict(l2, s, w, en)
    ins_is_tlbish = btype != BT_DATA
    ins_rrpv = jnp.where(ins_is_tlbish & pressure & tlb_aware, 0, RRIP_MAX - 1)
    aged = setv(aged, w, ins_rrpv)
    inc = en.astype(jnp.int32)
    return l2._replace(
        tags=put(l2.tags, s, w, n,
                 jnp.where(en, key, get(l2.tags, s, w, n))),
        valid=put(l2.valid, s, w, n, pick(row_valid, w) | en),
        rrpv=put_row(l2.rrpv, s, n, jnp.where(en, aged, row_rrpv)),
        btype=put(l2.btype, s, w, n,
                  jnp.where(en, btype, pick(row_btype, w))),
        reuse=put(l2.reuse, s, w, n,
                  jnp.where(en, 0, get(l2.reuse, s, w, n))),
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
    geom: L2Geom | None = None,
) -> L2Cache:
    """Victima §5.2: transform the cache line holding the fetched leaf PTEs
    into a TLB block, *unless* one already exists for this region.

    (The physical line was inserted by the walk's PTE fetch; lookup by VA
    requires the block to live in set(VA), so the transformation is modeled
    as an insert at set(key) — behaviourally identical.)
    """
    # check for an existing TLB block of this region+type (§5.2 step 2)
    s = _l2_set(l2, key, geom)
    exists = jnp.any(_l2_match(l2, s, key, jnp.asarray(btype, jnp.int32)))
    return l2_insert(
        l2, key, btype, pressure, tlb_aware,
        jnp.asarray(enable) & ~exists, geom,
    )


# ---------------------------------------------------------------- L3 (SRRIP)


def l3_access(l3: Assoc, key: jax.Array, enable):
    """Probe L3; fill on miss. Returns (l3, hit)."""
    en = jnp.asarray(enable)
    n = l3.n_ways
    hit, w, s = lookup(l3, key)
    # hit: promote to RRPV 0
    meta_row = row(l3.meta, s, n)
    meta_row = setv(meta_row, w, jnp.where(hit & en, 0, pick(meta_row, w)))
    # miss: insert with SRRIP
    valid_row = row(l3.valid, s, n)
    aged, vw = srrip_age_and_pick(meta_row, valid_row)
    do_ins = en & ~hit
    aged = setv(aged, vw, RRIP_MAX - 1)
    l3 = l3._replace(
        tags=put(l3.tags, s, vw, n,
                 jnp.where(do_ins, key, get(l3.tags, s, vw, n))),
        valid=put(l3.valid, s, vw, n, pick(valid_row, vw) | do_ins),
        meta=put_row(l3.meta, s, n, jnp.where(do_ins, aged, meta_row)),
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
    compiles the probe out and this reduces to the plain DRAM path; a
    traced ``False`` masks it off bit-exactly (the miss cost folds back
    to exactly ``lat.dram``).  Returns (h, miss_cyc, dram) where
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
                geom: L2Geom | None = None, dramc=None):
    """Demand data access L1D→L2→L3→[DRAM cache]→DRAM with fills.
    Returns (h, cycles).  ``dramc`` gates the die-stacked DRAM-cache
    probe (None = absent, compiled out)."""
    hit1, w1, s1 = lookup(h.l1d, line)
    h = h._replace(l1d=touch_lru(h.l1d, s1, w1, now))

    hit2, w2, s2 = l2_lookup(h.l2, line, BT_DATA, geom)
    go_l2 = ~hit1
    l2c = l2_touch(h.l2, s2, w2, pressure, tlb_aware, go_l2 & hit2)

    go_l3 = go_l2 & ~hit2
    l3c, hit3 = l3_access(h.l3, line, go_l3)
    # fill L2 on L2 miss (from L3 or DRAM)
    l2c = l2_insert(l2c, line, BT_DATA, pressure, tlb_aware, go_l3, geom)
    # stream prefetcher at L2 (Table 3): next-line fill on L2 miss.
    # This is what keeps PT/PTE lines from squatting in the L2 under
    # data-intensive streams (PTW latencies match the paper's Fig. 4).
    nxt = line + 1
    pf_hit, _, _ = l2_lookup(l2c, nxt, BT_DATA, geom)
    l2c = l2_insert(l2c, nxt, BT_DATA, pressure, tlb_aware,
                    go_l3 & ~pf_hit, geom)
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
                        ~bg_hit3, geom)

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
               geom: L2Geom | None = None, dramc=None):
    """Page-table-walker access (starts at L2). Returns (h, cycles, dram).

    `bt` lets POM-TLB lines be typed as TLB blocks so the TLB-aware SRRIP
    prioritizes them (Table 3: POM-TLB uses the §5.1 policy).  ``dramc``
    gates the die-stacked DRAM-cache probe between the L3 and main
    memory (None = absent, compiled out); a DRAM-cache hit counts as
    ``dram=False`` — the walk never left the package."""
    en = jnp.asarray(enable)
    hit2, w2, s2 = l2_lookup(h.l2, line, bt, geom)
    l2c = l2_touch(h.l2, s2, w2, pressure, tlb_aware, en & hit2)
    go_l3 = en & ~hit2
    l3c, hit3 = l3_access(h.l3, line, go_l3)
    l2c = l2_insert(l2c, line, bt, pressure, tlb_aware, go_l3, geom)
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
