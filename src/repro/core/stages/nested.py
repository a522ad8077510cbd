"""Stage: nested-paging 2-D page-table walk (virtualized, paper §9.3).

Every guest-PT access first resolves its own gPA -> hPA through the
nested TLB, optionally Victima's nested-TLB blocks in the L2 cache, and
finally a 4-level host walk.  The data page's own gPA is translated
last (identity gPA map: gpn = vpn).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import ptwcp
from repro.core.assoc import insert_lru, lookup, touch_lru
from repro.core.caches import (BT_NTLB, access_pte, l2_lookup,
                               l2_retag_to_tlb, l2_touch)
from repro.core.page_table import (PWC_LAT, PWCs, _level_lines_2m,
                                   _level_lines_4k, host_walk)
from repro.core.stages.base import (Stage, StageResult, dramc_of, hash_h,
                                    l2_geom_of)
from repro.core.stages.ptw import fill_walk_counters


def nested_translate(cfg, st, gpn, pressure, l2_bypass, enable,
                     geom=None, ven=None, dramc=None):
    """gPA-page -> hPA (virt.): nested TLB -> [Victima nested-TLB block] ->
    host walk.  Returns (st, cycles, host_walked, ntlb_hit, nvictima_hit).

    `geom` is the dynamic L2-cache view for ladder-batched runs; `ven`
    (None = static) gates the Victima nested-TLB-block machinery per
    lane, bit-exactly reproducing a plain-NP system when off."""
    en = jnp.asarray(enable)
    hit_n, w_n, s_n = lookup(st.ntlb, gpn)
    st = st._replace(ntlb=touch_lru(st.ntlb, s_n, w_n, st.now, en & hit_n))

    miss = en & ~hit_n
    cycles = jnp.where(en, 1, 0)  # 1-cycle nested TLB

    # Victima: probe L2 cache for a nested TLB block
    if cfg.victima:
        vh, vw, vs = l2_lookup(st.hier.l2, gpn >> 3, BT_NTLB, geom)
        vhit = miss & vh
        if ven is not None:
            vhit = vhit & ven
        l2c = l2_touch(st.hier.l2, vs, vw, pressure, cfg.tlb_aware, vhit)
        st = st._replace(hier=st.hier._replace(l2=l2c))
        cycles = cycles + jnp.where(vhit, cfg.lat.l2, 0)
    else:
        vhit = jnp.bool_(False)

    need_walk = miss & ~vhit
    hier, wc, ndram, _leaf = host_walk(
        st.hier, gpn, pressure, cfg.tlb_aware, cfg.lat, need_walk, geom,
        dramc,
    )
    st = st._replace(hier=hier)
    cycles = cycles + wc

    # host-page PTW-CP counters + nested-TLB-block insertion
    hidx = hash_h(gpn, cfg.n_pagesh)
    pch = ptwcp.update_counters(st.pch, hidx, ndram >= 1, need_walk)
    st = st._replace(pch=pch)
    if cfg.victima:
        pred = ptwcp.predict_page(pch, hidx) if cfg.use_ptwcp \
            else jnp.bool_(True)
        ins = need_walk & (pred | l2_bypass)
        if ven is not None:
            ins = ins & ven
        l2c = l2_retag_to_tlb(st.hier.l2, gpn >> 3, BT_NTLB, pressure,
                              cfg.tlb_aware, ins, geom)
        st = st._replace(hier=st.hier._replace(l2=l2c))

    # refill nested TLB; evicted nested entry triggers background host walk
    ntlb2, ev_tag, ev_valid = insert_lru(st.ntlb, gpn, st.now, miss)
    st = st._replace(ntlb=ntlb2)
    if cfg.victima:
        eidx = hash_h(ev_tag, cfg.n_pagesh)
        epred = ptwcp.predict_page(st.pch, eidx) if cfg.use_ptwcp \
            else jnp.bool_(True)
        bg = miss & ev_valid & (epred | l2_bypass)
        if ven is not None:
            bg = bg & ven
        hier, _, bdram, _ = host_walk(st.hier, ev_tag, pressure,
                                      cfg.tlb_aware, cfg.lat, bg, geom,
                                      dramc)
        pch = ptwcp.update_counters(st.pch, eidx, bdram >= 1, bg)
        l2c = l2_retag_to_tlb(hier.l2, ev_tag >> 3, BT_NTLB, pressure,
                              cfg.tlb_aware, bg, geom)
        st = st._replace(hier=hier._replace(l2=l2c), pch=pch)

    return st, cycles, need_walk, en & hit_n, vhit


def guest_walk_2d(cfg, st, vpn, is2m, pressure, l2_bypass, enable,
                  geom=None, ven=None, dramc=None):
    """Nested-paging 2-D walk: every guest-PT access first resolves its own
    gPA->hPA via ``nested_translate``.  Returns (st, cycles, n_dram,
    n_host_walks, n_ntlb_hits, n_nvictima_hits)."""
    en = jnp.asarray(enable)
    vpn2 = vpn >> 9
    l4k = _level_lines_4k(vpn)
    l2m = _level_lines_2m(vpn2)
    lines = [
        jnp.where(is2m, l2m[0], l4k[0]),
        jnp.where(is2m, l2m[1], l4k[1]),
        jnp.where(is2m, l2m[2], l4k[2]),
        l4k[3],
    ]
    n_levels = jnp.where(is2m, 3, 4)

    k_pml4 = jnp.where(is2m, vpn2 >> 18, vpn >> 27)
    k_pdp = jnp.where(is2m, vpn2 >> 9, vpn >> 18)
    k_pd = vpn >> 9
    hit4, _, _ = lookup(st.pwcs.pml4, k_pml4)
    hit3, _, _ = lookup(st.pwcs.pdp, k_pdp)
    hit2, _, _ = lookup(st.pwcs.pd, k_pd)
    hit2 = hit2 & ~is2m
    start = jnp.where(hit2, 3, jnp.where(hit3, 2, jnp.where(hit4, 1, 0)))
    start = jnp.where(is2m, jnp.minimum(start, 2), start)

    cycles = jnp.where(en, jnp.int32(PWC_LAT), 0)
    n_dram = jnp.int32(0)
    n_host = jnp.int32(0)
    n_nt_hit = jnp.int32(0)
    n_nv_hit = jnp.int32(0)
    for slot in range(4):
        slot_en = en & (slot >= start) & (slot < n_levels)
        # translate the guest-PT line's gPA page first
        st, ncyc, walked, nth, nvh = nested_translate(
            cfg, st, lines[slot] >> 6, pressure, l2_bypass, slot_en,
            geom, ven, dramc,
        )
        n_host = n_host + (walked & slot_en).astype(jnp.int32)
        n_nt_hit = n_nt_hit + nth.astype(jnp.int32)
        n_nv_hit = n_nv_hit + nvh.astype(jnp.int32)
        hier, c, d = access_pte(st.hier, lines[slot], pressure,
                                cfg.tlb_aware, cfg.lat, slot_en, geom=geom,
                                dramc=dramc)
        st = st._replace(hier=hier)
        cycles = cycles + ncyc + c
        n_dram = n_dram + d.astype(jnp.int32)

    p4, _, _ = insert_lru(st.pwcs.pml4, k_pml4, st.now, en & (start <= 0))
    p3, _, _ = insert_lru(st.pwcs.pdp, k_pdp, st.now, en & (start <= 1))
    p2, _, _ = insert_lru(st.pwcs.pd, k_pd, st.now,
                          en & (start <= 2) & ~is2m)
    st = st._replace(pwcs=PWCs(pml4=p4, pdp=p3, pd=p2))

    # finally translate the data page's own gPA (gpn = vpn, identity map)
    st, ncyc, walked, nth, nvh = nested_translate(
        cfg, st, vpn, pressure, l2_bypass, en, geom, ven, dramc)
    n_host = n_host + (walked & en).astype(jnp.int32)
    n_nt_hit = n_nt_hit + nth.astype(jnp.int32)
    n_nv_hit = n_nv_hit + nvh.astype(jnp.int32)
    return st, cycles + ncyc, n_dram, n_host, n_nt_hit, n_nv_hit


class NestedWalkStage(Stage):
    name = "ptw2d"

    def lookup(self, cfg, st, req, need):
        ven = None if req.dyn is None else req.dyn.victima_en
        st, wcyc, ndram, nhost, n_nt_hit, n_nv_hit = guest_walk_2d(
            cfg, st, req.vpn, req.is2m, req.pressure, req.l2_bypass, need,
            l2_geom_of(req.dyn), ven, dramc_of(cfg, req.dyn),
        )
        info = {
            "walk_en": need, "ndram": ndram, "nhost": nhost,
            "n_nt_hit": n_nt_hit, "n_nv_hit": n_nv_hit,
        }
        return st, StageResult(hit=need, cycles=wcyc, info=info)

    def fill(self, cfg, st, req, out):
        if cfg.victima:
            return st  # VictimaStage.fill owns the counter traffic
        return fill_walk_counters(cfg, st, req, out)
