"""Stage: Victima — TLB blocks living in the L2 cache (paper §5).

Lookup probes the L2 cache for a typed TLB block covering the missing
page's 8-page region.  Fill implements the PTW-CP-gated install of the
demand walk's leaf PTEs plus the eviction-triggered background walk that
re-homes entries evicted from the L2 TLB (paper §5.2).  All counter
traffic is fused into ONE gather + ONE scatter per table so the XLA CPU
backend keeps the (multi-MB) tables in place across the scan.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference import ptwcp
from bench.reference.caches import (BT_TLB2, BT_TLB4, l2_lookup, l2_retag_to_tlb,
                               l2_touch)
from bench.reference.page_table import walk
from bench.reference.stages.base import Stage, StageResult, dramc_of


class VictimaStage(Stage):
    name = "victima"

    def lookup(self, cfg, st, req, need):
        vkey = jnp.where(req.is2m, req.vpn2 >> 3, req.vpn >> 3)
        vbt = jnp.where(req.is2m, BT_TLB2, BT_TLB4)
        # typed lookup (btype must match)
        vh, vwy, sset = l2_lookup(st.hier.l2, vkey, vbt)
        vhit = need & vh
        l2c = l2_touch(st.hier.l2, sset, vwy, req.pressure, cfg.tlb_aware,
                       vhit)
        st = st._replace(hier=st.hier._replace(l2=l2c))
        return st, StageResult(hit=vhit,
                               cycles=jnp.where(vhit, cfg.lat.l2, 0),
                               info={"vkey": vkey, "vbt": vbt})

    def fill(self, cfg, st, req, out):
        walk_res = out["_walk"]
        walk_en = walk_res.info["walk_en"]
        ndram = walk_res.info["ndram"]
        miss2 = out["l2_tlb"].need
        ev_tag = out["l2_tlb"].info["ev_tag"]
        ev_valid = out["l2_tlb"].info["ev_valid"]
        vkey = out[self.name].info["vkey"]
        vbt = out[self.name].info["vbt"]
        now, is2m = req.now, req.is2m

        ev_vpn = ev_tag >> 1
        ev2m = (ev_tag & 1).astype(jnp.bool_)
        bg_vpn4 = jnp.where(ev2m, ev_vpn << 9, ev_vpn)

        # counter slot 0: the demand walk; slot 1: the background walk
        d4, b4 = req.vpn & (cfg.n_pages4 - 1), bg_vpn4 & (cfg.n_pages4 - 1)
        d2, b2 = req.vpn2 & (cfg.n_pages2 - 1), ev_vpn & (cfg.n_pages2 - 1)
        i4 = jnp.stack([d4, b4])
        i2 = jnp.stack([d2, b2])
        f4, c4 = st.pc4.freq[i4].astype(jnp.int32), \
            st.pc4.cost[i4].astype(jnp.int32)
        f2, c2 = st.pc2.freq[i2].astype(jnp.int32), \
            st.pc2.cost[i2].astype(jnp.int32)

        # demand prediction on post-walk counters (computed analytically)
        fpost = jnp.where(is2m, f2[0], f4[0]) + walk_en.astype(jnp.int32)
        cpost = jnp.where(is2m, c2[0], c4[0]) \
            + (walk_en & (ndram >= 1)).astype(jnp.int32)
        pred = ptwcp.predict(jnp.minimum(fpost, ptwcp.FREQ_MAX),
                             jnp.minimum(cpost, ptwcp.COST_MAX))
        pred = pred if cfg.use_ptwcp else jnp.bool_(True)
        ins = walk_en & (pred | req.l2_bypass)
        l2c = l2_retag_to_tlb(st.hier.l2, vkey, vbt, req.pressure,
                              cfg.tlb_aware, ins)
        st = st._replace(hier=st.hier._replace(l2=l2c))

        # eviction-triggered background walk + TLB-block install
        fe = jnp.where(ev2m, f2[1], f4[1])
        ce = jnp.where(ev2m, c2[1], c4[1])
        epred = ptwcp.predict(fe, ce)
        epred = epred if cfg.use_ptwcp else jnp.bool_(True)
        bg = miss2 & ev_valid & (epred | req.l2_bypass)
        hier, pwcs, _, bdram = walk(
            st.hier, st.pwcs, bg_vpn4, ev2m, now, req.pressure,
            cfg.tlb_aware, cfg.lat, bg, dramc_of(cfg),
        )
        ebt = jnp.where(ev2m, BT_TLB2, BT_TLB4)
        l2c = l2_retag_to_tlb(hier.l2, ev_vpn >> 3, ebt, req.pressure,
                              cfg.tlb_aware, bg)
        st = st._replace(hier=hier._replace(l2=l2c), pwcs=pwcs)
        out[self.name].info["n_bg"] = bg.astype(jnp.int32)

        # fused saturating counter writeback (2 slots per table)
        en4 = jnp.stack([walk_en & ~is2m, bg & ~ev2m])
        en2 = jnp.stack([walk_en & is2m, bg & ev2m])
        dr = jnp.stack([ndram >= 1, bdram >= 1])
        nf4 = jnp.minimum(f4 + en4, ptwcp.FREQ_MAX)
        nc4 = jnp.minimum(c4 + (en4 & dr), ptwcp.COST_MAX)
        nf2 = jnp.minimum(f2 + en2, ptwcp.FREQ_MAX)
        nc2 = jnp.minimum(c2 + (en2 & dr), ptwcp.COST_MAX)
        return st._replace(
            pc4=ptwcp.PageCounters(
                freq=st.pc4.freq.at[i4].set(nf4.astype(jnp.uint8)),
                cost=st.pc4.cost.at[i4].set(nc4.astype(jnp.uint8))),
            pc2=ptwcp.PageCounters(
                freq=st.pc2.freq.at[i2].set(nf2.astype(jnp.uint8)),
                cost=st.pc2.cost.at[i2].set(nc2.astype(jnp.uint8))),
        )
