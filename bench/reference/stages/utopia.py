"""Stage: Utopia — hybrid restrictive/flexible address mapping (PAPERS.md).

Utopia backs most translation-heavy pages with *RestSegs*: set-associative
memory segments whose virtual-to-physical mapping is restrictive, so the
candidate physical frame is computable from the VPN alone and a probe only
has to confirm the tag/permission metadata embedded in the set.  Pages the
RestSegs cannot hold live in the conventionally (flexibly) mapped
*FlexSeg* and fall back to the radix walker (``ptw``/``ptw2d``) — the
walkers are reused unchanged as the FlexSeg path.

The model keeps one RestSeg per page size (4K + 2M), mirroring the pc4/
pc2 counter split.  A probe fetches the set's tag line through the cache
hierarchy (DRAM-row cost when cold, typed as a TLB block so the TLB-aware
SRRIP prioritizes it like POM-TLB lines); a tag match resolves the
translation with NO page walk.  The *migration engine* in ``fill``
promotes costly-to-translate pages into a RestSeg after their demand
walk, reusing the PTW-CP counters — the exact predictor Victima trains —
and a set conflict demotes the LRU resident back to the FlexSeg.

"""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.assoc import insert_lru, lookup
from bench.reference.caches import BT_TLB4, access_pte
from bench.reference.page_table import RESTSEG2_BASE, RESTSEG4_BASE
from bench.reference.stages.base import (Stage, StageResult, dramc_of,
                                         ptwcp_walk_verdict)


class RestSegStage(Stage):
    name = "restseg"

    def lookup(self, cfg, st, req, need):
        # one tag/permission line per set, fetched through the caches
        s4 = req.vpn & (cfg.restseg4_sets - 1)
        s2 = req.vpn2 & (cfg.restseg2_sets - 1)
        tag_line = jnp.where(req.is2m, RESTSEG2_BASE + s2,
                             RESTSEG4_BASE + s4)
        hier, cyc, _ = access_pte(st.hier, tag_line, req.pressure,
                                  cfg.tlb_aware, cfg.lat, need,
                                  bt=BT_TLB4, dramc=dramc_of(cfg))
        st = st._replace(hier=hier)

        # probe both RestSegs; the access's page size selects the result
        h4, w4, i4 = lookup(st.restseg4, req.vpn)
        h2, w2, i2 = lookup(st.restseg2, req.vpn2)
        hit4 = need & ~req.is2m & h4
        hit2 = need & req.is2m & h2
        # LRU touch keeps conflict demotions picking the coldest resident
        rs4 = st.restseg4._replace(meta=st.restseg4.meta.at[i4, w4].set(
            jnp.where(hit4, req.now, st.restseg4.meta[i4, w4])))
        rs2 = st.restseg2._replace(meta=st.restseg2.meta.at[i2, w2].set(
            jnp.where(hit2, req.now, st.restseg2.meta[i2, w2])))
        st = st._replace(restseg4=rs4, restseg2=rs2)

        rhit = hit4 | hit2
        return st, StageResult(hit=rhit, cycles=cyc,
                               info={"probed": need})

    def fill(self, cfg, st, req, out):
        """Migration engine: promote costly-to-translate pages (§PTW-CP
        verdict after their demand walk) into a RestSeg; a set conflict
        demotes the evicted resident back to the FlexSeg."""
        mig = ptwcp_walk_verdict(cfg, st, req,
                                 out["_walk"].info["walk_en"])
        mig4 = mig & ~req.is2m
        mig2 = mig & req.is2m

        rs4, _, conf4 = insert_lru(st.restseg4, req.vpn, req.now, mig4)
        rs2, _, conf2 = insert_lru(st.restseg2, req.vpn2, req.now, mig2)
        out[self.name].info["n_mig"] = (mig4 | mig2).astype(jnp.int32)
        out[self.name].info["n_conflict"] = (conf4 | conf2).astype(jnp.int32)
        return st._replace(restseg4=rs4, restseg2=rs2)
