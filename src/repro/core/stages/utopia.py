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

Dyn gating: ``Dyn.utopia_en`` masks the probe's cache traffic, the hit
path and every migration write, so a non-Utopia lane of a batched ladder
is bit-identical to the composition without this stage;
``Dyn.restseg_ways`` runs the RestSeg-associativity sensitivity ladder
through way-masked views (assoc.lookup_dyn/insert_lru_dyn).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.assoc import (insert_lru, insert_lru_dyn, lookup,
                              lookup_dyn, touch_lru)
from repro.core.caches import BT_TLB4, access_pte
from repro.core.page_table import RESTSEG2_BASE, RESTSEG4_BASE
from repro.core.stages.base import (Stage, StageResult, dramc_of,
                                    l2_geom_of, ptwcp_walk_verdict)


class RestSegStage(Stage):
    name = "restseg"

    def lookup(self, cfg, st, req, need):
        uen = None if req.dyn is None else req.dyn.utopia_en
        probe = need if uen is None else need & uen

        # one tag/permission line per set, fetched through the caches
        s4 = req.vpn & (cfg.restseg4_sets - 1)
        s2 = req.vpn2 & (cfg.restseg2_sets - 1)
        tag_line = jnp.where(req.is2m, RESTSEG2_BASE + s2,
                             RESTSEG4_BASE + s4)
        hier, cyc, _ = access_pte(st.hier, tag_line, req.pressure,
                                  cfg.tlb_aware, cfg.lat, probe,
                                  bt=BT_TLB4, geom=l2_geom_of(req.dyn),
                                  dramc=dramc_of(cfg, req.dyn))
        st = st._replace(hier=hier)

        # probe both RestSegs; the access's page size selects the result
        if req.dyn is None:
            h4, w4, i4 = lookup(st.restseg4, req.vpn)
            h2, w2, i2 = lookup(st.restseg2, req.vpn2)
        else:
            h4, w4, i4 = lookup_dyn(st.restseg4, req.vpn,
                                    jnp.int32(cfg.restseg4_sets - 1),
                                    req.dyn.restseg_ways)
            h2, w2, i2 = lookup_dyn(st.restseg2, req.vpn2,
                                    jnp.int32(cfg.restseg2_sets - 1),
                                    req.dyn.restseg_ways)
        hit4 = probe & ~req.is2m & h4
        hit2 = probe & req.is2m & h2
        # LRU touch keeps conflict demotions picking the coldest resident
        st = st._replace(
            restseg4=touch_lru(st.restseg4, i4, w4, req.now, hit4),
            restseg2=touch_lru(st.restseg2, i2, w2, req.now, hit2))

        rhit = hit4 | hit2
        return st, StageResult(hit=rhit, cycles=cyc,
                               info={"probed": probe})

    def fill(self, cfg, st, req, out):
        """Migration engine: promote costly-to-translate pages (§PTW-CP
        verdict after their demand walk) into a RestSeg; a set conflict
        demotes the evicted resident back to the FlexSeg."""
        uen = None if req.dyn is None else req.dyn.utopia_en
        mig = ptwcp_walk_verdict(cfg, st, req,
                                 out["_walk"].info["walk_en"])
        if uen is not None:
            mig = mig & uen
        mig4 = mig & ~req.is2m
        mig2 = mig & req.is2m

        if req.dyn is None:
            rs4, _, conf4 = insert_lru(st.restseg4, req.vpn, req.now, mig4)
            rs2, _, conf2 = insert_lru(st.restseg2, req.vpn2, req.now, mig2)
        else:
            rs4, _, conf4 = insert_lru_dyn(
                st.restseg4, req.vpn, req.now,
                jnp.int32(cfg.restseg4_sets - 1), req.dyn.restseg_ways,
                mig4)
            rs2, _, conf2 = insert_lru_dyn(
                st.restseg2, req.vpn2, req.now,
                jnp.int32(cfg.restseg2_sets - 1), req.dyn.restseg_ways,
                mig2)
        out[self.name].info["n_mig"] = (mig4 | mig2).astype(jnp.int32)
        out[self.name].info["n_conflict"] = (conf4 | conf2).astype(jnp.int32)
        return st._replace(restseg4=rs4, restseg2=rs2)
