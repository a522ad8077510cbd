"""Stage: POM-TLB — software-managed L3 TLB resident in memory.

Entries are fetched through the cache hierarchy (typed as TLB blocks so
the TLB-aware SRRIP prioritizes them, per Table 3); hit/miss bookkeeping
is tracked by a shadow associative structure.  Fill learns both the
demand-walked entry and the L2 TLB's evicted entry.
"""
from __future__ import annotations

from repro.core.assoc import insert_lru, lookup, touch_lru
from repro.core.caches import BT_TLB4, access_pte
from repro.core.page_table import POM_BASE
from repro.core.stages.base import Stage, StageResult, dramc_of, l2_geom_of


class POMStage(Stage):
    name = "pom"

    def lookup(self, cfg, st, req, need):
        # dyn gate: a non-POM ladder lane must not fetch POM lines through
        # the caches (access_pte mutates L2/L3) nor probe the shadow assoc
        pen = None if req.dyn is None else req.dyn.pom_en
        probe = need if pen is None else need & pen
        pom_line = POM_BASE + (
            (req.key2 & ((cfg.pom_sets * cfg.pom_ways) - 1)) >> 2)
        hier, pc_cyc, _ = access_pte(
            st.hier, pom_line, req.pressure, cfg.tlb_aware, cfg.lat,
            probe, bt=BT_TLB4, geom=l2_geom_of(req.dyn),
            dramc=dramc_of(cfg, req.dyn),
        )
        st = st._replace(hier=hier)
        hp, wp, sp = lookup(st.pom, req.key2)
        pomhit = probe & hp
        st = st._replace(pom=touch_lru(st.pom, sp, wp, req.now, pomhit))
        return st, StageResult(hit=pomhit, cycles=pc_cyc, info={})

    def fill(self, cfg, st, req, out):
        walk_en = out["_walk"].info["walk_en"]
        miss2 = out["l2_tlb"].need
        ev_tag = out["l2_tlb"].info["ev_tag"]
        ev_valid = out["l2_tlb"].info["ev_valid"]
        if req.dyn is not None:
            walk_en = walk_en & req.dyn.pom_en
            ev_valid = ev_valid & req.dyn.pom_en
        pom2, _, _ = insert_lru(st.pom, req.key2, req.now, walk_en)
        pom2, _, _ = insert_lru(pom2, ev_tag, req.now, miss2 & ev_valid)
        return st._replace(pom=pom2)
