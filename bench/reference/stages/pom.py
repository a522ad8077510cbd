"""Stage: POM-TLB — software-managed L3 TLB resident in memory.

Entries are fetched through the cache hierarchy (typed as TLB blocks so
the TLB-aware SRRIP prioritizes them, per Table 3); hit/miss bookkeeping
is tracked by a shadow associative structure.  Fill learns both the
demand-walked entry and the L2 TLB's evicted entry.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.assoc import insert_lru, lookup
from bench.reference.caches import BT_TLB4, access_pte
from bench.reference.page_table import POM_BASE
from bench.reference.stages.base import Stage, StageResult, dramc_of


class POMStage(Stage):
    name = "pom"

    def lookup(self, cfg, st, req, need):
        pom_line = POM_BASE + (
            (req.key2 & ((cfg.pom_sets * cfg.pom_ways) - 1)) >> 2)
        hier, pc_cyc, _ = access_pte(
            st.hier, pom_line, req.pressure, cfg.tlb_aware, cfg.lat,
            need, bt=BT_TLB4, dramc=dramc_of(cfg),
        )
        st = st._replace(hier=hier)
        hp, wp, sp = lookup(st.pom, req.key2)
        pomhit = need & hp
        pom = st.pom._replace(meta=st.pom.meta.at[sp, wp].set(
            jnp.where(pomhit, req.now, st.pom.meta[sp, wp])))
        st = st._replace(pom=pom)
        return st, StageResult(hit=pomhit, cycles=pc_cyc, info={})

    def fill(self, cfg, st, req, out):
        walk_en = out["_walk"].info["walk_en"]
        miss2 = out["l2_tlb"].need
        ev_tag = out["l2_tlb"].info["ev_tag"]
        ev_valid = out["l2_tlb"].info["ev_valid"]
        pom2, _, _ = insert_lru(st.pom, req.key2, req.now, walk_en)
        pom2, _, _ = insert_lru(pom2, ev_tag, req.now, miss2 & ev_valid)
        return st._replace(pom=pom2)
