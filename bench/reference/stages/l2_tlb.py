"""Stage: unified L2 TLB (size-tagged keys, LRU).

The refill publishes the evicted entry into its ``info`` — POM-TLB learning and Victima's eviction-triggered
background walk consume it.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.assoc import insert_lru, lookup
from bench.reference.stages.base import Stage, StageResult


class L2TLBStage(Stage):
    name = "l2_tlb"
    past_l2 = False

    def lookup(self, cfg, st, req, need):
        ht, wt, stt = lookup(st.l2tlb, req.key2)
        lat = cfg.l2tlb_lat
        hit = need & ht
        l2tlb = st.l2tlb._replace(meta=st.l2tlb.meta.at[stt, wt].set(
            jnp.where(hit, req.now, st.l2tlb.meta[stt, wt])))
        st = st._replace(l2tlb=l2tlb)
        return st, StageResult(hit=hit, cycles=jnp.where(need, lat, 0),
                               info={})

    def fill(self, cfg, st, req, out):
        miss2 = out[self.name].need
        l2tlb2, ev_tag, ev_valid = insert_lru(
            st.l2tlb, req.key2, req.now, miss2)
        out[self.name].info["ev_tag"] = ev_tag
        out[self.name].info["ev_valid"] = ev_valid
        return st._replace(l2tlb=l2tlb2)
