"""Stage: unified L2 TLB (size-tagged keys, LRU).

Supports ladder-batched sizing: when the request carries ``Dyn`` scalars
the probe/refill run against a dynamically sized view of the allocated
structure (see assoc.lookup_dyn), so one compiled step serves the whole
L2-TLB size ladder under vmap.  The refill publishes the evicted entry
into its ``info`` — POM-TLB learning and Victima's eviction-triggered
background walk consume it.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.assoc import (insert_lru, insert_lru_dyn, lookup,
                              lookup_dyn, touch_lru)
from repro.core.stages.base import Stage, StageResult


class L2TLBStage(Stage):
    name = "l2_tlb"
    past_l2 = False

    def lookup(self, cfg, st, req, need):
        if req.dyn is None:
            ht, wt, stt = lookup(st.l2tlb, req.key2)
            lat = cfg.l2tlb_lat
        else:
            ht, wt, stt = lookup_dyn(st.l2tlb, req.key2,
                                     req.dyn.l2tlb_set_mask,
                                     req.dyn.l2tlb_ways)
            lat = req.dyn.l2tlb_lat
        hit = need & ht
        st = st._replace(l2tlb=touch_lru(st.l2tlb, stt, wt, req.now, hit))
        return st, StageResult(hit=hit, cycles=jnp.where(need, lat, 0),
                               info={})

    def fill(self, cfg, st, req, out):
        miss2 = out[self.name].need
        if req.dyn is None:
            l2tlb2, ev_tag, ev_valid = insert_lru(
                st.l2tlb, req.key2, req.now, miss2)
        else:
            l2tlb2, ev_tag, ev_valid = insert_lru_dyn(
                st.l2tlb, req.key2, req.now, req.dyn.l2tlb_set_mask,
                req.dyn.l2tlb_ways, miss2)
        out[self.name].info["ev_tag"] = ev_tag
        out[self.name].info["ev_valid"] = ev_valid
        return st._replace(l2tlb=l2tlb2)
