"""Stage: split L1 D-TLBs (64-entry 4K + 32-entry 2M, LRU)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.assoc import insert_lru, lookup, touch_lru
from repro.core.stages.base import Stage, StageResult


class L1TLBStage(Stage):
    name = "l1_tlb"
    past_l2 = False

    def lookup(self, cfg, st, req, need):
        h4, w4, s4 = lookup(st.l1d4, req.vpn)
        h2, w2, s2 = lookup(st.l1d2, req.vpn2)
        hit1 = jnp.where(req.is2m, h2, h4)
        l1d4 = touch_lru(st.l1d4, s4, w4, req.now, h4 & ~req.is2m)
        l1d2 = touch_lru(st.l1d2, s2, w2, req.now, h2 & req.is2m)
        st = st._replace(l1d4=l1d4, l1d2=l1d2)
        return st, StageResult(hit=hit1, cycles=jnp.int32(cfg.l1tlb_lat),
                               info={})

    def fill(self, cfg, st, req, out):
        miss1 = out[self.name].need
        l1d4b, _, _ = insert_lru(st.l1d4, req.vpn, req.now,
                                 miss1 & ~req.is2m)
        l1d2b, _, _ = insert_lru(st.l1d2, req.vpn2, req.now,
                                 miss1 & req.is2m)
        return st._replace(l1d4=l1d4b, l1d2=l1d2b)
