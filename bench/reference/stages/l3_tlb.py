"""Stage: optional hardware L3 TLB (probe latency swept in Fig. 8)."""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.assoc import insert_lru, lookup
from bench.reference.stages.base import Stage, StageResult


class L3TLBStage(Stage):
    name = "l3_tlb"

    def lookup(self, cfg, st, req, need):
        h3, w3, s3 = lookup(st.l3tlb, req.key2)
        l3hit = need & h3
        l3tlb = st.l3tlb._replace(meta=st.l3tlb.meta.at[s3, w3].set(
            jnp.where(l3hit, req.now, st.l3tlb.meta[s3, w3])))
        st = st._replace(l3tlb=l3tlb)
        # probe latency is paid by every access that reaches this level
        return st, StageResult(hit=l3hit,
                               cycles=jnp.where(need, cfg.l3tlb_lat, 0),
                               info={})

    def fill(self, cfg, st, req, out):
        walk_en = out["_walk"].info["walk_en"]
        l3t, _, _ = insert_lru(st.l3tlb, req.key2, req.now, walk_en)
        return st._replace(l3tlb=l3t)
