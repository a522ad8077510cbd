"""Stage: optional hardware L3 TLB (probe latency swept in Fig. 8)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.assoc import insert_lru, lookup, touch_lru
from repro.core.stages.base import Stage, StageResult


class L3TLBStage(Stage):
    name = "l3_tlb"

    def lookup(self, cfg, st, req, need):
        lat = cfg.l3tlb_lat if req.dyn is None else req.dyn.l3tlb_lat
        # dyn gate: a ladder lane without a hardware L3 TLB neither pays
        # the probe latency nor touches the (never-filled) structure
        len_ = None if req.dyn is None else req.dyn.l3tlb_en
        probe = need if len_ is None else need & len_
        h3, w3, s3 = lookup(st.l3tlb, req.key2)
        l3hit = probe & h3
        st = st._replace(l3tlb=touch_lru(st.l3tlb, s3, w3, req.now, l3hit))
        # probe latency is paid by every access that reaches this level
        return st, StageResult(hit=l3hit, cycles=jnp.where(probe, lat, 0),
                               info={})

    def fill(self, cfg, st, req, out):
        walk_en = out["_walk"].info["walk_en"]
        if req.dyn is not None:
            walk_en = walk_en & req.dyn.l3tlb_en
        l3t, _, _ = insert_lru(st.l3tlb, req.key2, req.now, walk_en)
        return st._replace(l3tlb=l3t)
