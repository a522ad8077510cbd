"""Stage: demand page-table walk (native radix / I-SP 1-D shadow walk).

The terminal stage: everything still unresolved walks.  Fill maintains
the PTW-CP per-page counters for non-Victima systems (Victima folds its
counter updates into its own fused fill).
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference import ptwcp
from bench.reference.page_table import walk
from bench.reference.stages.base import Stage, StageResult, dramc_of


def fill_walk_counters(cfg, st, req, out):
    """PTW-CP counter maintenance for the walked page (non-Victima)."""
    walk_en = out["_walk"].info["walk_en"]
    ndram = out["_walk"].info["ndram"]
    pc4 = ptwcp.update_counters(
        st.pc4, req.vpn & (cfg.n_pages4 - 1), ndram >= 1,
        walk_en & ~req.is2m)
    pc2 = ptwcp.update_counters(
        st.pc2, req.vpn2 & (cfg.n_pages2 - 1), ndram >= 1,
        walk_en & req.is2m)
    return st._replace(pc4=pc4, pc2=pc2)


class RadixWalkStage(Stage):
    name = "ptw"

    def lookup(self, cfg, st, req, need):
        hier, pwcs, wcyc, ndram = walk(
            st.hier, st.pwcs, req.vpn, req.is2m, req.now, req.pressure,
            cfg.tlb_aware, cfg.lat, need, dramc_of(cfg),
        )
        st = st._replace(hier=hier, pwcs=pwcs)
        info = {
            "walk_en": need, "ndram": ndram,
            "nhost": jnp.int32(0), "n_nt_hit": jnp.int32(0),
            "n_nv_hit": jnp.int32(0),
        }
        return st, StageResult(hit=need, cycles=wcyc, info=info)

    def fill(self, cfg, st, req, out):
        if cfg.victima:
            return st  # VictimaStage.fill owns the counter traffic
        return fill_walk_counters(cfg, st, req, out)
