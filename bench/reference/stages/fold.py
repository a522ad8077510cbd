"""Per-step folds over the pipeline's stage results: Stats accumulation
and the Table-2 per-page feature stream."""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.stages.base import (Feats, MMUState, Stats,
                                    WALK_HIST_BUCKETS, hash_h)


def _hit32(out, name):
    return out[name].hit.astype(jnp.int32) if name in out else jnp.int32(0)


def accum_stats(s0: Stats, st: MMUState, out, walk_res, trans, past_l2,
                dcyc) -> Stats:
    acc = s0.sum_trans_cyc.dtype  # cycle-sum precision, set by zero_stats
    miss2 = out["l2_tlb"].need
    walk_en = walk_res.info["walk_en"]
    wcyc = walk_res.cycles
    n_bg = out["victima"].info["n_bg"] if "victima" in out else jnp.int32(0)
    bucket = jnp.minimum(wcyc // 10, WALK_HIST_BUCKETS - 1)
    l2 = st.hier.l2
    if "restseg" in out:
        rs = out["restseg"]
        rs_probed = rs.info["probed"]
        rs_hit = rs.hit
        rs_cyc = rs.cycles
        rs_mig = rs.info["n_mig"]
        rs_conf = rs.info["n_conflict"]
    else:
        rs_probed = rs_hit = jnp.bool_(False)
        rs_mig = rs_conf = rs_cyc = jnp.int32(0)
    rs_bucket = jnp.minimum(rs_cyc // 10, WALK_HIST_BUCKETS - 1)
    if "rev" in out:
        rv = out["rev"]
        rv_hit = rv.hit
        rv_correct = rv.info["correct"]
        rv_mispred = rv.info["mispred"]
        rv_enroll = rv.info["n_enroll"]
        rv_vcyc = rv.info["verify_cyc"]
    else:
        rv_hit = rv_correct = rv_mispred = jnp.bool_(False)
        rv_enroll = rv_vcyc = jnp.int32(0)
    rv_bucket = jnp.minimum(rv_vcyc // 10, WALK_HIST_BUCKETS - 1)
    return Stats(
        n_access=s0.n_access + 1,
        n_l1tlb_hit=s0.n_l1tlb_hit + _hit32(out, "l1_tlb"),
        n_l2tlb_hit=s0.n_l2tlb_hit + _hit32(out, "l2_tlb"),
        n_l2tlb_miss=s0.n_l2tlb_miss + miss2.astype(jnp.int32),
        n_victima_hit=s0.n_victima_hit + _hit32(out, "victima"),
        n_l3tlb_hit=s0.n_l3tlb_hit + _hit32(out, "l3_tlb"),
        n_pom_hit=s0.n_pom_hit + _hit32(out, "pom"),
        n_demand_ptw=s0.n_demand_ptw + walk_en.astype(jnp.int32),
        n_bg_ptw=s0.n_bg_ptw + n_bg,
        n_host_ptw=s0.n_host_ptw + walk_res.info["nhost"],
        n_ntlb_hit=s0.n_ntlb_hit + walk_res.info["n_nt_hit"],
        n_nvictima_hit=s0.n_nvictima_hit + walk_res.info["n_nv_hit"],
        sum_trans_cyc=s0.sum_trans_cyc + trans.astype(acc),
        sum_l2miss_cyc=s0.sum_l2miss_cyc
        + jnp.where(miss2, past_l2, 0).astype(acc),
        sum_data_cyc=s0.sum_data_cyc + dcyc.astype(acc),
        sum_walk_cyc=s0.sum_walk_cyc
        + jnp.where(walk_en, wcyc, 0).astype(acc),
        hist_walk=s0.hist_walk.at[bucket].add(walk_en.astype(jnp.int32)),
        sum_tlb4_live=s0.sum_tlb4_live + l2.n_tlb4.astype(acc),
        sum_tlb2_live=s0.sum_tlb2_live + l2.n_tlb2.astype(acc),
        n_restseg_hit=s0.n_restseg_hit + rs_hit.astype(jnp.int32),
        n_restseg_miss=s0.n_restseg_miss
        + (rs_probed & ~rs_hit).astype(jnp.int32),
        n_restseg_mig=s0.n_restseg_mig + rs_mig,
        n_restseg_conflict=s0.n_restseg_conflict + rs_conf,
        sum_restseg_cyc=s0.sum_restseg_cyc + rs_cyc.astype(acc),
        hist_restseg=s0.hist_restseg.at[rs_bucket].add(
            rs_probed.astype(jnp.int32)),
        n_rev_hit=s0.n_rev_hit + rv_correct.astype(jnp.int32),
        n_rev_mispred=s0.n_rev_mispred + rv_mispred.astype(jnp.int32),
        n_rev_enroll=s0.n_rev_enroll + rv_enroll,
        sum_rev_verify_cyc=s0.sum_rev_verify_cyc
        + rv_vcyc.astype(acc),
        hist_rev_verify=s0.hist_rev_verify.at[rv_bucket].add(
            rv_hit.astype(jnp.int32)),
    )


def collect_feats(cfg, st: MMUState, req, out, walk_res) -> MMUState:
    """Table-2 per-page feature stream (hashed table)."""
    miss1 = out["l1_tlb"].need
    miss2 = out["l2_tlb"].need
    walk_en = walk_res.info["walk_en"]
    wcyc = walk_res.cycles
    fi = hash_h(req.vpn_sz, cfg.n_feat)
    ft = st.feats
    u1 = jnp.uint16(1)
    return st._replace(feats=Feats(
        n_access=ft.n_access.at[fi].add(u1),
        n_l1_miss=ft.n_l1_miss.at[fi].add(
            jnp.where(miss1, u1, 0).astype(jnp.uint16)),
        n_l2_miss=ft.n_l2_miss.at[fi].add(
            jnp.where(miss2, u1, 0).astype(jnp.uint16)),
        n_walk=ft.n_walk.at[fi].add(
            jnp.where(walk_en, u1, 0).astype(jnp.uint16)),
        walk_cyc=ft.walk_cyc.at[fi].add(
            jnp.where(walk_en, wcyc, 0).astype(jnp.float32)),
        is2m=ft.is2m.at[fi].set(req.is2m.astype(jnp.uint8)),
    ))
