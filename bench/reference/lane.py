"""One lane of the reference: a member's own static config and one
trace segment in, its Stats and extras out as numpy."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.caches import BT_DATA, Lat, access_data
from bench.reference.stages import (STAGES, Request, SimConfig,
                                    default_stages, dramc_of, fill_order,
                                    make_state)
from bench.reference.stages.fold import accum_stats


def sim_config(overrides: dict) -> SimConfig:
    """A member's SimConfig from its registry overrides (JSON form)."""
    ov = dict(overrides)
    if "lat" in ov:
        ov["lat"] = Lat(**ov["lat"])
    return SimConfig(**ov)


def config_dict(cfg) -> dict:
    """Every field of a SimConfig (the program's or this copy's), with
    the latency table as a plain dict, for comparing the two."""
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d["lat"] = dict(d["lat"]._asdict())
    return d


def make_step(cfg: SimConfig):
    """The per-access step of ``cfg``'s own composition, at its sizes.
    The carry is (MMU state, L2-TLB evictions so far, accesses that
    waited at the shared port)."""
    names = default_stages(cfg)
    stages = [STAGES[n] for n in names]
    fills = [STAGES[n] for n in fill_order(names)]
    pressure_thr = jnp.float32(cfg.pressure_mpki)
    bypass_thr = jnp.float32(cfg.bypass_l2mpki)
    dramc = dramc_of(cfg)

    def step(carry, acc):
        st, n_evict, n_port = carry
        vpn = acc["vpn"]
        is2m = acc["is2m"]
        ipa = acc["ipa"]
        now = st.now + 1
        st = st._replace(now=now)
        s0 = st.stats

        instrs = jnp.maximum(s0.n_access.astype(jnp.float32), 1.0) * ipa
        pressure = (s0.n_l2tlb_miss.astype(jnp.float32) * 1000.0
                    > pressure_thr * instrs)
        l2_bypass = (st.hier.n_l2_miss.astype(jnp.float32) * 1000.0
                     >= bypass_thr * instrs)
        vpn2 = vpn >> 9
        vpn_sz = jnp.where(is2m, vpn2, vpn)
        req = Request(
            vpn=vpn, is2m=is2m, line=acc["line"], ipa=ipa, vpn2=vpn2,
            vpn_sz=vpn_sz, key2=(vpn_sz << 1) | is2m.astype(jnp.int32),
            now=now, pressure=pressure, l2_bypass=l2_bypass,
        )
        out: dict = {}
        need = jnp.bool_(True)
        trans = jnp.int32(0)
        past_l2 = jnp.int32(0)
        for stg in stages:
            st, res = stg.lookup(cfg, st, req, need)
            need = need & ~res.hit
            out[stg.name] = res._replace(need=need)
            if stg.past_l2:
                past_l2 = past_l2 + res.cycles
            else:
                trans = trans + res.cycles
        walk_res = out["_walk"] = out[names[-1]]
        for stg in fills:
            st = stg.fill(cfg, st, req, out)
        if cfg.n_cores > 1:
            # the shared tier's port: an access past the L2 TLB waits
            # shared_port_cyc cycles per slot, the slot rotating with
            # (core + now) over the cores
            slot = (acc["core"] + now) % jnp.int32(cfg.n_cores)
            wait = jnp.where(out["l2_tlb"].need,
                             jnp.int32(cfg.shared_port_cyc) * slot, 0)
            past_l2 = past_l2 + wait
            n_port = n_port + (wait > 0).astype(jnp.int32)
        trans = trans + past_l2
        hier, dcyc = access_data(st.hier, req.line, now, pressure,
                                 cfg.tlb_aware, cfg.lat, dramc)
        st = st._replace(hier=hier)
        st = st._replace(stats=accum_stats(s0, st, out, walk_res,
                                           trans, past_l2, dcyc))
        n_evict = n_evict + out["l2_tlb"].info["ev_valid"].astype(jnp.int32)
        return (st, n_evict, n_port), ()

    return step


def _final_hists(l2):
    bucket = jnp.minimum(l2.reuse, 21)
    is_data = (l2.btype == BT_DATA) & l2.valid
    is_tlb = (l2.btype != BT_DATA) & l2.valid
    hd = l2.hist_reuse_data + jnp.zeros_like(l2.hist_reuse_data).at[
        bucket.reshape(-1)].add(is_data.reshape(-1).astype(jnp.int32))
    ht = l2.hist_reuse_tlb + jnp.zeros_like(l2.hist_reuse_tlb).at[
        bucket.reshape(-1)].add(is_tlb.reshape(-1).astype(jnp.int32))
    return hd, ht


def lane_fn(cfg: SimConfig, acc=jnp.float32):
    """``run(trace) -> outputs`` for one lane of ``cfg``, jitted once per
    cycle-sum dtype; ``trace`` leaves are [T]."""
    if cfg.collect:
        raise ValueError("the reference covers ladders without feature "
                         "collection")
    z = jnp.int32(0)

    @jax.jit
    def run(trace):
        (st, n_evict, n_port), _ = jax.lax.scan(
            make_step(cfg), (make_state(cfg, acc), z, z), trace)
        hd, ht = _final_hists(st.hier.l2)
        h = st.hier
        out = {"stats": st.stats, "l2_access": h.n_l2_access,
               "l2_miss": h.n_l2_miss, "hist_reuse_data": hd,
               "hist_reuse_tlb": ht, "l2tlb_evictions": n_evict,
               "lanes_port_delayed": (n_port > 0).astype(jnp.int32)}
        if shared_tier_extras(cfg):
            out.update(l3_access=h.n_l3_access, l3_trans=h.n_l3_trans,
                       dramc_access=h.n_dramc_access,
                       dramc_hit=h.n_dramc_hit)
        return out

    return run


def shared_tier_extras(cfg: SimConfig) -> bool:
    """Whether a lane's extras carry the shared tier's (L3, DRAM-cache)
    counters: on multicore, DRAM-cache or ``shared_tier_stats`` configs."""
    return (cfg.n_cores > 1 or cfg.dram_cache_sets > 0
            or cfg.shared_tier_stats)


EXTRAS = ("l2_access", "l2_miss", "hist_reuse_data", "hist_reuse_tlb",
          "l3_access", "l3_trans", "dramc_access", "dramc_hit")


def outputs(out) -> dict[str, np.ndarray]:
    """Flatten one lane's outputs to {name: numpy array}: the Stats
    fields and the extras the program returns for the lane."""
    flat = {f: np.asarray(v) for f, v in out["stats"]._asdict().items()}
    for k in EXTRAS:
        if k in out:
            flat[k] = np.asarray(out[k])
    return flat


# what the stages past the L2 TLB did in a lane: the compared counters
# that show them, the L2-TLB evictions that feed POM and Victima, and
# whether an access waited at the shared port (1 a lane)
ACTIVITY = ("l2tlb_evictions", "n_bg_ptw", "n_victima_hit", "n_l3tlb_hit",
            "n_pom_hit", "n_restseg_mig", "n_restseg_hit", "n_rev_enroll",
            "n_rev_hit", "n_ntlb_hit", "n_nvictima_hit", "dramc_access",
            "dramc_hit", "lanes_port_delayed")


def activity(out) -> dict[str, int]:
    """The ``ACTIVITY`` counts of one lane's outputs (0 where the lane
    has no such counter)."""
    st = out["stats"]._asdict()
    return {k: int(out[k] if k in out else st.get(k, 0)) for k in ACTIVITY}


__all__ = ["ACTIVITY", "activity", "config_dict", "lane_fn", "outputs",
           "sim_config"]
