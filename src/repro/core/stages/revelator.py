"""Stage: Revelator — hash-based speculative address translation
(PAPERS.md, arXiv 2508.02007).

Revelator attacks PTW latency from the opposite side of Victima/Utopia:
instead of enlarging translation reach, it *predicts* the translation.
System software enrolls pages into a hash-based speculative mapping; on
an L2-TLB miss the core hashes the VPN, probes a small signature table
at low fixed latency (``rev_lat``), and — on a signature hit — fetches
data with the predicted frame immediately while the regular page-table
walk *verifies* the prediction off the critical path.  A correct
prediction hides the entire walk (the access pays only the probe); a
misprediction is discovered when the verification walk completes, so
the access effectively waits the overlapped walk cost after all.

Model mapping onto the pipeline contract (the RestSeg probe-then-
fallback shape is the template, but with verify-later accounting):

  lookup — hash ``key2`` to a *lossy* signature, probe the table.  A
      signature hit resolves the translation (both correct predictions
      AND mispredictions: the verification walk itself produces the
      right translation), so downstream stages and the demand walker
      are skipped.  The verification walk runs here with
      ``enable=sig_hit`` — real cache/PT traffic, cycles accounted in
      ``Stats.sum_rev_verify_cyc`` — but only a mispredict puts those
      cycles on the critical path.  Aliasing between pages whose hashes
      share the low ``rev_sig_bits`` is the deterministic stand-in for
      the paper's frame-allocation conflicts; verification repairs the
      aliased entry in place.  Signature misses fall through to the
      composition's existing walkers untouched.

  fill — enrollment is PTW-CP-guided exactly like Utopia's migration
      engine: after a demand walk (which implies the page was NOT in
      the live table), the freshly trained counters decide whether the
      page is costly enough to enroll.

Dyn gating: ``Dyn.rev_en`` masks the probe, the verification walk and
every table write, so a non-Revelator lane of a batched ladder is
bit-identical to the composition without this stage.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.assoc import (get, install, lru_victim, put, row,
                              set_index, touch_lru)
from repro.core.page_table import walk
from repro.core.stages.base import (RevTable, Stage, StageResult,
                                    dramc_of, l2_geom_of,
                                    ptwcp_walk_verdict)
from repro.core.stages.nested import guest_walk_2d


def rev_sig(key2, bits: int):
    """Lossy multiplicative-hash signature of a size-tagged page id."""
    return (key2 * jnp.int32(-1640531535)) & jnp.int32((1 << bits) - 1)


def _rev_insert(rev: RevTable, sig, key2, now, enable) -> RevTable:
    """``insert_lru`` plus the shadow enrolled-page write (same way)."""
    tab = rev.tab
    s = set_index(sig, tab.n_sets)
    w = lru_victim(tab, s)
    new_tab, _, _ = install(tab, s, w, sig, now, enable)
    n = tab.n_ways
    return RevTable(tab=new_tab, vpn=put(
        rev.vpn, s, w, n, jnp.where(enable, key2, get(rev.vpn, s, w, n))))


class RevelatorStage(Stage):
    name = "rev"

    def lookup(self, cfg, st, req, need):
        ren = None if req.dyn is None else req.dyn.rev_en
        probe = need if ren is None else need & ren
        geom = l2_geom_of(req.dyn)

        sig = rev_sig(req.key2, cfg.rev_sig_bits)
        tab, n = st.rev.tab, st.rev.tab.n_ways
        s = set_index(sig, tab.n_sets)
        row_hits = row(tab.valid, s, n) & (row(tab.tags, s, n) == sig)
        w = jnp.argmax(row_hits)
        sig_hit = probe & jnp.any(row_hits)
        # a lossy-signature hit whose enrolled page differs is the
        # misprediction: the speculative frame belonged to the alias
        enrolled = get(st.rev.vpn, s, w, n)
        correct = sig_hit & (enrolled == req.key2)
        mispred = sig_hit & ~correct

        # LRU touch + in-place repair (verification rewrites the aliased
        # entry with the walked translation; no-op on correct hits)
        rev = RevTable(
            tab=touch_lru(tab, s, w, req.now, sig_hit),
            vpn=put(st.rev.vpn, s, w, n,
                    jnp.where(sig_hit, req.key2, enrolled)))
        st = st._replace(rev=rev)

        # verification walk — real PT/cache traffic, off the critical
        # path unless the prediction was wrong
        if cfg.virt and not cfg.ideal_shadow:
            ven = None if req.dyn is None else req.dyn.victima_en
            st, vcyc, _, _, _, _ = guest_walk_2d(
                cfg, st, req.vpn, req.is2m, req.pressure, req.l2_bypass,
                sig_hit, geom, ven, dramc_of(cfg, req.dyn))
        else:
            hier, pwcs, vcyc, _ = walk(
                st.hier, st.pwcs, req.vpn, req.is2m, req.now,
                req.pressure, cfg.tlb_aware, cfg.lat, sig_hit, geom,
                dramc_of(cfg, req.dyn))
            st = st._replace(hier=hier, pwcs=pwcs)
        vcyc = jnp.where(sig_hit, vcyc, 0)

        cycles = jnp.where(sig_hit,
                           cfg.rev_lat + jnp.where(mispred, vcyc, 0), 0)
        return st, StageResult(hit=sig_hit, cycles=cycles,
                               info={"probed": probe, "correct": correct,
                                     "mispred": mispred,
                                     "verify_cyc": vcyc})

    def fill(self, cfg, st, req, out):
        """PTW-CP-guided enrollment: after a demand walk, the freshly
        trained counters (this fill runs after the walker's / Victima's
        counter updates — see stages.fill_order) decide whether the
        walked page is costly enough to enroll in the signature table."""
        ren = None if req.dyn is None else req.dyn.rev_en
        enroll = ptwcp_walk_verdict(cfg, st, req,
                                    out["_walk"].info["walk_en"])
        if ren is not None:
            enroll = enroll & ren

        sig = rev_sig(req.key2, cfg.rev_sig_bits)
        st = st._replace(rev=_rev_insert(st.rev, sig, req.key2, req.now,
                                         enroll))
        out[self.name].info["n_enroll"] = enroll.astype(jnp.int32)
        return st
