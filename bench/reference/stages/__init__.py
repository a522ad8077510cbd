"""Composable translation-pipeline stages.

``STAGES`` maps stage names to singleton stage objects; a *composition*
is an ordered tuple of names ending in a walker stage ("ptw" or
"ptw2d").  ``default_stages(cfg)`` derives the canonical composition
from a SimConfig; the system registry (repro.sim.systems) declares each
evaluated system's composition explicitly and is validated against it.
"""
from __future__ import annotations

from bench.reference.stages.base import (Feats, MMUState, Request,
                                         SimConfig, Stage, StageResult,
                                         Stats, WALK_HIST_BUCKETS, dramc_of,
                                         make_state, zero_feats, zero_stats)
from bench.reference.stages.l1_tlb import L1TLBStage
from bench.reference.stages.l2_tlb import L2TLBStage
from bench.reference.stages.l3_tlb import L3TLBStage
from bench.reference.stages.nested import NestedWalkStage
from bench.reference.stages.pom import POMStage
from bench.reference.stages.ptw import RadixWalkStage
from bench.reference.stages.revelator import RevelatorStage
from bench.reference.stages.utopia import RestSegStage
from bench.reference.stages.victima import VictimaStage

STAGES: dict[str, Stage] = {
    s.name: s for s in (
        L1TLBStage(), L2TLBStage(), RevelatorStage(), VictimaStage(),
        L3TLBStage(), POMStage(), RestSegStage(), RadixWalkStage(),
        NestedWalkStage(),
    )
}

WALK_STAGES = ("ptw", "ptw2d")


def default_stages(cfg: SimConfig) -> tuple[str, ...]:
    """Canonical stage composition implied by a SimConfig."""
    names = ["l1_tlb", "l2_tlb"]
    if cfg.revelator:
        names.append("rev")  # speculate right at the L2-TLB miss: a
        #   correct prediction hides every later level AND the walk
    if cfg.victima:
        names.append("victima")
    if cfg.l3tlb_sets > 0:
        names.append("l3_tlb")
    if cfg.pom:
        names.append("pom")
    if cfg.utopia:
        names.append("restseg")  # last resort before the FlexSeg walk
    names.append("ptw2d" if cfg.virt and not cfg.ideal_shadow else "ptw")
    return tuple(names)


def validate_stages(cfg: SimConfig, names: tuple[str, ...]) -> None:
    """A composition must agree with the config flags the stages read."""
    expect = default_stages(cfg)
    if tuple(names) != expect:
        raise ValueError(
            f"stage composition {names} inconsistent with config "
            f"(expected {expect}: the rev/victima/l3/pom/utopia/virt "
            f"flags and the stage list must agree)")


def fill_order(names: tuple[str, ...]) -> tuple[str, ...]:
    """Refill/learning pass order for a composition.

    Victima systems: the L2 TLB refill's evicted entry feeds Victima's
    background walk, so it must land first.  Non-Victima systems update
    the walker's PTW-CP counters then refill the L2 TLB.  Utopia's
    migration engine reads the post-walk PTW-CP counters, so it runs
    right after whichever of those owns the counter traffic.  POM /
    L3-TLB learning and the L1 refill close out every composition.
    """
    walker = names[-1]
    order = ["l2_tlb", "victima"] if "victima" in names \
        else [walker, "l2_tlb"]
    if "restseg" in names:
        order.append("restseg")
    if "rev" in names:
        order.append("rev")  # enrollment reads post-walk counters too
    order += [n for n in ("pom", "l3_tlb") if n in names]
    order.append("l1_tlb")
    return tuple(order)


__all__ = [
    "Feats", "MMUState", "Request", "STAGES", "SimConfig", "Stage",
    "StageResult", "Stats", "WALK_HIST_BUCKETS", "WALK_STAGES",
    "default_stages", "dramc_of", "fill_order", "make_state",
    "validate_stages", "zero_feats", "zero_stats",
]
