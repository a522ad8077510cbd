"""The chip's access form, end to end, on the CPU.

``repro.core.assoc`` reads and writes its set-major arrays a whole
vector row at a time on an accelerator, and by direct flat offsets on
the CPU (``assoc.vector_rows``), where the vector-row form lowers,
compiles and runs two to three times as slowly.  Every other test of
the simulator therefore runs the flat form.  These run the vector-row
form through whole simulations: the golden Stats, and every member of
the native and the virtualized ladder against the flat form, with the
stage paths that cold benchmark segments never reach (POM, L3-TLB,
RestSeg and Revelator hits, Victima's background walk, the nested TLB)
shown to run.
"""
import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from golden_trace import (GOLDEN_CFG, GOLDEN_SYSTEMS, golden_trace,
                          stats_to_jsonable)
from repro.core import assoc, mmu
from repro.sim import systems
from test_systems_registry import GOLDEN_PATH, _stack_dyns, _tiny_config

# counters of the paths each family must reach for the check to mean
# anything (a cold 128-access segment reaches none of the first five)
REACHED = {
    "radix": ("n_pom_hit", "n_l3tlb_hit", "n_restseg_hit", "n_rev_hit",
              "n_bg_ptw", "n_victima_hit"),
    "np": ("n_pom_hit", "n_restseg_hit", "n_rev_hit", "n_ntlb_hit",
           "n_nvictima_hit", "n_host_ptw"),
}


def _vector_rows(monkeypatch):
    monkeypatch.setattr(assoc, "vector_rows", lambda: True)


def test_golden_snapshot_in_vector_rows(monkeypatch):
    """The golden Stats, bit for bit, with every access a vector row."""
    _vector_rows(monkeypatch)
    with open(GOLDEN_PATH) as f:
        snap = json.load(f)
    tr = {k: jnp.asarray(v) for k, v in golden_trace().items()}
    for name, overrides in GOLDEN_SYSTEMS.items():
        stats, _ = mmu.simulate(dataclasses.replace(GOLDEN_CFG, **overrides),
                                tr)
        got = stats_to_jsonable(stats)
        for field, want in snap[name].items():
            assert got[field] == want, (name, field, got[field], want)


def _same(a, b, ctx):
    assert np.array_equal(np.asarray(a), np.asarray(b)), ctx


@pytest.mark.parametrize("anchor", sorted(REACHED))
def test_ladder_in_vector_rows_matches_flat(anchor, monkeypatch):
    """Every member of the native (28-system) or virtualized (5-system)
    family, tiny-shrunk, on two workload lanes of 2,048 accesses: one
    ladder dispatch in the vector-row form equals the flat form's (which
    the registry tests hold to per-system static runs) in every Stats
    field and extra, bit for bit."""
    members = next(m for m in systems.LADDERS.values() if anchor in m)
    cfgs = [_tiny_config(s) for s in members]
    base, dyns = systems.dyn_base_config(cfgs), _stack_dyns(cfgs)
    tr = {k: jnp.asarray(v) for k, v in golden_trace(n=2048).items()}
    traces = {k: jnp.stack([v, v[::-1]], axis=1) for k, v in tr.items()}
    per_f, ex_f = mmu.simulate_systems(base, dyns, traces)
    _vector_rows(monkeypatch)
    per_v, ex_v = mmu.simulate_systems(base, dyns, traces)
    for si, name in enumerate(members):
        for wi in range(2):
            for field, a, b in zip(per_f[si][wi]._fields, per_f[si][wi],
                                   per_v[si][wi]):
                _same(a, b, (name, wi, field))
            assert ex_f[si][wi].keys() == ex_v[si][wi].keys()
            for key in ex_f[si][wi]:
                _same(ex_f[si][wi][key], ex_v[si][wi][key], (name, wi, key))
    for field in REACHED[anchor]:
        assert any(int(getattr(per_f[si][wi], field)) > 0
                   for si in range(len(members)) for wi in range(2)), field
