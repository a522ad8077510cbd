"""The access scan's stage scopes, obs spans as profiler annotations, and
the phase spans of a ladder dispatch.

- every stage of a composition names its ops (``<stage>.lookup`` /
  ``.fill``), beside ``data``, ``stats``, ``init`` and ``finalize``, in
  the HLO ``op_name`` metadata of the lowered ``run_systems``;
- a scope counts its trace seconds in ``sim.trace_s.<scope>``;
- under a running profiler an obs span is a host event of its name in
  the ``.xplane.pb``, with its JSONL record unchanged;
- ``make_systems_runner``'s ``run`` emits ``sim.launch``, ``sim.fetch``
  and ``sim.unpack`` once per call, and counts the unpacking's device
  reads.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from golden_trace import GOLDEN_CFG, golden_trace

import repro.obs as obs
from repro.core import mmu
from repro.core.stages import STAGES, default_stages, dyn_of, fill_order
from repro.obs import names
from repro.sim import parallel

# every native stage at test sizes (radix walk), and the nested walk
NATIVE = dataclasses.replace(
    GOLDEN_CFG, victima=True, revelator=True, rev_sets=16, rev_ways=4,
    rev_sig_bits=10, l3tlb_sets=16, l3tlb_ways=4, pom=True, pom_sets=16,
    pom_ways=4, utopia=True, restseg4_sets=16, restseg2_sets=8,
    restseg_ways=4)
VIRT = dataclasses.replace(GOLDEN_CFG, virt=True)
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture
def tr(tmp_path):
    """A fresh process-global tracer on a temp file (restored after)."""
    t = obs.configure(str(tmp_path / "trace.jsonl"))
    yield t
    obs.configure()


def _stacked_dyns(cfg, s: int):
    return jax.tree.map(lambda *x: jnp.stack(x), *[dyn_of(cfg)] * s)


def _traces(n: int, w: int) -> dict:
    return {k: jnp.asarray(np.stack([v] * w, axis=1))
            for k, v in golden_trace(n=n).items()}


def _scope_segments(op_names) -> set[str]:
    segs = {s for op in op_names for s in re.split(r"[/;]", op)}
    return {re.sub(r"^vmap\((.*)\)$", r"\1", s) for s in segs}


# ------------------------------------------------------------- scopes

def test_every_stage_has_declared_scopes():
    assert set(names.STAGES) == set(STAGES)
    for s in STAGES:
        assert {f"{s}.lookup", f"{s}.fill"} <= set(names.SCOPE_NAMES)
    assert len(set(names.CTR_TRACE_S)) == len(names.SCOPE_NAMES)


def test_scope_names_its_ops_and_counts_trace_seconds():
    key = names.TRACE_S_PREFIX + names.SCOPE_DATA
    before = obs.REGISTRY.counter(key)

    def f(x):
        with obs.scope(names.SCOPE_DATA):
            return x * 2 + 1

    fn = jax.jit(jax.vmap(f))
    hlo = fn.lower(jnp.ones((4, 3))).as_text(dialect="hlo",
                                             debug_info=True)
    assert "data" in _scope_segments(OP_NAME.findall(hlo))
    traced = obs.REGISTRY.counter(key)
    assert traced > before
    fn(jnp.ones((4, 3))).block_until_ready()
    fn(jnp.ones((4, 3))).block_until_ready()  # compiled: no trace
    assert obs.REGISTRY.counter(key) == traced
    with pytest.raises(ValueError, match="not declared"):
        with obs.scope("l9_tlb.lookup"):
            pass


def _run_systems_op_names(cfg, monkeypatch) -> set[str]:
    """op_names of the lowered ``run_systems`` of a 2-system ladder."""
    got = {}
    wrap = parallel.shard_wrap

    def grab(fn, plan):
        got["run_systems"] = fn
        return wrap(fn, plan)

    monkeypatch.setattr(parallel, "shard_wrap", grab)
    mmu.make_systems_runner(cfg, parallel.plan_mesh(2, 1, n_devices=1))
    low = jax.jit(got["run_systems"]).lower(_stacked_dyns(cfg, 2),
                                            _traces(8, 1))
    return set(OP_NAME.findall(low.as_text(dialect="hlo",
                                           debug_info=True)))


@pytest.mark.parametrize("cfg,walk,other", [(NATIVE, "ptw", "ptw2d"),
                                            (VIRT, "ptw2d", "ptw")],
                         ids=["native", "virt"])
def test_run_systems_op_names_hold_each_scope(cfg, walk, other,
                                              monkeypatch):
    stages = default_stages(cfg)
    assert stages[-1] == walk
    segs = _scope_segments(_run_systems_op_names(cfg, monkeypatch))
    want = ({f"{s}.lookup" for s in stages}
            | {"l1_tlb.fill", "l2_tlb.fill", names.SCOPE_DATA,
               names.SCOPE_STATS, names.SCOPE_INIT, names.SCOPE_FINALIZE})
    assert want <= segs
    fills = {f"{s}.fill" for s in fill_order(stages)}
    assert segs & set(names.SCOPE_NAMES) <= want | fills
    assert f"{other}.lookup" not in segs


# ---------------------------------------------- spans on the profiler

def test_span_is_a_profiler_annotation_while_one_records(tr, tmp_path):
    with obs.span("obs.fixture_span", k=1) as sp:
        assert sp._note is None  # no profiler: no annotation
    plain = tr.events[-1]
    logdir = str(tmp_path / "profile")
    with jax.profiler.trace(logdir):
        with obs.span("obs.fixture_span", k=1):
            jnp.ones(3).block_until_ready()
    rec = tr.events[-1]
    assert set(rec) == set(plain)
    assert (rec["kind"], rec["name"], rec["attrs"]) == (
        "span", "obs.fixture_span", {"k": 1})
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    hits = [e for p in ProfileData.from_file(path).planes
            for ln in p.lines for e in ln.events
            if e.name == "obs.fixture_span"]
    assert len(hits) == 1
    assert hits[0].duration_ns / 1e9 <= rec["dur_s"]


def test_obs_imports_and_spans_without_jax():
    code = ("import sys, repro.obs as obs\n"
            "with obs.span('x'):\n    pass\n"
            "assert 'jax' not in sys.modules\n")
    env = dict(os.environ, REPRO_OBS_TRACE=os.devnull)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)


# ------------------------------------------------- dispatch phases

def test_systems_runner_spans_its_phases_and_counts_unpack_reads(
        tr, monkeypatch):
    S, W = 2, 3
    run = mmu.make_systems_runner(GOLDEN_CFG,
                                  parallel.plan_mesh(S, W, n_devices=1))
    dyns, traces = _stacked_dyns(GOLDEN_CFG, S), _traces(16, W)
    run(dyns, traces)  # compiles

    seen = [0]  # device-array reads made inside the sim.unpack span
    device_get = jax.device_get

    def counting(x):
        cur = obs.current()
        if (cur is not None and cur.name == names.SPAN_SIM_UNPACK
                and isinstance(x, jax.Array)):
            seen[0] += 1
        return device_get(x)

    monkeypatch.setattr(jax, "device_get", counting)
    before = obs.REGISTRY.counter(names.CTR_UNPACK_READS)
    n0 = len(tr.events)
    for _ in range(2):
        per, extras = run(dyns, traces)
    spans = [e for e in tr.events[n0:] if e["kind"] == "span"]
    phases = [names.SPAN_SIM_LAUNCH, names.SPAN_SIM_FETCH,
              names.SPAN_SIM_UNPACK]
    assert [e["name"] for e in spans] == phases * 2
    reads = [e["attrs"]["reads"] for e in spans
             if e["name"] == names.SPAN_SIM_UNPACK]
    # l2_access, l2_miss and the two reuse histograms of every lane
    assert reads == [S * W * 4] * 2
    assert sum(reads) == seen[0]
    assert obs.REGISTRY.counter(names.CTR_UNPACK_READS) - before == seen[0]
    assert isinstance(extras[1][2]["l2_access"], int)
    assert len(per) == S and len(per[0]) == W
