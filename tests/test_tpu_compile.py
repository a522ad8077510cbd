"""Compiles of the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret-mode and CPU runs cannot show:
programs that do not fit the chip's memory, or that cannot be laid out
on its mesh.  These tests compile the simulator's ladder dispatch (the
``run_systems`` body of ``mmu.make_systems_runner`` under
``parallel.shard_jit``) for v5e at the native family's full Table-3
sizes, on one chip and on the 2x2 mesh of a four-chip host, and read
the one-chip program's access loop for whole-array copies of the cache
and TLB state.

The pallas MMU kernel has no test here: Mosaic refuses it at every
ladder size (``mmu.PALLAS_ON_TPU``).

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers all import this file.
"""
import re

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import assoc, mmu
from repro.core.stages import make_state
from repro.sim import parallel, runner, systems, trace_gen

HBM_BYTES = 16 * 10**9      # one v5e chip
LADDER = "radix"            # the 28-member native family
CHUNK = 2                   # workloads per dispatch
N = 20_000                  # accesses per workload
BIG_SETS = 2048             # set-associative arrays this large must not
#                             be copied inside the access loop


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache out
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield t
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


def _dispatch_body(cfg, plan):
    """The per-block function ``make_systems_runner`` hands to the mesh."""
    got = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(parallel, "shard_wrap", lambda fn, plan: got.append(fn))
        mmu.make_systems_runner(cfg, plan, backend="scan")
    return got[0]


def _compile(topo, mesh_shape):
    with pytest.MonkeyPatch.context() as mp:
        # this process runs on the CPU: trace the chip's access forms
        mp.setattr(assoc, "vector_rows", lambda: True)
        return _compile_for_chip(topo, mesh_shape)


def _compile_for_chip(topo, mesh_shape):
    members = systems.LADDERS[LADDER]
    cfg = systems.ladder_base_config(LADDER, members)
    plan = parallel.plan_mesh(len(members), CHUNK, force=mesh_shape)
    mesh = Mesh(np.asarray(topo.devices[:plan.n_devices]).reshape(
        plan.sys_dim, plan.wl_dim), (parallel.AXIS_SYS, parallel.AXIS_WL))

    dyn_sh = NamedSharding(mesh, P(parallel.AXIS_SYS))
    dyns = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((plan.pad_systems,) + x.shape[1:],
                                       x.dtype, sharding=dyn_sh),
        systems.ladder_dyn(members))
    tr_sh = NamedSharding(mesh, P(None, parallel.AXIS_WL))
    small = runner._stack_traces(
        [trace_gen.generate(w, n=8, seed=0) for w in ("rnd", "bc")], 8)
    traces = {k: jax.ShapeDtypeStruct((N, CHUNK) + v.shape[2:], v.dtype,
                                      sharding=tr_sh)
              for k, v in small.items()}

    jitted = parallel.shard_jit(_dispatch_body(cfg, plan), plan, mesh)
    return cfg, plan, jitted.lower(dyns, traces).compile()


@pytest.fixture(scope="module")
def ladder_compile(topo):
    """The native ladder dispatch compiled for v5e, once per mesh shape."""
    done = {}

    def get(mesh_shape):
        if mesh_shape not in done:
            done[mesh_shape] = _compile(topo, mesh_shape)
        return done[mesh_shape]

    return get


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)],
                         ids=["one_chip", "mesh_2x2"])
def test_native_ladder_dispatch_compiles_for_v5e(ladder_compile,
                                                 mesh_shape):
    _, _, compiled = ladder_compile(mesh_shape)
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes)
    assert 0 < per_device < HBM_BYTES
    assert "tpu_custom_call" not in compiled.as_text()  # no Pallas kernel


def _computations(hlo: str) -> dict:
    """Instruction lines of each computation in an HLO module's text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _loop_computations(hlo: str) -> set:
    """The bodies of the entry computation's ``while`` loops and every
    computation they call (fusions, nested loops, reductions)."""
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY %([\w.\-]+) ", hlo, re.M).group(1)
    todo = [b for line in comps[entry] if " while(" in line
            for b in re.findall(r"body=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(
                r"(?:body|condition|calls|to_apply)=%([\w.\-]+)", line)
            for group in re.findall(
                    r"(?:branch_computations|called_computations)="
                    r"\{([^}]*)\}", line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return seen


def _big_set_arrays(cfg) -> list:
    """(n_sets, n_ways) of every set-associative state structure with at
    least ``BIG_SETS`` sets."""
    state = jax.eval_shape(lambda: make_state(cfg))
    nodes = jax.tree_util.tree_leaves(
        state, is_leaf=lambda x: hasattr(x, "SET_FIELDS"))
    return sorted({(x.n_sets, x.n_ways) for x in nodes
                   if hasattr(x, "SET_FIELDS") and x.n_sets >= BIG_SETS})


def test_access_loop_copies_no_set_array(ladder_compile):
    """No ``copy`` in the access loop moves a set-associative array.

    Stored as ``[sets, ways]`` behind the lane axes, each such array was
    laid out sets-minor in the loop carry and ways-minor for the row
    gather, so the loop copied it whole before every row read.  Stored
    in set-major vector rows, a row is read in place.  A copy counts if
    its trailing dims are an array's stored, flat ``[sets*ways]`` or 2-D
    ``[sets, ways]`` form, or if it moves as many words as the smallest
    such array over every lane (a retiled form)."""
    cfg, plan, compiled = ladder_compile((1, 1))
    big = _big_set_arrays(cfg)
    assert big, "the native ladder allocates no large cache"
    forms = ({(s * w,) for s, w in big} | set(big)
             | {assoc.shape(s, w) for s, w in big})
    lanes = plan.pad_systems * CHUNK
    words = lanes * min(s * w for s, w in big)

    hlo = compiled.as_text()
    comps = _computations(hlo)
    bad = []
    for name in _loop_computations(hlo):
        for line in comps[name]:
            m = re.search(r"= \w+\[([\d,]*)\]\{[^}]*\} (copy|copy-done)\(",
                          line)
            if not m:
                continue
            dims = tuple(int(d) for d in m.group(1).split(",") if d)
            if (any(dims[-len(f):] == f for f in forms)
                    or np.prod(dims, dtype=np.int64) >= words):
                bad.append(line.strip()[:120])
    assert not bad, f"{len(bad)} whole-array copies in the loop: {bad[:4]}"


def test_access_loop_runs_no_per_lane_loop(ladder_compile):
    """The access loop holds no nested ``while``.

    A gather or scatter at a traced offset inside a vector row (the
    minor dimension), such as a slice of one set's ways out of a flat
    array, compiles to a loop over the lanes that moves one lane's slice
    per iteration.  Reads and writes of whole vector rows do not."""
    _, _, compiled = ladder_compile((1, 1))
    hlo = compiled.as_text()
    comps = _computations(hlo)
    nested = [line.strip()[:120] for name in _loop_computations(hlo)
              for line in comps[name] if " while(" in line]
    assert not nested, f"{len(nested)} loops in the loop: {nested[:4]}"
