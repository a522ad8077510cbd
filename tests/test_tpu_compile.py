"""Compiles of the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret-mode and CPU runs cannot show:
programs that do not fit the chip's memory, or that cannot be laid out
on its mesh.  These tests compile the simulator's ladder dispatch (the
``run_systems`` body of ``mmu.make_systems_runner`` under
``parallel.shard_jit``) for v5e at the native family's full Table-3
sizes, on one chip and on the 2x2 mesh of a four-chip host.

The pallas MMU kernel has no test here: Mosaic refuses it at every
ladder size (``mmu.PALLAS_ON_TPU``).

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers all import this file.
"""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import mmu
from repro.sim import parallel, runner, systems, trace_gen

HBM_BYTES = 16 * 10**9      # one v5e chip
LADDER = "radix"            # the 28-member native family
CHUNK = 2                   # workloads per dispatch
N = 20_000                  # accesses per workload


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


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)],
                         ids=["one_chip", "mesh_2x2"])
def test_native_ladder_dispatch_compiles_for_v5e(topo, mesh_shape):
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
    compiled = jitted.lower(dyns, traces).compile()

    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes)
    assert 0 < per_device < HBM_BYTES
    assert "tpu_custom_call" not in compiled.as_text()  # no Pallas kernel
