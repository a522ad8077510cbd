"""Device-mesh planning + shard_map dispatch for batched ladder sweeps.

A batched ladder run is an [S]-system x [W]-workload grid of mutually
independent scans (``mmu.simulate_systems``).  This module spreads that
grid over a 2-D ``("sys", "wl")`` device mesh:

- ``plan_mesh`` factorizes the visible devices into mesh dims.  The
  workload dim must divide W exactly (traces are big; we never pad
  them here — ``runner.run_ladder`` fixes W via chunking instead); the
  system dim may be anything, because ``shard_systems`` PADS the system
  axis up to a mesh multiple — "S divides the device count evenly" is
  NOT a precondition.
- ``shard_systems`` places the inputs (``NamedSharding``: Dyn leaves
  ``P("sys")``, trace leaves ``P(None, "wl")``), wraps the caller's
  per-block function in ``shard_map`` and slices the padding back off.
  On a 1x1 mesh the same code path degenerates to an identity
  partitioning of a plain jitted call, so single-device hosts (CI)
  exercise the exact production code.

Every (s, w) lane's computation is independent and elementwise per
lane, so the mesh factorization cannot change results: a sharded run is
bit-identical to the unsharded one (pinned by tests/test_parallel.py
and the multidev CI job).

This module deliberately imports nothing from ``repro.core`` or its
``repro.sim`` siblings — it is a pure pytree/mesh utility, so the core
layer (``mmu.simulate_systems``) may import it without a cycle.
(``repro.obs`` is a stdlib-only leaf below even this layer, so emitting
trace events is cycle-safe.)
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.obs as obs

AXIS_SYS = "sys"
AXIS_WL = "wl"
AXIS_CORE = "core"
AXIS_T = "t"
AXIS_LANE = "lane"

__all__ = ["AXIS_SYS", "AXIS_WL", "AXIS_CORE", "AXIS_T", "AXIS_LANE",
           "MeshPlan", "plan_mesh", "build_mesh", "shard_jit", "shard_wrap",
           "shard_systems", "pick_t_shards", "time_shard_scan",
           "plan_lane_dim", "shard_lanes"]


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """A (sys x wl [x core]) device-mesh factorization of a sweep grid.

    ``core_dim > 1`` adds a third mesh axis over the per-core trace
    lanes of a multicore run ([T, W, C] traces); ``core_dim == 1``
    (every single-core plan) keeps the exact 2-D mesh of before — the
    core axis, when present, then runs as an inner vmap lane instead.
    """

    sys_dim: int       # mesh extent along the system axis
    wl_dim: int        # mesh extent along the workload axis (divides W)
    n_systems: int     # unpadded S
    n_workloads: int   # W
    pad_systems: int   # S padded up to a sys_dim multiple
    core_dim: int = 1  # mesh extent along the core axis (divides C)
    n_cores: int = 1   # C (1 = single-core: traces have no core axis)

    @property
    def n_devices(self) -> int:
        return self.sys_dim * self.wl_dim * self.core_dim

    def describe(self) -> str:
        if self.core_dim > 1:
            return f"{self.sys_dim}x{self.wl_dim}x{self.core_dim}"
        return f"{self.sys_dim}x{self.wl_dim}"


def plan_mesh(n_systems: int, n_workloads: int, n_devices: int | None = None,
              force: tuple[int, ...] | None = None,
              n_cores: int = 1) -> MeshPlan:
    """Factorize the device count into a ("sys", "wl"[, "core"]) mesh.

    Policy: the workload dim takes the largest divisor of W that also
    divides the device count (traces shard without padding); the system
    dim takes the remaining devices, capped at S (an 8-device host never
    runs a 2-system ladder 4x redundantly).  The system axis is then
    padded up to a ``sys_dim`` multiple — divisibility of S is never
    required.  ``force=(sys, wl)`` or ``(sys, wl, core)`` overrides the
    factorization (the ``--mesh`` debug flag); ``n_devices`` defaults to
    the visible device count.  ``n_cores > 1`` declares a multicore run
    ([T, W, C] traces): the core axis defaults to an inner vmap lane
    (``core_dim=1``), and a 3-tuple ``force`` promotes it to a third
    mesh dim (``core_dim`` must divide C exactly — core lanes, like
    workloads, are never padded).  Empty grids are rejected up front: a
    sweep over zero systems or zero workloads is always a caller bug,
    and letting it reach the mesh reshape would produce an unrelated
    error.
    """
    if n_systems <= 0:
        raise ValueError(
            f"empty ladder: no systems to simulate (n_systems={n_systems})")
    if n_workloads <= 0:
        raise ValueError(
            f"empty ladder: no workloads to simulate "
            f"(n_workloads={n_workloads})")
    if n_cores < 1:
        raise ValueError(f"n_cores must be >= 1, got {n_cores}")
    core_dim = 1
    if force is not None:
        if len(force) not in (2, 3):
            raise ValueError(
                f"mesh force must be (sys, wl) or (sys, wl, core), "
                f"got {force}")
        sys_dim, wl_dim = int(force[0]), int(force[1])
        core_dim = int(force[2]) if len(force) == 3 else 1
        if sys_dim < 1 or wl_dim < 1 or core_dim < 1:
            raise ValueError(f"mesh dims must be >= 1, got {force}")
        if n_workloads % wl_dim != 0:
            raise ValueError(
                f"mesh wl dim {wl_dim} does not divide the workload axis "
                f"({n_workloads}); traces are never padded — pick a "
                f"divisor (the system axis is the padded one)")
        if core_dim > 1 and n_cores % core_dim != 0:
            raise ValueError(
                f"mesh core dim {core_dim} does not divide the core axis "
                f"({n_cores}); core lanes are never padded — pick a "
                f"divisor")
    else:
        d = n_devices if n_devices is not None else jax.local_device_count()
        wl_dim = max(k for k in range(1, min(d, n_workloads) + 1)
                     if n_workloads % k == 0 and d % k == 0)
        sys_dim = min(d // wl_dim, n_systems)
    pad = math.ceil(n_systems / sys_dim) * sys_dim
    return MeshPlan(sys_dim=sys_dim, wl_dim=wl_dim, n_systems=n_systems,
                    n_workloads=n_workloads, pad_systems=pad,
                    core_dim=core_dim, n_cores=n_cores)


def build_mesh(plan: MeshPlan) -> Mesh:
    """Materialize the plan over the first ``plan.n_devices`` devices."""
    devs = jax.devices()
    if len(devs) < plan.n_devices:
        raise ValueError(
            f"mesh {plan.describe()} needs {plan.n_devices} devices but "
            f"only {len(devs)} are visible")
    if plan.core_dim > 1:
        grid = np.asarray(devs[: plan.n_devices]).reshape(
            plan.sys_dim, plan.wl_dim, plan.core_dim)
        return Mesh(grid, (AXIS_SYS, AXIS_WL, AXIS_CORE))
    grid = np.asarray(devs[: plan.n_devices]).reshape(
        plan.sys_dim, plan.wl_dim)
    return Mesh(grid, (AXIS_SYS, AXIS_WL))


def _pad_sys(x: jax.Array, pad: int) -> jax.Array:
    # replicate the last lane: a valid config, so padded lanes simulate
    # harmlessly (their outputs are sliced off, never stored)
    return jnp.concatenate(
        [x, jnp.broadcast_to(x[-1:], (pad,) + x.shape[1:])])


def _specs(plan: MeshPlan):
    """(trace spec, output spec) of the plan's mesh."""
    if plan.core_dim > 1:
        # multicore 3-D mesh: trace leaves are [T, W, C] and every
        # output leaf leads with [S_blk, W_blk, C_blk]
        return P(None, AXIS_WL, AXIS_CORE), P(AXIS_SYS, AXIS_WL, AXIS_CORE)
    # single-core (or inner-vmap core lanes): a trailing core axis, if
    # any, stays replicated
    return P(None, AXIS_WL), P(AXIS_SYS, AXIS_WL)


def shard_jit(fn, plan: MeshPlan, mesh: Mesh):
    """``jit(shard_map(fn))`` over ``mesh`` with the plan's specs.

    ``check_vma=False``: the body carries no collectives, and its
    initial scan carry is built inside the body
    (``mmu.make_systems_runner``), so it is not typed as varying over
    the mesh axes; the lanes never mix, so there is nothing to verify.
    Takes the mesh so that a compile for a described (not attached)
    device can build the same program.
    """
    trace_spec, out_spec = _specs(plan)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(AXIS_SYS), trace_spec),
        out_specs=out_spec, check_vma=False))


def shard_wrap(fn, plan: MeshPlan):
    """Wrap ``fn`` for the mesh ONCE; returns ``call(dyns, traces)``.

    ``fn`` is a per-block function: Dyn leaves arrive ``[S_blk]``-shaped
    and trace leaves ``[T, W_blk, ...]``; every output leaf must lead
    with ``[S_blk, W_blk]``.  The system axis is padded to the mesh (see
    ``plan_mesh``) and sliced back before returning, so callers always
    see exactly [S, W] outputs.

    The shard_map + jit wrapper is built here, outside the returned
    closure: same-shape calls (``run_ladder``'s fixed-width chunks) hit
    one jit cache entry and trace/lower exactly once.
    """
    mesh = build_mesh(plan)
    trace_spec, _ = _specs(plan)
    jitted = shard_jit(fn, plan, mesh)

    def call(dyns, traces):
        S = jax.tree.leaves(dyns)[0].shape[0]
        W = jax.tree.leaves(traces)[0].shape[1]
        if (plan.n_systems, plan.n_workloads) != (S, W):
            raise ValueError(
                f"mesh plan is for a {plan.n_systems}x{plan.n_workloads} "
                f"grid but the inputs are {S}x{W}")
        pad = plan.pad_systems - S
        if pad:
            dyns = jax.tree.map(lambda x: _pad_sys(x, pad), dyns)
        dyns = jax.device_put(dyns, NamedSharding(mesh, P(AXIS_SYS)))
        traces = jax.device_put(traces, NamedSharding(mesh, trace_spec))
        out = jitted(dyns, traces)
        if pad:
            out = jax.tree.map(lambda x: x[:S], out)
        return out

    return call


def pick_t_shards(n: int, requested: int) -> int:
    """Largest divisor of the trace length ``n`` that is <= ``requested``.

    Time blocks must tile the trace exactly — padding the time axis
    would simulate phantom accesses and break bit-identity with the
    serial scan — so a requested shard count that does not divide ``n``
    is rounded DOWN to the nearest divisor (worst case 1: no sharding).
    """
    if n <= 0:
        raise ValueError(f"cannot time-shard an empty trace (n={n})")
    if requested < 1:
        raise ValueError(f"time-shard count must be >= 1, got {requested}")
    return max(t for t in range(1, min(requested, n) + 1) if n % t == 0)


def _block_eq(a, b, t: int) -> jax.Array:
    """Per-block (leading axis ``t``) bitwise equality of two pytrees."""
    eqs = jax.tree.map(
        lambda x, y: jnp.all((x == y).reshape(t, -1), axis=1), a, b)
    return functools.reduce(jnp.logical_and, jax.tree.leaves(eqs))


def time_shard_scan(block_fn, st0, trace, t_shards: int,
                    batch: str = "vmap"):
    """Run ``block_fn`` over ``t_shards`` trace blocks speculatively and
    resolve the carry hand-off to the exact serial result.

    ``block_fn(state, trace_block) -> state`` is one serial segment of
    the access scan (any backend).  The trace's time axis is split into
    ``t`` contiguous blocks; every block starts from a GUESSED carry
    (cold ``st0`` in round 1) and all blocks run in parallel — on a
    multi-device host the block axis is laid out on a 1-D ``("t",)``
    mesh, so single-trace latency scales with devices.  After each
    round the hand-off chain is re-seeded (``start[i+1] = end[i]``) and
    re-run until a fixed point: block 0's start is exact by definition,
    and block ``i``'s end is exact once its start matched the exact end
    of block ``i-1``.  The exact-known prefix grows by >= 1 block per
    round, so the loop terminates in <= ``t`` rounds and the returned
    state is BIT-IDENTICAL to the serial scan.  Feedback-heavy MMU
    state (``now``, pressure/MPKI counters) makes a cold guess almost
    never coincide with the true carry, so realistic convergence IS the
    worst case ``t`` rounds — the win is latency (each round is ``n/t``
    long on ``t`` devices), not total work.

    ``batch="vmap"`` runs blocks via ``jax.vmap``; ``batch="map"``
    (required for the pallas backend, whose grid seeding must not be
    rewritten by vmap batching) uses sequential ``lax.map``.

    Returns ``(final_state, info)`` with ``info = {"t_shards", "rounds",
    "requested"}``; ``t_shards`` is the requested count rounded down to
    a divisor of the trace length (see ``pick_t_shards``).
    """
    if batch not in ("vmap", "map"):
        raise ValueError(f"unknown batch mode {batch!r}")
    n = jax.tree.leaves(trace)[0].shape[0]
    t = pick_t_shards(n, t_shards)
    if t == 1:
        return block_fn(st0, trace), {
            "t_shards": 1, "rounds": 1, "requested": int(t_shards)}

    blocks = jax.tree.map(
        lambda x: x.reshape((t, n // t) + x.shape[1:]), trace)
    starts = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (t,) + x.shape), st0)

    d = jax.local_device_count()
    if batch == "vmap" and d > 1:
        g = max(k for k in range(1, min(d, t) + 1) if t % k == 0)
        if g > 1:
            mesh = Mesh(np.asarray(jax.devices()[:g]), (AXIS_T,))
            sh = NamedSharding(mesh, P(AXIS_T))
            blocks = jax.device_put(blocks, sh)
            starts = jax.device_put(starts, sh)

    @jax.jit
    def round_fn(starts, blocks):
        if batch == "vmap":
            ends = jax.vmap(block_fn)(starts, blocks)
        else:
            ends = jax.lax.map(lambda ab: block_fn(*ab), (starts, blocks))
        new_starts = jax.tree.map(
            lambda s0, e: jnp.concatenate([s0[None], e[:-1]]), st0, ends)
        return ends, new_starts, _block_eq(new_starts, starts, t)

    rounds = 0
    known = 0
    while known < t:
        ends, new_starts, eq = round_fn(starts, blocks)
        rounds += 1
        eq = np.asarray(jax.device_get(eq))
        # ends[0] came from the true st0, so it is exact; end i is exact
        # iff its start was, i.e. iff the start we USED equals the exact
        # end of block i-1 (eq[i]) and that end itself is exact
        known = 1
        while known < t and eq[known]:
            known += 1
        starts = new_starts
        # per-round hand-off telemetry: how far the exact prefix grew
        obs.event(obs.names.EV_TIME_SHARD_ROUND, round=rounds,
                  known_prefix=int(known), t_shards=t)
    final = jax.tree.map(lambda e: e[-1], ends)
    return final, {"t_shards": t, "rounds": rounds,
                   "requested": int(t_shards)}


def shard_systems(fn, dyns, traces, plan: MeshPlan | None = None):
    """One-shot form of ``shard_wrap``: plan (if needed), wrap, call."""
    S = jax.tree.leaves(dyns)[0].shape[0]
    W = jax.tree.leaves(traces)[0].shape[1]
    return shard_wrap(fn, plan or plan_mesh(S, W))(dyns, traces)


def plan_lane_dim(n_lanes: int, n_devices: int | None = None) -> int:
    """Mesh extent for a 1-D ``("lane",)`` mesh over ``n_lanes`` lanes.

    Largest divisor of ``n_lanes`` that fits the visible device count —
    lanes, like workloads, are never padded (each lane is an independent
    engine whose state must round-trip bit-exactly).  1 device → 1 (the
    identity partitioning).
    """
    if n_lanes < 1:
        raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
    d = n_devices if n_devices is not None else jax.local_device_count()
    if d < 1:
        raise ValueError(f"n_devices must be >= 1, got {d}")
    return max(k for k in range(1, min(d, n_lanes) + 1) if n_lanes % k == 0)


def shard_lanes(fn, n_lanes: int, n_devices: int | None = None):
    """Wrap a per-lane-batch ``fn`` for a 1-D ``("lane",)`` device mesh.

    The serving load harness's mesh: every pytree argument and output of
    ``fn`` leads with the lane axis ``[L, ...]`` (one engine per lane —
    its slot pool, KV page pool, and VTC all ride that leading axis), so
    sharding lane-batched state splits the slot and page pools across
    the device mesh.  ``fn`` is typically ``jax.vmap`` of a single-lane
    step; inside ``shard_map`` each device sees its ``[L/dim, ...]``
    block.  As with ``shard_wrap``, the jit(shard_map) wrapper is built
    ONCE here so every same-shape call hits one jit-cache entry, and a
    1-device host runs the identical code path as an identity
    partitioning.

    Returns ``call(*args)`` with attribute ``mesh_dim`` (the lane-mesh
    extent actually used).  Lanes must stay divisible: ``n_lanes`` is
    never padded, so the mesh dim comes from ``plan_lane_dim``.
    """
    dim = plan_lane_dim(n_lanes, n_devices)
    mesh = Mesh(np.asarray(jax.devices()[:dim]), (AXIS_LANE,))
    spec = P(AXIS_LANE)
    # no collectives in the per-lane step (see shard_jit)
    jitted = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=spec,
                                   out_specs=spec, check_vma=False))
    sharding = NamedSharding(mesh, spec)

    def call(*args):
        args = jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), sharding), args)
        return jitted(*args)

    call.mesh_dim = dim
    return call
