"""Pallas-fused blocked access scan for the MMU translation pipeline.

``jax.lax.scan`` threads the FULL ``MMUState`` carry through every
access: each step's gather/scatter-heavy assoc probes force XLA to
materialize the whole carry pytree per iteration, so the hot sweep loop
is dominated by carry traffic, not by translation math.  This kernel
restructures the scan into a grid of trace *blocks*:

  - the state pytree lives in kernel-resident buffers (VMEM on TPU) with
    a constant ``index_map``, so it persists ACROSS grid steps and is
    written back to HBM once, at the end — only the per-block trace
    slices stream in;
  - each grid step runs the unmodified per-access ``step`` over its
    block with an inner ``lax.scan`` whose carry never leaves the
    kernel, and folds the ``Stats`` deltas into the resident state.

The step function is the SAME traced composition ``mmu.make_step``
builds for the scan backend, so the two backends are bit-identical by
construction (pinned by tests/test_mmu_kernel.py on the full native and
virt ladder families).

On the CPU the kernel runs interpreted, which preserves bit-identity
but not the carry-residency speedup.  On a TPU it goes through Mosaic,
which refuses it today (``mmu.PALLAS_ON_TPU`` says why), so
``mmu.resolve_backend`` rejects the backend there up front.  Block
sizes are auto-tuned: see ``pick_block``.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import repro.obs as obs

def _interpret() -> bool:
    """Interpret the kernel on the CPU (the tests); compile it through
    Mosaic on a TPU.  Asked per call, not at import: querying the
    backend at import would initialize jax before sweep.py's --devices
    flag can set --xla_force_host_platform_device_count."""
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise ValueError(
            f"the pallas MMU kernel runs on a TPU (Mosaic) or interpreted "
            f"on the CPU, not on {platform!r}; use backend='scan'")
    return platform == "cpu"


# target grid length for auto-tuned blocks: enough blocks that the
# resident state demonstrably survives grid steps, few enough that
# interpret-mode CI (which pays per-grid-step kernel overhead) and the
# Mosaic unroll both stay cheap.  REPRO_PALLAS_BLOCK pins an explicit
# block-size target instead (pick_block still snaps it to a divisor).
TARGET_GRID = 8
_BLOCK_ENV = "REPRO_PALLAS_BLOCK"


def _divisors(n: int) -> list[int]:
    out = set()
    d = 1
    while d * d <= n:
        if n % d == 0:
            out.add(d)
            out.add(n // d)
        d += 1
    return sorted(out)


def pick_block(n: int, target: int | None = None) -> int:
    """Auto-tune the trace block size for an ``n``-access scan.

    The block must divide ``n`` exactly (padding the time axis would
    simulate phantom accesses and break bit-identity).  With no target,
    pick the divisor whose grid length is closest to ``TARGET_GRID`` —
    the measured compile-cost sweet spot: more blocks shrink the
    per-block working set but grow the (interpret-mode) per-step
    overhead and the kernel's compile time roughly linearly.  An
    explicit ``target`` (the ``REPRO_PALLAS_BLOCK`` env knob) snaps to
    the nearest divisor instead.  Ties prefer the LARGER block (fewer
    grid steps).  A prime ``n`` degenerates to one whole-trace block —
    still correct, just no blocking.
    """
    if n <= 0:
        raise ValueError(f"cannot block an empty trace (n={n})")
    if target is None:
        env = os.environ.get(_BLOCK_ENV, "").strip()
        target = int(env) if env else None
    divs = _divisors(n)
    if target is None:
        return min(divs, key=lambda d: (abs(n // d - TARGET_GRID), -d))
    if target < 1:
        raise ValueError(f"block target must be >= 1, got {target}")
    return min(divs, key=lambda d: (abs(d - target), -d))


def _r1(x):
    """Kernel refs want rank >= 1: scalar leaves ride as (1,) views."""
    return x.reshape((1,)) if x.ndim == 0 else x


def _full_spec(shape):
    nd = len(shape)
    return pl.BlockSpec(shape, lambda i, _nd=nd: (0,) * _nd)


@functools.partial(jax.jit,
                   static_argnames=("step", "treedefs", "block",
                                    "interpret", "n_leaves"))
def _blocked_scan_impl(step, treedefs, block, interpret, n_leaves,
                       tr_leaves, st_leaves, const_leaves):
    st_def, tr_def, const_def = treedefs
    n_tr, n_st = n_leaves
    st_shapes = tuple(x.shape for x in st_leaves)
    const_shapes = tuple(x.shape for x in const_leaves)
    ins = [_r1(x) for x in st_leaves]
    cins = [_r1(x) for x in const_leaves]
    n = tr_leaves[0].shape[0]

    def kernel(*refs):
        tr_refs = refs[:n_tr]
        init_refs = refs[n_tr:n_tr + n_st]
        const_refs = refs[n_tr + n_st:-n_st]
        out_refs = refs[-n_st:]

        # grid step 0 seeds the resident state from the initial carry;
        # later steps keep accumulating into the same buffers
        @pl.when(pl.program_id(0) == 0)
        def _seed():
            for o, i in zip(out_refs, init_refs):
                o[...] = i[...]

        st = jax.tree.unflatten(
            st_def, [o[...].reshape(s)
                     for o, s in zip(out_refs, st_shapes)])
        tr = jax.tree.unflatten(tr_def, [r[...] for r in tr_refs])
        if const_def is not None:
            consts = jax.tree.unflatten(
                const_def, [r[...].reshape(s)
                            for r, s in zip(const_refs, const_shapes)])
            body = lambda ss, acc: step(ss, acc, consts)  # noqa: E731
        else:
            body = step
        st, _ = jax.lax.scan(body, st, tr)
        for o, leaf in zip(out_refs, jax.tree.leaves(st)):
            o[...] = leaf.reshape(o.shape)

    def _tr_spec(x):
        nd = x.ndim
        return pl.BlockSpec((block,) + x.shape[1:],
                            lambda i, _nd=nd: (i,) + (0,) * (_nd - 1))

    # the grid is a sequential reduction over trace blocks — the
    # resident-state pattern requires in-order execution
    params = (None if interpret
              else pltpu.CompilerParams(dimension_semantics=("arbitrary",)))

    out = pl.pallas_call(
        kernel,
        grid=(n // block,),
        in_specs=([_tr_spec(x) for x in tr_leaves]
                  + [_full_spec(x.shape) for x in ins]
                  + [_full_spec(x.shape) for x in cins]),
        out_specs=[_full_spec(x.shape) for x in ins],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in ins],
        interpret=interpret,
        compiler_params=params,
    )(*tr_leaves, *ins, *cins)
    return jax.tree.unflatten(
        st_def, [o.reshape(s) for o, s in zip(out, st_shapes)])


def blocked_scan(step, st0, trace, consts=None, block: int | None = None):
    """Scan ``step`` over ``trace`` (time axis 0) in resident-state blocks.

    Drop-in for ``lax.scan(step, st0, trace)[0]`` (per-step outputs are
    discarded — the sweep folds everything into ``Stats`` inside the
    carry).  ``step(state, access[, consts]) -> (state, _)`` may be any
    traced function, including a workload/system-vmapped composition;
    ``consts`` is an optional pytree of per-call constants (e.g. the
    ladder's stacked ``Dyn`` scalars) delivered to the kernel as inputs
    — pallas kernels cannot close over traced arrays.  ``block``
    overrides the auto-tuned trace block size (``pick_block``).  The
    kernel is interpreted on the CPU and compiled through Mosaic on a
    TPU, never interpreted there.
    """
    interpret = _interpret()

    # the stage composition bakes config-derived scalars into its
    # closure; a pallas kernel cannot capture constants, so the step is
    # traced to a jaxpr here and its captured consts hoisted into
    # explicit inputs that ride along with the caller's consts pytree
    # (jax.closure_convert only hoists tracers, not concrete arrays)
    ex_acc = jax.tree.map(lambda x: x[0], trace)

    def _stepc(st, acc, cst):
        return step(st, acc) if consts is None else step(st, acc, cst)

    closed, out_shape = jax.make_jaxpr(_stepc, return_shape=True)(
        st0, ex_acc, consts)
    out_def = jax.tree.structure(out_shape)
    hoisted = tuple(jnp.asarray(c) for c in closed.consts)

    def step_k(st, acc, ca):
        cst, hs = ca
        flat = jax.core.eval_jaxpr(closed.jaxpr, hs,
                                   *jax.tree.leaves((st, acc, cst)))
        return jax.tree.unflatten(out_def, flat)

    consts_all = (consts, tuple(hoisted))
    st_leaves, st_def = jax.tree.flatten(st0)
    tr_leaves, tr_def = jax.tree.flatten(trace)
    const_leaves, const_def = jax.tree.flatten(consts_all)
    n = tr_leaves[0].shape[0]
    blk = pick_block(n, block)
    # trace-time telemetry (static Python ints only — safe under any
    # transform): one event per kernel BUILD, i.e. per lowering, not per
    # execution, which is exactly the compile-cost signal TPU phase-2
    # block tuning needs
    obs.event(obs.names.EV_PALLAS_KERNEL, n=n, block=blk,
              grid=n // blk, interpret=bool(interpret))
    return _blocked_scan_impl(
        step_k, (st_def, tr_def, const_def), blk, interpret,
        (len(tr_leaves), len(st_leaves)),
        tuple(tr_leaves), tuple(st_leaves), tuple(const_leaves))
