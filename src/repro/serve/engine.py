"""Batched serving engine with Victima-paged KV.

Continuous-batching style: a fixed pool of request slots; arriving
requests prefill into paged KV (pages allocated from the pool), decode
proceeds in lock-step over active slots, finished slots are shot down
(``translation_cache.invalidate_request`` + ``block_table.unmap_request``)
and refilled.  Translation of logical→physical KV pages goes through the
VTC (TC hit / cluster hit / radix walk) — the serving-side embodiment of
the paper (DESIGN.md §2.2); hit-rate stats come back with every batch.

Correctness invariants the serving load harness leans on:

  * **No aliasing under exhaustion.**  ``admit`` and the decode-tick
    ``grow`` only take a page when one is actually free; an exhausted
    pool rejects the admission / defers the growth (and bumps the
    ``serve.pool_exhausted`` accounting) instead of double-mapping
    whatever ``argmax`` of an all-zero free vector points at (page 0).
  * **Dead slots are invisible.**  Only live, un-stalled slots enter the
    per-tick translation batch (``translate_batch(..., valid=...)``), so
    parked slots cannot walk unmapped block 0 and pollute the pressure
    signal or the VTC counters.
  * **Pressure is a sampled window.**  The paper's L2-TLB miss-rate
    signal (§5.3) is sampled over an epoch, not accumulated forever:
    ``EngineState`` carries a per-epoch walk/total window and latches
    ``pressure`` at each epoch boundary, so pressure decays when the
    working set shrinks.

All engine/batch-step functions are jit/scan-safe; the ``scope``
parameters on the host-side telemetry entry points (``retire``,
``decode_step``, ``stats``) suffix registry metric names with
``[scope]`` so multiple engines in one process (e.g. the cluster vs
no-cluster ablation) do not share counters.

The numerics path uses the dense models' decode_step on gathered pages
(CPU/functional mode); on TPU the gather is replaced by the Pallas
``paged_attention`` kernel whose BlockSpec index maps consume the same
translated tables.
"""
from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp

import repro.obs as obs
from repro.paged import block_table as btab
from repro.paged import translation_cache as vtc_mod


def scoped(name: str, scope: str | None) -> str:
    """Registry metric name for one engine instance: ``name[scope]``.

    The obs registry is process-global; without a scope two engines
    (e.g. benchmarks/serving.py's VTC vs no-cluster ablation) would
    interleave ``inc_to`` samples and report the max of both."""
    return f"{name}[{scope}]" if scope else name


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 8                 # concurrent requests
    max_blocks_per_req: int = 64     # × TOKENS_PER_PAGE tokens
    n_pool_pages: int = 512
    n_leaf_rows: int = 64
    tc_sets: int = 16
    tc_ways: int = 4
    n_clusters: int = 64
    pressure_thresh: float = 0.3     # windowed walk rate → "pressure"
    pressure_epoch: int = 64         # ticks per pressure sampling window
    # PTW-CP cluster-install gate (freq_min, cost_min) — lower bounds
    # only (see translation_cache.translate); tuned from the simulator's
    # PTW-CP sweep by serve.load.tune_gate
    gate_freq_min: int = 1
    gate_cost_min: int = 1

    def __post_init__(self):
        # surface the VTC's power-of-two indexing requirement at config
        # construction (vtc_mod.make re-checks, but the engine config is
        # the user-facing knob)
        if not vtc_mod._pow2(self.tc_sets):
            raise ValueError(
                f"EngineConfig.tc_sets must be a power of two, "
                f"got {self.tc_sets}")
        if not vtc_mod._pow2(self.n_clusters):
            raise ValueError(
                f"EngineConfig.n_clusters must be a power of two, "
                f"got {self.n_clusters}")
        if self.pressure_epoch < 1:
            raise ValueError(
                f"EngineConfig.pressure_epoch must be >= 1, "
                f"got {self.pressure_epoch}")
        if self.gate_freq_min < 0 or self.gate_cost_min < 0:
            raise ValueError(
                f"EngineConfig gate thresholds must be >= 0, got "
                f"({self.gate_freq_min}, {self.gate_cost_min})")


class EngineState(NamedTuple):
    bt: btab.BlockTables
    vtc: vtc_mod.VTC
    page_free: jax.Array      # int32 [n_pool_pages] 1=free
    slot_len: jax.Array       # int32 [n_slots] tokens decoded
    slot_live: jax.Array      # bool  [n_slots]
    tick: jax.Array           # int32 decode ticks since init
    win_walk: jax.Array       # int32 walks in the current pressure epoch
    win_total: jax.Array      # int32 translations in the current epoch
    pressure: jax.Array       # bool  latched at the last epoch boundary
    n_pool_stall: jax.Array   # int32 pool-exhausted events (cumulative)


def init(cfg: EngineConfig) -> EngineState:
    return EngineState(
        bt=btab.make(cfg.n_slots, cfg.max_blocks_per_req, cfg.n_leaf_rows),
        vtc=vtc_mod.make(cfg.tc_sets, cfg.tc_ways, cfg.n_clusters),
        page_free=jnp.ones((cfg.n_pool_pages,), jnp.int32),
        slot_len=jnp.zeros((cfg.n_slots,), jnp.int32),
        slot_live=jnp.zeros((cfg.n_slots,), jnp.bool_),
        tick=jnp.int32(0),
        win_walk=jnp.int32(0),
        win_total=jnp.int32(0),
        pressure=jnp.bool_(False),
        n_pool_stall=jnp.int32(0),
    )


def admit(st: EngineState, slot, prompt_blocks):
    """Admit a request into `slot`: allocate + map its prompt pages.

    Returns ``(state, ok)``.  The admission is ATOMIC against pool
    exhaustion: when fewer than ``prompt_blocks`` pages are free (or the
    slot is already live, or the request is empty/oversized) NOTHING is
    allocated and ``ok`` is False — the caller re-queues the request.
    Without the guard an exhausted pool would map every remaining block
    onto ``argmax(free) == 0``, aliasing page 0 across requests.

    jit-safe: `slot` and `prompt_blocks` may be tracers (the scan runs a
    fixed ``capacity`` iterations, masked by ``b < prompt_blocks``).
    """
    capacity = st.bt.directory.shape[1] * btab.FANOUT
    nb = jnp.int32(prompt_blocks)
    slot = jnp.int32(slot)
    ok = ((nb > 0) & (nb <= capacity)
          & (jnp.sum(st.page_free) >= nb)
          & ~st.slot_live[slot])

    def body(carry, b):
        bt, free = carry
        take = ok & (b < nb)
        page = jnp.argmax(free)
        free = jnp.where(take, free.at[page].set(0), free)
        bt2 = btab.map_block(bt, slot, b, page)
        bt = jax.tree.map(lambda a, c: jnp.where(take, c, a), bt, bt2)
        return (bt, free), None

    (bt, free), _ = jax.lax.scan(
        body, (st.bt, st.page_free), jnp.arange(capacity))
    st = st._replace(
        bt=bt, page_free=free,
        slot_len=st.slot_len.at[slot].set(
            jnp.where(ok, nb * btab.TOKENS_PER_PAGE, st.slot_len[slot])),
        slot_live=st.slot_live.at[slot].set(st.slot_live[slot] | ok))
    return st, ok


def admit_where(st: EngineState, prompt_blocks):
    """Batch admission: try ``prompt_blocks[i]`` into every slot `i`
    (0 = no request for that slot).  Sequential scan, so the free-page
    guard stays atomic across slots.  Returns ``(state, oks[n_slots])``.
    """
    def body(s, i):
        s, ok = admit(s, i, prompt_blocks[i])
        return s, ok
    st, oks = jax.lax.scan(body, st,
                           jnp.arange(st.slot_len.shape[0]))
    return st, oks


def _retire_one(st: EngineState, slot):
    """Pure shootdown of one slot. Returns (state, n_invalidated)."""
    slot = jnp.int32(slot)
    rows = st.bt.directory[slot]
    # free the physical pages reachable from this request's leaves
    valid_rows = rows >= 0
    pages = st.bt.leaves[jnp.maximum(rows, 0)]           # [dir, FANOUT]
    pmask = (pages >= 0) & valid_rows[:, None]
    free = st.page_free.at[jnp.maximum(pages, 0).reshape(-1)].max(
        pmask.reshape(-1).astype(jnp.int32))
    bt = btab.unmap_request(st.bt, slot)
    n_tc, n_cl = vtc_mod.invalidation_counts(st.vtc, slot)
    vtc = vtc_mod.invalidate_request(st.vtc, slot)
    st = st._replace(
        bt=bt, vtc=vtc, page_free=free,
        slot_len=st.slot_len.at[slot].set(0),
        slot_live=st.slot_live.at[slot].set(False))
    return st, n_tc + n_cl


def pages_consistent(st: EngineState) -> jax.Array:
    """Whether every physical page is exactly one of free or mapped once.

    False when a page is reachable from two block-table entries (an
    aliased page) or is both mapped and marked free.  Traceable: the
    load harness evaluates it inside its jitted tick.
    """
    rows = st.bt.directory                               # [slots, dir]
    pages = st.bt.leaves[jnp.maximum(rows, 0)]           # [slots, dir, F]
    mapped = (rows >= 0)[..., None] & (pages >= 0)
    n_map = jnp.zeros_like(st.page_free).at[
        jnp.maximum(pages, 0).reshape(-1)].add(
        mapped.reshape(-1).astype(st.page_free.dtype))
    return jnp.all(n_map + st.page_free == 1)


def retire(st: EngineState, slot, scope: str | None = None) -> EngineState:
    """Finish a request: shootdown — unmap pages, invalidate translations."""
    st, n_inval = _retire_one(st, slot)
    # tracer-safe: under jit these counts are tracers and the registry
    # skips the bump — host-path retires (the scheduler loop) do count
    obs.count(scoped(obs.names.CTR_VTC_INVALIDATE, scope), n_inval)
    return st


def retire_where(st: EngineState, mask):
    """Batch shootdown of every slot where ``mask`` is True.

    Returns ``(state, n_invalidated)`` with the total invalidation count
    as an int32 scalar (a tracer under jit — the load harness fetches it
    and feeds the scoped counter host-side).
    """
    def body(s, i):
        s2, n = _retire_one(s, i)
        s = jax.tree.map(lambda a, b: jnp.where(mask[i], b, a), s, s2)
        return s, jnp.where(mask[i], n, 0)
    st, ns = jax.lax.scan(body, st, jnp.arange(st.slot_len.shape[0]))
    return st, jnp.sum(ns)


def decode_translate(st: EngineState, cfg: EngineConfig):
    """One decode tick's translation work: every live slot translates the
    block holding its current position (+ appends a page on boundary).
    Returns (state, phys_pages [n_slots], src [n_slots]).

    Slots that hit a page boundary with an EXHAUSTED pool stall this
    tick (no growth, no translation, no length advance — retried next
    tick); parked (non-live) slots never enter the translation batch.
    ``src`` is -1 for stalled/parked slots.
    """
    n = st.slot_len.shape[0]
    pos = st.slot_len
    blocks = pos // btab.TOKENS_PER_PAGE
    # page-boundary: map a fresh page where needed — IF one is free;
    # an exhausted pool defers the growth instead of aliasing page 0
    def grow(carry, i):
        bt, free = carry
        need = st.slot_live[i] & (pos[i] % btab.TOKENS_PER_PAGE == 0)
        have = jnp.sum(free) > 0
        take = need & have
        page = jnp.argmax(free)
        free = jnp.where(take, free.at[page].set(0), free)
        bt2 = btab.map_block(bt, i, blocks[i], page)
        bt = jax.tree.map(lambda a, b: jnp.where(take, b, a), bt, bt2)
        return (bt, free), need & ~have
    (bt, free), stalled = jax.lax.scan(
        grow, (st.bt, st.page_free), jnp.arange(n))

    active = st.slot_live & ~stalled
    # paged attention reads the WHOLE context per token — translate the
    # current block plus sampled context blocks (the re-read stream where
    # the Victima tiers earn their keep).  Dead/stalled slots are MASKED
    # out of the batch: they touch no VTC state and report src = -1.
    h1 = (pos * 48271 % jnp.maximum(blocks, 1)).astype(jnp.int32)
    h2 = ((pos + 7) * 40503 % jnp.maximum(blocks, 1)).astype(jnp.int32)
    reqs = jnp.concatenate([jnp.arange(n)] * 3)
    blks = jnp.concatenate([blocks, h1, h2])
    valid = jnp.concatenate(
        [active, active & (blocks > 0), active & (blocks > 0)])
    vtc, bt, phys_all, src_all = vtc_mod.translate_batch(
        st.vtc, bt, reqs, blks, st.pressure, valid=valid,
        gate=(cfg.gate_freq_min, cfg.gate_cost_min))
    phys, src = phys_all[:n], src_all[:n]

    # sampled-window pressure (paper §5.3): accumulate this tick's
    # walk/total into the epoch window; at the epoch boundary latch
    # pressure from the WINDOW's walk rate and reset — so pressure can
    # decay when the working set shrinks, unlike the lifetime counters
    win_walk = st.win_walk + jnp.sum((src_all == 2).astype(jnp.int32))
    win_total = st.win_total + jnp.sum((src_all >= 0).astype(jnp.int32))
    tick = st.tick + 1
    boundary = (tick % cfg.pressure_epoch) == 0
    rate = (win_walk.astype(jnp.float32)
            / jnp.maximum(win_total, 1).astype(jnp.float32))
    pressure = jnp.where(boundary, rate > cfg.pressure_thresh, st.pressure)
    win_walk = jnp.where(boundary, 0, win_walk)
    win_total = jnp.where(boundary, 0, win_total)

    st = st._replace(
        bt=bt, vtc=vtc, page_free=free,
        slot_len=jnp.where(active, pos + 1, pos),
        tick=tick, win_walk=win_walk, win_total=win_total,
        pressure=pressure,
        n_pool_stall=st.n_pool_stall
        + jnp.sum(stalled.astype(jnp.int32)))
    return st, phys, src


def decode_step(st: EngineState, cfg: EngineConfig, fn=None,
                scope: str | None = None):
    """One TIMED decode tick: the instrumented serving entry point.

    Runs ``fn(state)`` (default: ``decode_translate`` under this `cfg`;
    pass a jitted closure for hot loops) inside a ``serve.decode_step``
    span, blocks on the results so the measured latency is real device
    time, and feeds the obs registry: the decode-step latency histogram
    and the step counter the serving load harness reports from.
    """
    if fn is None:
        fn = lambda s: decode_translate(s, cfg)  # noqa: E731
    with obs.span(obs.names.SPAN_DECODE_STEP):
        t0 = time.perf_counter()
        out = fn(st)
        jax.block_until_ready(out)
        obs.observe(scoped(obs.names.HIST_DECODE_STEP_S, scope),
                    time.perf_counter() - t0)
    obs.count(scoped(obs.names.CTR_DECODE_STEPS, scope))
    return out


def stats(st: EngineState, scope: str | None = None) -> dict:
    """Engine-level snapshot, routed through the obs registry.

    VTC counters live in device state (cumulative across the request's
    jitted steps), so sampling here raises the registry counters
    monotonically (``inc_to``) rather than double-counting; pool/slot
    occupancy land as gauges.  Pass ``scope`` when more than one engine
    lives in the process — registry names are suffixed ``[scope]`` so
    engines never share counters (and ``inc_to`` monotonicity holds per
    engine, not across the max of several).
    """
    v = vtc_mod.stats(st.vtc)
    pages_free = int(jnp.sum(st.page_free))
    slot_occ = float(jnp.mean(st.slot_live.astype(jnp.float32)))
    pool_stall = int(st.n_pool_stall)
    obs.REGISTRY.inc_to(
        scoped(obs.names.CTR_VTC_HIT_TC, scope), v["n_hit_tc"])
    obs.REGISTRY.inc_to(
        scoped(obs.names.CTR_VTC_HIT_CLUSTER, scope), v["n_hit_cluster"])
    obs.REGISTRY.inc_to(
        scoped(obs.names.CTR_VTC_WALK, scope), v["n_walk"])
    obs.REGISTRY.inc_to(
        scoped(obs.names.CTR_POOL_EXHAUSTED, scope), pool_stall)
    obs.gauge(scoped(obs.names.GAUGE_PAGES_FREE, scope), pages_free)
    obs.gauge(scoped(obs.names.GAUGE_SLOT_OCCUPANCY, scope), slot_occ)
    return {
        "tc_hit_rate": v["tc_hit_rate"],
        "cluster_hit_rate": v["cluster_hit_rate"],
        "walk_rate": v["walk_rate"],
        "vtc_hit_rate": v["vtc_hit_rate"],
        "pages_free": pages_free,
        "slot_occupancy": slot_occ,
        "pool_stall": pool_stall,
        "pressure": bool(st.pressure),
        "invalidate_count": obs.REGISTRY.counter(
            scoped(obs.names.CTR_VTC_INVALIDATE, scope)),
    }
