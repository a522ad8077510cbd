"""Set-associative structure primitives (branchless, scan-friendly).

Every TLB / PWC / cache in ``repro.core`` is a pair-of-arrays structure

    tags  : int32  [n_sets, n_ways]
    valid : bool_  [n_sets, n_ways]
    meta  : int32  [n_sets, n_ways]   (LRU stamp or RRPV, policy-dependent)

All operations take a *dynamic* set index (traced scalar) and return pure
functional updates.  Victims are chosen branchlessly:

  * LRU    — argmin timestamp (invalid ways forced to -1 so they win).
  * SRRIP  — age all RRPVs by (RRIP_MAX - max RRPV) then argmax; the
             TLB-aware variant re-rolls once onto non-TLB ways per the
             paper's Listing 1.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

RRIP_BITS = 2
RRIP_MAX = (1 << RRIP_BITS) - 1  # 3


class Assoc(NamedTuple):
    """A set-associative array structure."""

    tags: jax.Array   # int32 [S, W]
    valid: jax.Array  # bool  [S, W]
    meta: jax.Array   # int32 [S, W] — LRU stamp or RRPV

    @property
    def n_sets(self) -> int:
        return self.tags.shape[0]

    @property
    def n_ways(self) -> int:
        return self.tags.shape[1]


def make(n_sets: int, n_ways: int) -> Assoc:
    return Assoc(
        tags=jnp.zeros((n_sets, n_ways), jnp.int32),
        valid=jnp.zeros((n_sets, n_ways), jnp.bool_),
        meta=jnp.zeros((n_sets, n_ways), jnp.int32),
    )


def set_index(key: jax.Array, n_sets: int) -> jax.Array:
    """Low-order-bit set indexing (n_sets must be a power of two)."""
    assert n_sets & (n_sets - 1) == 0, "n_sets must be a power of two"
    return key & (n_sets - 1)


def lookup(a: Assoc, key: jax.Array):
    """Probe. Returns (hit: bool scalar, way: int scalar, set_idx)."""
    s = set_index(key, a.n_sets)
    row_t = a.tags[s]
    row_v = a.valid[s]
    hits = row_v & (row_t == key)
    hit = jnp.any(hits)
    way = jnp.argmax(hits)  # first hitting way (0 if none; guard with `hit`)
    return hit, way, s


# ---------------------------------------------------------------- LRU


def touch_lru(a: Assoc, s: jax.Array, way: jax.Array, now: jax.Array) -> Assoc:
    return a._replace(meta=a.meta.at[s, way].set(now))


def lru_victim(a: Assoc, s: jax.Array) -> jax.Array:
    stamps = jnp.where(a.valid[s], a.meta[s], jnp.int32(-1))
    return jnp.argmin(stamps)


def insert_lru(a: Assoc, key: jax.Array, now: jax.Array, enable=True):
    """Insert `key` at set(key), evicting LRU. Returns (assoc, evicted_tag,
    evicted_valid)."""
    s = set_index(key, a.n_sets)
    w = lru_victim(a, s)
    ev_tag = a.tags[s, w]
    ev_valid = a.valid[s, w]
    en = jnp.asarray(enable)
    new = Assoc(
        tags=a.tags.at[s, w].set(jnp.where(en, key, a.tags[s, w])),
        valid=a.valid.at[s, w].set(jnp.where(en, True, a.valid[s, w])),
        meta=a.meta.at[s, w].set(jnp.where(en, now, a.meta[s, w])),
    )
    return new, ev_tag, ev_valid & en


# ---------------------------------------------------------------- SRRIP

def srrip_age_and_pick(rrpv_row: jax.Array, valid_row: jax.Array):
    """Age the row so at least one way reaches RRIP_MAX and pick a victim.

    Invalid ways are preferred (treated as RRPV=+inf).  Returns
    (aged_row, victim_way).
    """
    eff = jnp.where(valid_row, rrpv_row, jnp.int32(RRIP_MAX + 1))
    bump = jnp.maximum(RRIP_MAX - jnp.max(eff), 0)
    aged = jnp.where(valid_row, rrpv_row + bump, rrpv_row)
    pick = jnp.where(valid_row, aged, jnp.int32(RRIP_MAX + 1))
    victim = jnp.argmax(pick)
    return aged, victim


def srrip_victim_tlb_aware(
    rrpv_row: jax.Array,
    valid_row: jax.Array,
    is_tlb_row: jax.Array,
    pressure: jax.Array,
):
    """Paper Listing 1 `chooseReplacementCandidate`.

    If the SRRIP victim is a TLB block and translation pressure is high,
    make ONE more attempt: choose a non-TLB way at RRIP_MAX (post-aging).
    If none exists the TLB block is evicted after all.
    Returns (aged_row, victim_way).
    """
    aged, v0 = srrip_age_and_pick(rrpv_row, valid_row)
    # invalid ways already won in v0 if present
    non_tlb_max = valid_row & (~is_tlb_row) & (aged >= RRIP_MAX)
    have_alt = jnp.any(non_tlb_max)
    v1 = jnp.argmax(non_tlb_max)
    reroll = pressure & valid_row[v0] & is_tlb_row[v0] & have_alt
    victim = jnp.where(reroll, v1, v0)
    return aged, victim
