"""Four-level radix page-table walk model (paper §2.2, Figs. 1 & 4).

Address map (64B-line ids, int32-safe):
  data lines            [0, 2^28)            line = va >> 6
  leaf PTE lines (4K)   LEAF4_BASE + vpn>>3  (8 PTEs / 64B line)
  PD lines              PD_BASE   + (vpn>>9)>>3   (also 2M leaf level)
  PDP lines             PDP_BASE  + (vpn>>18)>>3
  PML4 lines            PML4_BASE + (vpn>>27)>>3
  host PT lines (virt)  H*_BASE   + analogous, keyed by gpn
  POM-TLB lines         POM_BASE  + (vpn mod 64K)>>2
  RestSeg tag lines     RESTSEG*_BASE + set    (Utopia, one line per set)

The walker is equipped with 3 split PWCs covering PML4/PDP/PD (2-cycle,
Table 3); a PWC hit at depth d skips all accesses above d.  4K walks touch
up to 4 lines, 2M walks up to 3 (the PD entry is the leaf).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bench.reference.assoc import Assoc, insert_lru, lookup, make
from bench.reference.caches import Hier, Lat, access_pte

# line-id bases (disjoint regions; all < 2^30, int32-safe).
# Data lines occupy [0, 2^29): footprints up to 2^23 4K pages × 64 lines.
# Each PT region gets a 2^22-line window (leaf needs vpn>>3 ≤ 2^20).
_B = 1 << 29
_W = 1 << 22
LEAF4_BASE = _B + 0 * _W
PD_BASE = _B + 1 * _W
PDP_BASE = _B + 2 * _W
PML4_BASE = _B + 3 * _W
HLEAF_BASE = _B + 4 * _W
HPD_BASE = _B + 5 * _W
HPDP_BASE = _B + 6 * _W
HPML4_BASE = _B + 7 * _W
POM_BASE = _B + 8 * _W
RESTSEG4_BASE = _B + 9 * _W   # Utopia 4K RestSeg tag/permission lines
RESTSEG2_BASE = _B + 10 * _W  # Utopia 2M RestSeg tag/permission lines

PWC_LAT = 2


class PWCs(NamedTuple):
    pml4: Assoc  # keyed vpn>>27
    pdp: Assoc   # keyed vpn>>18
    pd: Assoc    # keyed vpn>>9

def make_pwcs(sets=8, ways=4) -> PWCs:
    return PWCs(pml4=make(sets, ways), pdp=make(sets, ways), pd=make(sets, ways))


def _level_lines_4k(vpn: jax.Array):
    return (
        PML4_BASE + ((vpn >> 27) >> 3),
        PDP_BASE + ((vpn >> 18) >> 3),
        PD_BASE + ((vpn >> 9) >> 3),
        LEAF4_BASE + (vpn >> 3),
    )


def _level_lines_2m(vpn2: jax.Array):
    # for 2M pages the PD entry is the leaf; walk depth 3
    return (
        PML4_BASE + ((vpn2 >> 18) >> 3),
        PDP_BASE + ((vpn2 >> 9) >> 3),
        PD_BASE + (vpn2 >> 3),
    )


def _host_lines(gpn: jax.Array):
    return (
        HPML4_BASE + ((gpn >> 27) >> 3),
        HPDP_BASE + ((gpn >> 18) >> 3),
        HPD_BASE + ((gpn >> 9) >> 3),
        HLEAF_BASE + (gpn >> 3),
    )


def walk(
    h: Hier,
    pwcs: PWCs,
    vpn4k: jax.Array,
    is2m: jax.Array,
    now: jax.Array,
    pressure: jax.Array,
    tlb_aware: bool,
    lat: Lat,
    enable,
    dramc=None,
):
    """One native (or guest-PT-only) radix walk.

    Returns (hier, pwcs, cycles, n_dram).  `cycles` includes the PWC probe.
    All state updates are masked by `enable` (background walks pass True
    but callers discard `cycles`).  `dramc` gates the die-stacked
    DRAM-cache probe (None = absent, compiled out).
    """
    en = jnp.asarray(enable)
    vpn2 = vpn4k >> 9

    l4k = _level_lines_4k(vpn4k)
    l2m = _level_lines_2m(vpn2)
    # unified 4-slot access plan; slot i line + which walk depth it is
    lines = [
        jnp.where(is2m, l2m[0], l4k[0]),
        jnp.where(is2m, l2m[1], l4k[1]),
        jnp.where(is2m, l2m[2], l4k[2]),
        l4k[3],
    ]
    n_levels = jnp.where(is2m, 3, 4)

    # PWC probes: keys per level (2M pages use vpn2-derived upper keys)
    k_pml4 = jnp.where(is2m, vpn2 >> 18, vpn4k >> 27)
    k_pdp = jnp.where(is2m, vpn2 >> 9, vpn4k >> 18)
    k_pd = vpn4k >> 9  # only meaningful for 4K walks
    hit4, _, _ = lookup(pwcs.pml4, k_pml4)
    hit3, _, _ = lookup(pwcs.pdp, k_pdp)
    hit2, _, _ = lookup(pwcs.pd, k_pd)
    hit2 = hit2 & ~is2m  # PD entries of 2M walks are leaves, not PWC-cached

    # deepest covered level → first slot that must be fetched
    # 4K: pd hit → start 3 (leaf only); pdp → 2; pml4 → 1; none → 0
    # 2M: pdp hit → start 2 (PD leaf); pml4 → 1; none → 0
    start = jnp.where(
        hit2, 3, jnp.where(hit3, 2, jnp.where(hit4, 1, 0))
    )
    start = jnp.where(is2m, jnp.minimum(start, 2), start)

    cycles = jnp.where(en, jnp.int32(PWC_LAT), 0)
    n_dram = jnp.int32(0)
    for slot in range(4):
        slot_en = en & (slot >= start) & (slot < n_levels)
        h, c, d = access_pte(h, lines[slot], pressure, tlb_aware, lat,
                             slot_en, dramc=dramc)
        cycles = cycles + c
        n_dram = n_dram + d.astype(jnp.int32)

    # fill PWCs for the upper levels just walked
    p4, _, _ = insert_lru(pwcs.pml4, k_pml4, now, en & (start <= 0))
    p3, _, _ = insert_lru(pwcs.pdp, k_pdp, now, en & (start <= 1))
    p2, _, _ = insert_lru(pwcs.pd, k_pd, now, en & (start <= 2) & ~is2m)
    return h, PWCs(pml4=p4, pdp=p3, pd=p2), cycles, n_dram


def host_walk(h: Hier, gpn: jax.Array, pressure: jax.Array,
              tlb_aware: bool, lat: Lat, enable,
              dramc=None):
    """Host-PT walk (virt., no PWCs — paper Fig. 3 gives the host walker a
    nested TLB instead). 4 sequential PTE-line accesses through the caches.
    Returns (hier, cycles, n_dram, leaf_line)."""
    en = jnp.asarray(enable)
    lines = _host_lines(gpn)
    cycles = jnp.int32(0)
    n_dram = jnp.int32(0)
    for ln in lines:
        h, c, d = access_pte(h, ln, pressure, tlb_aware, lat, en,
                             dramc=dramc)
        cycles = cycles + c
        n_dram = n_dram + d.astype(jnp.int32)
    return h, cycles, n_dram, lines[3]
