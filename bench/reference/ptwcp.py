"""PTW Cost Predictor (paper §5.2, Fig. 15/16, Table 2).

The production predictor is the 4-comparator bounding-box circuit: a page
is predicted costly-to-translate iff its (PTW cost, PTW frequency) counter
pair lies inside the box spanning (1,1)..(12,7):

    1 <= cost <= 12   (4-bit saturating counter, +1 per walk touching DRAM)
    1 <= freq <= 7    (3-bit saturating counter, +1 per walk)

Counters live in otherwise-unused PTE bits; here they are dense per-page
uint8 arrays updated by the MMU after every demand walk.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

FREQ_BITS = 3
COST_BITS = 4
FREQ_MAX = (1 << FREQ_BITS) - 1  # 7
COST_MAX = (1 << COST_BITS) - 1  # 15

# bounding box from Fig. 16 — (cost, freq) in (1,1)..(12,7)
BOX_COST_LO, BOX_COST_HI = 1, 12
BOX_FREQ_LO, BOX_FREQ_HI = 1, 7


class PageCounters(NamedTuple):
    freq: jax.Array  # uint8 [n_pages]
    cost: jax.Array  # uint8 [n_pages]


def make_counters(n_pages: int) -> PageCounters:
    return PageCounters(
        freq=jnp.zeros((n_pages,), jnp.uint8),
        cost=jnp.zeros((n_pages,), jnp.uint8),
    )


def update_counters(pc: PageCounters, page: jax.Array, had_dram, enable
                    ) -> PageCounters:
    """MMU updates after a demand PTW (saturating)."""
    en = jnp.asarray(enable)
    f = pc.freq[page]
    c = pc.cost[page]
    nf = jnp.minimum(f.astype(jnp.int32) + 1, FREQ_MAX).astype(jnp.uint8)
    nc = jnp.minimum(
        c.astype(jnp.int32) + jnp.asarray(had_dram).astype(jnp.int32), COST_MAX
    ).astype(jnp.uint8)
    return PageCounters(
        freq=pc.freq.at[page].set(jnp.where(en, nf, f)),
        cost=pc.cost.at[page].set(jnp.where(en, nc, c)),
    )


def predict(freq: jax.Array, cost: jax.Array) -> jax.Array:
    """The comparator tree — one cycle, 4 comparators, 4 threshold regs."""
    f = freq.astype(jnp.int32)
    c = cost.astype(jnp.int32)
    return (
        (c >= BOX_COST_LO) & (c <= BOX_COST_HI)
        & (f >= BOX_FREQ_LO) & (f <= BOX_FREQ_HI)
    )


def predict_page(pc: PageCounters, page: jax.Array) -> jax.Array:
    return predict(pc.freq[page], pc.cost[page])
