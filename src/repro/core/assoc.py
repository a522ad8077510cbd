"""Set-associative structure primitives (branchless, scan-friendly).

Every TLB / PWC / cache in ``repro.core`` is a set of arrays holding its
elements in set-major order, ``(s, w)`` at flat position
``s * n_ways + w``, one array per simulation lane:

    tags  : int32  [n_sets * n_ways]   (stored as [rows, k * n_ways])
    valid : bool_  [n_sets * n_ways]
    meta  : int32  [n_sets * n_ways]   (LRU stamp or RRPV, policy-dependent)

The flat order is cut into *vector rows* of ``k`` whole sets, ``k`` the
largest power of two with ``k * n_ways <= 128`` (one TPU vector of
words), so a lane's array is ``[n_sets // k, k * n_ways]`` (``shape``).
``n_ways`` is a static field of the structure (pytree metadata, not an
array); ``k`` and ``n_sets`` follow from the array's shape.

Inside the access scan every access goes through a whole vector row:
``row`` reads set ``s``'s ways by gathering vector row ``s // k`` and
selecting its ways with a mask, ``put_row`` and ``put`` write by
selecting into that vector row and scattering it back, and ``get``
reads one element with a mask.  No access indexes inside a vector row
with a traced offset.  On the CPU, where such an offset is cheap, the
same order is read and written directly (``vector_rows``).

Why this layout (measured on a TPU v5e, PERF.md): stored as
``[..., sets, ways]`` the arrays sat sets-minor in the loop carry while
the row gather wanted them ways-minor, so the compiler copied each
whole array before every row read.  Stored as one flat vector, a row
read is a slice at a traced offset inside the minor dimension, which
the compiler turns into a loop over the lanes, one lane at a time.  A
gather or scatter of whole vector rows is neither.  Readers outside the
scan (tests, final histograms) take the 2-D view with ``rows``.

All operations take a *dynamic* set index (traced scalar) and return pure
functional updates.  Victims are chosen branchlessly:

  * LRU    — argmin timestamp (invalid ways forced to -1 so they win).
  * SRRIP  — age all RRPVs by (RRIP_MAX - max RRPV) then argmax; the
             TLB-aware variant re-rolls once onto non-TLB ways per the
             paper's Listing 1.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

RRIP_BITS = 2
RRIP_MAX = (1 << RRIP_BITS) - 1  # 3
VECTOR_WORDS = 128  # words in one vector row (the TPU's lane count)


# ------------------------------------------------------ vector-row access


def shape(n_sets: int, n_ways: int) -> tuple[int, int]:
    """One lane's array shape: ``[n_sets // k, k * n_ways]``, ``k`` the
    most whole sets (a power of two, at most ``n_sets``) whose ways fit
    in one vector row."""
    assert n_sets & (n_sets - 1) == 0, "n_sets must be a power of two"
    k = 1
    while 2 * k <= n_sets and 2 * k * n_ways <= VECTOR_WORDS:
        k *= 2
    return n_sets // k, k * n_ways


def pick(v: jax.Array, i: jax.Array) -> jax.Array:
    """``v[i]`` of a short vector, by mask and reduction."""
    hit = jnp.arange(v.shape[-1]) == i
    if v.dtype == jnp.bool_:
        return jnp.any(hit & v)
    return jnp.sum(jnp.where(hit, v, 0), dtype=v.dtype)


def setv(v: jax.Array, i: jax.Array, value) -> jax.Array:
    """``v`` with ``v[i] = value``, by mask."""
    return jnp.where(jnp.arange(v.shape[-1]) == i, value, v)


def _row_vec(x, s, n_ways):
    k = x.shape[-1] // n_ways
    vrow = x[s // k]
    if k == 1:
        return vrow
    sel = (jnp.arange(k) == s % k)[:, None]
    groups = vrow.reshape(k, n_ways)
    if x.dtype == jnp.bool_:
        return jnp.any(sel & groups, axis=0)
    return jnp.sum(jnp.where(sel, groups, 0), axis=0, dtype=x.dtype)


def _put_row_vec(x, s, r, n_ways):
    k = x.shape[-1] // n_ways
    if k == 1:
        return x.at[s].set(r)
    sel = (jnp.arange(x.shape[-1]) // n_ways) == s % k
    return x.at[s // k].set(jnp.where(sel, jnp.tile(r, k), x[s // k]))


def _get_vec(x, s, w, n_ways):
    k = x.shape[-1] // n_ways
    return pick(x[s // k], (s % k) * n_ways + w)


def _put_vec(x, s, w, value, n_ways):
    k = x.shape[-1] // n_ways
    return x.at[s // k].set(setv(x[s // k], (s % k) * n_ways + w, value))


# The CPU lowers a slice at a traced offset to a cheap gather, and takes
# about twice as long to lower, 1.7-2.7x to compile and 2.1-2.5x to run
# a simulation in the vector-row forms above (x86 host; PERF.md), so there
# the flat order is read and written directly.  Both forms give the same
# values; tests/test_vector_rows.py runs the vector-row forms through
# whole simulations on the CPU.

def _row_flat(x, s, n_ways):
    return jax.lax.dynamic_slice(x.reshape(-1), (s * n_ways,), (n_ways,))


def _put_row_flat(x, s, r, n_ways):
    return jax.lax.dynamic_update_slice(
        x.reshape(-1), r, (s * n_ways,)).reshape(x.shape)


def _get_flat(x, s, w, n_ways):
    return x.reshape(-1)[s * n_ways + w]


def _put_flat(x, s, w, value, n_ways):
    return x.reshape(-1).at[s * n_ways + w].set(value).reshape(x.shape)


def vector_rows() -> bool:
    """Whether the primitives move whole vector rows: on every platform
    but the CPU, where traced offsets are cheap (module docstring)."""
    return jax.default_backend() != "cpu"


def row(x: jax.Array, s: jax.Array, n_ways: int) -> jax.Array:
    """Set ``s``'s ``n_ways`` elements."""
    return (_row_vec if vector_rows() else _row_flat)(x, s, n_ways)


def put_row(x: jax.Array, s: jax.Array, n_ways: int,
            r: jax.Array) -> jax.Array:
    """``x`` with set ``s``'s elements replaced by ``r``."""
    return (_put_row_vec if vector_rows() else _put_row_flat)(
        x, s, r, n_ways)


def get(x: jax.Array, s: jax.Array, w: jax.Array, n_ways: int):
    """Element ``(s, w)``."""
    return (_get_vec if vector_rows() else _get_flat)(x, s, w, n_ways)


def put(x: jax.Array, s: jax.Array, w: jax.Array, n_ways: int, value):
    """``x`` with element ``(s, w)`` set to ``value``."""
    return (_put_vec if vector_rows() else _put_flat)(
        x, s, w, value, n_ways)


def rows(a):
    """The 2-D ``[..., n_sets, n_ways]`` view of a set-associative
    structure's arrays, for reading outside the scan (leading batch
    axes are kept).  The result is for reading only: its ``n_sets``
    and the primitives below assume the stored form."""
    return a._replace(**{
        f: getattr(a, f).reshape(getattr(a, f).shape[:-2] + (-1, a.n_ways))
        for f in a.SET_FIELDS})


def set_assoc(*set_fields: str):
    """Register a frozen dataclass as a pytree whose ``n_ways`` field is
    static metadata and every other field an array; ``set_fields`` name
    the set-major arrays (``rows`` reshapes those)."""
    def wrap(cls):
        cls = dataclasses.dataclass(frozen=True, eq=False)(cls)
        names = [f.name for f in dataclasses.fields(cls)]
        cls.SET_FIELDS = set_fields
        cls._replace = dataclasses.replace
        cls.n_sets = property(
            lambda self: (self.tags.shape[-2]
                          * (self.tags.shape[-1] // self.n_ways)),
            doc="Set count of the stored arrays (static).")
        return jax.tree_util.register_dataclass(
            cls, data_fields=[n for n in names if n != "n_ways"],
            meta_fields=["n_ways"])
    return wrap


@set_assoc("tags", "valid", "meta")
class Assoc:
    """A set-associative array structure (set-major vector rows)."""

    tags: jax.Array   # int32 [S/k, k*W]
    valid: jax.Array  # bool  [S/k, k*W]
    meta: jax.Array   # int32 [S/k, k*W] — LRU stamp or RRPV
    n_ways: int       # static: ways per set


def make(n_sets: int, n_ways: int) -> Assoc:
    shp = shape(n_sets, n_ways)
    return Assoc(
        tags=jnp.zeros(shp, jnp.int32),
        valid=jnp.zeros(shp, jnp.bool_),
        meta=jnp.zeros(shp, jnp.int32),
        n_ways=n_ways,
    )


def set_index(key: jax.Array, n_sets: int) -> jax.Array:
    """Low-order-bit set indexing (n_sets must be a power of two)."""
    assert n_sets & (n_sets - 1) == 0, "n_sets must be a power of two"
    return key & (n_sets - 1)


def lookup(a: Assoc, key: jax.Array):
    """Probe. Returns (hit: bool scalar, way: int scalar, set_idx)."""
    s = set_index(key, a.n_sets)
    hits = row(a.valid, s, a.n_ways) & (row(a.tags, s, a.n_ways) == key)
    hit = jnp.any(hits)
    way = jnp.argmax(hits)  # first hitting way (0 if none; guard with `hit`)
    return hit, way, s


# ---------------------------------------------------------------- LRU


def touch_lru(a: Assoc, s: jax.Array, way: jax.Array, now: jax.Array,
              enable=True) -> Assoc:
    """Stamp ``(s, way)`` with ``now`` (only where ``enable``)."""
    n = a.n_ways
    v = now if enable is True else jnp.where(
        enable, now, get(a.meta, s, way, n))
    return a._replace(meta=put(a.meta, s, way, n, v))


def lru_victim(a: Assoc, s: jax.Array) -> jax.Array:
    stamps = jnp.where(row(a.valid, s, a.n_ways), row(a.meta, s, a.n_ways),
                       jnp.int32(-1))
    return jnp.argmin(stamps)


def install(a: Assoc, s: jax.Array, w: jax.Array, key: jax.Array,
            now: jax.Array, enable=True):
    """Write ``key`` stamped ``now`` into way ``w`` of set ``s`` where
    ``enable``.  Returns (assoc, evicted_tag, evicted_valid)."""
    n = a.n_ways
    ev_tag = get(a.tags, s, w, n)
    ev_valid = get(a.valid, s, w, n)
    en = jnp.asarray(enable)
    new = a._replace(
        tags=put(a.tags, s, w, n, jnp.where(en, key, ev_tag)),
        valid=put(a.valid, s, w, n, jnp.where(en, True, ev_valid)),
        meta=put(a.meta, s, w, n,
                 jnp.where(en, now, get(a.meta, s, w, n))),
    )
    return new, ev_tag, ev_valid & en


def insert_lru(a: Assoc, key: jax.Array, now: jax.Array, enable=True):
    """Insert `key` at set(key), evicting LRU. Returns (assoc, evicted_tag,
    evicted_valid)."""
    s = set_index(key, a.n_sets)
    return install(a, s, lru_victim(a, s), key, now, enable)


# ------------------------------------------------- dynamic-size LRU views
#
# A structure allocated at its ladder-maximum shape can emulate any
# smaller power-of-two geometry with *traced* size parameters: the set
# index is masked with `set_mask` (= live_sets - 1) and victim selection
# is restricted to ways below `n_ways`.  Because inserts never touch
# ways >= n_ways, lookups and LRU choices are bit-identical to a
# statically allocated (live_sets, n_ways) structure — which is what
# lets one compiled step be vmapped across a whole size ladder.  The
# stored layout stays that of the allocated ``a.n_ways``.


def lookup_dyn(a: Assoc, key: jax.Array, set_mask: jax.Array,
               n_ways: jax.Array):
    """`lookup` against a dynamically sized view of `a`."""
    s = key & set_mask
    way_ok = jnp.arange(a.n_ways) < n_ways
    hits = (row(a.valid, s, a.n_ways) & (row(a.tags, s, a.n_ways) == key)
            & way_ok)
    return jnp.any(hits), jnp.argmax(hits), s


def insert_lru_dyn(a: Assoc, key: jax.Array, now: jax.Array,
                   set_mask: jax.Array, n_ways: jax.Array, enable=True):
    """`insert_lru` against a dynamically sized view of `a`."""
    s = key & set_mask
    way_ok = jnp.arange(a.n_ways) < n_ways
    stamps = jnp.where(way_ok,
                       jnp.where(row(a.valid, s, a.n_ways),
                                 row(a.meta, s, a.n_ways), jnp.int32(-1)),
                       jnp.iinfo(jnp.int32).max)
    return install(a, s, jnp.argmin(stamps), key, now, enable)


# ---------------------------------------------------------------- SRRIP

def srrip_age_and_pick(rrpv_row: jax.Array, valid_row: jax.Array,
                       way_ok: jax.Array | None = None):
    """Age the row so at least one way reaches RRIP_MAX and pick a victim.

    Invalid ways are preferred (treated as RRPV=+inf).  `way_ok` (bool
    per way, optional) restricts both the aging max and the victim pick
    to a dynamically sized view's live ways: masked-off ways contribute
    -1 (they never dominate the max and never win the argmax), which
    keeps the view bit-identical to a statically smaller row.  Returns
    (aged_row, victim_way).
    """
    eff = jnp.where(valid_row, rrpv_row, jnp.int32(RRIP_MAX + 1))
    if way_ok is not None:
        eff = jnp.where(way_ok, eff, jnp.int32(-1))
    bump = jnp.maximum(RRIP_MAX - jnp.max(eff), 0)
    aged = jnp.where(valid_row, rrpv_row + bump, rrpv_row)
    pick = jnp.where(valid_row, aged, jnp.int32(RRIP_MAX + 1))
    if way_ok is not None:
        pick = jnp.where(way_ok, pick, jnp.int32(-1))
    victim = jnp.argmax(pick)
    return aged, victim


def srrip_victim_tlb_aware(
    rrpv_row: jax.Array,
    valid_row: jax.Array,
    is_tlb_row: jax.Array,
    pressure: jax.Array,
    way_ok: jax.Array | None = None,
):
    """Paper Listing 1 `chooseReplacementCandidate`.

    If the SRRIP victim is a TLB block and translation pressure is high,
    make ONE more attempt: choose a non-TLB way at RRIP_MAX (post-aging).
    If none exists the TLB block is evicted after all.
    Returns (aged_row, victim_way).
    """
    aged, v0 = srrip_age_and_pick(rrpv_row, valid_row, way_ok)
    # invalid ways already won in v0 if present
    non_tlb_max = valid_row & (~is_tlb_row) & (aged >= RRIP_MAX)
    if way_ok is not None:
        non_tlb_max = non_tlb_max & way_ok
    have_alt = jnp.any(non_tlb_max)
    v1 = jnp.argmax(non_tlb_max)
    reroll = (pressure & pick(valid_row, v0) & pick(is_tlb_row, v0)
              & have_alt)
    victim = jnp.where(reroll, v1, v0)
    return aged, victim
