"""The set-major set-associative primitives against a 2-D model.

``repro.core.assoc`` and ``repro.core.caches`` store every structure in
flat set-major order, element ``(s, w)`` at ``s * n_ways + w``, cut into
vector rows of whole sets (``assoc.shape``).  The model below keeps
plain ``[n_sets, n_ways]`` NumPy arrays and spells out each operation's
semantics directly.  Random operation sequences, on
several geometries and on dynamically sized views with fewer live sets
or ways, must leave both in the same state and return the same values,
bit for bit, in both access forms (``assoc.vector_rows``).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import assoc, caches
from repro.core.caches import BT_DATA, BT_NTLB, BT_TLB2, BT_TLB4, L2Geom

SEED = 20261018
RRIP_MAX = 3
INT_MAX = np.iinfo(np.int32).max

# (allocated sets, allocated ways, live sets, live ways); live None = the
# static path
GEOMS = [(1, 4, None, None), (8, 4, None, None), (16, 12, None, None),
         (64, 16, None, None), (16, 8, 4, 8), (16, 8, 16, 3),
         (32, 16, 8, 5)]


@pytest.fixture(params=[True, False], ids=["vector_rows", "flat"],
                autouse=True)
def access_form(request, monkeypatch):
    """Run every test with both access forms: whole vector rows (the
    chip's) and direct flat offsets (the CPU's)."""
    monkeypatch.setattr(assoc, "vector_rows", lambda: request.param)
    return request.param


def _ids(g):
    return "s{}w{}".format(*g[:2]) + ("" if g[2] is None
                                      else "_live{}x{}".format(*g[2:]))


class Model:
    """One set-associative structure as 2-D NumPy arrays."""

    def __init__(self, sets, ways, fields=("tags", "valid", "meta")):
        self.sets, self.ways = sets, ways
        for f in fields:
            setattr(self, f, np.zeros((sets, ways),
                                      bool if f == "valid" else np.int32))

    def set_of(self, key, mask):
        return key & (self.sets - 1 if mask is None else mask)

    def way_ok(self, live):
        return (np.ones(self.ways, bool) if live is None
                else np.arange(self.ways) < live)

    # -------------------------------------------------------- LRU
    def lookup(self, key, mask=None, live=None):
        s = self.set_of(key, mask)
        hits = self.valid[s] & (self.tags[s] == key) & self.way_ok(live)
        return bool(hits.any()), int(np.argmax(hits)), s

    def touch(self, s, w, now, en):
        if en:
            self.meta[s, w] = now

    def insert(self, key, now, en, mask=None, live=None):
        s = self.set_of(key, mask)
        stamps = np.where(self.valid[s], self.meta[s], -1)
        if live is not None:
            stamps = np.where(self.way_ok(live), stamps, INT_MAX)
        w = int(np.argmin(stamps))
        ev_tag, ev_valid = int(self.tags[s, w]), bool(self.valid[s, w])
        if en:
            self.tags[s, w], self.valid[s, w], self.meta[s, w] = key, True, now
        return ev_tag, ev_valid and en


def _srrip(rrpv, valid, way_ok):
    eff = np.where(way_ok, np.where(valid, rrpv, RRIP_MAX + 1), -1)
    aged = np.where(valid, rrpv + max(RRIP_MAX - eff.max(), 0), rrpv)
    pick = np.where(way_ok, np.where(valid, aged, RRIP_MAX + 1), -1)
    return aged.astype(np.int32), int(np.argmax(pick))


class L2Model(Model):
    """The L2 cache: SRRIP, block types, reuse counts and histograms."""

    def __init__(self, sets, ways):
        super().__init__(sets, ways,
                         ("tags", "valid", "rrpv", "btype", "reuse"))
        self.hist_d = np.zeros(caches.REUSE_BUCKETS, np.int32)
        self.hist_t = np.zeros(caches.REUSE_BUCKETS, np.int32)
        self.live = {BT_TLB4: 0, BT_TLB2: 0, BT_NTLB: 0}

    def l2_lookup(self, key, bt, mask=None):
        s = self.set_of(key, mask)
        hits = self.valid[s] & (self.tags[s] == key) & (self.btype[s] == bt)
        return bool(hits.any()), int(np.argmax(hits)), s

    def l2_touch(self, s, w, pressure, tlb_aware, en):
        if en:
            dec = 3 if (self.btype[s, w] != BT_DATA and pressure
                        and tlb_aware) else 1
            self.rrpv[s, w] = max(self.rrpv[s, w] - dec, 0)
            self.reuse[s, w] += 1

    def l2_insert(self, key, bt, pressure, tlb_aware, en, mask=None,
                  live=None):
        s = self.set_of(key, mask)
        ok = self.way_ok(live)
        aged, w = _srrip(self.rrpv[s], self.valid[s], ok)
        if tlb_aware:
            is_tlb = self.btype[s] != BT_DATA
            alt = self.valid[s] & ~is_tlb & (aged >= RRIP_MAX) & ok
            if pressure and self.valid[s, w] and is_tlb[w] and alt.any():
                w = int(np.argmax(alt))
        if en and self.valid[s, w]:  # the evicted block's bookkeeping
            old = int(self.btype[s, w])
            bucket = min(int(self.reuse[s, w]), caches.REUSE_BUCKETS - 1)
            (self.hist_d if old == BT_DATA else self.hist_t)[bucket] += 1
            if old != BT_DATA:
                self.live[old] -= 1
        aged[w] = 0 if (bt != BT_DATA and pressure and tlb_aware) \
            else RRIP_MAX - 1
        if en:
            self.tags[s, w], self.valid[s, w] = key, True
            self.rrpv[s], self.btype[s, w], self.reuse[s, w] = aged, bt, 0
            if bt != BT_DATA:
                self.live[bt] += 1

    def l2_retag(self, key, bt, pressure, tlb_aware, en, mask=None,
                 live=None):
        exists = self.l2_lookup(key, bt, mask)[0]
        self.l2_insert(key, bt, pressure, tlb_aware, en and not exists,
                       mask, live)

    def l3_access(self, key, en):
        hit, w, s = self.lookup(key)
        if hit and en:
            self.rrpv[s, w] = 0
        aged, vw = _srrip(self.rrpv[s], self.valid[s],
                          np.ones(self.ways, bool))
        aged[vw] = RRIP_MAX - 1
        if en and not hit:
            self.tags[s, vw], self.valid[s, vw] = key, True
            self.rrpv[s] = aged
        return hit


def _same(got, model, fields, names=None):
    view = assoc.rows(got)
    for f, m in zip(fields, names or fields):
        assert np.array_equal(np.asarray(getattr(view, f)),
                              getattr(model, m)), f


@pytest.mark.parametrize("geom", GEOMS, ids=_ids)
def test_lru_primitives_match_2d_model(geom):
    sets, ways, live_sets, live_ways = geom
    dyn = live_sets is not None
    mask = None if not dyn else live_sets - 1
    rng = np.random.default_rng([SEED, sets, ways, live_sets or 0])
    a, m = assoc.make(sets, ways), Model(sets, ways)
    assert a.tags.size == sets * ways and a.n_sets == sets
    for t in range(250):
        key = int(rng.integers(0, 4 * sets * ways))
        now = t + 1
        op = rng.random()
        if op < 0.45:
            if dyn:
                h, w, s = assoc.lookup_dyn(a, jnp.int32(key),
                                           jnp.int32(mask),
                                           jnp.int32(live_ways))
            else:
                h, w, s = assoc.lookup(a, jnp.int32(key))
            want = m.lookup(key, mask, live_ways)
            assert (bool(h), int(w), int(s)) == want, t
            en = bool(rng.random() < 0.7)
            a = assoc.touch_lru(a, s, w, jnp.int32(now), jnp.bool_(en))
            m.touch(want[2], want[1], now, en)
        else:
            en = bool(rng.random() < 0.9)
            if dyn:
                a, ev_t, ev_v = assoc.insert_lru_dyn(
                    a, jnp.int32(key), jnp.int32(now), jnp.int32(mask),
                    jnp.int32(live_ways), jnp.bool_(en))
            else:
                a, ev_t, ev_v = assoc.insert_lru(
                    a, jnp.int32(key), jnp.int32(now), jnp.bool_(en))
            want_t, want_v = m.insert(key, now, en, mask, live_ways)
            assert (int(ev_t), bool(ev_v)) == (want_t, want_v), t
    _same(a, m, ("tags", "valid", "meta"))
    assert m.valid.any()


@pytest.mark.parametrize("tlb_aware", [True, False])
@pytest.mark.parametrize("geom", GEOMS, ids=_ids)
def test_l2_primitives_match_2d_model(geom, tlb_aware):
    sets, ways, live_sets, live_ways = geom
    dyn = live_sets is not None
    mask = None if not dyn else live_sets - 1
    g = None if not dyn else L2Geom(set_mask=jnp.int32(mask),
                                    n_ways=jnp.int32(live_ways))
    rng = np.random.default_rng([SEED, sets, ways, live_sets or 0,
                                 tlb_aware])
    l2, m = caches.make_l2(sets, ways), L2Model(sets, ways)
    bts = [BT_DATA, BT_TLB4, BT_TLB2, BT_NTLB]
    for t in range(250):
        key = int(rng.integers(0, 3 * sets * ways))
        bt = bts[rng.integers(0, len(bts))]
        pressure = bool(rng.random() < 0.5)
        en = bool(rng.random() < 0.85)
        op = rng.random()
        if op < 0.35:
            h, w, s = caches.l2_lookup(l2, jnp.int32(key), bt, g)
            want = m.l2_lookup(key, bt, mask)
            assert (bool(h), int(w), int(s)) == want, t
            l2 = caches.l2_touch(l2, s, w, jnp.bool_(pressure), tlb_aware,
                                 jnp.bool_(en and want[0]))
            m.l2_touch(want[2], want[1], pressure, tlb_aware,
                       en and want[0])
        elif op < 0.75:
            l2 = caches.l2_insert(l2, jnp.int32(key), bt,
                                  jnp.bool_(pressure), tlb_aware,
                                  jnp.bool_(en), g)
            m.l2_insert(key, bt, pressure, tlb_aware, en, mask, live_ways)
        else:
            bt = BT_TLB4 if bt == BT_DATA else bt
            l2 = caches.l2_retag_to_tlb(l2, jnp.int32(key), bt,
                                        jnp.bool_(pressure), tlb_aware,
                                        jnp.bool_(en), g)
            m.l2_retag(key, bt, pressure, tlb_aware, en, mask, live_ways)
    _same(l2, m, ("tags", "valid", "rrpv", "btype", "reuse"))
    assert np.array_equal(np.asarray(l2.hist_reuse_data), m.hist_d)
    assert np.array_equal(np.asarray(l2.hist_reuse_tlb), m.hist_t)
    assert (int(l2.n_tlb4), int(l2.n_tlb2), int(l2.n_ntlb)) == (
        m.live[BT_TLB4], m.live[BT_TLB2], m.live[BT_NTLB])
    if dyn:  # nothing lands outside the live view
        outside = np.ones((sets, ways), bool)
        outside[:live_sets, :live_ways] = False
        assert not m.valid[outside].any()


@pytest.mark.parametrize("geom", [g for g in GEOMS if g[2] is None],
                         ids=_ids)
def test_l3_access_matches_2d_model(geom):
    sets, ways = geom[:2]
    rng = np.random.default_rng([SEED, sets, ways, 3])
    l3, m = assoc.make(sets, ways), L2Model(sets, ways)
    hits = 0
    for t in range(250):
        key = int(rng.integers(0, 3 * sets * ways))
        en = bool(rng.random() < 0.85)
        l3, hit = caches.l3_access(l3, jnp.int32(key), jnp.bool_(en))
        want = m.l3_access(key, en)
        assert bool(hit) == want, t
        hits += want
    _same(l3, m, ("tags", "valid", "meta"), ("tags", "valid", "rrpv"))
    assert hits > 0
