"""The copied generator: fixed segments for a seed, and segments that
tile one stream."""
import json
import os
import zlib

import numpy as np
import pytest

from bench import gen, spec

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "traffic_segments.json")


@pytest.fixture(scope="module")
def specs():
    return {s.name: s for s in gen.workload_specs(spec.traffic("all11.s128"))}


def test_segments_match_the_recorded_fixture(specs):
    with open(FIXTURE, encoding="utf-8") as f:
        fx = json.load(f)
    for want in fx["segments"]:
        st = gen.Stream(specs[want["workload"]], fx["seed"],
                        fx["seg_accesses"], fx["block_accesses"])
        sg = st.segment(want["index"])
        got = {k: zlib.crc32(sg[k].tobytes()) for k in want["crc32"]}
        assert got == want["crc32"], (want["workload"], want["index"])
        assert sg["vpn"][:6].tolist() == want["vpn_head"]
        assert sg["line"][:6].tolist() == want["line_head"]
        assert int(sg["is2m"].sum()) == want["is2m_count"]


@pytest.mark.parametrize("seg", [128, 512])
def test_consecutive_segments_tile_one_stream(specs, seg):
    block = 2048
    st = gen.Stream(specs["pr"], 2**31 + 3, seg, block)
    n = 2 * block // seg  # two blocks' worth
    joined = np.concatenate([st.segment(j)["vpn"] for j in range(n)])
    whole = np.concatenate([gen.block(specs["pr"], block, 2**31 + 3, b)["vpn"]
                            for b in range(2)])
    assert np.array_equal(joined, whole)
    # a longer segment is the concatenation of the shorter ones
    wide = gen.Stream(specs["pr"], 2**31 + 3, 2 * seg, block)
    assert np.array_equal(wide.segment(1)["line"], np.concatenate(
        [st.segment(2)["line"], st.segment(3)["line"]]))


def test_seeds_and_workloads_give_different_streams(specs):
    a = gen.block(specs["bc"], 512, 1, 0)["vpn"]
    assert not np.array_equal(a, gen.block(specs["bc"], 512, 2, 0)["vpn"])
    assert not np.array_equal(a, gen.block(specs["bc"], 512, 1, 1)["vpn"])
    assert not np.array_equal(a, gen.block(specs["cc"], 512, 1, 0)["vpn"])


def test_dispatch_plan_walks_the_workloads_in_order():
    cols = [gen.dispatch_plan(11, 6, k) for k in range(11)]
    flat = [c for d in cols for c in d]
    # 11 dispatches of 6 read every workload's segments 0..5 in order
    for w in range(11):
        assert [j for ww, j in flat if ww == w] == list(range(6))
    assert cols[1][:5] == [(6, 0), (7, 0), (8, 0), (9, 0), (10, 0)]
    assert cols[1][5] == (0, 1)


def test_segment_length_must_divide_the_block(specs):
    with pytest.raises(ValueError):
        gen.Stream(specs["bc"], 0, 100, 16384)


def test_ipa_and_dtypes(specs):
    sg = gen.Stream(specs["xs"], 9, 128, 16384).segment(0)
    assert sg["vpn"].dtype == np.int32 and sg["line"].dtype == np.int32
    assert sg["is2m"].dtype == np.bool_
    assert np.all(sg["ipa"] == np.float32(specs["xs"].ipa))
    st = gen.stack([sg, sg])
    assert st["vpn"].shape == (128, 2)


# ---------------------------------------------------------------- cores
PINS = os.path.join(os.path.dirname(__file__), "fixtures",
                    "single_core_outputs.json")
MIX4 = os.path.join(os.path.dirname(__file__), "fixtures", "mix4.json")


@pytest.fixture(scope="module")
def mix4():
    with open(MIX4, encoding="utf-8") as f:
        traffic = json.load(f)
    traffic.update(seg_accesses=128, block_accesses=512)
    return traffic


def test_mix_segments_are_t_w_c(mix4):
    from bench import cell as cellmod

    c = cellmod.Cell({}, mix4, 2**31 + 21, 1, "")
    tr = c.segments(0)
    assert set(tr) == {"vpn", "is2m", "line", "ipa", "core"}
    for k, dt in (("vpn", np.int32), ("line", np.int32), ("is2m", np.bool_),
                  ("ipa", np.float32), ("core", np.int32)):
        assert tr[k].shape == (128, 6, 4) and tr[k].dtype == dt, k
    assert np.array_equal(tr["core"], np.broadcast_to(np.arange(4),
                                                      (128, 6, 4)))
    ipa = {s["name"]: s["ipa"] for s in mix4["workloads"]}
    for w, mix in enumerate(mix4["mixes"][:6]):
        assert np.all(tr["ipa"][:, w] == np.float32([ipa[n] for n in mix]))


def test_mix_plan_and_streams_tile_each_core(mix4):
    """Dispatch k takes mixes 6k..6k+5 mod 11; the columns a (mix, core)
    stream lands in, read in dispatch order, tile that stream."""
    from bench import cell as cellmod

    seed = 2**31 + 22
    c = cellmod.Cell({}, mix4, seed, 1, "")
    assert [m for m, _ in gen.dispatch_plan(11, 6, 1)] == [6, 7, 8, 9, 10, 0]
    seen: dict = {}
    for k in range(8):  # mix 0 comes round in dispatches 0, 1, 3, 5, 7
        tr = c.segments(k)
        for w, (m, _) in enumerate(gen.dispatch_plan(11, 6, k)):
            seen.setdefault(m, []).append(tr["vpn"][:, w])
    specs = {s.name: s for s in gen.workload_specs(mix4)}
    for cc, name in enumerate(mix4["mixes"][0]):
        got = np.concatenate([v[:, cc] for v in seen[0]])
        want = np.concatenate([gen.block(specs[name], 512, seed, b, (0, cc))
                               ["vpn"] for b in range(2)])[:len(got)]
        assert len(got) == 5 * 128 and np.array_equal(got, want)


def test_two_cores_on_one_workload_differ(mix4):
    """Mix 2 runs bc on core 3, mix 0 on core 0: two streams."""
    spec_bc = gen.workload_specs(mix4)[0]
    assert mix4["mixes"][0][0] == mix4["mixes"][2][3] == spec_bc.name
    a = gen.block(spec_bc, 512, 5, 0, (0, 0))["vpn"]
    b = gen.block(spec_bc, 512, 5, 0, (2, 3))["vpn"]
    assert not np.array_equal(a, b)
    assert not np.array_equal(a, gen.block(spec_bc, 512, 5, 0)["vpn"])


def test_every_workload_runs_on_four_core_slots(mix4):
    names = [s["name"] for s in mix4["workloads"]]
    assert mix4["mixes"] == [[names[(4 * k + i) % 11] for i in range(4)]
                             for k in range(11)]
    slots = [n for mix in mix4["mixes"] for n in mix]
    assert all(slots.count(n) == 4 for n in names)


@pytest.mark.parametrize("name", ["all11.s128", "all11.s64"])
def test_single_core_segments_as_recorded(name):
    """A file without ``cores`` stacks the same [T, W] bytes as before
    the core axis existed."""
    from bench import cell as cellmod

    with open(PINS, encoding="utf-8") as f:
        pins = json.load(f)["segments"]
    c = cellmod.Cell({}, spec.traffic(name), 2**31 + 7, 1, "")
    for k, want in enumerate(pins[name]):
        tr = c.segments(k)
        assert list(tr["vpn"].shape) == pins[name + ".shape"]
        assert {n: zlib.crc32(v.tobytes()) for n, v in tr.items()} == want


def test_single_core_sample_as_recorded():
    from bench import check

    with open(PINS, encoding="utf-8") as f:
        pins = json.load(f)["sample"]
    got = check.sample(*pins["args"])
    assert [list(p) for p in got] == pins["picks"]
