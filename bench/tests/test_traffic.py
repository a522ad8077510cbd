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
