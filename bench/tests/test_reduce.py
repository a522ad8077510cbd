"""The reduction from a profiler trace to the per-layer metrics, on a
small hand-made event list and on a TPU trace recorded on the chip
(``fixtures/tpu_trace.json``, made by ``make_trace_fixture.py``: three
``bench.dispatch`` spans, four calls of ``fixture_step``, a 20 ms host
sleep inside the second span)."""
import os

import pytest

from bench import spec, trace
from bench.trace import Event

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_trace.json")
DEV = "/device:TPU:0"
HOST = "/host:CPU"
METRICS = ("sim.scan_us_per_lane_step", "sim.dispatch_host_ms",
           "device_idle_share.sim")


def hand_made():
    return [
        Event(DEV, "XLA Modules", "jit_run_systems(7)", 100, 300),
        Event(DEV, "XLA Modules", "jit_gather(3)", 500, 100),
        Event(HOST, "main", "bench.dispatch", 50, 650),
        Event(HOST, "main", "PjitFunction(run_systems)", 60, 60),
        Event(HOST, "main", "unpack", 610, 80),
        Event(HOST, "other", "elsewhere", 0, 1000),
    ]


OPS = [("%while.1 = (s32[], f32[8]{0}) while(%t), body=%b", 100, 200),
       ("%fusion.2 = f32[8]{0} fusion(%x), kind=kLoop", 120, 100),
       ("%fusion.3 = f32[8]{0} fusion(%y), kind=kLoop", 300, 100),
       ("%copy.4 = s32[28,6]{1,0} copy(%z)", 500, 100),
       ("%copy.5 = s32[28,6]{0,1} copy(%z)", 900, 100)]


def test_busy_is_the_union_of_module_executions():
    ev = hand_made()
    assert trace.device_planes(ev) == [DEV]
    assert trace.busy(ev, DEV, 50, 700) == [(100, 400), (500, 600)]
    assert trace.busy_ns(ev, DEV, 50, 700) == 400
    assert trace.busy_ns(ev, DEV, 0, 150) == 50  # clipped to the window


def test_program_time_sums_its_module_events():
    ev = hand_made()
    assert trace.program_ns(ev, DEV, "run_systems", 0, 1000) == 300
    assert trace.program_ns(ev, DEV, "run_system", 0, 1000) == 0
    assert trace.program_ns(ev, DEV, "run_systems", 200, 1000) == 0


def test_idle_gaps_are_named_by_span_and_host_event():
    ev = hand_made()
    span = trace.spans(ev, "bench.dispatch")[0]
    assert trace.idle_gaps(ev, DEV, span) == [
        ["bench.dispatch/host", 100e-9],
        ["bench.dispatch/unpack", 100e-9],
        ["bench.dispatch/PjitFunction(run_systems)", 50e-9]]


def test_holds_program_needs_its_whole_module_in_each_span():
    ev = hand_made()
    assert trace.holds_program(ev, "bench.dispatch", "run_systems")
    # a program whose module event was dropped with its trace buffer
    lost = [e for e in ev if e.name != "jit_run_systems(7)"]
    assert not trace.holds_program(lost, "bench.dispatch", "run_systems")
    # a module that runs past the span's end
    late = [e._replace(dur=700) if e.name == "jit_run_systems(7)" else e
            for e in ev]
    assert not trace.holds_program(late, "bench.dispatch", "run_systems")
    # no device plane
    host = [e for e in ev if e.plane == HOST]
    assert not trace.holds_program(host, "bench.dispatch", "run_systems")


def test_op_self_times_by_kind_inside_the_window():
    got = trace.self_times(iter(OPS), 0, 800)
    # while.1 holds fusion.2 for 100 of its 200 ns; copy.5 is outside
    assert got == {"while (s32[], f32[8])": 100, "fusion f32[8]": 200,
                   "copy s32[28,6]": 100}
    top = trace.top_ops({DEV: got, "/device:TPU:1": {"copy s32[28,6]": 300}})
    assert top[0] == ["copy s32[28,6]", 200e-9]  # averaged over 2 chips


def test_metric_readers_on_a_hand_made_run():
    run = trace.TracedRun(hand_made(), "bench.dispatch", "run_systems",
                          lanes_per_chip=5, seg_accesses=10)
    read = {m: spec.metric(m).read(run) for m in METRICS}
    assert read["sim.scan_us_per_lane_step"] == pytest.approx(300 / 1e3 / 50)
    assert read["sim.dispatch_host_ms"] == pytest.approx(250 / 1e6)
    assert read["device_idle_share.sim"] == pytest.approx(100 * 250 / 650)


def test_readers_return_nothing_without_a_device():
    ev = [e for e in hand_made() if e.plane == HOST]
    run = trace.TracedRun(ev, "bench.dispatch", "run_systems", 5, 10)
    for m in METRICS:
        assert spec.metric(m).read(run) is None


@pytest.fixture(scope="module")
def recorded():
    return trace.load(FIXTURE)


def test_recorded_trace_planes_spans_and_programs(recorded):
    assert trace.device_planes(recorded) == [DEV]
    d = trace.spans(recorded, "bench.dispatch")
    assert len(d) == 3
    modules = [e for e in recorded if e.line == trace.MODULES_LINE]
    assert len(modules) == 4
    assert trace.program_ns(recorded, DEV, "fixture_step", 0, 10**12) == \
        sum(e.dur for e in modules)
    assert trace.program_ns(recorded, DEV, "run_systems", 0, 10**12) == 0


def test_recorded_trace_names_the_host_sleep_gap(recorded):
    second = trace.spans(recorded, "bench.dispatch")[1]
    name, secs = trace.idle_gaps(recorded, DEV, second)[0]
    assert name == "bench.dispatch/fixture.host_sleep"
    assert 0.019 < secs < 0.0215


def test_recorded_trace_readers(recorded):
    # in this trace the device clock runs about 1.0 ms ahead of the
    # host's, more than these 2 ms spans leave before their first call:
    # a span then holds no module of its program, and the scan reader
    # reads nothing rather than a wrong number
    run = trace.TracedRun(recorded, "bench.dispatch", "fixture_step",
                          lanes_per_chip=1, seg_accesses=8)
    assert spec.metric(METRICS[0]).read(run) is None
    assert spec.metric(METRICS[2]).read(run) > 95
    # with the device events moved 1.1 ms later, every span holds its calls
    moved = [e._replace(start=e.start + 1_100_000)
             if e.plane == DEV else e for e in recorded]
    run = trace.TracedRun(moved, "bench.dispatch", "fixture_step", 1, 8)
    read = {m: spec.metric(m).read(run) for m in METRICS}
    modules = [e.dur for e in moved if e.line == trace.MODULES_LINE]
    assert run.busy_ns() == sum(modules)
    lo, hi = run.window
    assert read["device_idle_share.sim"] == pytest.approx(
        100 * (1 - sum(modules) / (hi - lo)))
    assert read["sim.scan_us_per_lane_step"] == pytest.approx(
        sum(modules) / 3 / 1e3 / 8)
    assert read["sim.dispatch_host_ms"] == pytest.approx(
        (sum(d.dur for d in run.dispatches) - sum(modules)) / 3 / 1e6)


def test_recorded_trace_op_self_times(recorded):
    ops = sorted(((e.name, e.start, e.dur) for e in recorded
                  if e.line == trace.OPS_LINE), key=lambda o: o[1])
    got = trace.self_times(iter(ops), 0, 10**12)
    kinds = {k.split(" ")[0] for k in got}
    assert {"while", "fusion", "copy"} <= kinds
    # self times tile the ops' union: no op counted twice
    union = trace.union(((s, s + d) for _, s, d in ops), 0, 10**12)
    assert sum(got.values()) == sum(e - s for s, e in union)
