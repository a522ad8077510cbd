"""Device time by stage scope and idle time by program span
(``bench/scopes.py``), the ``sim.unpack_ms`` reader, and the readers
that were there before, pinned on the recorded TPU trace."""
import glob
import os

import pytest

from bench import scopes, spec, trace
from bench.trace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_trace.json")


# ------------------------------------------------------------ op_name

@pytest.mark.parametrize("op_name,scope", [
    ("jit(run_systems)/while/body/closed_call/vmap(l2_tlb.lookup)/add",
     "l2_tlb.lookup"),
    ("jit(run_systems)/shard_map/vmap(one_system)/while/body/closed_call/"
     "vmap(vmap(victima.fill))/jit(_where)/select_n", "victima.fill"),
    # nested scopes: the innermost wins
    ("jit(run_systems)/vmap(ptw2d.lookup)/vmap(data)/gather", "data"),
    ("jit(run_systems)/init/broadcast_in_dim", "init"),
    ("jit(run_systems)/vmap(finalize)/jvp(vmap(stats))/add", "stats"),
    # merged names: the first part that names a scope
    ("broadcast_in_dim;jit(run_systems)/vmap(pom.fill)/mul", "pom.fill"),
    # none: wrappers, jitted helpers and dotted argument names are not scopes
    ("jit(run_systems)/while/body/closed_call", None),
    ("jit(run_systems)/vmap(one_system)/jit(data)/add", None),
    ("jit(run_systems)/d.l2_set_mask", None),
    ("", None),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_groups_and_instruction_names():
    assert scopes.group_of("l3_tlb.fill") == "tlb"
    assert scopes.group_of("victima.lookup") == "victima"
    assert scopes.group_of("rev.lookup") == "walk"
    assert scopes.group_of("data") == "cache"
    for s in ("stats", "init", "finalize", scopes.UNSCOPED):
        assert scopes.group_of(s) is None
    assert scopes.instruction(
        "%copy.7 = s32[28,6]{1,0:T(8,128)} copy(s32[28,6] %p)") == "copy.7"
    assert scopes.instruction("copy-start.2") == "copy-start.2"


# ----------------------------------------------------- self time

def test_self_times_by_op_keeps_ops_inside_the_windows():
    ops = [("%while.1 = (s32[]) while(%t)", 100, 90),
           ("%fusion.2 = s32[8]{0} fusion(%x)", 110, 30),
           ("%copy.3 = s32[28,6]{1,0} copy(%y)", 150, 30),
           ("%fusion.4 = s32[8]{0} fusion(%z)", 192, 8),
           ("%copy.3 = s32[28,6]{1,0} copy(%y)", 310, 20),   # other module
           ("%fusion.2 = s32[8]{0} fusion(%x)", 460, 100)]   # runs past
    windows = [(100, 200, "jit_run_systems(7)"),
               (300, 350, "jit_gather(3)"),
               (450, 500, "jit_run_systems(7)")]
    got = scopes.self_times_by_op(iter(ops), windows)
    assert got == {("jit_run_systems(7)", ops[0][0]): 30,
                   ("jit_run_systems(7)", ops[1][0]): 30,
                   ("jit_run_systems(7)", ops[2][0]): 30,
                   ("jit_run_systems(7)", ops[3][0]): 8,
                   ("jit_gather(3)", ops[4][0]): 20}
    names = {"jit_run_systems(7)": {
        "fusion.2": "jit(run_systems)/vmap(l2_tlb.lookup)/add",
        "fusion.4": "jit(run_systems)/vmap(finalize)/add",
        "while.1": "jit(run_systems)/while"}}
    assert scopes.by_scope(got, names) == {
        "l2_tlb.lookup": {"fusion s32[8]": 30},
        "finalize": {"fusion s32[8]": 8},
        scopes.UNSCOPED: {"while (s32[])": 30, "copy s32[28,6]": 50}}


# ------------------------------------------ a hand-made XSpace, read

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, payload: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _hlo_proto(op_names: dict) -> bytes:
    """An HloProto holding one computation of the named instructions."""
    ins = b"".join(_field(2, _field(1, n.encode()) + _field(2, b"add")
                          + _field(7, _field(1, b"add")
                                   + _field(2, op.encode())))
                   for n, op in op_names.items())
    comp = _field(1, b"body") + ins
    return _field(1, _field(1, b"jit_run_systems") + _field(3, comp))


OPS = [("%while.1 = (s32[]) while(%t)", 0, 90),
       ("%fusion.2 = s32[8]{0} fusion(%x)", 10, 30),
       ("%copy.3 = s32[28,6]{1,0} copy(%y)", 50, 30),
       ("%fusion.4 = s32[8]{0} fusion(%z)", 92, 8),
       ("%copy.3 = s32[28,6]{1,0} copy(%y)", 210, 20),
       ("%fusion.2 = s32[8]{0} fusion(%x)", 510, 30)]
MODULES = [("jit_run_systems(7)", 0, 100), ("jit_gather(3)", 200, 50),
           ("jit_run_systems(7)", 500, 100)]


def _xspace(tmp_path, with_hlo: bool = True) -> str:
    """A TPU-shaped trace: a bench.dispatch span (900-1400 ns) holding
    one run_systems execution and one other module's; a later
    run_systems execution outside it."""
    from jax.profiler import ProfileData

    meta, ids = [], {}

    def mid(name):
        if name not in ids:
            ids[name] = len(ids) + 1
            meta.append(f'event_metadata {{ key: {ids[name]} value {{ '
                        f'id: {ids[name]} name: "{name}" }} }}')
        return ids[name]

    def line(i, name, evs):
        body = " ".join(f"events {{ metadata_id: {mid(n)} offset_ps: "
                        f"{s * 1000} duration_ps: {d * 1000} }}"
                        for n, s, d in evs)
        return (f'lines {{ id: {i} name: "{name}" timestamp_ns: 1000 '
                f'{body} }}')

    dev = line(1, trace.MODULES_LINE, MODULES) + line(2, trace.OPS_LINE, OPS)
    dev_plane = f'planes {{ id: 1 name: "{DEV}" {dev} {" ".join(meta)} }}'
    host = (f'planes {{ id: 2 name: "{HOST}" lines {{ id: 3 name: "main" '
            f'timestamp_ns: 900 events {{ metadata_id: 1 offset_ps: 0 '
            f'duration_ps: 500000 }} }} event_metadata {{ key: 1 value {{ '
            f'id: 1 name: "bench.dispatch" }} }} }}')
    hlo = _hlo_proto({
        "fusion.2": "jit(run_systems)/while/body/vmap(l2_tlb.lookup)/add",
        "fusion.4": "jit(run_systems)/vmap(finalize)/add",
        "while.1": "jit(run_systems)/while"})
    blob = "".join(f"\\{b:03o}" for b in hlo)
    metadata = (f'planes {{ id: 3 name: "{scopes.METADATA_PLANE}" '
                f'event_metadata {{ key: 1 value {{ id: 1 name: '
                f'"jit_run_systems(7)" stats {{ metadata_id: 1 '
                f'bytes_value: "{blob}" }} }} }} stat_metadata {{ key: 1 '
                f'value {{ id: 1 name: "{scopes.HLO_PROTO_STAT}" }} }} }}')
    text = dev_plane + host + (metadata if with_hlo else "")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_scope_times_of_a_tpu_shaped_trace(tmp_path):
    path = _xspace(tmp_path)
    got = scopes.scope_times(path, "bench.dispatch", "run_systems")
    assert got == {DEV: {
        "modules": 1, "named": 1, "module_ns": 100,
        "scopes": {"l2_tlb.lookup": {"fusion s32[8]": 30},
                   "finalize": {"fusion s32[8]": 8},
                   scopes.UNSCOPED: {"while (s32[])": 30,
                                     "copy s32[28,6]": 30}}}}
    rows = scopes.device_scopes(got)
    assert [r[0] for r in rows] == [scopes.UNSCOPED, "l2_tlb.lookup",
                                    "finalize"]
    assert rows[1] == ["l2_tlb.lookup", 30e-9, [["fusion s32[8]", 30e-9]]]
    assert sorted(rows[0][2]) == [["copy s32[28,6]", 30e-9],
                                  ["while (s32[])", 30e-9]]
    m = scopes.stage_metrics(got, lanes_per_chip=2, seg_accesses=5)
    assert m["sim.scan.tlb_us_per_lane_step"] == pytest.approx(30e-3 / 10)
    assert m["sim.scan.victima_us_per_lane_step"] == 0
    assert m["sim.scan.cache_us_per_lane_step"] == 0
    assert m["sim.scan.unscoped_share"] == pytest.approx(100 * 60 / 98)
    # without the HLO protos every op is unscoped
    bare = scopes.scope_times(_xspace(tmp_path, with_hlo=False),
                              "bench.dispatch", "run_systems")
    assert bare[DEV]["named"] == 0
    assert set(bare[DEV]["scopes"]) == {scopes.UNSCOPED}


def test_stage_metrics_without_the_program_read_nothing():
    assert scopes.stage_metrics({}, 2, 5) == {}
    assert scopes.stage_metrics(
        {DEV: {"modules": 0, "named": 0, "module_ns": 0, "scopes": {}}},
        2, 5) == {}


def test_hlo_op_names_of_a_cpu_profile(tmp_path):
    """The profiler's own HLO protos, recorded on the CPU."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run_systems(c, xs):
        def step(cc, x):
            with jax.named_scope("l1_tlb.lookup"):
                cc = cc.at[x % 16].add(1)
            with jax.named_scope("data"):
                cc = cc * 3 + 1
            return cc, ()

        def body(cc, x):
            return jax.vmap(step)(cc, x)[0], ()
        return jax.lax.scan(body, c, xs)[0]

    c = jnp.zeros((4, 32), jnp.int32)
    xs = jnp.arange(32, dtype=jnp.int32).reshape(8, 4)
    run_systems(c, xs).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = True
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        run_systems(c, xs).block_until_ready()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    with open(path, "rb") as f:
        names = scopes.hlo_op_names(f.read())
    mods = [m for m in names if m.startswith("jit_run_systems")]
    assert len(mods) == 1
    found = {scopes.scope_of(op) for op in names[mods[0]].values()}
    assert {"l1_tlb.lookup", "data"} <= found


# ------------------------------------------------------ idle by span

def _dispatch_events():
    return [
        Event(DEV, "XLA Modules", "jit_run_systems(7)", 100, 300),
        Event(DEV, "XLA Modules", "jit_gather(3)", 520, 20),
        Event(HOST, "main", "bench.dispatch", 50, 650),
        Event(HOST, "main", "sim.launch", 60, 30),
        Event(HOST, "main", "sim.fetch", 90, 330),
        Event(HOST, "main", "sim.unpack", 420, 270),
        Event(HOST, "other", "sim.unpack", 0, 1000),   # another thread
    ]


def test_idle_by_span_splits_the_holes():
    ev = _dispatch_events()
    span = trace.spans(ev, "bench.dispatch")[0]
    got = scopes.idle_by_span(ev, DEV, span)
    # holes: 50-100, 400-520, 540-700
    assert got == {"sim.launch": 30, "sim.fetch": 30,
                   "sim.unpack": 100 + 150, "none": 10 + 10}
    assert sum(got.values()) == 650 - 320


# ------------------------------------------------------------ readers

def test_unpack_reader_on_a_hand_made_run():
    ev = _dispatch_events() + [
        Event(HOST, "main", "bench.dispatch", 1000, 500),
        Event(HOST, "main", "sim.unpack", 1300, 100),
        Event(HOST, "main", "sim.unpack", 1420, 20)]
    run = trace.TracedRun(ev, "bench.dispatch", "run_systems", 5, 10)
    read = spec.metric("sim.unpack_ms").read
    assert read(run) == pytest.approx((270 + 120) / 2 / 1e6)
    # a program without the span (the parent's), or no device: nothing
    bare = [e for e in ev if e.name != "sim.unpack"]
    assert read(trace.TracedRun(bare, "bench.dispatch", "run_systems",
                                5, 10)) is None
    host = [e for e in ev if e.plane == HOST]
    assert read(trace.TracedRun(host, "bench.dispatch", "run_systems",
                                5, 10)) is None


def test_unpack_metric_is_declared():
    m = [x for x in spec.load()["per_layer"] if x["name"] == "sim.unpack_ms"]
    assert m == [{"name": "sim.unpack_ms", "unit": "ms", "better": "lower",
                  "source": "program_span",
                  "layer": "ladder dispatch host side",
                  "moves": "sim_accesses_per_s"}]


@pytest.mark.parametrize("shift_ns,want", [
    (0, {"sim.scan_us_per_lane_step": None,
         "sim.dispatch_host_ms": 9.075463666666666,
         "device_idle_share.sim": 99.81107575660954,
         "sim.unpack_ms": None}),
    (1_100_000, {"sim.scan_us_per_lane_step": 2.8735833333333334,
                 "sim.dispatch_host_ms": 9.069658,
                 "device_idle_share.sim": 99.74724341171184,
                 "sim.unpack_ms": None}),
])
def test_readers_on_the_recorded_trace_are_pinned(shift_ns, want):
    """The readers give the recorded TPU trace the values they gave it
    before the stage scopes and program spans existed."""
    ev = [e._replace(start=e.start + shift_ns) if e.plane == DEV else e
          for e in trace.load(FIXTURE)]
    run = trace.TracedRun(ev, "bench.dispatch", "fixture_step", 1, 8)
    got = {m: spec.metric(m).read(run) for m in want}
    assert got == pytest.approx(want)


# ------------------------------------------------- the measurement

@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """A set-up cell of a tiny two-member ladder, the program's registry
    made to agree with it."""
    import dataclasses

    from bench import cell as cellmod
    from repro.core.mmu import SimConfig
    from repro.sim import systems

    tiny = dict(l2tlb_sets=4, l2tlb_ways=4, l1d4_sets=2, l1d4_ways=2,
                l1d2_sets=2, l1d2_ways=2, l2_sets=64, l2_ways=8,
                l3_sets=64, l3_ways=8, n_pages4=1 << 12, n_pages2=1 << 8,
                n_pagesh=1 << 8, n_feat=1 << 10)
    members = {"radix": dict(tiny), "victima": dict(tiny, victima=True)}
    traffic = dict(spec.traffic("all11.s128"), width=2, seg_accesses=16,
                   block_accesses=64)
    cfg = dict(spec.config("native28"), name="tiny2", members=members)
    saved = systems.LADDERS, systems.config
    systems.LADDERS = dict(saved[0], radix=tuple(members))
    systems.config = lambda m: dataclasses.replace(SimConfig(),
                                                   **members[m])
    try:
        c = cellmod.Cell(cfg, traffic, 2**31 + 5, 1,
                         str(tmp_path_factory.mktemp("cache")))
        with cellmod.CompileWatch() as watch:
            c.setup()
        yield c, watch
    finally:
        systems.LADDERS, systems.config = saved


def test_measure_runs_end_to_end_on_the_cpu(tiny_cell, tmp_path):
    """No device plane on the CPU: the device readings are empty, the
    counts and the costs are not."""
    from bench import cell as cellmod

    c, watch = tiny_cell
    t = scopes.trace_seconds(watch.secs[cellmod.COMPILE_EVENTS[0]])
    assert t["jaxpr_trace_s"] > 0
    assert {"l1_tlb.lookup", "victima.fill", "data", "init"} <= set(
        t["scopes"])
    out = scopes.measure(c, ["sim.unpack_ms"], str(tmp_path / "profile"))
    assert out["unpack_reads"] == 2 * 2 * 4  # 2 members x 2 workloads
    assert out["metrics"] == {"sim.unpack_ms": None}
    assert out["device_scopes"] == [] and out["idle_by_span"] == []
    cost = out["cost"]
    assert cost["untraced_dispatch_s"] > 0 and cost["traced_dispatch_s"] > 0
    assert cost["obs_overhead_s"] > 0
    assert len(c.times) == 2
