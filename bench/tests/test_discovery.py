"""Configurations, traffic mixes and per-layer metrics are found by
name as files of their own; BENCHMARK.json's rules are enforced."""
import copy
import json
import os
import shutil

import pytest

from bench import spec


@pytest.fixture
def bench():
    return spec.load()


@pytest.fixture
def tree(tmp_path, bench):
    """A copy of the benchmark directory to add files to."""
    d = tmp_path / "bench"
    shutil.copytree(spec.BENCH, d, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    return d


def test_committed_benchmark_is_valid(bench):
    for c in bench["configs"]:
        cfg = spec.config(c["name"])
        assert os.path.join(spec.ROOT, c["file"]) == os.path.join(
            spec.BENCH, "configs", c["name"] + ".json")
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert spec.traffic(w["traffic"])["seg_accesses"] > 0
    for m in bench["per_layer"]:
        assert callable(spec.metric(m["name"]).read)


def test_new_files_are_picked_up_without_edits(tree, bench):
    """A new configuration, mix and metric: new files plus new entries."""
    (tree / "configs" / "native6.json").write_text(json.dumps(
        dict(spec.config("native28"), name="native6")))
    (tree / "traffic" / "two.s64.json").write_text(json.dumps(
        dict(spec.traffic("all11.s128"), seg_accesses=64)))
    (tree / "metrics" / "sim.fixture_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    before = {p: p.read_bytes() for p in tree.rglob("*") if p.is_file()
              and p.name not in ("native6.json", "two.s64.json",
                                 "sim.fixture_metric.py")}
    b = copy.deepcopy(bench)
    b["configs"].append(dict(b["configs"][0], name="native6",
                             file="bench/configs/native6.json"))
    b["workloads"].append({"name": "native6.two", "config": "native6",
                           "traffic": "two.s64", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "sim.fixture_metric", "unit": "us",
                           "better": "lower", "source": "device_trace",
                           "layer": "access scan",
                           "moves": "sim_accesses_per_s",
                           "workloads": ["native6.two"]})
    spec.validate(b)
    cell = spec.cell(b, "native6.two")
    assert spec.config(cell["config"], str(tree))["name"] == "native6"
    assert spec.traffic(cell["traffic"], str(tree))["seg_accesses"] == 64
    layer = spec.metrics_of(b, "per_layer", "native6.two")
    assert "sim.fixture_metric" in [m["name"] for m in layer]
    assert "sim.fixture_metric" not in [
        m["name"] for m in spec.metrics_of(b, "per_layer", "native28.all11")]
    assert spec.metric("sim.fixture_metric", str(tree)).read(None) == 42.0
    after = {p: p.read_bytes() for p in before}
    assert after == before


@pytest.mark.parametrize("where,value", [
    ("cell name", "native28 all11"),
    ("cell name", "native28/all11"),
    ("cell name", "x" * 65),
    ("metric name", "ttft,p95"),
    ("metric name", ".hidden"),
    ("unit", "tokens per second"),
    ("unit", "µs"),
    ("unit", "x" * 17),
    ("traffic", "all11 s128"),
])
def test_names_and_units_outside_the_alphabet_are_refused(bench, where,
                                                          value):
    b = copy.deepcopy(bench)
    if where == "cell name":
        b["workloads"][0]["name"] = value
    elif where == "metric name":
        b["end_to_end"][0]["name"] = value
    elif where == "unit":
        b["per_layer"][0]["unit"] = value
    else:
        b["workloads"][0]["traffic"] = value
    with pytest.raises(spec.SpecError):
        spec.validate(b)


@pytest.mark.parametrize("change", [
    lambda b: b["end_to_end"][0].update(bound=0.3),
    lambda b: b["end_to_end"][0].update(bound=0.001),
    lambda b: b.update(run_seconds=52),
    lambda b: b["end_to_end"].pop(),                      # no setup_s
    lambda b: b["per_layer"][0].update(moves="nope"),
    lambda b: b["per_layer"][0].update(why="extra key"),
    lambda b: b["workloads"][0].update(chips=2),
    lambda b: b["workloads"].append(dict(b["workloads"][0], name="dup")),
    lambda b: b["configs"][0].update(file="elsewhere/native28.json"),
    lambda b: b["end_to_end"][0].update(source="program_counter"),
])
def test_rule_breaks_are_refused(bench, change):
    b = copy.deepcopy(bench)
    change(b)
    with pytest.raises(spec.SpecError):
        spec.validate(b)


def test_missing_files_are_errors(tree):
    with pytest.raises(spec.SpecError):
        spec.config("nope", str(tree))
    with pytest.raises(spec.SpecError):
        spec.traffic("nope", str(tree))
    with pytest.raises(spec.SpecError):
        spec.metric("nope", str(tree))
    (tree / "metrics" / "sim.no_read.py").write_text("x = 1\n")
    with pytest.raises(spec.SpecError):
        spec.metric("sim.no_read", str(tree))
