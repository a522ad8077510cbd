"""BENCHMARK.json: its rules, and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by its name:

- ``bench/configs/<name>.json``  a ladder configuration;
- ``bench/traffic/<name>.json``  a traffic mix (data only);
- ``bench/metrics/<name>.py``    a per-layer metric, a module with
  ``read(run) -> float | None`` (``None``: nothing to read here).

Adding one is adding its file and its entry in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
E2E_SOURCES = {"host_clock", "device_trace"}
SOURCES = E2E_SOURCES | {"program_span", "program_counter"}


class SpecError(ValueError):
    pass


def _need(ok: bool, what: str) -> None:
    if not ok:
        raise SpecError(what)


def _name(v, what: str) -> None:
    _need(isinstance(v, str) and NAME.fullmatch(v) is not None,
          f"{what} {v!r}: a name is 1-64 of A-Z a-z 0-9 _ . - and starts "
          f"with a letter, digit or _")


def _line(v, what: str) -> None:
    _need(isinstance(v, str) and 1 <= len(v) <= 200
          and not re.search(r"[\n\r\t]", v),
          f"{what} must be 1-200 characters on one line, without tabs")


def _keys(entry: dict, allowed: set, extra: set, what: str) -> None:
    _need(isinstance(entry, dict), f"{what} must be an object")
    missing = allowed - set(entry)
    unknown = set(entry) - allowed - extra
    _need(not missing, f"{what} lacks {sorted(missing)}")
    _need(not unknown, f"{what} has unknown keys {sorted(unknown)}")


def _metric(m: dict, keys: set, sources: set, what: str) -> None:
    _keys(m, keys, {"workloads"}, what)
    _name(m["name"], f"{what} name")
    _need(isinstance(m["unit"], str) and UNIT.fullmatch(m["unit"])
          is not None, f"{what} unit {m['unit']!r}: 1-16 of A-Z a-z 0-9 "
          f"_ / % . -")
    _need(m["better"] in ("lower", "higher"),
          f"{what} better must be lower or higher")
    _need(m["source"] in sources, f"{what} source {m['source']!r} is not "
          f"one of {sorted(sources)}")


def validate(b: dict) -> dict:
    """Check ``b`` against the benchmark's rules; returns it."""
    _keys(b, TOP_KEYS, set(), "BENCHMARK.json")
    cmd, paths = b["command"], b["paths"]
    _need(isinstance(cmd, list) and 1 <= len(cmd) <= 32, "command: 1-32 "
          "strings")
    for w in cmd:
        _line(w, "a word of command")
    _need(isinstance(paths, list) and 1 <= len(paths) <= 16, "paths: 1-16")
    for p in paths:
        _need(isinstance(p, str) and PATH.fullmatch(p) is not None
              and not p.startswith("/") and ".." not in p.split("/"),
              f"path {p!r} must be relative, inside the repo")
    rs = b["run_seconds"]
    _need(isinstance(rs, int) and not isinstance(rs, bool) and 1 <= rs <= 51,
          "run_seconds: a whole number from 1 to 51")

    configs = b["configs"]
    _need(isinstance(configs, list) and 1 <= len(configs) <= 24,
          "configs: 1-24 entries")
    for c in configs:
        _keys(c, CONFIG_KEYS, set(), "config")
        _name(c["name"], "config name")
        _line(c["source"], f"config {c['name']} source")
        _line(c["why"], f"config {c['name']} why")
        _need(isinstance(c["reduced"], list) and len(c["reduced"]) <= 16,
              f"config {c['name']} reduced: a list of at most 16 keys")
        for k in c["reduced"]:
            _name(k, f"config {c['name']} reduced key")
        _need(any(c["file"].startswith(p.rstrip("/") + "/") for p in paths),
              f"config {c['name']} file {c['file']!r} is not under paths")

    cells = b["workloads"]
    _need(isinstance(cells, list) and 1 <= len(cells) <= 24,
          "workloads: 1-24 cells")
    cfg_names = [c["name"] for c in configs]
    for w in cells:
        _keys(w, CELL_KEYS, set(), "cell")
        _name(w["name"], "cell name")
        _name(w["traffic"], f"cell {w['name']} traffic")
        _need(w["config"] in cfg_names,
              f"cell {w['name']} names unknown config {w['config']!r}")
        _need(w["chips"] in (1, 4), f"cell {w['name']} chips: 1 or 4")
        _line(w["why"], f"cell {w['name']} why")
    pairs = [(w["config"], w["traffic"]) for w in cells]
    _need(len(set(pairs)) == len(pairs), "a (config, traffic) pair "
          "appears twice")
    _need(sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2),
          "too many four-chip cells")
    _need(all(any(w["config"] == c for w in cells) for c in cfg_names),
          "every config is used by some cell")

    e2e, layers = b["end_to_end"], b["per_layer"]
    _need(isinstance(e2e, list) and 1 <= len(e2e) <= 16, "end_to_end: 1-16")
    _need(isinstance(layers, list) and 1 <= len(layers) <= 128,
          "per_layer: 1-128")
    for m in e2e:
        _metric(m, E2E_KEYS, E2E_SOURCES, "end-to-end metric")
        bound = m["bound"]
        _need(isinstance(bound, (int, float)) and 0.01 <= bound <= 0.25,
              f"{m['name']} bound must lie in [0.01, 0.25]")
    _need(any(m["name"] == "setup_s" for m in e2e), "setup_s is required")
    e2e_names = {m["name"] for m in e2e}
    cell_names = {w["name"] for w in cells}
    for m in layers:
        _metric(m, LAYER_KEYS, SOURCES, "per-layer metric")
        _line(m["layer"], f"{m['name']} layer")
        _need(m["moves"] in e2e_names,
              f"{m['name']} moves unknown metric {m['moves']!r}")
    for m in e2e + layers:
        for w in m.get("workloads", []):
            _need(w in cell_names, f"{m['name']} lists unknown cell {w!r}")
    names = ([c["name"] for c in configs], [w["name"] for w in cells],
             [m["name"] for m in e2e + layers])
    for group in names:
        _need(len(set(group)) == len(group), f"duplicate name in {group}")
    _need(len(json.dumps(b)) <= 64 * 1024, "BENCHMARK.json over 64 KiB")
    return b


def load(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json"),
              encoding="utf-8") as f:
        return validate(json.load(f))


def cell(b: dict, name: str) -> dict:
    for w in b["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"unknown cell {name!r}; cells: "
                    f"{', '.join(w['name'] for w in b['workloads'])}")


def metrics_of(b: dict, group: str, cell_name: str) -> list[dict]:
    """The ``group`` ("end_to_end" or "per_layer") metrics a cell reports."""
    return [m for m in b[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def _data(kind: str, name: str, bench_dir: str) -> dict:
    _name(name, kind)
    path = os.path.join(bench_dir, kind, name + ".json")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} file for {name!r} at {path}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def config(name: str, bench_dir: str = BENCH) -> dict:
    return _data("configs", name, bench_dir)


def traffic(name: str, bench_dir: str = BENCH) -> dict:
    return _data("traffic", name, bench_dir)


def metric(name: str, bench_dir: str = BENCH):
    """The reader module of per-layer metric ``name``."""
    _name(name, "metric")
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no reader for metric {name!r} at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metric reader {path} has no read(run)")
    return mod
