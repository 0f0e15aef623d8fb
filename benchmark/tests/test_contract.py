"""``BENCHMARK.json`` against the contract's limits, its data files, and the
rule that the harness names no cell and no metric."""

import json
import os
import re

from benchmark import cells

ROOT = cells.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def spec():
    return cells.load_benchmark()


def test_keys_names_units_and_limits():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert isinstance(s["run_seconds"], int) and 1 <= s["run_seconds"] <= 51
    assert 2 <= len(s["workloads"]) <= 24 and 1 <= len(s["configs"]) <= 24
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert c["file"].startswith(s["paths"][0] + "/")
        held = json.load(open(os.path.join(ROOT, c["file"])))
        assert held["source"] == c["source"] and held["reduced"] == c["reduced"]
        assert held["guarantees"] and held["assumed"] and held["rung"]
    pairs = set()
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        assert w["config"] in {c["name"] for c in s["configs"]}
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert {c["name"] for c in s["configs"]} == {w["config"]
                                                 for w in s["workloads"]}
    four = sum(w["chips"] == 4 for w in s["workloads"])
    assert four <= max(1, len(s["workloads"]) // 2)
    names = [m["name"] for m in s["end_to_end"] + s["per_layer"]]
    assert len(names) == len(set(names))
    for m in s["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in s["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in s["end_to_end"]}
        assert m["source"] in SOURCES
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= {w["name"] for w in s["workloads"]}
    assert "setup_s" in {m["name"] for m in s["end_to_end"]}


def test_every_cell_reports_what_the_contract_asks():
    for name in cells.cell_names():
        cell = cells.load_cell(name)
        e2e = {m["name"] for m in cell.metrics["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.metrics["per_layer"]
        # a per-layer metric is reported only where the metric it moves is
        assert {m["moves"] for m in cell.metrics["per_layer"]} <= e2e


def test_metric_files_hold_the_reader_and_nothing_of_benchmark_json():
    """Unit, direction, source, layer and the cells that report a metric are
    ``BENCHMARK.json``'s alone, so that a later cell reporting a metric which
    is there edits no file under ``paths``."""
    s = spec()
    for group, sub in cells.METRIC_DIRS.items():
        for m in s[group]:
            path = os.path.join(ROOT, s["paths"][0], sub, m["name"] + ".json")
            held = json.load(open(path))
            assert "reader" in held, path
            assert set(held) <= {"reader", "args", "what"}, (m["name"], set(held))
            reader = os.path.join(ROOT, s["paths"][0], "readers",
                                  held["reader"] + ".py")
            assert os.path.exists(reader), reader


def test_layers_are_perf_md_layers():
    text = open(os.path.join(ROOT, "PERF.md")).read()
    for m in spec()["per_layer"]:
        assert f"| {m['layer']} |" in text, m["layer"]


def test_the_harness_names_no_cell_and_no_metric():
    s = spec()
    words = ([w["name"] for w in s["workloads"]]
             + [c["name"] for c in s["configs"]]
             + [m["name"] for m in s["end_to_end"] + s["per_layer"]])
    for fn in ("run.py", "harness.py", "cells.py"):
        src = open(os.path.join(ROOT, s["paths"][0], fn)).read()
        for wd in words:
            assert not re.search(rf"\b{re.escape(wd)}\b", src), (fn, wd)
        assert "if workload ==" not in src


def test_files_under_paths_are_named_from_the_allowed_characters():
    s = spec()
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for dirpath, dirs, files in os.walk(os.path.join(ROOT, s["paths"][0])):
        dirs[:] = [d for d in dirs if d not in ("cache", "__pycache__")]
        for fn in files:
            rel = os.path.relpath(os.path.join(dirpath, fn), ROOT)
            assert ok.match(rel), rel
