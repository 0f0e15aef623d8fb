"""Both job kinds, tiny, on the CPU's virtual devices through the real
harness loop (``cpu_harness``: only the chip check, the cache directory and
the peaks table are replaced — in this file, not in the harness)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import cells, xtrace

ROOT = cells.ROOT
TINY = {"invindex": {"file_bytes": 64 << 10}, "oink_script": {"scale": 8}}


def tiny_cell(name, chips=None):
    cell = cells.load_cell(name)
    cell.config.update(TINY[cell.traffic["kind"]])
    if chips:
        cell.chips = chips
    if "files" in cell.config:
        cell.config["files"] = 2 * cell.chips
    return cell


@pytest.fixture
def cpu_trace(monkeypatch):
    """The CPU backend has no device plane: present its executor threads as
    devices so that the real reduction runs."""
    real = xtrace.load

    def load(path, host_names=None):
        raw = real(path)
        for name, events in sorted(raw["host"].items()):
            if "PjRtCpuClient" in name:
                raw["devices"][len(raw["devices"])] = {
                    "ops": [e for e in events if e[1] > e[0]], "modules": []}
        return raw

    monkeypatch.setattr(xtrace, "load", load)


@pytest.mark.parametrize("name", cells.cell_names())
def test_cell_end_to_end_metrics(cpu_harness, name):
    cell = tiny_cell(name)
    line = cpu_harness.run_cell(cell, seed=5, seconds=1.0, trace=False,
                                t_process=0.0)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    want = {m["name"] for m in cell.metrics["end_to_end"]}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    units = {m["name"]: m["unit"] for m in cell.metrics["end_to_end"]}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == units


@pytest.mark.parametrize("name,chips", [("invindex-puma-1chip", 4),
                                        ("graph-build-4chip", 4),
                                        ("graph-iter-1chip", 1)])
def test_cell_traced(cpu_harness, cpu_trace, name, chips):
    cell = tiny_cell(name, chips)
    line = cpu_harness.run_cell(cell, seed=6, seconds=1.0, trace=True,
                                t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    got = set(line["metrics"])
    declared = {m["name"] for m in cell.metrics["per_layer"]}
    # readers with nothing to read on the CPU leave their metric out: no
    # memory statistics, and no program events in the stand-in planes
    assert got <= declared
    assert declared - got <= {"peak_hbm_gib", "extract_roofline"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    assert 0 < line["metrics"]["device_idle_share"]["value"] < 100
    for key in ("device_ops", "idle_gaps"):
        rows = line["breakdown"][key]
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s > 0 for n, s in rows)
    if chips > 1 and "exchange_pad_share" in declared:
        assert 0 <= line["metrics"]["exchange_pad_share"]["value"] < 100
        assert line["metrics"]["aggregate_s"]["value"] > 0
    # idle gaps are named by program spans, not only by the harness's
    names = {n for n, _ in line["breakdown"]["idle_gaps"]}
    assert names - {"bench.job", "(no program span open)"}


def test_wrong_invindex_reference_makes_correct_false(cpu_harness,
                                                      monkeypatch):
    from benchmark.gen import corpus
    real = corpus.index_reference

    def off_by_one(paths):
        want, npairs = real(paths)
        want.pop(next(iter(want)))
        return want, npairs

    monkeypatch.setattr(corpus, "index_reference", off_by_one)
    line = cpu_harness.run_cell(tiny_cell("invindex-puma-1chip"), seed=5,
                                seconds=0.2, trace=False, t_process=0.0)
    assert line["correct"] is False


def test_wrong_graph_reference_makes_correct_false(cpu_harness, monkeypatch):
    from benchmark.refs import graph
    real = graph.components_reference

    def relabel(e, verts):
        zone = real(e, verts).copy()
        zone[-1] += 1
        return zone

    monkeypatch.setattr(graph, "components_reference", relabel)
    line = cpu_harness.run_cell(tiny_cell("graph-iter-1chip"), seed=5,
                                seconds=0.2, trace=False, t_process=0.0)
    assert line["correct"] is False


def test_a_job_that_differs_from_the_warm_up_is_counted(cpu_harness,
                                                        monkeypatch):
    """Later jobs are held to the warm-up job's verified output."""
    from benchmark.jobs import oink_script
    real = oink_script.Job.digest
    calls = []

    def digest(self, result, outdir):
        calls.append(outdir)
        return real(self, result, outdir) + ("x" if len(calls) == 3 else "")

    monkeypatch.setattr(oink_script.Job, "digest", digest)
    line = cpu_harness.run_cell(tiny_cell("graph-build-1chip"), seed=5,
                                seconds=0.5, trace=False, t_process=0.0)
    assert line["correct"] is False and line["failed"] == 1


def _run_py(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_means_no_result_line():
    p = _run_py("--workload", "graph-build-1chip", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "needs 1 TPU chip(s), found platform 'cpu'" in p.stderr
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout


def test_list_prints_every_cell():
    p = _run_py("--list")
    assert p.returncode == 0
    assert [ln.split("\t")[0] for ln in p.stdout.splitlines()] == \
        cells.cell_names()


def test_without_the_package_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    p = _run_py("--workload", "graph-build-1chip", "--seed", "1",
                "--seconds", "1", "--trace", "0", cwd=str(tmp_path))
    assert p.returncode != 0 and '"metrics"' not in p.stdout
