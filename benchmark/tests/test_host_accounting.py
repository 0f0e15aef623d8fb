"""The host's half of the accounting (ISSUE 34): the seven metrics that
read the entry points' root spans, the part-file loop's off-CPU seconds and
JAX's own lowering and backend seconds; the names their files quote; and
the blind spot of ``compiles_in_window`` that ``rejit_s`` closes."""

import dataclasses
import json
import os

import pytest

from benchmark import cells
from benchmark.readers import (compiles_in_window, program_load_seconds,
                               span_attr_sum)
from benchmark.tests.test_names_readers import _job, _run

DATA_ONLY = ("host_cpu_s", "host_off_cpu_s", "proc_cpu_s", "invol_switches",
             "part_write_off_cpu_s", "rejit_s")
NEW = DATA_ONLY + ("program_load_s",)


def _args(name):
    with open(os.path.join(cells.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        held = json.load(f)
    return held["reader"], held["args"]


def test_new_metric_files_quote_only_declared_names():
    from gpu_mapreduce_tpu.obs import names
    listed = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    for name in NEW:
        assert name in listed and listed[name]["workloads"], name
        reader, args = _args(name)
        assert (reader == "span_attr_sum") == (name in DATA_ONLY), name
        assert set(args["spans"]) <= set(names.SPANS), name
        assert set(args["attrs"]) <= set(names.SPAN_ATTRS), name
    roots = {names.INVINDEX_RUN, names.OINK_SCRIPT}
    assert all(set(_args(n)[1]["spans"]) == roots
               for n in NEW if n != "part_write_off_cpu_s")
    assert _args("part_write_off_cpu_s")[1]["spans"] == [names.PARTS_WRITE]
    # every cell of this PR's day has a root span (a later cell adds its
    # name); the part files are InvertedIndex's
    parts = {"invindex-puma-1chip", "invindex-puma-4chip"}
    everywhere = parts | {
        "graph-build-1chip", "graph-iter-1chip", "graph-build-4chip",
        "wordfreq-zipf-4chip", "graph-tri-1chip"}
    for name in NEW:
        want = parts if name == "part_write_off_cpu_s" else everywhere
        assert want <= set(listed[name]["workloads"]), name
    assert set(listed["part_write_off_cpu_s"]["workloads"]) == parts
    text = open(os.path.join(cells.ROOT, "PERF.md")).read()
    for name in NEW:
        assert f"`{name}`" in text, name


def test_rejit_shows_where_compiles_in_window_reads_zero():
    """A program traced and lowered again in every job and then served by
    the persistent cache adds one request and one hit: ``requests − hits``
    is 0, the root span's seconds are not."""
    root = {"cpu_s": 0.5, "off_cpu_s": 0.5, "jit_lowerings": 1,
            "jit_lower_s": 0.21, "jit_backend_s": 0.13, "jit_cache_loads": 1}
    jobs = [_job(i, [("invindex.run", dict(root)),
                     ("parts.write", {"cpu_s": 0.3, "off_cpu_s": 0.1})])
            for i in (1, 2, 3)]
    run = dataclasses.replace(
        _run(jobs), compiles={"window": {"requests": 3, "hits": 3}})
    assert compiles_in_window.read(run, {}) == 0
    assert span_attr_sum.read(run, _args("rejit_s")[1]) == pytest.approx(0.34)
    assert span_attr_sum.read(
        run, _args("part_write_off_cpu_s")[1]) == pytest.approx(0.1)
    # nothing rebuilt: the root span states its zeros, and 0 is reported
    quiet = dict(root, jit_lowerings=0, jit_lower_s=0, jit_backend_s=0,
                 jit_cache_loads=0)
    run = _run([_job(1, [("oink.script", quiet), ("oink.script", quiet)])])
    assert span_attr_sum.read(run, _args("rejit_s")[1]) == 0
    assert span_attr_sum.read(run, _args("host_cpu_s")[1]) == 1.0
    # a program from before the root spans: nothing, not zero
    old = _run([_job(1, [("oink.rmat", {"rounds": 6})])])
    for name in DATA_ONLY:
        assert span_attr_sum.read(old, _args(name)[1]) is None, name


def test_program_load_is_the_totals_less_the_windows_jobs(monkeypatch):
    from gpu_mapreduce_tpu.core import runtime
    args = _args("program_load_s")[1]
    c = runtime.Counters()
    c.add(jit_lower_s=2.0, jit_backend_s=5.5, jit_lowerings=30)
    monkeypatch.setattr(runtime, "_GLOBAL_COUNTERS", c)
    jobs = [_job(1, [("oink.script", {"jit_lower_s": 0.25,
                                      "jit_backend_s": 0.25}),
                     ("oink.script", {"jit_lower_s": 0, "jit_backend_s": 0})]),
            _job(2, [("oink.script", {"jit_lower_s": 0, "jit_backend_s": 0})])]
    assert program_load_seconds.read(_run(jobs), args) == pytest.approx(7.0)
    # the tracer was off: the totals were never fed
    assert program_load_seconds.read(_run([_job(1, [])]), args) is None
    # a program whose counters keep no such totals
    monkeypatch.setattr(runtime.Counters, "snapshot",
                        lambda self: {"ndispatch": 3})
    assert program_load_seconds.read(_run(jobs), args) is None


# -- through the harness, tiny, on the CPU ---------------------------------------

from benchmark.tests.test_harness import cpu_trace, tiny_cell  # noqa: E402,F401


@pytest.mark.parametrize("name,chips", [("invindex-puma-1chip", 4),
                                        ("graph-build-4chip", 4),
                                        ("graph-iter-1chip", 1)])
def test_traced_cell_reports_the_host_accounting(cpu_harness, cpu_trace,
                                                 name, chips):
    from gpu_mapreduce_tpu.obs import get_tracer, names
    cell = tiny_cell(name, chips)
    try:
        line = cpu_harness.run_cell(cell, seed=7, seconds=1.0, trace=True,
                                    t_process=0.0)
        events = get_tracer().events()
    finally:
        get_tracer().reset()
    assert line["correct"] is True and line["failed"] == 0
    declared = {m["name"] for m in cell.metrics["per_layer"]}
    want = set(NEW) & declared
    assert want == set(NEW) - ({"part_write_off_cpu_s"}
                               if "graph" in name else set())
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert want <= set(value)
    assert value["host_cpu_s"] > 0 and value["host_off_cpu_s"] >= 0
    # every thread's CPU holds the calling thread's
    assert value["proc_cpu_s"] >= value["host_cpu_s"] * 0.99
    assert value["invol_switches"] >= 0
    # the window runs warm programs: nothing is lowered under a job
    assert value["rejit_s"] == 0
    assert value["program_load_s"] > 0
    # each root span splits its wall into the two
    roots = [e for e in events if e["cat"] == names.ENTRY]
    assert roots and all(e["parent"] == 0 for e in roots)
    assert all(e["args"][names.ATTR_JIT_LOWERINGS] == 0 for e in roots)
    for e in roots:
        a, dur = e["args"], e["dur"] * 1e-6
        assert abs(a[names.ATTR_CPU_S] + a[names.ATTR_OFF_CPU_S] - dur) \
            <= max(0.01 * dur, 1e-3)
    if "part_write_off_cpu_s" in want:
        assert 0 <= value["part_write_off_cpu_s"] <= value["part_write_s"]
