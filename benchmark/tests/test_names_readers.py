"""The readers ISSUE 24 adds, on hand-made runs; and the rule that a new
metric's file quotes only names the program declares (``obs/names.py``)."""

import json
import os

import pytest

from benchmark import cells, harness
from benchmark.readers import program_seconds, span_attr_sum

NEW = ("layout_dev_s", "sort_dev_s", "shuffle_dev_s", "cc_loop_dev_s",
       "pagerank_loop_dev_s", "aggregate_host_s", "count_sync_s",
       "engine_host_s", "part_write_s", "entry_glue_s", "work_rounds")


def _run(jobs=(), trace=None):
    return harness.Run(
        cell=None, setup_seconds=1.0, warmup=None, jobs=list(jobs),
        window_t0=0.0, work={}, compiles={}, memory_peak_bytes=0,
        device_kind="TPU v5 lite", info={}, trace=trace)


def _job(index, spans):
    return harness.JobRecord(index, 0.0, 1.0, "", {}, spans=[
        {"name": n, "cat": "x", "ts": 0.0, "dur": 1.0, "args": a}
        for n, a in spans])


def test_program_seconds_sums_modules_of_per_job_medians():
    # xtrace.reduce has summed each module's executions inside one job;
    # two devices, two traced jobs
    run = _run(trace={"program_job_seconds": {
        "jit_shuffle_phase1": {0: [0.10, 0.12], 1: [0.10, 0.14]},
        "jit_shuffle_phase2": {0: [1.0, 3.0], 1: [1.0, 3.0]},
        "jit_other": {0: [9.0, 9.0]}}})
    # median(0.10, 0.10, 0.12, 0.14) + median(1, 1, 3, 3)
    assert program_seconds.read(run, {"modules": [
        "jit_shuffle_phase1", "jit_shuffle_phase2",
        "jit_shuffle_phase2_wire"]}) == pytest.approx(0.11 + 2.0)
    assert program_seconds.read(
        run, {"modules": ["jit_other"]}) == pytest.approx(9.0)
    # none of them ran (another cell, or the program before its names)
    assert program_seconds.read(run, {"modules": ["jit_cc_loop"]}) is None
    # an untraced run
    assert program_seconds.read(_run(), {"modules": ["jit_other"]}) is None


def test_program_seconds_on_a_reduced_trace(tmp_path):
    """Through ``xtrace``: a program dispatched twice in the first job and
    once in the second counts 50 ms and 40 ms, median 45 ms."""
    from jax.profiler import ProfileData

    from benchmark import xtrace
    from benchmark.tests.test_xtrace import _plane
    text = "\n".join([
        _plane(1, "/device:TPU:0", {
            "XLA Modules": [("jit_convert_layout(1)", 10, 20),
                            ("jit_convert_layout(1)", 50, 30),
                            ("jit_convert_sort(2)", 90, 5),
                            ("jit_convert_layout(1)", 110, 40)],
            "XLA Ops": [("fusion.1", 10, 20), ("fusion.1", 50, 30),
                        ("sort.2", 90, 5), ("fusion.1", 110, 40)]}),
        _plane(2, "/host:CPU", {
            "python3": [("bench.job", 0, 100), ("bench.job", 100, 100)]})])
    p = tmp_path / "t.xplane.pb"
    p.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    run = _run(trace=xtrace.reduce(xtrace.load(str(p))))
    assert program_seconds.read(
        run, {"modules": ["jit_convert_layout"]}) == pytest.approx(0.045)
    # the sort ran in one job only: that job is its median
    assert program_seconds.read(
        run, {"modules": ["jit_convert_sort"]}) == pytest.approx(0.005)


def test_span_attr_sum_is_a_median_of_per_job_sums():
    args = {"spans": ["oink.rmat", "cc.loop", "pagerank.loop"],
            "attrs": ["rounds", "iters"]}
    jobs = [
        _job(1, [("oink.rmat", {"rounds": 6, "dispatches": 40}),
                 ("oink.edge_upper", {"rounds": 99})]),
        _job(2, [("cc.loop", {"iters": 5, "n": 7}),
                 ("pagerank.loop", {"iters": 5})]),
        _job(3, [("oink.rmat", {"rounds": 5})])]
    assert span_attr_sum.read(_run(jobs), args) == 6    # of 6, 10, 5
    # the spans are there and none carries the count (the program before
    # this PR), or the tracer was off: nothing, not zero
    old = [_job(1, [("oink.rmat", {"dispatches": 40})])]
    assert span_attr_sum.read(_run(old), args) is None
    assert span_attr_sum.read(_run([_job(1, [])]), args) is None
    assert span_attr_sum.read(_run(), args) is None


def test_new_metric_files_quote_only_declared_names():
    from gpu_mapreduce_tpu.obs import names
    spec = cells.load_benchmark()
    listed = {m["name"]: m for m in spec["per_layer"]}
    older_cats = {"mr_op", "shuffle", "ingest"}
    stage_leaves = {"stage.read", "stage.h2d", "stage.map_device",
                    "stage.url_dict"}
    for name in NEW:
        assert name in listed and "workloads" in listed[name], name
        with open(os.path.join(cells.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        assert set(args.get("modules", [])) <= set(names.PROGRAMS), name
        for key in ("names", "spans"):
            assert set(args.get(key, [])) <= set(names.SPANS), (name, key)
        assert set(args.get("child_cats", [])) <= older_cats | {
            names.HOST, names.ENGINE}
        assert set(args.get("child_names", [])) <= stage_leaves
    # what these metrics are for is PERF.md's to say
    text = open(os.path.join(cells.ROOT, "PERF.md")).read()
    for name in NEW:
        assert f"`{name}`" in text, name


def test_every_cell_lists_its_new_metrics():
    want = {
        "invindex-puma-1chip": {"sort_dev_s", "part_write_s",
                                "entry_glue_s"},
        "graph-build-1chip": {"layout_dev_s", "sort_dev_s",
                              "aggregate_host_s", "entry_glue_s",
                              "work_rounds"},
        "graph-iter-1chip": {"cc_loop_dev_s", "pagerank_loop_dev_s",
                             "engine_host_s", "entry_glue_s", "work_rounds"},
        "graph-build-4chip": {"layout_dev_s", "sort_dev_s", "shuffle_dev_s",
                              "aggregate_host_s", "count_sync_s",
                              "entry_glue_s", "work_rounds"}}
    for cell, metrics in want.items():
        got = {m["name"] for m in cells.load_cell(cell).metrics["per_layer"]}
        assert got & set(NEW) == metrics, cell


# -- through the harness, tiny, on the CPU ---------------------------------------

from benchmark.tests.test_harness import cpu_trace, tiny_cell  # noqa: E402,F401


@pytest.mark.parametrize("name,chips", [("invindex-puma-1chip", 4),
                                        ("graph-build-4chip", 4),
                                        ("graph-iter-1chip", 1)])
def test_traced_cell_reports_the_new_span_metrics(cpu_harness, cpu_trace,
                                                  capsys, name, chips):
    """What ``test_harness.test_cell_traced`` checks, with the set of
    metrics the CPU cannot read widened by the new device metrics: its
    stand-in planes hold no program events, so the ``*_dev_s`` readers
    find nothing and leave their metric out (that test's own set names
    two metrics and, being the benchmark's, is not this PR's to edit)."""
    cell = tiny_cell(name, chips)
    line = cpu_harness.run_cell(cell, seed=6, seconds=1.0, trace=True,
                                t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    declared = {m["name"]: m for m in cell.metrics["per_layer"]}
    got = set(line["metrics"])
    missing = set(declared) - got
    assert missing <= {"peak_hbm_gib", "extract_roofline"} | {
        n for n in NEW if declared.get(n, {}).get("source") == "device_trace"}
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 <= value["entry_glue_s"] <= value["entry_self_s"]
    if "work_rounds" in declared:
        # the commands' own messages: "... N iterations"
        checked = next(ln for ln in capsys.readouterr().out.splitlines()
                       if ln.startswith("bench: warm-up job checked "))
        messages = json.loads(checked[checked.index("{"):])["messages"]
        said = sum(int(m.split()[-2]) for m in messages
                   if m.endswith("iterations"))
        assert value["work_rounds"] == said > 0
    if "aggregate_host_s" in declared:
        assert 0 < value["aggregate_host_s"] <= value["aggregate_s"]
    if "count_sync_s" in declared:
        assert value["count_sync_s"] > 0
    if "engine_host_s" in declared:
        assert value["engine_host_s"] > 0
        # the loops are under engine spans now: glue is what is left
        assert value["entry_glue_s"] < value["entry_self_s"]
    if "part_write_s" in declared:
        assert 0 < value["part_write_s"] <= value["group_reduce_s"]

