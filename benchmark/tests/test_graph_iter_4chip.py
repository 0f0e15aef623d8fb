"""ISSUE 46: the four-chip graph-iter cell and the two metrics it brings
(``python -m pytest benchmark/tests``, CPU, not tier-1).  The traffic file,
the job kind and the references are ``graph-iter-1chip``'s, untouched: this
cell adds a configuration, two metric files and two readers."""

import importlib
import json
import os

import pytest

from benchmark import cells, xtrace
from benchmark.readers import collective_seconds
from benchmark.tests.test_harness import cpu_trace, tiny_cell  # noqa: F401
from benchmark.tests.test_xtrace import _plane

CELL = "graph-iter-4chip"
CONFIG = "mrmpi-rmat-weak-4chip"
STRONG = "mrmpi-rmat-4chip"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tiny_mesh.xplane.pb")
NEW = {"engine_collective_dev_s": ("device_trace", "s"),
       "engine_allreduce_mb": ("program_counter", "MB")}
JOINED = ("cc_find_s", "pagerank_s", "cc_loop_dev_s", "pagerank_loop_dev_s",
          "stage_dev_s", "engine_host_s", "work_rounds", "entry_glue_s",
          "host_cpu_s", "host_off_cpu_s", "proc_cpu_s", "invol_switches",
          "rejit_s", "program_load_s")
# what states the size, the source and the realized graph; every other key
# is mrmpi-rmat-4chip's, value for value
MAY_DIFFER = {"name", "source", "scale", "rung", "readings", "ladder",
              "rmat_seed_why", "reduced_why", "assumed"}
PREFIXES = ["all-reduce", "all-gather", "all-to-all", "collective-permute",
            "reduce-scatter"]


def test_the_cell_and_its_configuration_are_declared_as_the_issue_names_them():
    spec = cells.load_benchmark()
    cell = cells.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        4, CONFIG, "graph-iter")
    assert cell.traffic == cells.load_cell("graph-iter-1chip").traffic
    assert cell.traffic["kind"] == "oink_script"
    cfg = cell.config
    with open(os.path.join(cells.BENCH_DIR, "configs", STRONG + ".json")) as f:
        strong = json.load(f)
    assert set(strong) <= set(cfg) and set(cfg) - set(strong) == {"deployment"}
    assert {k for k in strong if cfg[k] != strong[k]} == MAY_DIFFER
    assert cfg["scale"] in (22, 21) and strong["scale"] == 20
    for key in ("abcd", "pagerank"):        # the assumptions kept, one added
        assert cfg["assumed"][key] == strong["assumed"][key]
    assert set(cfg["assumed"]) == set(strong["assumed"]) | {"weak_scaling"}
    assert cfg["reduced"] == ["scale"] and cfg["ladder"]["rungs"] == [26, 24, 22]
    assert cfg["layout"] == {"chips": 4, "fuse": 0,
                             "mesh": "2x2, one process, edges sharded by row"}
    assert "8,388,608 edges" in cfg["source"] and len(cfg["source"]) <= 200
    assert "TO FILL" not in json.dumps(cfg)
    named = [w for w in spec["workloads"] if w["name"] == CELL]
    assert len(named) == 1 and named[0]["chips"] == 4
    held = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert len(held) == 1 and held[0]["reduced"] == ["scale"]
    assert held[0]["file"] == f"benchmark/configs/{CONFIG}.json"
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert e2e == {"job_s", "edge_rate", "setup_s"}
    # five of eleven cells take four chips: the slots are full
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) == 5 and len(spec["workloads"]) == 11
    assert len(four) <= len(spec["workloads"]) // 2


def test_its_metrics_are_listed_and_the_new_files_resolve_to_readers():
    from gpu_mapreduce_tpu.obs import names
    spec = cells.load_benchmark()
    listed = {m["name"]: m for m in spec["per_layer"]}
    perf = open(os.path.join(cells.ROOT, "PERF.md")).read()
    for name in JOINED:
        assert listed[name]["workloads"][-1] == CELL, name
        assert "graph-iter-1chip" in listed[name]["workloads"], name
    assert spec["per_layer"][-2]["name"] == "engine_collective_dev_s"
    assert spec["per_layer"][-1]["name"] == "engine_allreduce_mb"
    for name, (source, unit) in NEW.items():
        m = listed[name]
        assert m["workloads"] == [CELL] and m["better"] == "lower"
        assert (m["layer"], m["moves"]) == ("graph engines", "job_s")
        assert (m["source"], m["unit"]) == (source, unit)
        with open(os.path.join(cells.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            held = json.load(f)
        assert set(held) == {"reader", "args", "what"}
        reader = importlib.import_module("benchmark.readers." + held["reader"])
        assert callable(reader.read)
        assert set(held["args"].get("modules", [])) <= set(names.PROGRAMS)
        assert set(held["args"].get("spans", [])) <= set(names.SPANS)
        assert f"`{name}`" in perf, name
    with open(os.path.join(cells.BENCH_DIR, "layer_metrics",
                           "engine_collective_dev_s.json")) as f:
        args = json.load(f)["args"]
    assert set(args["modules"]) == {names.CC_LOOP, names.PAGERANK_LOOP,
                                    names.STAGE_RANK_GRAPH}
    assert args["prefixes"] == PREFIXES


# -- the collective reader: by hand, then on the recorded four-chip trace ---------

@pytest.fixture
def handmade(tmp_path):
    """Two traced jobs, 0-100 and 100-200 ms, two devices.  On device 0 a
    job runs the loop (10-50: a ``while`` holding two all-reduces of 4 ms
    and a fusion; 110-150 the same with all-reduces of 6 ms), the ranking
    (60-80, one all-gather 62-70 whose ``done`` 66-70 lies inside it) and a
    program the metric does not name (85-95, an all-reduce of 8 ms).
    Device 1 spends less."""
    from jax.profiler import ProfileData

    def loop(t0, ar):
        return [("%while.1 = s32[8] while(...)", t0, 40),
                ("%fusion.3 = s32[8] fusion(...)", t0 + 1, 5),
                # named by the JAX primitive, as the chip names them
                ("%pmin.7 = s32[8]{0:T(1024)S(1)} all-reduce(s32[8]{0} %m), "
                 "channel_id=1", t0 + 10, ar),
                ("%pmin.7 = s32[8]{0:T(1024)S(1)} all-reduce(s32[8]{0} %m), "
                 "channel_id=1", t0 + 25, ar)]

    text = "\n".join([
        _plane(1, "/device:TPU:0", {
            # the first loop starts "before" its job: the device's clock
            "XLA Modules": [("jit_cc_loop(11)", -0.5, 50.5),
                            ("jit_stage_rank_graph(12)", 60, 20),
                            ("jit_convert_sort(13)", 85, 10),
                            ("jit_cc_loop(11)", 110, 40)],
            "XLA Ops": loop(10, 4) + [
                ("%all-gather-start.2 = (u32[2]{0}, u32[8]{0:T(1024)}) "
                 "all-gather-start(u32[2]{0} %p), dimensions={0}", 62, 8),
                ("all-gather-done.2", 66, 4),
                ("%sort.9 = u32[8] sort(...)", 70, 10),
                ("%all-reduce.1 = s32[8] all-reduce(...)", 86, 8),
            ] + loop(110, 6)}),
        _plane(2, "/device:TPU:1", {
            "XLA Modules": [("jit_cc_loop(11)", 10, 40),
                            ("jit_cc_loop(11)", 110, 40)],
            "XLA Ops": loop(10, 1) + loop(110, 1)}),
        _plane(3, "/host:CPU", {
            "python3": [("bench.job", 0, 100), ("bench.job", 100, 100)]})])
    path = tmp_path / "handmade.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return xtrace.load(str(path), {xtrace.JOB_SPAN})


def test_collective_seconds_by_hand(handmade):
    read = collective_seconds.collective_seconds
    named = ["jit_cc_loop", "jit_pagerank_loop", "jit_stage_rank_graph"]
    # device 0: job 0 holds 4 + 4 in the loop and 8 in the ranking (the
    # done half lies inside the start's interval: once), job 1 holds 6 + 6;
    # the median of 16 and 12 ms.  Device 1: 2 and 2 ms.  The larger; the
    # unnamed program's 8 ms are in neither
    assert read(handmade, named, PREFIXES) == pytest.approx(0.014)
    assert read(handmade, ["jit_cc_loop"], PREFIXES) == pytest.approx(0.010)
    assert read(handmade, ["jit_cc_loop"], ["all-gather"]) == 0.0
    assert read(handmade, ["jit_convert_sort"], PREFIXES) == pytest.approx(0.008)
    assert read(handmade, ["jit_pagerank_loop"], PREFIXES) is None
    assert collective_seconds.opcode(
        "%while.3 = (s32[8]{0:T(1024)S(1)}, pred[]{:T(512)}, /*index=2*/"
        "pred[8]{0:T(1024)(128)(4,1)}) while((s32[8]{0} %a)), body=%b") == "while"
    assert collective_seconds.opcode("%fusion.2 = u64[8] fusion(...)") == "fusion"
    assert collective_seconds.opcode("sort.3") == "sort.3"


def test_collective_seconds_on_the_recorded_four_chip_trace():
    """``data/tiny_mesh.xplane.pb`` (``record_collective_trace.py``, PR 46):
    two jobs, each the package's sharded cc loop (a ``while`` with one
    ``pmin`` a round) and a program outside the names (one ``psum``)."""
    raw = xtrace.load(RECORDED, {xtrace.JOB_SPAN})
    assert sorted(raw["devices"]) == [0, 1, 2, 3]
    reduced = xtrace.reduce(raw, set())
    assert reduced["traced_jobs"] == 2
    read = collective_seconds.collective_seconds
    loop = read(raw, ["jit_cc_loop"], PREFIXES)
    other = read(raw, ["jit_other_psum"], PREFIXES)
    both = read(raw, ["jit_cc_loop", "jit_other_psum"], PREFIXES)
    assert loop > 0 and other > 0
    assert max(loop, other) <= both <= loop + other + 1e-9
    # the loop's collectives are a part of the loop program's seconds on
    # the device that spent most, and not all of it
    # (``xtrace.reduce`` files an execution under the job that holds its
    # start, and here the loop is dispatched as the job begins: the device's
    # clock puts its start 0.05 and 0.39 ms before the job's, so the
    # reduction files it under no job; the reader's rule is the overlap)
    assert "jit_cc_loop" not in reduced["program_job_seconds"]
    assert loop < max(max(runs) for runs in
                      reduced["programs"]["jit_cc_loop"].values())
    assert read(raw, ["jit_cc_loop"], ["fusion"]) > 0
    assert read(raw, ["jit_pagerank_loop"], PREFIXES) is None


def test_the_reader_finds_the_trace_the_harness_wrote_and_reads_nothing_without(
        tmp_path, handmade, monkeypatch):
    import types
    run = types.SimpleNamespace(
        trace={"traced": True},
        warmup=types.SimpleNamespace(outdir=str(tmp_path / "job00000")))
    args = {"modules": ["jit_cc_loop"], "prefixes": PREFIXES}
    assert collective_seconds.read(run, args) is None      # no file
    d = tmp_path / "trace" / "plugins" / "profile" / "2026_10_02"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(xtrace, "load", lambda path, names=None: handmade)
    assert collective_seconds.read(run, args) == pytest.approx(0.010)
    run.trace = None
    assert collective_seconds.read(run, args) is None      # not traced


# -- through the harness, tiny, on four of the CPU's virtual devices --------------

def test_cell_traced_reports_what_the_loops_merged(cpu_harness, cpu_trace,
                                                   capsys):
    cell = tiny_cell(CELL)
    assert cell.chips == 4 and cell.config["scale"] == 8
    line = cpu_harness.run_cell(cell, seed=(1 << 31) + 46, seconds=1.0,
                                trace=True, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    declared = {m["name"]: m for m in cell.metrics["per_layer"]}
    assert set(NEW) | set(JOINED) <= set(declared)
    missing = set(declared) - set(line["metrics"])
    # the CPU's stand-in planes hold no program events and no memory counts
    assert missing <= {"peak_hbm_gib"} | {
        n for n, m in declared.items() if m["source"] == "device_trace"}
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["compiles_in_window"] == 0 and value["rejit_s"] == 0
    out = capsys.readouterr().out
    checked = next(ln for ln in out.splitlines()
                   if ln.startswith("bench: warm-up job checked "))
    facts = json.loads(checked[checked.index("{"):])
    said = " ".join(facts["messages"])
    import re
    cc_iters = int(re.search(r"components in (\d+) iterations", said)[1])
    n_pr, pr_iters = map(int, re.search(
        r"PageRank: (\d+) vertices, \d+ edges, (\d+) iterations", said).groups())
    assert value["work_rounds"] == cc_iters + pr_iters
    # one all-reduce of n int32 / float32 a round; cc_find's n is the upper
    # edges' vertex count, at most pagerank's
    assert 0 < value["engine_allreduce_mb"] <= 4e-6 * n_pr * (cc_iters + pr_iters)
    assert value["engine_allreduce_mb"] >= 4e-6 * n_pr * pr_iters


def test_cell_untraced_reports_the_end_to_end_metrics(cpu_harness):
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=46, seconds=0.5,
                                trace=False, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"job_s", "edge_rate", "setup_s"}
