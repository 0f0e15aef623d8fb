"""ISSUE 50: the TPC-H Query 1 cell and the metrics it brings, tiny, through
the harness on the CPU, and its reference and byte count on their own
(``python -m pytest benchmark/tests``, not tier-1).

``test_harness.tiny_cell`` sizes a cell by its job kind from a table that
this PR may not edit; as ``test_tpch_q3_1chip.py`` does, this file enters
the kind it adds as it is imported."""

import json
import os
import sys

import numpy as np
import pytest

from benchmark import CheckFailure, cells, kernels_combine
from benchmark.gen import tpch as gen
from benchmark.refs import tpch as ref
from benchmark.refs import tpch_q1 as refq1
from benchmark.tests import test_harness
from benchmark.tests.test_harness import cpu_trace, tiny_cell  # noqa: F401

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_harness as _collected  # noqa: E402

for _module in (test_harness, _collected):
    _module.TINY.setdefault("tpch_q1", {"scale_factor": 0.002})

CELL = "tpch-q1-1chip"
CONFIG = "tpch-pricing-1chip"
NEW = {"combine_s": ("group + reduce", "program_span", "job_s"),
       "combine_dev_s": ("group + reduce", "device_trace", "job_s"),
       "combine_roofline": ("group + reduce", "device_trace", "corpus_rate"),
       "combine_fold": ("group + reduce", "program_counter", "job_s"),
       "q1_scan_dev_s": ("ingest", "device_trace", "job_s")}
JOINED = ("scan_s", "group_reduce_s", "aggregate_s", "aggregate_host_s",
          "sort_dev_s", "layout_dev_s", "entry_glue_s", "host_cpu_s",
          "host_off_cpu_s", "proc_cpu_s", "invol_switches", "rejit_s",
          "program_load_s", "step_named_share")
NO_DEVICE = {"peak_hbm_gib"}    # the CPU stand-in has no memory statistics


def _tables(tmp_path, sf=0.002, seed=(1 << 31) + 5):
    paths = gen.make_tables(str(tmp_path / "tables"), sf, seed)
    return paths, ref.read_table("lineitem", paths["lineitem"])


# -- the reference and the byte count on their own ----------------------------

def _brute_q1(lineitem, delta):
    """Q1 row by row in Python integers: slow, obvious."""
    last = ref.day("1998-12-01") - delta
    groups = {}
    for v in lineitem[1].tolist():
        if v[10] > last:
            continue
        price = v[4] | (v[5] << 32)
        net = price * (100 - v[6])
        g = groups.setdefault(("ARN"[v[8]], "FO"[v[9]]), [0] * 6)
        for i, x in enumerate((v[3], price, net, net * (100 + v[7]), v[6],
                               1)):
            g[i] += x
    return sorted(groups.items())


@pytest.mark.parametrize("delta", [90, 0, 1270, 2000, 2600])
def test_q1_against_a_row_by_row_query(tmp_path, delta):
    _, lineitem = _tables(tmp_path)
    want = _brute_q1(lineitem, delta)
    got = refq1.q1(lineitem, delta)
    assert len(want) == {90: 4, 0: 4, 1270: 3, 2000: 2, 2600: 0}[delta]
    assert [(chr(f) , chr(s)) for f, s in zip(
        got["returnflag"], got["linestatus"])] == [k for k, _ in want]
    assert [[int(got[n][i]) for n in refq1.SUMS]
            for i in range(len(want))] == [g for _, g in want]
    assert got["scanned"]["lineitem"] == [
        len(lineitem[0]), sum(g[5] for _, g in want)]
    printed = refq1.lines(got)
    assert len(printed) == len(want)
    for line, ((flag, status), g) in zip(printed, want):
        cells_ = line.split("|")
        assert cells_[:3] == [flag, status, str(g[0])]
        assert cells_[3] == f"{g[1] // 100}.{g[1] % 100:02d}"
        assert cells_[5] == f"{g[3] // 10 ** 6}.{g[3] % 10 ** 6:06d}"
        assert cells_[6] == f"{round(g[0] / g[5] + 1e-9, 2):.2f}"
        assert cells_[9] == str(g[5])
    refq1.check_q1(got, {k: got[k] for k in (
        "returnflag", "linestatus") + refq1.SUMS}, printed)


def test_the_reference_uses_nothing_of_the_program():
    for module in (refq1, kernels_combine):
        assert "gpu_mapreduce_tpu" not in open(
            module.__file__).read().split('"""', 2)[2], module.__name__


def test_combine_bytes_counts_what_its_docstring_says():
    assert kernels_combine.combine_bytes(10, 8, 48, 4) == 10 * 56 + 4 * 56
    assert kernels_combine.combine_bytes(0, 8, 48, 0) == 0
    assert kernels_combine.combine_bytes(1, 4, 4, 1) == 16
    # the cell's: 5.88 x 10^7 kept rows of 56 bytes, four rows out
    assert kernels_combine.combine_bytes(58_800_000, 8, 48, 4) == \
        3_292_800_224


def test_the_check_refuses_wrong_sums_groups_and_lines(tmp_path):
    _, lineitem = _tables(tmp_path)
    want = refq1.q1(lineitem, 90)
    got = {k: want[k].copy() for k in ("returnflag", "linestatus")
           + refq1.SUMS}
    printed = refq1.lines(want)
    assert refq1.check_q1(want, got, printed) == {"groups": 4, "lines": 4}
    shuffled = {k: v[::-1] for k, v in got.items()}
    assert refq1.check_q1(want, shuffled, printed)["groups"] == 4
    smallest = int(np.argmin(got["count"]))
    assert printed[smallest].startswith("N|F|")
    with pytest.raises(CheckFailure, match="3 groups where"):
        refq1.check_q1(want, {k: np.delete(v, smallest)
                              for k, v in got.items()}, printed)
    for name in refq1.SUMS:
        off = dict(got, **{name: got[name] + np.eye(4, dtype=np.int64)[2]})
        with pytest.raises(CheckFailure, match="1 groups differ"):
            refq1.check_q1(want, off, printed)
    with pytest.raises(CheckFailure, match="lines differ"):
        refq1.check_q1(want, got, printed[:-1])


# -- the cell -----------------------------------------------------------------

def test_the_cell_and_its_metrics_are_declared_as_the_issue_names_them():
    from gpu_mapreduce_tpu.obs import names
    spec = cells.load_benchmark()
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.traffic_name == "tpch-q1"
    assert cell.traffic["kind"] == "tpch_q1"
    q3 = cells.load_cell("tpch-q3-1chip")
    assert cell.traffic["setup"] == q3.traffic["setup"]
    cfg = cell.config
    assert cfg["reduced"] == ["scale_factor"] and cfg["architecture"] is None
    assert cfg["scale_factor"] == q3.config["scale_factor"] == 10
    assert cfg["scale_factor"] in cfg["ladder"]["rungs"]
    assert cfg["delta_days"] == 90 and cfg["columns"] == q3.config["columns"]
    assert cfg["layout"]["fuse"] == 0 and cfg["layout"]["chips"] == 1
    assert len(cfg["guarantees"]) >= 4
    assert set(q3.config["assumed"]) | {"query", "averages"} <= set(
        cfg["assumed"])
    assert "READINGS" != cfg["readings"] and "not measured" not in cfg[
        "readings"]
    named = [w for w in spec["workloads"] if w["name"] == CELL]
    assert len(named) == 1 and named[0]["config"] == CONFIG
    declared = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert len(declared) == 1 and declared[0]["source"] == cfg["source"]
    assert len(cfg["source"]) <= 200 and "2.4.1" in cfg["source"]
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert e2e == {"job_s", "corpus_rate", "setup_s"}
    listed = {m["name"]: m for m in spec["per_layer"]}
    perf = open(os.path.join(cells.ROOT, "PERF.md")).read()
    for name in tuple(NEW) + JOINED:
        assert CELL in listed[name]["workloads"], name
    readers = set(os.listdir(os.path.join(cells.BENCH_DIR, "readers")))
    for name, (layer, source, moves) in NEW.items():
        m = listed[name]
        assert m["workloads"] == [CELL] and m["moves"] == moves
        assert (m["layer"], m["source"]) == (layer, source), name
        with open(os.path.join(cells.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            data = json.load(f)
        assert data["reader"] + ".py" in readers, name
        args = data["args"]
        for key in ("names", "spans"):
            assert set(args.get(key, [])) <= set(names.SPANS) | {
                names.COMPRESS_SPAN}, (name, key)
        for module in args.get("modules", []):
            assert names.declared_program(module), module
        for key in ("num", "den"):
            assert set(args.get(key, [])) <= set(names.SPAN_ATTRS), name
        assert f"`{name}`" in perf, name
    assert listed["combine_roofline"]["unit"] == "%"
    # twelve cells, five of them on four chips, where six may
    chips = [w["chips"] for w in spec["workloads"]]
    assert len(chips) == 12 and chips.count(4) == 5 < len(chips) // 2 + 1


def test_the_cell_brings_new_files_only():
    for path in ("configs/tpch-pricing-1chip.json", "traffic/tpch-q1.json",
                 "jobs/tpch_q1.py", "refs/tpch_q1.py", "kernels_combine.py",
                 "layer_metrics/combine_s.json",
                 "layer_metrics/combine_dev_s.json",
                 "layer_metrics/combine_roofline.json",
                 "layer_metrics/combine_fold.json",
                 "layer_metrics/q1_scan_dev_s.json"):
        assert os.path.exists(os.path.join(cells.BENCH_DIR, path)), path


def test_cell_traced_reports_every_new_metric(cpu_harness, cpu_trace, capsys):
    cell = tiny_cell(CELL)
    line = cpu_harness.run_cell(cell, seed=(1 << 31) + 7, seconds=1.0,
                                trace=True, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    declared = {m["name"]: m for m in cell.metrics["per_layer"]}
    missing = set(declared) - set(line["metrics"])
    assert missing <= NO_DEVICE | {n for n, m in declared.items()
                                   if m["source"] == "device_trace"}
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["compiles_in_window"] == 0 and value["rejit_s"] == 0
    for name in ("combine_s", "scan_s", "group_reduce_s", "aggregate_s",
                 "host_cpu_s", "proc_cpu_s"):
        assert value[name] > 0, name
    out = capsys.readouterr().out
    checked = next(ln for ln in out.splitlines()
                   if ln.startswith("bench: warm-up job checked "))
    facts = json.loads(checked[checked.index("{"):])
    prepared = next(ln for ln in out.splitlines()
                    if ln.startswith("bench: prepared "))
    made = json.loads(prepared[prepared.index("{"):])
    assert facts["groups"] == facts["lines"] == made["groups"] == 4
    assert value["combine_fold"] == pytest.approx(made["kept"] / 4)
    assert facts["spans"]["scan"] == [made["rows"]["lineitem"], made["kept"]]
    assert facts["spans"]["compress"] == {
        "rows": made["kept"], "groups": 4,
        "group_rows_max": max(made["group_rows"]), "key_words": 2,
        "value_words": 12, "combined": 1}
    assert made["corpus_bytes"] == 68 * made["rows"]["lineitem"]
    assert facts["messages"] == [
        f"TPC-H Q1 DELTA 90: {made['rows']['lineitem']} lineitem rows "
        f"scanned, {made['kept']} kept; 4 groups, 4 lines"]


def test_the_roofline_reader_gets_the_combiners_bytes(cpu_harness):
    """On the CPU no program event reaches the trace, so the share itself
    is left out; what the job module hands the reader is read here."""
    import jax
    from benchmark.cache import Cache
    from benchmark.jobs import tpch_q1 as job_module
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    cell = tiny_cell(CELL)
    job = job_module.Job(cell.config, cell.traffic,
                         make_mesh(devices=jax.devices()[:1]), 1, Cache())
    facts = job.prepare()
    assert facts["rows"]["customer"] == 300 and facts["groups"] == 4
    assert sum(facts["group_rows"]) == facts["kept"]
    assert job.work() == {"corpus_bytes": 68 * facts["rows"]["lineitem"]}
    info = job.info()
    assert info["programs"] == {"combine": "jit_combine_tpch_q1"}
    assert info["bytes_moved"]["combine"] == kernels_combine.combine_bytes(
        facts["kept"], 8, 48, 4)


def test_cell_untraced_reports_corpus_rate(cpu_harness):
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=(1 << 31) + 3,
                                seconds=0.5, trace=False, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"job_s", "corpus_rate", "setup_s"}
    assert line["attempted"] >= 2


# -- a wrong result makes ``correct`` false -----------------------------------

def _float32_sum(monkeypatch):
    import jax.numpy as jnp
    from gpu_mapreduce_tpu.parallel import group
    real = group._FOLD["sum"]
    monkeypatch.setitem(group._FOLD, "sum", (
        lambda x, m: real[0](x.astype(jnp.float32), m).astype(jnp.int64),
        *real[1:]))


def _tiles_last_row_lost(monkeypatch):
    import jax.numpy as jnp
    from gpu_mapreduce_tpu.parallel import group
    real = group._FOLD["sum"]

    def short(x, m):
        last = jnp.arange(m.shape[0]).reshape(m.shape[:1] + (1,) * (
            m.ndim - 1)) == m.shape[0] - 1
        return real[0](x, m & ~last)
    monkeypatch.setattr(group, "COMBINE_TILE", 256)
    monkeypatch.setitem(group._FOLD, "sum", (short, *real[1:]))


def _smallest_group_dropped(monkeypatch):
    from gpu_mapreduce_tpu.parallel import group
    real = group.combine_sharded

    def fewer(skv, op):
        out = real(skv, op)
        if out is not None and int(out.counts[0]) == 4:
            # N/F is the second key of four: the third and fourth move up
            out.key = out.key.at[1:3].set(out.key[2:4])
            out.value = out.value.at[1:3].set(out.value[2:4])
            out.counts[0] = 3
        return out
    monkeypatch.setattr(group, "combine_sharded", fewer)


@pytest.mark.parametrize("fault, said", [
    (_float32_sum, "groups differ from the reference"),
    (_tiles_last_row_lost, "groups differ from the reference"),
    (_smallest_group_dropped, "3 groups where the reference has 4")],
    ids=["float32-sum", "tiles-last-row-lost", "smallest-group-dropped"])
def test_a_planted_fault_makes_correct_false(cpu_harness, monkeypatch,
                                             capsys, fault, said):
    from gpu_mapreduce_tpu.parallel import group
    fault(monkeypatch)
    group._combine_jit.cache_clear()
    try:
        line = cpu_harness.run_cell(tiny_cell(CELL), seed=4, seconds=0.2,
                                    trace=False, t_process=0.0)
    finally:
        monkeypatch.undo()
        group._combine_jit.cache_clear()
    assert line["correct"] is False
    out = capsys.readouterr().out
    assert "WRONG RESULT" in out and said in out


def test_a_job_that_changes_a_table_makes_correct_false(cpu_harness,
                                                        monkeypatch):
    from benchmark.jobs import tpch_q1 as job_module
    real = job_module.Job.run
    calls = []

    def run(self, outdir):
        result = real(self, outdir)
        calls.append(outdir)
        if len(calls) == 2:
            from gpu_mapreduce_tpu.oink.objects import _mesh_frame
            fr = _mesh_frame(self.shared.obj.get_mr("lineitem"))
            fr.value = fr.value.at[3, 12].add(1)    # l_receiptdate: unread
        return result

    monkeypatch.setattr(job_module.Job, "run", run)
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                                trace=False, t_process=0.0)
    assert line["correct"] is False and line["failed"] >= 1


def test_a_tree_without_the_query_is_refused_in_prepare(monkeypatch):
    import jax
    from benchmark.jobs import tpch_q1 as job_module
    from gpu_mapreduce_tpu.apps import tpch as app
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    monkeypatch.delattr(app, "q1")
    cell = tiny_cell(CELL)
    job = job_module.Job(cell.config, cell.traffic,
                         make_mesh(devices=jax.devices()[:1]), 1, None)
    with pytest.raises(CheckFailure, match="has no q1"):
        job.prepare()
