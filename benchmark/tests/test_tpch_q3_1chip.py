"""ISSUE 43: the TPC-H Query 3 cell and the metrics it brings, tiny, through
the harness on the CPU, and its reference, generator and byte count on
their own (``python -m pytest benchmark/tests``, not tier-1).

``test_harness.tiny_cell`` sizes a cell by its job kind from a table that
this PR may not edit; as ``test_terasort_1chip.py`` does, this file enters
the kind it adds as it is imported."""

import json
import os
import sys

import numpy as np
import pytest

from benchmark import CheckFailure, cells, kernels_join
from benchmark.gen import tpch as gen
from benchmark.refs import tpch as ref
from benchmark.tests import test_harness
from benchmark.tests.test_harness import cpu_trace, tiny_cell  # noqa: F401

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_harness as _collected  # noqa: E402

for _module in (test_harness, _collected):
    _module.TINY.setdefault("tpch", {"scale_factor": 0.002})

CELL = "tpch-q3-1chip"
CONFIG = "tpch-1chip"
NEW = {"join_s": ("group + reduce", "program_span"),
       "join_dev_s": ("group + reduce", "device_trace"),
       "join_roofline": ("group + reduce", "device_trace"),
       "scan_s": ("ingest", "program_span"),
       "scan_dev_s": ("ingest", "device_trace"),
       "join_match_share": ("group + reduce", "program_counter"),
       "q3_topn_s": ("entry points", "program_span")}
JOINED = ("group_reduce_s", "sort_dev_s", "layout_dev_s", "aggregate_s",
          "aggregate_host_s", "entry_glue_s", "host_cpu_s", "host_off_cpu_s",
          "proc_cpu_s", "invol_switches", "rejit_s", "program_load_s")
NO_DEVICE = {"peak_hbm_gib"}    # the CPU stand-in has no memory statistics


def _tables(tmp_path, sf=0.002, seed=(1 << 31) + 5):
    paths = gen.make_tables(str(tmp_path / "tables"), sf, seed)
    return paths, [ref.read_table(t, paths[t]) for t in ref.TABLES]


# -- the reference and the generator on their own -----------------------------

def _brute_q3(customer, orders, lineitem, segment, date):
    """Q3 row by row with dicts: slow, obvious."""
    d, seg = ref.day(date), ref.SEGMENTS.index(segment)
    building = {int(k) for k, v in zip(*customer) if v[3] == seg}
    open_ = {}
    for k, v in zip(*orders):
        if v[5] < d and ((int(v[0]) << 32) | int(v[1])) in building:
            open_[int(k)] = (int(v[5]), int(v[8]))
    revenue = {}
    for k, v in zip(*lineitem):
        if v[10] > d and int(k) in open_:
            price = int(v[4]) | (int(v[5]) << 32)
            revenue[int(k)] = revenue.get(int(k), 0) + price * (
                100 - int(v[6]))
    rows = [(k, r) + open_[k] for k, r in revenue.items()]
    return sorted(rows, key=lambda r: (-r[1], r[2], r[0]))


@pytest.mark.parametrize("segment, date", [
    ("BUILDING", "1995-03-15"), ("MACHINERY", "1993-07-01"),
    ("HOUSEHOLD", "1998-01-01")])
def test_q3_against_a_row_by_row_query(tmp_path, segment, date):
    _, tables = _tables(tmp_path)
    want = _brute_q3(*tables, segment, date)
    got = ref.q3(*tables, segment, date)
    assert len(want) > 5
    assert list(zip(got["orderkey"].tolist(), got["revenue"].tolist(),
                    got["orderdate"].tolist(),
                    got["shippriority"].tolist())) == want
    assert ref.lines(got)[0] == ref.line(*want[0])
    assert got["matched"]["lineitem"][1] >= len(want)


def test_a_date_before_every_order_selects_nothing(tmp_path):
    _, tables = _tables(tmp_path)
    got = ref.q3(*tables, "BUILDING", "1992-01-01")
    assert len(got["orderkey"]) == 0 and ref.lines(got) == []
    assert got["scanned"]["orders"][1] == 0


def test_the_reference_uses_nothing_of_the_program():
    for module in (ref, gen, kernels_join):
        assert "gpu_mapreduce_tpu" not in open(
            module.__file__).read().split('"""', 2)[2], module.__name__


def test_the_generator_draws_the_specifications_shapes(tmp_path):
    paths, (customer, orders, lineitem) = _tables(tmp_path, sf=0.01)
    assert [os.path.basename(p) for p in paths["orders"]] == [
        "orders-00000.dat"]
    (ck, cv), (ok, ov), (lk, lv) = customer, orders, lineitem
    assert len(ck) == 1500 and len(ok) == 15000
    assert 3.8 < len(lk) / len(ok) < 4.2
    assert ck.tolist() == list(range(1, 1501))
    # 8 of every 32 order keys, from 1
    assert ok[:9].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 33]
    assert ((ok - 1) % 32 < 8).all() and len(np.unique(ok)) == len(ok)
    custkey = ov[:, 1].astype(np.int64)
    assert (custkey % 3 != 0).all() and custkey.min() >= 1
    assert custkey.max() <= 1500 and (ov[:, 0] == 0).all()
    assert ov[:, 5].max() <= ref.day("1998-08-02") and (ov[:, 8] == 0).all()
    per_order = np.bincount(np.searchsorted(ok, lk))
    assert per_order.min() == 1 and per_order.max() == 7
    odate = ov[np.searchsorted(ok, lk), 5]
    ship = lv[:, 10].astype(np.int64) - odate
    assert ship.min() == 1 and ship.max() == 121
    assert lv[:, 3].min() == 1 and lv[:, 3].max() == 50
    assert lv[:, 6].max() == 10 and lv[:, 7].max() == 8
    assert set(np.unique(cv[:, 3])) == set(range(5))
    price = ref.money(lv, "lineitem", "extendedprice")
    part = lv[:, 0].astype(np.int64)
    assert (price == lv[:, 3] * (90000 + (part // 10) % 20001
                                 + 100 * (part % 1000))).all()
    again, _ = _tables(tmp_path / "again", sf=0.01)
    assert all(open(a, "rb").read() == open(b, "rb").read()
               for t in ref.TABLES for a, b in zip(paths[t], again[t]))
    assert ref.record_bytes("lineitem") == 68
    assert os.path.getsize(paths["lineitem"][0]) == 68 * len(lk)


def test_join_bytes_counts_what_its_docstring_says():
    assert kernels_join.join_bytes(10, 4, 3, 8, 16, 4) == (
        2 * (10 * 24 + 4 * 12) + 3 * 28)
    assert kernels_join.join_bytes(0, 0, 0, 8, 8, 8) == 0


def test_the_plain_join_is_a_dict():
    assert ref.join([(1,), (2,), (1,), (3,)], [(10,), (20,), (11,), (30,)],
                    [(1,), (3,)], [(7, 8), (9, 9)]) == [
        ((1,), (10, 7, 8)), ((1,), (11, 7, 8)), ((3,), (30, 9, 9))]
    with pytest.raises(ValueError, match="occurs twice"):
        ref.join([(1,)], [(0,)], [(1,), (1,)], [(0,), (0,)])


# -- the cell -----------------------------------------------------------------

def test_the_cell_and_its_metrics_are_declared_as_the_issue_names_them():
    from gpu_mapreduce_tpu.obs import names
    spec = cells.load_benchmark()
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.traffic_name == "tpch-q3" and cell.traffic["kind"] == "tpch"
    cfg = cell.config
    assert cfg["reduced"] == ["scale_factor"] and cfg["architecture"] is None
    assert cfg["scale_factor"] in cfg["ladder"]["rungs"]
    assert (cfg["segment"], cfg["date"]) == ("BUILDING", "1995-03-15")
    assert {t: tuple(c) for t, c in cfg["columns"].items()} == ref.COLUMNS
    assert [len(c) for c in cfg["columns"].values()] == [4, 9, 15]
    assert len(cfg["guarantees"]) >= 5
    assert {"specification", "generator", "text_columns"} <= set(
        cfg["assumed"])
    assert "not measured yet" not in json.dumps(cfg)
    named = [w for w in spec["workloads"] if w["name"] == CELL]
    assert len(named) == 1 and named[0]["config"] == CONFIG
    declared = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert len(declared) == 1 and declared[0]["source"] == cfg["source"]
    assert len(cfg["source"]) <= 200
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert e2e == {"job_s", "corpus_rate", "setup_s"}
    listed = {m["name"]: m for m in spec["per_layer"]}
    perf = open(os.path.join(cells.ROOT, "PERF.md")).read()
    for name in tuple(NEW) + JOINED:
        assert CELL in listed[name]["workloads"], name
    readers = set(os.listdir(os.path.join(cells.BENCH_DIR, "readers")))
    for name, (layer, source) in NEW.items():
        m = listed[name]
        assert m["workloads"] == [CELL] and m["moves"] == "job_s"
        assert (m["layer"], m["source"]) == (layer, source), name
        with open(os.path.join(cells.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            data = json.load(f)
        assert data["reader"] + ".py" in readers, name
        args = data["args"]
        for key in ("names", "spans"):
            assert set(args.get(key, [])) <= set(names.SPANS) | {
                names.JOIN_SPAN}, (name, key)
        for module in args.get("modules", []):
            assert names.declared_program(module), module
        for key in ("num", "den"):
            assert set(args.get(key, [])) <= set(names.SPAN_ATTRS), name
        assert f"`{name}`" in perf, name
    assert listed["join_roofline"]["unit"] == "%"


def test_the_cell_brings_data_files_and_its_own_job_module_only():
    """Nothing that was there is edited: the cell is new files and
    appended entries (``test_contract.py`` holds the whole declaration)."""
    for path in ("configs/tpch-1chip.json", "traffic/tpch-q3.json",
                 "jobs/tpch.py", "gen/tpch.py", "refs/tpch.py",
                 "kernels_join.py"):
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
    for name in ("join_s", "scan_s", "q3_topn_s", "group_reduce_s",
                 "aggregate_s", "host_cpu_s", "proc_cpu_s"):
        assert value[name] > 0, name
    out = capsys.readouterr().out
    checked = next(ln for ln in out.splitlines()
                   if ln.startswith("bench: warm-up job checked "))
    facts = json.loads(checked[checked.index("{"):])
    prepared = next(ln for ln in out.splitlines()
                    if ln.startswith("bench: prepared "))
    matched = json.loads(prepared[prepared.index("{"):])
    probes = matched["orders"][0] + matched["lineitem"][0]
    hits = matched["orders"][1] + matched["lineitem"][1]
    assert value["join_match_share"] == pytest.approx(hits / probes)
    assert facts["groups"] > 5 and facts["lines"] == 10
    assert facts["spans"]["joins"] == [matched["orders"],
                                       matched["lineitem"]]
    assert facts["messages"][0].endswith(
        f"{matched['orders'][1]} orders and {matched['lineitem'][1]} lines "
        f"joined; {facts['groups']} groups, 10 lines")


def test_the_roofline_reader_gets_the_joins_bytes(cpu_harness):
    """On the CPU no program event reaches the trace, so the share itself
    is left out; what the job module hands the reader is read here."""
    import jax
    from benchmark.cache import Cache
    from benchmark.jobs import tpch as job_module
    from gpu_mapreduce_tpu.obs import names
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    cell = tiny_cell(CELL)
    job = job_module.Job(cell.config, cell.traffic,
                         make_mesh(devices=jax.devices()[:1]), 1, Cache())
    facts = job.prepare()
    rows = facts["rows"]
    assert rows["customer"] == 300 and rows["orders"] == 3000
    assert facts["corpus_bytes"] == (300 * 24 + 3000 * 44
                                     + rows["lineitem"] * 68)
    assert job.work() == {"corpus_bytes": facts["corpus_bytes"]}
    early, open_orders = facts["orders"]
    late, joined = facts["lineitem"]
    info = job.info()
    assert info["programs"] == {"join": names.JOIN_ROWS}
    assert info["bytes_moved"]["join"] == (
        kernels_join.join_bytes(early, job.facts["scanned"]["customer"][1],
                                open_orders, 8, 16, 4)
        + kernels_join.join_bytes(late, open_orders, joined, 8, 8, 8))


def test_cell_untraced_reports_corpus_rate(cpu_harness):
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=3, seconds=0.5,
                                trace=False, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"job_s", "corpus_rate", "setup_s"}


def test_the_cell_on_the_mesh_of_four(cpu_harness):
    """The half of the join one chip never runs (both sides placed by one
    destination spec through the exchange), through the same harness."""
    line = cpu_harness.run_cell(tiny_cell(CELL, chips=4), seed=11,
                                seconds=0.5, trace=False, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0


# -- a wrong result makes ``correct`` false -----------------------------------

def _float32_sum(monkeypatch):
    """Revenue summed in float32 and cast back: exact for a group of one
    small line, off by a few units of 10^-4 for most."""
    import jax.numpy as jnp
    from gpu_mapreduce_tpu.parallel import group
    real = group.segment_reduce_rows

    def lossy(x, seg, valid, gcap, op):
        if op == "sum" and x.dtype == jnp.int64:
            return real(x.astype(jnp.float32), seg, valid, gcap,
                        op).astype(jnp.int64)
        return real(x, seg, valid, gcap, op)
    monkeypatch.setattr(group, "segment_reduce_rows", lossy)
    group._reduce_cached.cache_clear()


def _last_build_row_lost(monkeypatch):
    """The join sees one build row fewer than the shard holds."""
    from gpu_mapreduce_tpu.parallel import group
    real = group.join_rows_body

    def short(pk, pc, bk, bc):
        return real(pk, pc, bk, bc - 1)
    monkeypatch.setattr(group, "join_rows_body", short)
    group._join_jit.cache_clear()


@pytest.mark.parametrize("fault, said", [
    (_float32_sum, "groups differ from the reference"),
    (_last_build_row_lost, "WRONG RESULT")],
    ids=["float32-sum", "last-build-row-lost"])
def test_a_planted_fault_makes_correct_false(cpu_harness, monkeypatch,
                                             capsys, fault, said):
    from gpu_mapreduce_tpu.parallel import group
    fault(monkeypatch)
    try:
        # seed 4: the last customer of the segment has orders before the
        # date (one whose key is a multiple of three has none, and losing
        # its row changes nothing)
        line = cpu_harness.run_cell(tiny_cell(CELL), seed=4, seconds=0.2,
                                    trace=False, t_process=0.0)
    finally:
        monkeypatch.undo()
        group._reduce_cached.cache_clear()
        group._join_jit.cache_clear()
    assert line["correct"] is False
    assert said in capsys.readouterr().out


def test_a_job_that_changes_a_table_makes_correct_false(cpu_harness,
                                                        monkeypatch):
    """The tables are read where set-up left them: a window job that
    leaves one changed is caught by the checksum in every digest."""
    from benchmark.jobs import tpch as job_module
    real = job_module.Job.run
    calls = []

    def run(self, outdir):
        result = real(self, outdir)
        calls.append(outdir)
        if len(calls) == 2:
            from gpu_mapreduce_tpu.oink.objects import _mesh_frame
            fr = _mesh_frame(self.shared.obj.get_mr("orders"))
            fr.value = fr.value.at[3, 7].add(1)
        return result

    monkeypatch.setattr(job_module.Job, "run", run)
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                                trace=False, t_process=0.0)
    assert line["correct"] is False and line["failed"] >= 1


def test_a_tie_in_the_ten_is_held_by_its_two_columns_alone(cpu_harness,
                                                           monkeypatch):
    """Where two of the lines tie in (revenue, o_orderdate) the order of
    their keys is the job's to choose: the digest then reads those two
    columns of q3.txt, not the keys."""
    from benchmark.jobs import tpch as job_module
    monkeypatch.setattr(ref, "tied", lambda result, limit=ref.LIMIT: True)
    real = job_module.Job.run
    calls = []

    def run(self, outdir):
        result = real(self, outdir)
        calls.append(outdir)
        if len(calls) == 2:     # another key on the first line
            path = os.path.join(outdir, job_module.LINES)
            rows = open(path).read().splitlines()
            rows[0] = "7|" + rows[0].split("|", 1)[1]
            open(path, "w").write("".join(r + "\n" for r in rows))
        return result

    monkeypatch.setattr(job_module.Job, "run", run)
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                                trace=False, t_process=0.0)
    assert line["correct"] is True and len(calls) >= 2


def test_a_tree_without_the_application_is_refused_in_prepare(monkeypatch):
    import importlib.util

    import jax
    from benchmark.jobs import tpch as job_module
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name.endswith("apps.tpch")
        else real(name, *a))
    cell = tiny_cell(CELL)
    job = job_module.Job(cell.config, cell.traffic,
                         make_mesh(devices=jax.devices()[:1]), 1, None)
    with pytest.raises(CheckFailure, match="no gpu_mapreduce_tpu.apps.tpch"):
        job.prepare()
