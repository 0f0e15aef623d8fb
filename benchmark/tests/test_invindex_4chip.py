"""ISSUE 26: the four-chip InvertedIndex cell and the metrics it brings, on
hand-made runs and, tiny, through the harness on the CPU's virtual devices
(``python -m pytest benchmark/tests``, not tier-1)."""

import json
import os

import pytest

from benchmark import cells
from benchmark.readers import span_attr_ratio
from benchmark.tests.test_harness import cpu_trace, tiny_cell  # noqa: F401
from benchmark.tests.test_names_readers import _job, _run

CELL = "invindex-puma-4chip"
NEW = ("collision_dev_s", "collision_s", "url_dict_s", "exchange_skew")
# what the CPU stand-in cannot read: no memory statistics, and no program
# events in its planes (every ``device_trace`` metric)
NO_DEVICE = {"peak_hbm_gib"}


def test_span_attr_ratio_weighs_each_span_by_its_denominator():
    args = {"spans": ["shuffle.exchange"], "num": ["recv_rows_max"],
            "den": ["recv_rows_mean"]}
    jobs = [_job(1, [("shuffle.exchange", {"recv_rows_max": 30,
                                           "recv_rows_mean": 10.0}),
                     ("aggregate", {"recv_rows_max": 999})]),
            _job(2, [("shuffle.exchange", {"recv_rows_max": 10,
                                           "recv_rows_mean": 10.0})])]
    assert span_attr_ratio.read(_run(jobs), args) == pytest.approx(2.0)
    # the program before PR 26 (the parent): the span is there, the
    # attributes are not; or no exchange ran; or the tracer was off
    old = [_job(1, [("shuffle.exchange", {"rows": 40})])]
    assert span_attr_ratio.read(_run(old), args) is None
    assert span_attr_ratio.read(_run([_job(1, [])]), args) is None
    assert span_attr_ratio.read(_run(), args) is None


def test_the_new_metric_files_quote_only_declared_names():
    from gpu_mapreduce_tpu.obs import names
    listed = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    text = open(os.path.join(cells.ROOT, "PERF.md")).read()
    for name in NEW:
        assert CELL in listed[name]["workloads"], name
        with open(os.path.join(cells.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        assert set(args.get("modules", [])) <= set(names.PROGRAMS), name
        for key in ("names", "spans"):
            assert set(args.get(key, [])) <= set(names.SPANS), (name, key)
        assert f"`{name}`" in text, name


def test_the_cell_is_the_one_chip_cell_on_four_chips():
    """Same shapes, same traffic file, same job module: the rung, the
    layout and the guarantees that exist only across shards differ."""
    four, one = cells.load_cell(CELL), cells.load_cell("invindex-puma-1chip")
    assert four.chips == 4 and four.traffic == one.traffic
    assert four.config["shapes"] == one.config["shapes"]
    assert four.config["file_bytes"] == one.config["file_bytes"]
    assert four.config["source"] != one.config["source"]
    assert four.config["files"] in four.config["ladder"]["rungs"]
    assert four.config["files"] % four.chips == 0
    assert set(one.config["guarantees"]) < set(four.config["guarantees"])
    names = lambda c: {m["name"] for g in c.metrics.values() for m in g}
    assert names(one) - names(four) == set()    # it joins all of them
    assert names(four) - names(one) >= {"shuffle_dev_s", "exchange_pad_share",
                                        "count_sync_s", "collision_s"}


def test_cell_traced_reports_what_exists_only_across_shards(
        cpu_harness, cpu_trace, capsys):
    cell = tiny_cell(CELL)
    assert cell.chips == 4 and cell.config["files"] == 8
    line = cpu_harness.run_cell(cell, seed=(1 << 31) + 11, seconds=1.0,
                                trace=True, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    declared = {m["name"]: m for m in cell.metrics["per_layer"]}
    missing = set(declared) - set(line["metrics"])
    assert missing <= NO_DEVICE | {n for n, m in declared.items()
                                   if m["source"] == "device_trace"}
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["compiles_in_window"] == 0
    assert 0 < value["collision_s"] <= value["map_device_s"]
    assert 0 < value["url_dict_s"] and 0 < value["count_sync_s"]
    assert 1.0 <= value["exchange_skew"] <= 4.0
    assert 0 <= value["exchange_pad_share"] < 100
    assert 0 < value["aggregate_host_s"] <= value["aggregate_s"]
    assert 0 < value["part_write_s"] <= value["group_reduce_s"]
    checked = next(ln for ln in capsys.readouterr().out.splitlines()
                   if ln.startswith("bench: warm-up job checked "))
    facts = json.loads(checked[checked.index("{"):])
    assert facts["parts"] == 4 and facts["exchange"]["rows"] > 0
    assert facts["map_stats"]["nbatches"] == 4
