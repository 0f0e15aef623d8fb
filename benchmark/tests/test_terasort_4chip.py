"""ISSUE 39: the four-chip TeraSort cell and the two metrics it brings,
tiny, through the harness on four of the CPU's virtual devices
(``python -m pytest benchmark/tests``, not tier-1).  The reference, the
generator and the job module are ``test_terasort_1chip.py``'s: this cell
adds data files only.

``test_harness.tiny_cell`` sizes a cell by its job kind from a table that
this PR may not edit; as ``test_terasort_1chip.py`` does, this file enters
the kind as it is imported (whichever of the two is collected first)."""

import json
import os
import sys

from benchmark import cells
from benchmark.tests import test_harness
from benchmark.tests.test_harness import cpu_trace, tiny_cell  # noqa: F401

# pytest imports the test files of this directory by their bare names, so
# its ``test_harness`` is another module object than the one imported
# above: enter the kind in both (see test_graph_tri_1chip.py)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_harness as _collected  # noqa: E402

for _module in (test_harness, _collected):
    _module.TINY.setdefault("terasort", {"file_records": 1500})

CELL = "terasort-4chip"
CONFIG = "sortbench-terasort-4chip"
ONE_CHIP = "sortbench-terasort-1chip"
NEW = {"sample_s": ("shuffle", "job_s", "s"),
       "phase1_builds": ("compile", "job_s", "count")}
JOINED = ("aggregate_s", "aggregate_host_s", "count_sync_s",
          "exchange_pad_share", "exchange_skew", "shuffle_dev_s",
          "record_read_s", "record_sort_dev_s", "record_sort_roofline",
          "record_write_s", "sort_s", "entry_glue_s", "host_cpu_s",
          "host_off_cpu_s", "proc_cpu_s", "invol_switches", "rejit_s",
          "program_load_s")
NO_DEVICE = {"peak_hbm_gib"}    # the CPU stand-in has no memory statistics


def test_the_cell_and_its_metrics_are_declared_as_the_issue_names_them():
    from gpu_mapreduce_tpu.obs import names
    spec = cells.load_benchmark()
    cell = cells.load_cell(CELL)
    assert cell.chips == 4 and cell.config_name == CONFIG
    assert cell.traffic_name == "terasort"
    assert cell.traffic["kind"] == "terasort"
    cfg, one = cell.config, cells.load_cell("terasort-hbm-1chip").config
    # the source's shapes, unchanged, and the one-chip file's
    for key in ("record_bytes", "key_bytes", "file_records",
                "prefix_twin_rate", "sample"):
        assert cfg[key] == one[key], key
    assert (cfg["record_bytes"], cfg["key_bytes"], cfg["file_records"],
            cfg["sample"]) == (100, 10, 1_250_000, 100_000)
    assert cfg["reduced"] == ["files"] and cfg["architecture"] is None
    assert cfg["ladder"]["rungs"] == [32, 16, 8]
    assert cfg["files"] in (16, 8)
    assert cfg["layout"]["chips"] == 4 and cfg["layout"]["fuse"] == 0
    assert len(cfg["guarantees"]) == len(one["guarantees"]) == 5
    assert "part i+1" in cfg["guarantees"][1]
    assert "four part files" in cfg["guarantees"][1]
    assert set(one["assumed"]) | {"splitters", "part_sizes"} <= set(
        cfg["assumed"])
    assert "TO FILL" not in json.dumps(cfg)
    assert "16 files" in cfg["readings"] and "8 files" in cfg["readings"]
    # that the cell is there as the issue names it; how many cells there
    # are, and how many may take four chips, is test_contract.py's
    named = [w for w in spec["workloads"] if w["name"] == CELL]
    assert len(named) == 1 and named[0]["config"] == CONFIG
    assert named[0]["chips"] == 4 and len(named[0]["why"]) <= 200
    held = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert len(held) == 1 and held[0]["file"].endswith(CONFIG + ".json")
    assert held[0]["file"] != next(
        c["file"] for c in spec["configs"] if c["name"] == ONE_CHIP)
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert e2e == {"job_s", "corpus_rate", "setup_s"}
    listed = {m["name"]: m for m in spec["per_layer"]}
    perf = open(os.path.join(cells.ROOT, "PERF.md")).read()
    for name in tuple(NEW) + JOINED:
        assert CELL in listed[name]["workloads"], name
    for name, (layer, moves, unit) in NEW.items():
        m = listed[name]
        assert m["better"] == "lower"
        assert (m["layer"], m["moves"], m["unit"]) == (layer, moves, unit)
        with open(os.path.join(cells.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            held = json.load(f)
        # a data file over a reader that exists, reading names of the
        # program's own table
        assert held["reader"] in ("span_seconds", "span_attr_sum")
        for key in ("names", "spans"):
            assert set(held["args"].get(key, [])) <= set(names.SPANS) | {
                names.SHUFFLE_EXCHANGE}, (name, key)
        assert f"`{name}`" in perf, name


def _checked(out):
    line = next(ln for ln in out.splitlines()
                if ln.startswith("bench: warm-up job checked "))
    return json.loads(line[line.index("{"):])


def test_cell_traced_writes_four_parts_and_reports_the_new_metrics(
        cpu_harness, cpu_trace, capsys):
    cell = tiny_cell(CELL)
    assert cell.chips == 4 and cell.config["files"] == 8
    line = cpu_harness.run_cell(cell, seed=(1 << 31) + 39, seconds=1.0,
                                trace=True, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    declared = {m["name"]: m for m in cell.metrics["per_layer"]}
    missing = set(declared) - set(line["metrics"])
    assert missing <= NO_DEVICE | {n for n, m in declared.items()
                                   if m["source"] == "device_trace"}
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["phase1_builds"] == 0 and value["rejit_s"] == 0
    assert value["compiles_in_window"] == 0
    assert value["sample_s"] > 0
    assert 1.0 <= value["exchange_skew"] < 1.2
    for name in ("aggregate_s", "count_sync_s", "record_read_s",
                 "record_write_s", "sort_s"):
        assert value[name] > 0, name
    facts = _checked(capsys.readouterr().out)
    assert facts["records"] == 12000 and len(facts["rows_per_part"]) == 4
    assert sum(facts["rows_per_part"]) == 12000
    assert all(n > 0 for n in facts["rows_per_part"])
    assert facts["messages"] == [
        "TeraSort: 12000 records, 4 part files, 3 splitters"]


def test_cell_untraced_reports_the_end_to_end_metrics(cpu_harness):
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=39, seconds=0.5,
                                trace=False, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"job_s", "corpus_rate", "setup_s"}


def test_the_two_middle_part_files_swapped_are_refused(cpu_harness,
                                                       monkeypatch, capsys):
    """The fault only a cluster can make: every part file in order, every
    record there once, and part 1's keys above part 2's."""
    from benchmark.jobs import terasort as job_module
    real = job_module.Job.check

    def check(self, result, outdir):
        parts = self._parts(outdir)
        assert len(parts) == 4
        tmp = parts[1] + ".swap"
        os.rename(parts[1], tmp)
        os.rename(parts[2], parts[1])
        os.rename(tmp, parts[2])
        return real(self, result, outdir)

    monkeypatch.setattr(job_module.Job, "check", check)
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                                trace=False, t_process=0.0)
    assert line["correct"] is False
    assert "below the last key of the part before" in capsys.readouterr().out
