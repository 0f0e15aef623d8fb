"""ISSUE 36: the one-chip TeraSort cell and the metrics it brings, tiny,
through the harness on the CPU, and its reference, generator and byte
count on their own (``python -m pytest benchmark/tests``, not tier-1).

``test_harness.tiny_cell`` sizes a cell by its job kind from a table that
this PR may not edit; as ``test_graph_tri_1chip.py`` does, this file enters
the kind it adds as it is imported."""

import json
import os
import sys

import numpy as np
import pytest

from benchmark import CheckFailure, cells, kernels_sort
from benchmark.gen import records
from benchmark.refs import terasort as ref
from benchmark.tests import test_harness
from benchmark.tests.test_harness import cpu_trace, tiny_cell  # noqa: F401

# pytest imports the test files of this directory by their bare names, so
# its ``test_harness`` is another module object than the one imported
# above: enter the kind in both (see test_graph_tri_1chip.py)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_harness as _collected  # noqa: E402

for _module in (test_harness, _collected):
    _module.TINY.setdefault("terasort", {"file_records": 1500})

CELL = "terasort-hbm-1chip"
CONFIG = "sortbench-terasort-1chip"
NEW = {"record_read_s": ("ingest", "job_s"),
       "record_sort_dev_s": ("group + reduce", "job_s"),
       "record_sort_roofline": ("group + reduce", "corpus_rate"),
       "record_write_s": ("entry points", "job_s"),
       "sort_s": ("group + reduce", "job_s")}
JOINED = ("entry_glue_s", "host_cpu_s", "host_off_cpu_s", "proc_cpu_s",
          "invol_switches", "rejit_s", "program_load_s")
NO_DEVICE = {"peak_hbm_gib"}    # the CPU stand-in has no memory statistics


# -- the reference on its own ----------------------------------------------------

def _recs(seed, n=4000):
    rng = np.random.default_rng(seed)
    recs = rng.integers(0, 256, (n, ref.RECORD), dtype=np.uint8)
    twins = np.arange(1, n, 9)          # equal up to byte 8, and NUL bytes
    recs[twins, :8] = recs[twins - 1, :8]
    recs[::5, 3:6] = 0
    return recs


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_sort_records_against_a_brute_force_sort(seed):
    recs = _recs(seed)
    brute = sorted((bytes(r) for r in recs), key=lambda r: r[:ref.KEY])
    got = ref.sort_records(recs)
    assert [bytes(r) for r in got] == brute     # distinct keys: one order
    assert not ref.summary([recs])["duplicate_keys"]


def test_sort_records_is_stable_and_summary_sees_equal_keys():
    recs = _recs(4, n=600)
    recs[:, :ref.KEY] = recs[np.arange(600) % 7, :ref.KEY]
    brute = sorted((bytes(r) for r in recs), key=lambda r: r[:ref.KEY])
    assert [bytes(r) for r in ref.sort_records(recs)] == brute
    assert ref.summary([recs])["duplicate_keys"]
    assert ref.summary([])["records"] == 0


def _swapped(out):
    out[[10, 11]] = out[[11, 10]]
    return [out]


def _dropped(out):
    return [out[:-1]]


def _values_exchanged(out):
    """Two neighbours keep their keys and trade their values: the keys
    alone would not show it."""
    out[[20, 21], ref.KEY:] = out[[21, 20], ref.KEY:]
    return [out]


def _written_twice(out):
    out[30] = out[31]
    return [out]


def _prefix_only(out):
    """Ordered by the first 8 key bytes, bytes 8-9 dropped."""
    prefix = np.ascontiguousarray(out[:, :8]).view(">u8").ravel()
    tail = np.ascontiguousarray(out[:, 8:10]).view(">u2").ravel()
    return [out[np.lexsort((-tail.astype(np.int64), prefix))]]


def _parts_out_of_order(out):
    return [out[2000:], out[:2000]]


@pytest.mark.parametrize("fault, said", [
    (_swapped, "below the one before it"),
    (_dropped, "3999 records in the part files, 4000"),
    (_values_exchanged, "whole-record checksum"),
    (_written_twice, "whole-record checksum"),
    (_prefix_only, "below the one before it"),
    (_parts_out_of_order, "below the last key of the part before")],
    ids=["swapped", "dropped", "values-exchanged", "written-twice",
         "prefix-only", "parts-out-of-order"])
def test_validate_catches_a_planted_fault(fault, said):
    recs = _recs(5)
    want = ref.summary([recs])
    good = ref.sort_records(recs)
    assert ref.validate([good[:1500], good[1500:1500], good[1500:]],
                        want)["rows_per_part"] == [1500, 0, 2500]
    with pytest.raises(CheckFailure, match=said):
        ref.validate(fault(good.copy()), want)


def test_the_reference_uses_nothing_of_the_program():
    for module in (ref, records, kernels_sort):
        assert "gpu_mapreduce_tpu" not in open(
            module.__file__).read().split('"""', 2)[2], module.__name__


def test_record_mix_sees_every_byte_and_where_it_is():
    recs = _recs(6, n=8)
    base = ref.record_mix(recs)
    for at in range(ref.RECORD):
        changed = recs.copy()
        changed[:, at] ^= 1
        assert (ref.record_mix(changed) != base).all(), at
    moved = recs.copy()
    moved[:, [40, 41]] = moved[:, [41, 40]]
    differ = recs[:, 40] != recs[:, 41]
    assert (ref.record_mix(moved) != base)[differ].all() and differ.any()


# -- the generator ----------------------------------------------------------------

def test_the_generator_lays_a_record_out_as_gensort_does(tmp_path):
    paths = records.make_records(str(tmp_path), 3, 5000, seed=(1 << 31) + 9)
    assert [os.path.basename(p) for p in paths] == [
        f"part-{i:05d}.dat" for i in range(3)]
    recs = np.concatenate([ref.records(p) for p in paths])
    assert recs.shape == (15000, 100)
    v = recs[:, ref.KEY:]
    assert (v[:, :2] == (0x00, 0x11)).all()
    assert (v[:, 34:38] == (0x88, 0x99, 0xAA, 0xBB)).all()
    assert (v[:, 86:] == (0xCC, 0xDD, 0xEE, 0xFF)).all()
    numbers = [int(bytes(row), 16) for row in v[:, 2:34]]
    assert numbers == list(range(15000))
    # keys and filler are drawn: every byte value occurs, no column is fixed
    for col in list(range(ref.KEY)) + list(range(ref.KEY + 38, ref.KEY + 86)):
        assert len(np.unique(recs[:, col])) > 200, col
    again = records.make_records(str(tmp_path / "again"), 3, 5000,
                                 seed=(1 << 31) + 9)
    assert all(open(a, "rb").read() == open(b, "rb").read()
               for a, b in zip(paths, again))
    other = records.make_file(0, 5000, seed=1, index=0)
    assert not np.array_equal(other[:, :ref.KEY], recs[:5000, :ref.KEY])


def test_the_generator_plants_prefix_twins_at_its_rate():
    recs = records.make_file(0, 400_000, seed=7, index=0)
    same8 = (recs[1:, :8] == recs[:-1, :8]).all(axis=1)
    assert 15 <= same8.sum() <= 80          # 40 expected at 1e-4
    twins = np.flatnonzero(same8) + 1
    assert (recs[twins, 8:10] != recs[twins - 1, 8:10]).any(axis=1).mean() > 0.9
    none = records.make_file(0, 100_000, seed=7, index=0, twin_rate=0.0)
    assert not (none[1:, :8] == none[:-1, :8]).all(axis=1).any()
    # what they are for: an order by the u64 prefix fails the check
    want = ref.summary([recs])
    wrong = 0
    for seed in range(3):       # a twin pair is in order by chance half the time
        shuffled = recs[np.random.default_rng(seed).permutation(len(recs))]
        p = np.ascontiguousarray(shuffled[:, :8]).view(">u8").ravel()
        try:
            ref.validate([shuffled[np.argsort(p, kind="stable")]], want)
        except CheckFailure:
            wrong += 1
    assert wrong == 3


def test_sort_bytes_counts_what_its_docstring_says():
    assert kernels_sort.sort_bytes(10, 100) == 2000
    assert kernels_sort.sort_bytes(10_000_000, 100) == 2 * 10 ** 9
    assert kernels_sort.sort_bytes(0, 100) == 0


# -- the cell ---------------------------------------------------------------------

def test_the_cell_and_its_metrics_are_declared_as_the_issue_names_them():
    from gpu_mapreduce_tpu.obs import names
    spec = cells.load_benchmark()
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.traffic_name == "terasort"
    assert cell.traffic["kind"] == "terasort"
    cfg = cell.config
    assert (cfg["record_bytes"], cfg["key_bytes"], cfg["file_records"],
            cfg["prefix_twin_rate"]) == (100, 10, 1_250_000, 1e-4)
    assert cfg["reduced"] == ["files"] and cfg["architecture"] is None
    assert cfg["ladder"]["rungs"] == [80, 40, 16, 8, 4]
    assert cfg["files"] in cfg["ladder"]["rungs"] and cfg["files"] >= 4
    assert cfg["layout"]["chips"] == 1 and cfg["layout"]["fuse"] == 0
    assert len(cfg["guarantees"]) >= 5
    assert {"value_layout", "prefix_twins", "sample"} <= set(cfg["assumed"])
    assert "TO FILL" not in json.dumps(cfg)
    # that the cell is there as the issue names it; how many cells there
    # are, and how many may take four chips, is test_contract.py's
    named = [w for w in spec["workloads"] if w["name"] == CELL]
    assert len(named) == 1 and named[0]["config"] == CONFIG
    assert [c["name"] for c in spec["configs"]].count(CONFIG) == 1
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert e2e == {"job_s", "corpus_rate", "setup_s"}
    listed = {m["name"]: m for m in spec["per_layer"]}
    perf = open(os.path.join(cells.ROOT, "PERF.md")).read()
    for name in tuple(NEW) + JOINED:
        assert CELL in listed[name]["workloads"], name
    for name, (layer, moves) in NEW.items():
        m = listed[name]
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["moves"]) == (layer, moves), name
        with open(os.path.join(cells.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        for key in ("names", "spans"):
            assert set(args.get(key, [])) <= set(names.SPANS) | {
                names.SORT_KEYS_SPAN}, (name, key)
        assert set(args.get("modules", [])) <= set(names.PROGRAMS), name
        assert f"`{name}`" in perf, name
    assert listed["record_sort_roofline"]["unit"] == "%"


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
    for name in ("record_read_s", "record_write_s", "sort_s", "host_cpu_s",
                 "proc_cpu_s"):
        assert value[name] > 0, name
    out = capsys.readouterr().out
    checked = next(ln for ln in out.splitlines()
                   if ln.startswith("bench: warm-up job checked "))
    facts = json.loads(checked[checked.index("{"):])
    assert facts["records"] == 3000 and facts["rows_per_part"] == [3000]
    assert facts["messages"] == [
        "TeraSort: 3000 records, 1 part files, 0 splitters"]


def test_the_roofline_reader_gets_the_records_bytes(cpu_harness):
    """On the CPU no program event reaches the trace, so the share itself
    is left out; what the job module hands the reader is read here."""
    import jax
    from benchmark.cache import Cache
    from benchmark.jobs import terasort as job_module
    from gpu_mapreduce_tpu.obs import names
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    cell = tiny_cell(CELL)
    job = job_module.Job(cell.config, cell.traffic,
                         make_mesh(devices=jax.devices()[:1]), 1, Cache())
    facts = job.prepare()
    assert facts["records"] == 3000 and facts["corpus_bytes"] == 300_000
    assert job.work() == {"corpus_bytes": 300_000}
    assert job.info() == {
        "programs": {"record_sort": names.SORT_ROWS},
        "bytes_moved": {"record_sort": 600_000}}


def test_cell_untraced_reports_corpus_rate(cpu_harness):
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=3, seconds=0.5,
                                trace=False, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"job_s", "corpus_rate", "setup_s"}


def test_the_cell_on_the_mesh_of_four(cpu_harness):
    """The half of the job one chip never runs (the sampled splitters, the
    exchange of whole records), through the same harness and checks."""
    line = cpu_harness.run_cell(tiny_cell(CELL, chips=4), seed=11,
                                seconds=0.5, trace=False, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0


# -- a wrong result makes ``correct`` false ---------------------------------------

def _rewrite_parts(monkeypatch, change):
    """Let the job write its part files and change them before the check."""
    from benchmark.jobs import terasort as job_module
    real = job_module.Job.check

    def check(self, result, outdir):
        for path in self._parts(outdir):
            change(ref.records(path).copy()).tofile(path)
        return real(self, result, outdir)

    monkeypatch.setattr(job_module.Job, "check", check)


def _prefix_order(recs):
    """In order by the first 8 key bytes; records that tie there in the
    order a sort that never read bytes 8-9 may leave them in."""
    return _prefix_only(recs)[0]


@pytest.mark.parametrize("change, said", [
    (lambda r: r[::-1], "below the one before it"),
    (lambda r: r[1:], "records in the part files"),
    (lambda r: np.concatenate([r[:5], r[4:5], r[6:]]), "whole-record")],
    ids=["reversed", "one-dropped", "one-twice"])
def test_wrong_part_files_make_correct_false(cpu_harness, monkeypatch, capsys,
                                             change, said):
    _rewrite_parts(monkeypatch, change)
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                                trace=False, t_process=0.0)
    assert line["correct"] is False
    assert said in capsys.readouterr().out


def test_a_prefix_only_order_makes_correct_false(cpu_harness, monkeypatch,
                                                 capsys):
    """The fault the generator's twins are planted for, at a twin rate
    that a tiny cell can see."""
    _rewrite_parts(monkeypatch, _prefix_order)
    cell = tiny_cell(CELL)
    cell.config["prefix_twin_rate"] = 0.2
    line = cpu_harness.run_cell(cell, seed=5, seconds=0.2, trace=False,
                                t_process=0.0)
    assert line["correct"] is False
    assert "below the one before it" in capsys.readouterr().out


def test_a_window_job_that_differs_makes_correct_false(cpu_harness,
                                                       monkeypatch):
    """Window jobs are held to the warm-up job by their part files' bytes
    and a device checksum of the key column."""
    from benchmark.jobs import terasort as job_module
    real = job_module.Job.run
    calls = []

    def run(self, outdir):
        result = real(self, outdir)
        calls.append(outdir)
        if len(calls) == 2:
            path = self._parts(outdir)[0]
            recs = ref.records(path).copy()
            recs[7, 50] ^= 1
            recs.tofile(path)
        return result

    monkeypatch.setattr(job_module.Job, "run", run)
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                                trace=False, t_process=0.0)
    assert line["correct"] is False and line["failed"] >= 1


def test_equal_keys_are_held_by_the_key_column_alone(cpu_harness,
                                                     monkeypatch):
    """Where the reference found two equal keys their order is the sort's
    to choose: the digest then reads the key column, not the values."""
    from benchmark.jobs import terasort as job_module
    real_make = records.make_file

    def make_file(first, n, seed, index, twin_rate=records.TWIN_RATE):
        recs = real_make(first, n, seed, index, twin_rate)
        recs[1, :ref.KEY] = recs[0, :ref.KEY]
        return recs

    monkeypatch.setattr(records, "make_file", make_file)
    real = job_module.Job.run
    calls = []

    def run(self, outdir):
        result = real(self, outdir)
        calls.append(outdir)
        if len(calls) == 2:     # the tied pair the other way round
            path = self._parts(outdir)[0]
            recs = ref.records(path).copy()
            k = ref.keys(recs)
            at = int(np.flatnonzero(k[1:] == k[:-1])[0])
            recs[[at, at + 1]] = recs[[at + 1, at]]
            recs.tofile(path)
        return result

    monkeypatch.setattr(job_module.Job, "run", run)
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                                trace=False, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0 and len(calls) >= 2


def test_a_sample_that_is_not_the_applications_is_refused_in_prepare():
    """The configuration states the sample; the application holds it as a
    constant (no option): the two are tied here, as the record's shape is."""
    import jax
    from benchmark.jobs import terasort as job_module
    from gpu_mapreduce_tpu.apps import terasort as app
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    cell = tiny_cell(CELL)
    assert cell.config["sample"] == app.SAMPLE == 100_000
    cell.config["sample"] = 50_000
    job = job_module.Job(cell.config, cell.traffic,
                         make_mesh(devices=jax.devices()[:1]), 1, None)
    with pytest.raises(CheckFailure, match="samples 100000 keys"):
        job.prepare()


def test_a_tree_without_the_application_is_refused_in_prepare(monkeypatch):
    import importlib.util

    import jax
    from benchmark.jobs import terasort as job_module
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name.endswith("apps.terasort")
        else real(name, *a))
    cell = tiny_cell(CELL)
    job = job_module.Job(cell.config, cell.traffic,
                         make_mesh(devices=jax.devices()[:1]), 1, None)
    with pytest.raises(CheckFailure, match="no gpu_mapreduce_tpu.apps"):
        job.prepare()
