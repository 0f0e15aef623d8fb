"""ISSUE 30: the four-chip word-count cell and the metrics it brings, tiny,
through the harness on the CPU's virtual devices, and its reference and
generator on their own (``python -m pytest benchmark/tests``, not tier-1).

``test_harness.tiny_cell`` sizes a cell by its job kind from a table that
this PR may not edit; the kind this PR adds is entered here, as this file
is imported, so that ``test_harness``'s cases over every cell find it when
the directory is collected."""

import collections
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import CheckFailure, cells
from benchmark.gen import text
from benchmark.refs import wordcount
from benchmark.tests import test_harness
from benchmark.tests.test_harness import cpu_trace, tiny_cell  # noqa: F401

# pytest imports the test files of this directory by their bare names
# (it has no __init__.py), so its ``test_harness`` is another module object
# than the one imported above: enter the kind in both
for _module in (test_harness, sys.modules.get("test_harness")):
    if _module is not None:
        _module.TINY.setdefault("wordfreq", {"file_bytes": 64 << 10})

CELL = "wordfreq-zipf-4chip"
NEW = ("tokenize_s", "intern_s", "topn_s", "hub_group_share")
JOINED = ("aggregate_s", "aggregate_host_s", "count_sync_s", "shuffle_dev_s",
          "exchange_pad_share", "exchange_skew", "group_reduce_s",
          "sort_dev_s", "layout_dev_s", "entry_glue_s")
NO_DEVICE = {"peak_hbm_gib"}    # the CPU stand-in has no memory statistics


# -- the generator and the reference -------------------------------------------

@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    d = tmp_path_factory.mktemp("text")
    return text.make_corpus(str(d), 3, 200_000, (1 << 31) + 5, 1 << 21)


def test_corpus_has_the_configurations_shapes(corpus):
    shapes = cells.load_cell(CELL).config["shapes"]
    counts = collections.Counter()
    for p in corpus:
        with open(p, "rb") as f:
            raw = f.read()
        assert 200_000 <= len(raw) < 200_000 + text.LONG_MAX + 2
        assert set(raw[i] for i in range(len(raw)) if raw[i] < 33) <= {
            9, 10, 32}                  # tab, newline, space: no other
        assert 60 < len(raw) / raw.count(b"\n") < 100
        counts.update(raw.split())
    n = sum(counts.values())
    lens = np.array([len(w) for w in counts.elements()])
    lo, hi = shapes["long_token_bytes"]
    long_ = lens >= lo
    assert 0.06 < counts.most_common(1)[0][1] / n < 0.08
    assert 0.005 < long_.mean() < 0.02 and lens.max() <= hi
    assert lens[~long_].max() <= shapes["word_bytes"][1] and lens.min() == 1
    assert 4.5 < lens[~long_].mean() < 5.7
    assert sum(1 for c in counts.values() if c == 1) > len(counts) // 3


def test_the_same_seed_gives_the_same_bytes_and_another_seed_others(tmp_path):
    def sha(seed, sub):
        paths = text.make_corpus(str(tmp_path / sub), 2, 50_000, seed, 1 << 16)
        return [open(p, "rb").read() for p in paths]
    a, b, c = sha(7, "a"), sha(7, "b"), sha(8, "c")
    assert a == b and a != c and a[0] != a[1]
    # the seed draws the tokens, it does not spell the words: the hub
    # words, and so the shards they are routed to, are every seed's
    top = lambda files: [w for w, _n in collections.Counter(
        b"".join(files).split()).most_common(5)]
    assert top(a) == top(c)


def test_no_two_ranks_spell_one_word():
    v = text.Vocabulary(1 << 18)
    plain = v.rows[~v.is_long]
    assert len(np.unique(plain.view("V16"))) == len(plain)
    assert len(np.unique(v.long_rows.view(f"V{v.long_rows.shape[1]}"))) \
        == len(v.long_rows)
    assert v.lens[~v.is_long].min() == 1 and v.lens.max() == text.WORD_BYTES
    assert not v.is_long[:text.LONG_AFTER].any()


@pytest.mark.parametrize("block", [1 << 24, 65_537, 211])
def test_reference_counts_words_whole_across_blocks(corpus, block):
    want = collections.Counter()
    for p in corpus:
        with open(p, "rb") as f:
            want.update(f.read().split())
    assert wordcount.count_words(corpus, block=block) == dict(want)


def test_reference_uses_nothing_of_the_program():
    for module in (wordcount, text):
        src = open(module.__file__).read()
        head = src.split("def check_counts")[0]
        assert "gpu_mapreduce_tpu" not in head.split('"""', 2)[2], module


# -- the cell --------------------------------------------------------------------

def test_the_cell_and_its_metrics_are_declared_as_the_issue_names_them():
    from gpu_mapreduce_tpu.obs import names
    spec = cells.load_benchmark()
    cell = cells.load_cell(CELL)
    assert cell.chips == 4 and cell.config_name == "puma-wordcount-4chip"
    assert cell.traffic["kind"] == "wordfreq" and cell.config["files"] == 4
    assert cell.config["files"] in cell.config["ladder"]["rungs"]
    assert cell.config["reduced"] == ["files"]
    assert cell.config["layout"]["fuse"] == 0
    six = spec["workloads"]
    assert len(six) == 6 and sum(w["chips"] == 4 for w in six) == 3
    assert six[-1]["name"] == CELL and spec["configs"][-1]["name"] \
        == "puma-wordcount-4chip"
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert e2e == {"job_s", "corpus_rate", "setup_s"}
    listed = {m["name"]: m for m in spec["per_layer"]}
    perf = open(os.path.join(cells.ROOT, "PERF.md")).read()
    for name in NEW + JOINED:
        assert CELL in listed[name]["workloads"], name
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        with open(os.path.join(cells.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        for key in ("names", "spans"):
            assert set(args.get(key, [])) <= set(names.SPANS) | {
                names.CONVERT_SPAN}, (name, key)
        assert f"`{name}`" in perf, name
    hub = json.load(open(os.path.join(cells.BENCH_DIR, "layer_metrics",
                                      "hub_group_share.json")))["args"]
    assert hub == {"spans": [names.CONVERT_SPAN],
                   "num": [names.ATTR_GROUP_ROWS_MAX],
                   "den": [names.ATTR_ROWS]}


def test_no_file_that_the_benchmark_had_is_edited():
    base = "464c25f99eac7f3525db07488c9f92f8a08ebaf4"
    try:
        out = subprocess.run(
            ["git", "diff", "--name-status", base, "--", "benchmark"],
            cwd=cells.ROOT, capture_output=True, text=True, check=True).stdout
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("not a git checkout that holds the parent commit")
    assert all(ln.startswith("A") for ln in out.splitlines()), out


def test_cell_traced_reports_the_word_map_and_the_skew(
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
    assert 0 < value["tokenize_s"] and 0 < value["intern_s"]
    assert 0 < value["topn_s"] < value["entry_self_s"] + value["topn_s"]
    assert 0.05 < value["hub_group_share"] < 0.09      # the first word
    assert 1.0 < value["exchange_skew"] <= 4.0
    assert 0 <= value["exchange_pad_share"] < 100
    assert 0 < value["count_sync_s"] and 0 < value["aggregate_s"]
    names = {n for n, _ in line["breakdown"]["idle_gaps"]}
    assert names & {"ingest.intern", "wordfreq.topn", "ingest.tokenize"}
    out = capsys.readouterr().out
    checked = next(ln for ln in out.splitlines()
                   if ln.startswith("bench: warm-up job checked "))
    facts = json.loads(checked[checked.index("{"):])
    assert facts["exchange"]["rows"] > 0
    assert len(facts["unique_per_shard"]) == 4 and min(
        facts["unique_per_shard"]) > 0
    assert facts["messages"][0].startswith("WordFreq: 8 files, ")
    assert len(facts["messages"]) == 11


def test_cell_untraced_reports_corpus_rate(cpu_harness):
    cell = tiny_cell(CELL)
    line = cpu_harness.run_cell(cell, seed=3, seconds=0.5, trace=False,
                                t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"job_s", "corpus_rate", "setup_s"}
    assert line["metrics"]["corpus_rate"]["unit"] == "MB/s"


def _wrong(monkeypatch, change):
    real = wordcount.count_words

    def counts(paths, **kw):
        want = real(paths, **kw)
        change(want)
        return want

    monkeypatch.setattr(wordcount, "count_words", counts)


def _pop_rare(want):
    want.pop(min(want, key=lambda w: (want[w], w)))


def _bump_rare(want):
    want[min(want, key=lambda w: (want[w], w))] += 1


def _add_word(want):
    want[b"never-written"] = 1


@pytest.mark.parametrize("change", [_bump_rare, _add_word, _pop_rare])
def test_a_wrong_count_or_a_missing_word_makes_correct_false(
        cpu_harness, monkeypatch, capsys, change):
    """The reference is made to differ from the files by one count of a
    word outside the top ten, by a word the system cannot have, and by
    lacking a word the system has."""
    _wrong(monkeypatch, change)
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                                trace=False, t_process=0.0)
    assert line["correct"] is False
    assert "bench: WRONG RESULT" in capsys.readouterr().out


def test_a_word_in_two_shards_makes_correct_false(cpu_harness, monkeypatch,
                                                  capsys):
    """Shard 1 is made to hold, under another id and in its own table, a
    word that shard 0 holds."""
    real = wordcount.shard_rows
    seen = {}

    def rows(frame, p):
        ids, values = real(frame, p)
        if p == 0:
            seen["word"] = frame.key_decode.shard(0)[int(ids[0])]
        if p == 1:
            frame.key_decode.shard(1)[12345] = seen["word"]
            ids = np.append(ids, np.uint64(12345))
            values = np.append(values, 1)
        return ids, values

    monkeypatch.setattr(wordcount, "shard_rows", rows)
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                                trace=False, t_process=0.0)
    assert line["correct"] is False
    assert "is in two shards" in capsys.readouterr().out


def test_a_tree_without_the_ranges_path_is_refused_in_prepare(monkeypatch):
    from benchmark.jobs import wordfreq
    from gpu_mapreduce_tpu.core.column import BytesColumn
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    import jax
    monkeypatch.delattr(BytesColumn, "from_ranges")
    cell = tiny_cell(CELL)
    job = wordfreq.Job(cell.config, cell.traffic,
                       make_mesh(devices=jax.devices()[:4]), 1, None)
    with pytest.raises(CheckFailure, match="from_ranges"):
        job.prepare()
