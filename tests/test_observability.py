"""Observability parity (VERDICT r1 #6): verbosity=2 / timer=2 per-shard
histograms (reference write_histo, src/mapreduce.cpp:3251-3311), per-op
spill/comm deltas, and tier notes — plus the structured obs/ tracing
layer (spans, sinks, Chrome export, mr.stats())."""

import json

import numpy as np
import pytest

from gpu_mapreduce_tpu import MapReduce
from gpu_mapreduce_tpu.core.runtime import histogram


def test_histogram_bins():
    lo, ave, hi, bins = histogram([0, 5, 10, 10], nbins=5)
    assert (lo, hi) == (0, 10)
    assert ave == 6.25
    assert sum(bins) == 4
    assert bins[0] == 1 and bins[-1] == 2
    lo, ave, hi, bins = histogram([7, 7, 7])
    assert (lo, hi) == (7, 7) and bins[0] == 3


def test_verbosity2_histograms_mesh(capsys):
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    mr = MapReduce(make_mesh(4), verbosity=2)
    keys = np.arange(4000, dtype=np.uint64) % 97
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, keys))
    mr.collate()
    outp = capsys.readouterr().out
    assert "KV pairs (per shard):" in outp
    assert "histogram:" in outp
    assert "shuffled" in outp          # comm delta reported for aggregate


def test_timer2_row_histogram(capsys):
    mr = MapReduce(timer=2)
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.arange(100, dtype=np.uint64), np.ones(100, np.uint64)))
    mr.sort_keys(1)
    outp = capsys.readouterr().out
    assert "sort time (secs)" in outp
    assert "rows (per shard):" in outp


def test_tier_note_host_reduce(capsys):
    mr = MapReduce(verbosity=2)
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.array([1, 1, 2], np.uint64), np.ones(3, np.uint64)))
    mr.convert()
    mr.reduce(lambda k, v, kv, p: kv.add(k, len(v)))
    assert "host per-group tier" in capsys.readouterr().out


def test_spill_delta_reported(tmp_path, capsys):
    mr = MapReduce(outofcore=1, memsize=1, maxpage=1, fpath=str(tmp_path),
                   verbosity=2)
    n = 3 << 16
    keys = np.arange(n, dtype=np.uint64)
    step = n // 4
    mr.map(1, lambda i, kv, p: [kv.add_batch(keys[s:s + step],
                                             keys[s:s + step])
                                for s in range(0, n, step)])
    mr.sort_keys(1)
    outp = capsys.readouterr().out
    assert "Mb spilled" in outp


# ---------------------------------------------------------------------------
# obs/ tracing subsystem (PR 1): spans, sinks, export, stats
# ---------------------------------------------------------------------------

@pytest.fixture
def tracer():
    """The process-global tracer, reset before and after the test so
    span rings/sinks never leak across tests."""
    from gpu_mapreduce_tpu.obs import get_tracer
    tr = get_tracer()
    tr.reset()
    yield tr
    tr.reset()


def test_span_nesting_and_counter_deltas():
    from gpu_mapreduce_tpu.core.runtime import Counters
    from gpu_mapreduce_tpu.obs import Tracer

    c = Counters()
    tr = Tracer(counters=c).enable()
    with tr.span("outer", cat="t"):
        with tr.span("inner", cat="t", shards=4):
            c.add(cssize=100, cspad=7, wsize=50)
            c.mem(1 << 20)
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # exit order
    inner, outer = evs
    assert inner["parent"] == outer["id"]          # nesting recorded
    assert outer["parent"] == 0
    assert inner["args"]["shards"] == 4
    # counter deltas land on every span that was open during the bump
    for ev in (inner, outer):
        assert ev["args"]["shuffle_sent_bytes"] == 100
        assert ev["args"]["shuffle_pad_bytes"] == 7
        assert ev["args"]["spill_write_bytes"] == 50
        assert ev["args"]["hbm_hiwater_bytes"] == 1 << 20
    assert inner["dur"] <= outer["dur"]


def test_jsonl_sink_round_trip(tmp_path, tracer):
    from gpu_mapreduce_tpu.obs import read_jsonl

    path = str(tmp_path / "t.jsonl")
    mr = MapReduce(trace=path)
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.arange(100, dtype=np.uint64), np.ones(100, np.uint64)))
    mr.sort_keys(1)
    evs = read_jsonl(path)
    assert [e["name"] for e in evs] == ["map", "sort_keys"]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in evs)
    assert evs[0]["args"]["npairs"] == 100
    assert evs[0]["cat"] == "mr_op"


def test_chrome_trace_export_valid(tmp_path, tracer):
    from gpu_mapreduce_tpu.obs import write_chrome_trace

    tracer.enable()
    mr = MapReduce()
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.arange(64, dtype=np.uint64), np.ones(64, np.uint64)))
    mr.compress(lambda k, v, kv, p: kv.add(k, len(v)))
    out = str(tmp_path / "chrome.json")
    n = write_chrome_trace(out, tracer.events())
    doc = json.load(open(out))                 # must parse as plain JSON
    evs = doc["traceEvents"]
    assert len(evs) == n >= 3                  # map, convert, reduce, compress
    # complete ("X") events must carry ts+dur; any B has a matching E
    opens = {}
    for e in evs:
        assert e["ph"] in ("X", "B", "E")
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float))
        elif e["ph"] == "B":
            opens[e["id"]] = opens.get(e["id"], 0) + 1
        else:
            opens[e["id"]] -= 1
    assert all(v == 0 for v in opens.values())
    # compress parents its convert+reduce
    byname = {e["name"]: e for e in evs}
    assert byname["convert"]["parent"] == byname["compress"]["id"]
    assert byname["reduce"]["parent"] == byname["compress"]["id"]


def test_stats_matches_cummulative_print(tmp_path, capsys):
    mr = MapReduce(outofcore=1, memsize=1, maxpage=1, fpath=str(tmp_path))
    n = 3 << 16
    keys = np.arange(n, dtype=np.uint64)
    step = n // 4
    mr.map(1, lambda i, kv, p: [kv.add_batch(keys[s:s + step],
                                             keys[s:s + step])
                                for s in range(0, n, step)])
    mr.sort_keys(1)
    s = mr.stats()
    # every printed cummulative_stats field is a stats() key
    assert {"msizemax", "rsize", "wsize", "cssize", "crsize", "cspad",
            "commtime"} <= set(s)
    assert s["wsize"] > 0 and s["rsize"] > 0    # the spill ran
    mr.cummulative_stats(1)
    out = capsys.readouterr().out
    # the print is a formatting consumer of the same snapshot: rebuild
    # each line from stats() and require byte equality
    assert (f"Cummulative hi-water mem = "
            f"{s['msizemax'] / (1 << 20):.3g} Mb") in out
    assert (f"Cummulative spill I/O = {s['rsize'] / (1 << 20):.3g} Mb read, "
            f"{s['wsize'] / (1 << 20):.3g} Mb written") in out
    assert (f"Cummulative comm = {s['cssize'] / (1 << 20):.3g} Mb sent, "
            f"{s['crsize'] / (1 << 20):.3g} Mb received, "
            f"{s['cspad'] / (1 << 20):.3g} Mb padding, "
            f"{s['commtime']:.3g} secs") in out


def test_spill_deltas_land_on_spans(tmp_path, tracer):
    tracer.enable()
    mr = MapReduce(outofcore=1, memsize=1, maxpage=1, fpath=str(tmp_path))
    n = 3 << 16
    keys = np.arange(n, dtype=np.uint64)
    step = n // 4
    mr.map(1, lambda i, kv, p: [kv.add_batch(keys[s:s + step],
                                             keys[s:s + step])
                                for s in range(0, n, step)])
    mr.sort_keys(1)
    evs = tracer.events()
    assert any(e["args"].get("spill_write_bytes", 0) > 0 for e in evs)
    assert any(e["args"].get("spill_read_bytes", 0) > 0 for e in evs)


def test_tracer_disabled_zero_cost(tracer):
    import time

    from gpu_mapreduce_tpu.obs import NULL_SPAN

    # the disabled fast path returns the shared no-op singleton: no
    # allocation, no stack touch, no sink work
    assert tracer.span("x") is NULL_SPAN
    assert tracer.span("y", cat="z") is NULL_SPAN
    mr = MapReduce()
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.arange(16, dtype=np.uint64), np.ones(16, np.uint64)))
    assert tracer.events() == []               # nothing recorded
    t0 = time.perf_counter()
    for _ in range(100_000):
        tracer.span("x")
    dt = time.perf_counter() - t0
    assert dt < 1.0                            # ~µs/call ceiling, generous


def test_wordfreq_mesh_trace_acceptance(tmp_path, tracer):
    """The PR acceptance path: a traced wordfreq run yields a JSONL
    trace whose Chrome export is valid, with spans for every MR op and
    shuffle sent/pad bytes on the exchange."""
    from gpu_mapreduce_tpu.obs import chrome_trace, read_jsonl
    from gpu_mapreduce_tpu.oink.kernels import count, read_words
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh

    words = tmp_path / "w.txt"
    words.write_text("a b c a b a d e f g h a b\n" * 50)
    jsonl = str(tmp_path / "wf.jsonl")
    mr = MapReduce(make_mesh(4), trace=jsonl)
    mr.map_files([str(words)], read_words)
    mr.collate()
    mr.reduce(count, batch=True)
    evs = read_jsonl(jsonl)
    names = {e["name"] for e in evs}
    assert {"map_files", "aggregate", "convert", "collate",
            "reduce"} <= names
    assert "shuffle.exchange" in names         # child span of aggregate
    ex = next(e for e in evs if e["name"] == "shuffle.exchange")
    agg = next(e for e in evs if e["name"] == "aggregate")
    assert ex["parent"] == agg["id"]
    assert ex["args"]["sent_bytes"] > 0
    assert ex["args"]["pad_bytes"] >= 0
    assert ex["args"]["bucket"] > 0 and ex["args"]["nrounds"] >= 1
    assert agg["args"]["shuffle_sent_bytes"] == ex["args"]["sent_bytes"]
    doc = chrome_trace(evs)
    json.loads(json.dumps(doc))                # fully serializable
    assert len(doc["traceEvents"]) == len(evs)


def test_dump_trace_script_command(tmp_path, tracer):
    tracer.enable()
    from gpu_mapreduce_tpu.oink.script import OinkScript

    words = tmp_path / "w.txt"
    words.write_text("a b b c c c\n")
    out = tmp_path / "trace.json"
    interp = OinkScript(screen=False)
    try:
        interp.run_string(f"wordfreq 2 -i {words} -o NULL NULL\n"
                          f"dump_trace {out}")
    finally:
        interp.close()
    doc = json.load(open(out))
    names = {e["name"] for e in doc["traceEvents"]}
    assert "oink.wordfreq" in names            # script-command span
    assert {"map_files", "collate", "reduce"} <= names
