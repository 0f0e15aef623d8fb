"""Request-scoped trace context, exact per-request cost attribution,
and the tenant SLO engine (obs/context.py, obs/slo.py) — plus the
operator surfaces that ride them: /v1/jobs/<id>/{profile,events},
/v1/slo, mrctl profile/watch, trace_view --trace, and the
metric-catalog lint."""

import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.core.runtime import global_counters
from gpu_mapreduce_tpu.obs import context as obs_context
from gpu_mapreduce_tpu.obs import slo as obs_slo
from gpu_mapreduce_tpu.obs import get_tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")


def load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(SCRIPTS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def ctx_state():
    """Reset the process-global tracer/registry/flight/context/SLO
    state around every test — attribution must never leak across."""
    from gpu_mapreduce_tpu.obs import flight, metrics

    def _reset():
        get_tracer().reset()
        metrics.reset()
        flight.reset()
        obs_context.reset()
        obs_slo.reset()

    _reset()
    yield
    _reset()


# ---------------------------------------------------------------------------
# RequestAccount + scopes
# ---------------------------------------------------------------------------

def test_request_scope_charges_exactly_this_scope():
    with obs_context.request_scope(tenant="t", label="a") as acct:
        global_counters().add(cssize=100, cspad=10, wsize=7,
                              ndispatch=3)
        global_counters().mem(4096)
        global_counters().mem(-4096)
    prof = acct.profile()
    assert prof["exchange"]["sent_bytes"] == 100
    assert prof["exchange"]["pad_bytes"] == 10
    assert prof["spill"]["write_bytes"] == 7
    assert prof["dispatches"] == 3
    assert prof["hbm"]["hi_water_bytes"] == 4096
    assert prof["tenant"] == "t" and prof["trace_id"]
    # after the scope closes, charges no longer land on it
    global_counters().add(cssize=999)
    assert acct.profile()["exchange"]["sent_bytes"] == 100


def test_two_threads_never_bleed_synthetic():
    """The mechanism itself: two concurrent scopes hammering the SAME
    process-global counters each see exactly their own deltas."""
    accounts = {}
    barrier = threading.Barrier(2)

    def work(name, n, nbytes):
        with obs_context.request_scope(label=name) as acct:
            accounts[name] = acct
            barrier.wait()
            for _ in range(n):
                global_counters().add(cssize=nbytes, ndispatch=1)
    ta = threading.Thread(target=work, args=("a", 200, 13))
    tb = threading.Thread(target=work, args=("b", 300, 7))
    ta.start(); tb.start(); ta.join(); tb.join()
    pa, pb = accounts["a"].profile(), accounts["b"].profile()
    assert pa["exchange"]["sent_bytes"] == 200 * 13
    assert pb["exchange"]["sent_bytes"] == 300 * 7
    assert pa["dispatches"] == 200 and pb["dispatches"] == 300


def test_two_threads_never_bleed_real_workload(tmp_path):
    """Real MR work: a spill-heavy external sort in scope A, a pure
    in-memory pipeline in scope B, concurrently.  B's account shows
    ZERO spill traffic even while A spills next door — the
    exact-under-concurrency contract."""
    accounts = {}
    barrier = threading.Barrier(2)
    keys = (np.arange(300_000, dtype=np.uint64) * 7919) % (1 << 40)

    def spiller():
        with obs_context.request_scope(label="spiller") as acct:
            accounts["a"] = acct
            barrier.wait()
            mr = MapReduce(outofcore=1, memsize=1, maxpage=1,
                           fpath=str(tmp_path / "spill"))
            mr.map(1, lambda i, kv, p: kv.add_batch(keys, keys))
            mr.sort_keys(1)

    def light():
        with obs_context.request_scope(label="light") as acct:
            accounts["b"] = acct
            barrier.wait()
            for _ in range(3):
                mr = MapReduce()
                small = np.arange(5000, dtype=np.uint64)
                mr.map(1, lambda i, kv, p: kv.add_batch(small, small))
                mr.aggregate()
    os.makedirs(tmp_path / "spill", exist_ok=True)
    ta = threading.Thread(target=spiller)
    tb = threading.Thread(target=light)
    ta.start(); tb.start(); ta.join(120); tb.join(120)
    pa, pb = accounts["a"].profile(), accounts["b"].profile()
    assert pa["spill"]["write_bytes"] > 0          # A really spilled
    assert pb["spill"]["write_bytes"] == 0         # ...and B saw none
    assert pb["spill"]["read_bytes"] == 0
    assert pa["trace_id"] != pb["trace_id"]


# ---------------------------------------------------------------------------
# span trace ids + cross-thread propagation (goldens on the JSONL sink)
# ---------------------------------------------------------------------------

def read_jsonl(path):
    from gpu_mapreduce_tpu.obs import read_jsonl as _rj
    return _rj(str(path))


def test_spans_carry_scope_trace_id(tmp_path):
    trace = tmp_path / "t.jsonl"
    get_tracer().enable(jsonl=str(trace))
    with obs_context.request_scope(label="golden") as acct:
        mr = MapReduce()
        k = np.arange(100, dtype=np.uint64)
        mr.map(2, lambda i, kv, p: kv.add_batch(k, k))
        mr.aggregate()
    events = read_jsonl(trace)
    assert events, "no spans written"
    assert {e.get("trace") for e in events} == {acct.trace_id}


def test_prefetch_producer_carries_submitting_trace(tmp_path):
    from gpu_mapreduce_tpu.exec.prefetch import prefetch_iter
    trace = tmp_path / "t.jsonl"
    get_tracer().enable(jsonl=str(trace))
    with obs_context.request_scope(label="consumer") as acct:
        out = list(prefetch_iter(iter(range(32)), depth=2))
    assert out == list(range(32))
    evs = [e for e in read_jsonl(trace) if e["name"] == "exec.prefetch"]
    assert evs, "producer span missing"
    assert evs[0].get("trace") == acct.trace_id
    # and it really ran on another thread
    assert evs[0]["tid"] != threading.get_ident() & 0x7FFFFFFF


def test_spill_writer_carries_submitting_trace(tmp_path):
    from gpu_mapreduce_tpu.exec.spill import SpillWriter, atomic_save
    trace = tmp_path / "t.jsonl"
    get_tracer().enable(jsonl=str(trace))
    w = SpillWriter(path="spill")
    arr = np.arange(64, dtype=np.uint64)
    with obs_context.request_scope(label="sorter") as acct:
        pend = w.submit(lambda: atomic_save(
            str(tmp_path / "run0.npy"), arr))
        pend.wait()
    w.close()
    evs = [e for e in read_jsonl(trace)
           if e["name"] == "exec.spill_write"]
    assert evs and evs[0].get("trace") == acct.trace_id
    assert evs[0]["tid"] != threading.get_ident() & 0x7FFFFFFF
    # the wsize counter bump from the writer thread charged the scope
    assert acct.profile()["spill"]["write_bytes"] == 0  # atomic_save
    #   alone doesn't bump wsize — external.py does; the span is the
    #   propagation proof here


def test_ingest_pool_tasks_charge_submitting_request():
    """mapstyle-2 pool tasks run under the submitting request's
    context: counter traffic from worker threads lands on the scope."""
    with obs_context.request_scope(label="pooled") as acct:
        mr = MapReduce(mapstyle=2)
        def cb(itask, kv, ptr):
            global_counters().add(cssize=11)
            kv.add(str(itask), "x")
        mr.map(8, cb)
    assert acct.profile()["exchange"]["sent_bytes"] == 8 * 11


def test_oink_script_gets_own_trace_and_journal_stamps(tmp_path,
                                                       monkeypatch):
    from gpu_mapreduce_tpu.ft.journal import read_journal
    from gpu_mapreduce_tpu.oink.script import OinkScript
    jdir = tmp_path / "journal"
    monkeypatch.setenv("MRTPU_JOURNAL", str(jdir))
    tracer = get_tracer().enable()
    s = OinkScript(screen=False)
    s.run_string("mr x\nx delete\n")
    ids = {e.get("trace") for e in tracer.events()}
    assert len(ids) == 1 and None not in ids
    (tid,) = ids
    recs = read_journal(str(jdir))
    assert recs, "journal empty"
    assert all(r.get("trace") == tid for r in recs), recs
    # a SECOND top-level script is a different request
    tracer.clear()
    s2 = OinkScript(screen=False)
    s2.run_string("mr y\ny delete\n")
    ids2 = {e.get("trace") for e in tracer.events()}
    assert len(ids2) == 1 and ids2 != ids


def test_process_default_context_and_profile_knob(monkeypatch):
    tracer = get_tracer().enable()
    mr = MapReduce()
    k = np.arange(10, dtype=np.uint64)
    mr.map(1, lambda i, kv, p: kv.add_batch(k, k))
    evs = tracer.events()
    assert evs and all(e.get("trace") for e in evs)
    # the id is the process context's, and stable across ops
    proc = obs_context.active_account()
    assert {e["trace"] for e in evs} == {proc.trace_id}
    # MRTPU_PROFILE=0: no implicit context, spans carry no trace
    monkeypatch.setenv("MRTPU_PROFILE", "0")
    obs_context.reset()
    tracer.clear()
    mr.map(1, lambda i, kv, p: kv.add_batch(k, k))
    assert all(e.get("trace") is None for e in tracer.events())
    assert obs_context.active_account() is None


def test_flight_dump_carries_trace_id(tmp_path):
    from gpu_mapreduce_tpu.obs import flight
    get_tracer().enable()
    rec = flight.enable(dir=str(tmp_path))
    with obs_context.request_scope(label="doomed") as acct:
        mr = MapReduce()
        k = np.arange(10, dtype=np.uint64)
        mr.map(1, lambda i, kv, p: kv.add_batch(k, k))
        path = rec.dump("test")
    doc = json.load(open(path))
    assert doc["trace_id"] == acct.trace_id
    assert any(s.get("trace") == acct.trace_id for s in doc["spans"])


# ---------------------------------------------------------------------------
# SLO engine
# ---------------------------------------------------------------------------

def test_parse_slo():
    objs = obs_slo.parse_slo(
        "tenant=acme;p99_ms=2000;err_pct=0.5;windows=60,600"
        "|tenant=*;err_pct=5")
    assert objs[0].tenant == "acme" and objs[0].p99_ms == 2000
    assert objs[0].windows == (60.0, 600.0)
    assert objs[1].tenant == "*" and objs[1].p99_ms is None
    eng = obs_slo.SLOEngine(objs)
    assert eng.objective_for("acme").p99_ms == 2000
    assert eng.objective_for("other").err_pct == 5
    for bad in ("tenant=*", "tenant=*;p99_ms=0", "tenant=*;typo=1",
                "tenant=*;err_pct=200", "p99_ms"):
        with pytest.raises(ValueError):
            obs_slo.parse_slo(bad)


def _feed_sessions(reg, tenant, done=0, failed=0, wall_s=0.01):
    c = reg.counter("mrtpu_serve_sessions_total", "", ("tenant",
                                                       "status"))
    h = reg.histogram("mrtpu_serve_session_seconds", "", ("tenant",
                                                          "status"))
    for status, n in (("done", done), ("failed", failed)):
        if n:
            c.inc(n, tenant=tenant, status=status)
            for _ in range(n):
                h.observe(wall_s, tenant=tenant, status=status)


def test_burn_rate_and_alert_arms_flight():
    from gpu_mapreduce_tpu.obs import flight
    from gpu_mapreduce_tpu.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    eng = obs_slo.SLOEngine(obs_slo.parse_slo(
        "tenant=*;p99_ms=5000;err_pct=1;windows=60,600"))
    t0 = 1_000_000.0
    # 10 sessions, 5 failed → err fraction 0.5 over a 1% budget = 50×
    _feed_sessions(reg, "acme", done=5, failed=5)
    burn = eng.tick(now=t0, reg=reg)
    assert burn["acme"]["60s"] == pytest.approx(50.0)
    assert burn["acme"]["600s"] == pytest.approx(50.0)
    snap = eng.snapshot()
    assert "acme" in snap["firing"]
    assert snap["alerts"] and snap["alerts"][0]["tenant"] == "acme"
    assert flight.get() is not None          # the alert ARMED it
    # gauges exported into the same registry
    g = reg.collect()["mrtpu_slo_burn_ratio"]["samples"]
    by = {(s["labels"]["tenant"], s["labels"]["window"]): s["value"]
          for s in g}
    assert by[("acme", "60s")] == pytest.approx(50.0)
    # no NEW traffic in the next minute → the 60s window cools to 0
    eng.tick(now=t0 + 61, reg=reg)
    eng.tick(now=t0 + 122, reg=reg)
    burn = eng.tick(now=t0 + 183, reg=reg)
    assert burn["acme"]["60s"] == 0.0
    assert "acme" not in eng.snapshot()["firing"]


def test_latency_burn_uses_bucket_resolution():
    from gpu_mapreduce_tpu.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    eng = obs_slo.SLOEngine(obs_slo.parse_slo(
        "tenant=*;p99_ms=5000;windows=60"))
    # 100 done sessions, 4 of them slower than 5 s → 4% slow over the
    # 1% tail budget = 4× burn
    _feed_sessions(reg, "t", done=96, wall_s=0.01)
    _feed_sessions(reg, "t", done=4, wall_s=9.0)
    burn = eng.tick(now=1_000_000.0, reg=reg)
    assert burn["t"]["60s"] == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# offline: trace_view --trace / --traces + the metric-catalog lint
# ---------------------------------------------------------------------------

def _synthetic_trace(path):
    evs = [
        {"name": "oink.wordfreq", "cat": "oink", "ph": "X", "ts": 0.0,
         "dur": 1_000_000.0, "id": 1, "parent": 0, "trace": "T1",
         "args": {"dispatches": 5, "shuffle_sent_bytes": 1 << 20}},
        {"name": "map_files", "cat": "mr_op", "ph": "X", "ts": 0.0,
         "dur": 300_000.0, "id": 2, "parent": 1, "trace": "T1",
         "args": {}},
        {"name": "collate", "cat": "mr_op", "ph": "X", "ts": 300_000.0,
         "dur": 600_000.0, "id": 3, "parent": 1, "trace": "T1",
         "args": {}},
        {"name": "shuffle.exchange", "cat": "shuffle", "ph": "X",
         "ts": 350_000.0, "dur": 500_000.0, "id": 4, "parent": 3,
         "trace": "T1", "args": {}},
        {"name": "oink.other", "cat": "oink", "ph": "X", "ts": 0.0,
         "dur": 50_000.0, "id": 5, "parent": 0, "trace": "T2",
         "args": {}},
    ]
    with open(path, "w") as f:
        for e in evs:
            f.write(json.dumps(e) + "\n")


def test_trace_view_trace_filter_and_critical_path(tmp_path, capsys):
    tv = load_script("trace_view")
    path = str(tmp_path / "t.jsonl")
    _synthetic_trace(path)
    assert tv.main([path, "--traces"]) == 0
    out = capsys.readouterr().out
    assert "T1" in out and "T2" in out
    assert tv.main([path, "--trace", "T1", "--json"]) == 0
    prof = json.loads(capsys.readouterr().out)
    assert prof["spans"] == 4
    assert prof["dispatches"] == 5
    assert prof["shuffle_sent_bytes"] == 1 << 20
    path_names = [h["name"] for h in prof["critical_path"]]
    assert path_names == ["oink.wordfreq", "collate",
                          "shuffle.exchange"]
    # self time: collate 0.6s with a 0.5s child → 0.1s self
    assert prof["critical_path"][1]["self_s"] == pytest.approx(0.1)
    # human-readable report renders without error
    assert tv.main([path, "--trace", "T1"]) == 0
    assert "critical path" in capsys.readouterr().out


def test_metric_catalog_lint_passes():
    mrlint = load_script("mrlint")
    assert mrlint.main(["-r", "metric-catalog"]) == 0


def test_trace_index_wall():
    tv = load_script("trace_view")
    idx = tv.trace_index([
        {"trace": "A", "ts": 0.0, "dur": 1e6, "parent": 0, "id": 1},
        {"trace": "A", "ts": 5e5, "dur": 1e6, "parent": 1, "id": 2}])
    assert idx["A"]["spans"] == 2 and idx["A"]["top_spans"] == 1
    assert idx["A"]["wall_s"] == pytest.approx(1.5)
