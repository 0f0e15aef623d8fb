"""Fleet-wide observability (ISSUE 16): cross-process trace stitching,
rank/replica metrics federation and collective straggler attribution —
obs/fleetobs.py units (sync observer, dump channel, federation
renderer), the trace_view merged-run views, the router's
``/metrics/fleet``, plus the multi-process goldens: one trace_id across
a 4-proc mrlaunch run, an injected slow rank named with the right
cause, and the federation chaos drill (kill -9 a replica and a rank —
stale, never absent)."""

import collections
import importlib.util
import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")
MRLAUNCH = os.path.join(SCRIPTS, "mrlaunch.py")


def load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(SCRIPTS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def obs_state():
    """Reset tracer/registry/flight/context before AND after — the
    observer feeds process-global state that must not leak."""
    from gpu_mapreduce_tpu.obs import context, flight, get_tracer, metrics

    def _reset():
        get_tracer().reset()
        metrics.reset()
        flight.reset()
        context.reset()

    _reset()
    yield metrics
    _reset()


# ---------------------------------------------------------------------------
# cause classification + the delay fault kind
# ---------------------------------------------------------------------------

def test_classify_straggler_cases(monkeypatch):
    from gpu_mapreduce_tpu.obs.fleetobs import classify_straggler
    # no evidence, or the slowest rank outside the row vector: the
    # conservative verdict is the host's fault, not the data's
    assert classify_straggler(1, []) == "host_slow"
    assert classify_straggler(5, [10, 10]) == "host_slow"
    assert classify_straggler(0, [0, 0, 0]) == "host_slow"
    # balanced rows, late anyway → host_slow
    assert classify_straggler(2, [100, 100, 100, 100]) == "host_slow"
    # the slowest rank got 2x the mean rows → data_skew
    assert classify_straggler(3, [50, 50, 50, 300]) == "data_skew"
    # the ratio is a knob
    monkeypatch.setenv("MRTPU_DIST_SKEW_RATIO", "10.0")
    assert classify_straggler(3, [50, 50, 50, 300]) == "host_slow"


def test_delay_kind_restricted_to_dist_sites():
    from gpu_mapreduce_tpu.ft.inject import FaultSpec
    with pytest.raises(ValueError):
        FaultSpec(site="spill.write", kind="delay")
    FaultSpec(site="dist.exchange", kind="delay")   # allowed


def test_delay_fault_sleeps_then_proceeds(monkeypatch):
    """kind=delay is a SLOW host, not a dead one: fault_point stalls
    MRTPU_DIST_DELAY_S and then RETURNS — the caller still enters the
    collective (late), which is what the attribution must observe."""
    from gpu_mapreduce_tpu import ft
    from gpu_mapreduce_tpu.ft import inject
    monkeypatch.setenv("MRTPU_DIST_DELAY_S", "0.3")
    inject.schedule(site="dist.exchange", kind="delay", max_faults=1)
    try:
        t0 = time.monotonic()
        inject.fault_point("dist.exchange")       # no exception raised
        assert time.monotonic() - t0 >= 0.25
        t0 = time.monotonic()
        inject.fault_point("dist.exchange")       # budget spent: no-op
        assert time.monotonic() - t0 < 0.2
    finally:
        ft.clear_faults()


# ---------------------------------------------------------------------------
# the sync observer
# ---------------------------------------------------------------------------

def _stamp(rundir, rank, site, seq, ts, gen=0, rows=None, torn=False):
    """Hand-write a peer's arrival record the way its SyncObserver
    would."""
    from gpu_mapreduce_tpu.obs.fleetobs import sync_path
    path = sync_path(rundir, rank, gen)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    rec = {"site": site, "seq": seq, "rank": rank, "ts": ts}
    if rows is not None:
        rec["rows"] = rows
    with open(path, "ab") as f:
        data = json.dumps(rec).encode()
        f.write(data[:-4] if torn else data + b"\n")


def test_sync_observer_spread_slowest_and_cause(tmp_path, obs_state):
    from gpu_mapreduce_tpu.obs.fleetobs import (SyncObserver,
                                                read_sync_records)
    rundir = str(tmp_path)
    obs = SyncObserver(rundir, rank=0, world=3)
    try:
        base = time.time()
        rec = obs.arrive("dist.exchange")
        # peers arrived around us; rank 2 was 0.5s late
        _stamp(rundir, 1, "dist.exchange", 0, base + 0.01)
        _stamp(rundir, 2, "dist.exchange", 0, base + 0.5)
        out = obs.complete("dist.exchange", rec)
        assert out is not None
        assert out["slowest"] == 2
        assert out["ranks_seen"] == 3
        assert 0.4 <= out["spread_s"] <= 0.7
        assert out["cause"] == "host_slow"        # no row evidence
        # rank 2 is also the data-heavy rank → the verdict flips
        obs.note_rows([10, 10, 100])
        rec = obs.arrive("dist.exchange")
        assert rec["seq"] == 1 and rec["rows"] == 10
        _stamp(rundir, 1, "dist.exchange", 1, rec["ts"] + 0.01)
        _stamp(rundir, 2, "dist.exchange", 1, rec["ts"] + 0.5)
        out = obs.complete("dist.exchange", rec)
        assert out["cause"] == "data_skew" and out["slowest"] == 2
        # both spread records landed in OUR shard, tagged by kind
        spreads = [r for r in read_sync_records(rundir)
                   if r.get("kind") == "spread"]
        assert len(spreads) == 2
        assert {r["cause"] for r in spreads} == {"host_slow",
                                                 "data_skew"}
    finally:
        obs.close()


def test_sync_observer_no_peer_evidence_is_none(tmp_path, obs_state):
    from gpu_mapreduce_tpu.obs.fleetobs import SyncObserver
    obs = SyncObserver(str(tmp_path), rank=0, world=4)
    try:
        rec = obs.arrive("dist.count_sync")
        assert obs.complete("dist.count_sync", rec) is None
    finally:
        obs.close()


def test_sync_observer_skips_torn_peer_lines(tmp_path, obs_state):
    from gpu_mapreduce_tpu.obs.fleetobs import SyncObserver
    rundir = str(tmp_path)
    obs = SyncObserver(rundir, rank=0, world=2)
    try:
        rec = obs.arrive("dist.exchange")
        # peer 1 is mid-append: no trailing newline → not consumed
        _stamp(rundir, 1, "dist.exchange", 0, rec["ts"] + 0.1,
               torn=True)
        assert obs.complete("dist.exchange", rec) is None
        # the append completes (rewrite whole line) → consumed now
        from gpu_mapreduce_tpu.obs.fleetobs import sync_path
        with open(sync_path(rundir, 1), "wb") as f:
            f.write(json.dumps({"site": "dist.exchange", "seq": 0,
                                "rank": 1,
                                "ts": rec["ts"] + 0.1}).encode() + b"\n")
        out = obs.complete("dist.exchange", rec)
        assert out is not None and out["slowest"] == 1
    finally:
        obs.close()


def test_sync_observer_metrics_and_profile_feed(tmp_path, obs_state):
    """A completed sync lands in the registry (spread histogram, sync
    counter, slowest gauge, straggler counter past the warn threshold)
    and in the active request's ``straggler`` profile section."""
    metrics = obs_state
    from gpu_mapreduce_tpu.obs import context
    from gpu_mapreduce_tpu.obs.fleetobs import SyncObserver
    rundir = str(tmp_path)
    obs = SyncObserver(rundir, rank=0, world=2)
    try:
        with context.request_scope(label="t") as acct:
            rec = obs.arrive("dist.exchange")
            _stamp(rundir, 1, "dist.exchange", 0, rec["ts"] + 0.6)
            out = obs.complete("dist.exchange", rec)
            assert out is not None
            prof = acct.profile()
        snap = metrics.snapshot()
        assert "mrtpu_dist_sync_spread_seconds" in snap
        assert "mrtpu_dist_sync_total" in snap
        assert "mrtpu_dist_sync_slowest_rank" in snap
        strag = snap["mrtpu_dist_sync_straggler_total"]["samples"]
        assert any(s["labels"].get("cause") == "host_slow"
                   and s["labels"].get("site") == "dist.exchange"
                   for s in strag)
        row = prof["straggler"]["dist.exchange"]
        assert row["count"] == 1
        assert row["slowest_rank"] == 1
        assert row["worst_cause"] == "host_slow"
        assert row["ranks_seen"] == 2
        assert row["max_spread_s"] >= 0.5
    finally:
        obs.close()


def test_note_sync_rows_folds_shards_onto_ranks(tmp_path, obs_state):
    """The [P,P] count matrix's destination sums reach the observer as
    per-RANK rows, folding multiple local shards per rank."""
    import numpy as np

    from gpu_mapreduce_tpu.obs.fleetobs import SyncObserver
    from gpu_mapreduce_tpu.parallel import dist
    rt = dist.DistRuntime(0, 2, str(tmp_path), heartbeat_s=0.1,
                          lease_s=1.0, skew_s=0.1)
    rt.sync_obs = SyncObserver(str(tmp_path), 0, 2)
    prev = dist.activate(rt)
    try:
        # P=4 shards over world=2: columns 0+1 → rank 0, 2+3 → rank 1
        mat = np.arange(16).reshape(4, 4)
        dist.note_sync_rows(mat)
        assert rt.sync_obs._rows == [24 + 28, 32 + 36]
    finally:
        dist.activate(prev)
        rt.sync_obs.close()


# ---------------------------------------------------------------------------
# the per-rank metrics dump channel
# ---------------------------------------------------------------------------

def test_rank_metrics_dump_roundtrip(tmp_path, obs_state):
    metrics = obs_state
    from gpu_mapreduce_tpu.obs import context
    from gpu_mapreduce_tpu.obs.fleetobs import (RankMetricsDumper,
                                                rank_dump_stale,
                                                read_rank_dumps)
    context.set_process_trace_id("feedbeef01020304")
    metrics.get_registry().counter("t_obsdist_total", "t").inc(3)
    d = RankMetricsDumper(str(tmp_path), rank=2, gen=1, every_s=30.0)
    path = d.dump_once("start")
    assert path and os.path.exists(path)
    d.stop("exit")                       # final dump, thread never ran
    dumps = read_rank_dumps(str(tmp_path))
    assert list(dumps) == [2]
    doc = dumps[2]
    assert doc["rank"] == 2 and doc["gen"] == 1
    assert doc["reason"] == "exit"
    assert doc["trace_id"] == "feedbeef01020304"
    fam = doc["metrics"]["t_obsdist_total"]
    assert fam["samples"][0]["value"] == 3
    assert rank_dump_stale(doc) < 5.0
    assert rank_dump_stale({"ts": "bogus"}) == float("inf")


def test_set_process_trace_id_survives_profile_gate(monkeypatch,
                                                    obs_state):
    """An explicit launch-minted trace id outranks MRTPU_PROFILE=0:
    the stitch must work even with implicit profiling off."""
    monkeypatch.setenv("MRTPU_PROFILE", "0")
    from gpu_mapreduce_tpu.obs import context
    context.reset()
    assert context.current_trace_id() is None
    context.set_process_trace_id("aa00aa00aa00aa00")
    assert context.current_trace_id() == "aa00aa00aa00aa00"


# ---------------------------------------------------------------------------
# federation rendering
# ---------------------------------------------------------------------------

def _counter_snap(name, value, labels=None):
    return {name: {"type": "counter", "help": "h", "labelnames":
                   sorted(labels or {}),
                   "samples": [{"labels": labels or {},
                                "value": value}]}}


def test_federate_text_labels_and_staleness():
    from gpu_mapreduce_tpu.obs.fleetobs import federate_text, member_row
    members = [
        member_row(replica="a", up=True, stale=False, age_s=0.2,
                   metrics=_counter_snap("x_total", 7,
                                         {"site": "exchange"}),
                   state="ready"),
        member_row(replica="b", up=False, stale=True, age_s=12.5,
                   metrics=None, state="expired"),
        member_row(rank="1", up=True, stale=False, age_s=1.0, metrics={
            "lat_seconds": {"type": "histogram", "help": "hh",
                            "labelnames": [], "samples": [{
                                "labels": {}, "count": 2, "sum": 0.5,
                                "buckets": {"0.1": 1, "+Inf": 2}}]}}),
    ]
    text = federate_text(members)
    # liveness/staleness for EVERY member — the dead one included
    assert 'mrtpu_fleet_member_up{replica="a",rank=""} 1' in text
    assert 'mrtpu_fleet_member_up{replica="b",rank=""} 0' in text
    assert 'mrtpu_fleet_member_stale{replica="b",rank=""} 1' in text
    assert 'mrtpu_fleet_member_up{replica="",rank="1"} 1' in text
    assert 'mrtpu_fleet_member_age_seconds{replica="b",rank=""} 12.5' \
        in text
    # merged series carry the member's {replica,rank} labels appended
    assert 'x_total{site="exchange",replica="a",rank=""} 7' in text
    assert 'lat_seconds_bucket{replica="",rank="1",le="0.1"} 1' in text
    assert 'lat_seconds_sum{replica="",rank="1"} 0.5' in text
    assert 'lat_seconds_count{replica="",rank="1"} 2' in text
    # HELP/TYPE render once per family
    assert text.count("# TYPE x_total counter") == 1


def test_federate_text_escapes_label_values():
    from gpu_mapreduce_tpu.obs.fleetobs import federate_text, member_row
    text = federate_text([member_row(
        replica='we"ird\\x', up=True, stale=False, age_s=0.0,
        metrics=_counter_snap("y_total", 1))])
    assert 'replica="we\\"ird\\\\x"' in text


# ---------------------------------------------------------------------------
# trace_view: merged per-rank shards + sync alignment
# ---------------------------------------------------------------------------

def _write_shard(rundir, rank, events):
    with open(os.path.join(rundir, f"trace-r{rank}.jsonl"), "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")


def test_read_trace_dir_rebases_and_namespaces(tmp_path):
    tv = load_script("trace_view")
    rundir = str(tmp_path)
    # rank 0's perf epoch started at wall=1000.0, rank 1's at 1000.2 —
    # identical local ts must land 0.2s apart after the rebase
    _write_shard(rundir, 0, [
        {"name": "a", "id": 7, "parent": 0, "ts": 0.0, "dur": 100.0,
         "wall": 1000.0, "trace": "t1"},
        {"name": "b", "id": 8, "parent": 7, "ts": 500.0, "dur": 50.0,
         "wall": 1000.0005, "trace": "t1"}])
    _write_shard(rundir, 1, [
        {"name": "a", "id": 7, "parent": 0, "ts": 0.0, "dur": 100.0,
         "wall": 1000.2, "trace": "t1"}])
    events, nshards = tv.read_trace_dir(rundir)
    assert nshards == 2
    assert [ev["rank"] for ev in events] == [0, 0, 1]
    r0a, r0b, r1a = events
    assert r0a["ts"] == 0.0
    assert r0b["ts"] == 500.0                  # intra-shard preserved
    assert abs(r1a["ts"] - 200000.0) < 1.0     # 0.2s in microseconds
    # span ids namespaced per rank: the two "id 7" spans stay distinct
    assert r0a["id"] != r1a["id"]
    assert r0b["parent"] == r0a["id"]          # parent chain intact
    tl = tv.rank_timeline(events)
    assert set(tl) == {0, 1}
    assert tl[0]["spans"] == 2
    report = tv.dist_report(events, rundir)
    assert "rank 0" in report and "rank 1" in report


def test_sync_alignment_dedupes_across_ranks(tmp_path):
    tv = load_script("trace_view")
    from gpu_mapreduce_tpu.obs.fleetobs import sync_path
    rundir = str(tmp_path)
    for rank, seen in ((0, 2), (1, 3)):
        path = sync_path(rundir, rank)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(json.dumps({
                "kind": "spread", "site": "dist.exchange", "seq": 0,
                "spread_s": 0.1, "slowest": 1, "cause": "host_slow",
                "ranks_seen": seen, "rank": rank,
                "arrivals": {"0": 0.0, "1": 0.1}}) + "\n")
    syncs = tv.sync_alignment(rundir)
    assert len(syncs) == 1                 # same (gen, site, seq)
    assert syncs[0]["ranks_seen"] == 3     # fullest evidence wins
    report = tv.dist_report([], rundir)
    assert "dist.exchange" in report and "host_slow" in report


# ---------------------------------------------------------------------------
# flight recorder: the lease-table snapshot
# ---------------------------------------------------------------------------

def test_flight_snapshot_embeds_lease_table(tmp_path, obs_state):
    from gpu_mapreduce_tpu.obs import flight
    from gpu_mapreduce_tpu.parallel import dist
    rundir = str(tmp_path)
    rt = dist.DistRuntime(0, 2, rundir, heartbeat_s=0.1, lease_s=1.0,
                          skew_s=0.1)
    dist.write_beat(rundir, 0, 1.0)
    # peer 1 never wrote a beat: missing AND expired in the table
    prev = dist.activate(rt)
    try:
        rec = flight.enable(dir=rundir)
        doc = rec.snapshot("test")
        table = doc.get("dist")
        assert table is not None
        assert table["rank"] == 0 and table["world"] == 2
        assert table["peers"]["1"].get("missing") is True
        assert table["peers"]["1"]["expired"] is True
        assert table["peers"]["0"]["expired"] is False
        assert "1" in table["dead"]
    finally:
        dist.activate(prev)


# ---------------------------------------------------------------------------
# the router's /metrics/fleet + mrctl top
# ---------------------------------------------------------------------------

def _write_rank_dump(rundir, rank, ts, value=1.0, every_s=5.0):
    from gpu_mapreduce_tpu.utils.fsio import atomic_write_json
    from gpu_mapreduce_tpu.obs.fleetobs import rank_metrics_path
    atomic_write_json(rank_metrics_path(rundir, rank), {
        "rank": rank, "gen": 0, "pid": 1, "ts": ts,
        "every_s": every_s, "reason": "cadence", "trace_id": "t",
        "metrics": _counter_snap("r_rows_total", value)})


def test_router_metrics_fleet_replicas_and_ranks(tmp_path, monkeypatch,
                                                 obs_state):
    from gpu_mapreduce_tpu.serve import Router, ServeClient, Server
    root = tmp_path / "fleet"
    rundir = tmp_path / "run"
    rundir.mkdir()
    monkeypatch.setenv("MRTPU_FLEET_RUNDIR", str(rundir))
    _write_rank_dump(str(rundir), 0, time.time())              # fresh
    _write_rank_dump(str(rundir), 1, time.time() - 120.0)      # stale
    a = Server(port=0, workers=1, queue_cap=4, fleet_dir=str(root),
               replica_id="a", lease_s=5.0, heartbeat_s=0.5)
    a.start()
    rt = Router(str(root))
    rport = rt.start()
    try:
        c = ServeClient.local(rport)
        doc = c.fleet_metrics()
        by = {(m["replica"], m["rank"]): m for m in doc["members"]}
        rep = by[("a", "")]
        assert rep["up"] and not rep["stale"]
        assert rep["metrics"]            # live /metrics.json scrape
        r0, r1 = by[("", "0")], by[("", "1")]
        assert r0["up"] and not r0["stale"]
        assert not r1["up"] and r1["stale"]      # old dump: stale...
        assert r1["metrics"]["r_rows_total"]     # ...but NOT absent
        # the text exposition carries the same verdicts
        import urllib.request
        with urllib.request.urlopen(
                f"http://127.0.0.1:{rport}/metrics/fleet",
                timeout=10) as r:
            text = r.read().decode()
        assert 'mrtpu_fleet_member_up{replica="a",rank=""} 1' in text
        assert 'mrtpu_fleet_member_stale{replica="",rank="1"} 1' in text
        assert 'r_rows_total{replica="",rank="0"} 1' in text
    finally:
        rt.stop()
        a.shutdown()


def test_mrctl_top_table_renders_members():
    mrctl = load_script("mrctl")
    doc = {"members": [
        {"replica": "a", "rank": "", "up": True, "stale": False,
         "age_s": 0.4, "state": "ready", "metrics": {
             "mrtpu_dist_sync_spread_seconds": {
                 "type": "histogram", "samples": [
                     {"labels": {"site": "dist.exchange"},
                      "count": 4, "sum": 1.0, "buckets": {}}]}}},
        {"replica": "", "rank": "2", "up": False, "stale": True,
         "age_s": 33.0, "state": "", "metrics": None}]}
    text = mrctl._top_table(doc)
    assert "replica:a" in text and "rank:2" in text
    assert "0.250" in text               # 1.0s over 4 syncs
    assert mrctl._top_table({"members": []}).endswith(
        "(no federation members)")


# ---------------------------------------------------------------------------
# multi-process goldens (slow)
# ---------------------------------------------------------------------------

def _write_corpus(path, nwords=4000, seed=5):
    import random
    rng = random.Random(seed)
    words = [f"w{i:03d}".encode() for i in range(97)]
    with open(path, "wb") as f:
        for _ in range(nwords):
            f.write(rng.choice(words))
            f.write(b" " if rng.random() < 0.85 else b"\n")
    return path


def _expected_output(corpus):
    from gpu_mapreduce_tpu.utils.io import read_words
    counts = collections.Counter()
    with open(corpus, "rb") as f:
        counts.update(read_words(f.read()))
    rows = sorted(counts.items(), key=lambda wc: (-wc[1], wc[0]))
    return b"".join(w + b" %d\n" % c for w, c in rows)


def _mrlaunch(nproc, rundir, corpus, out, chunks=4, env=None,
              timeout=300, expect_rc=0):
    e = dict(os.environ)
    e.pop("MRTPU_FAULTS", None)
    e.update(env or {})
    r = subprocess.run(
        [sys.executable, MRLAUNCH, "--np", str(nproc),
         "--rundir", rundir, "wordfreq", "--files", corpus,
         "--out", out, "--chunks", str(chunks)],
        env=e, cwd=REPO, capture_output=True, timeout=timeout)
    assert r.returncode == expect_rc, \
        f"mrlaunch rc={r.returncode}\n{r.stdout.decode()[-2000:]}" \
        f"\n{r.stderr.decode()[-2000:]}"
    return r


@pytest.mark.slow
def test_obsdist_stitched_trace_golden(tmp_path):
    """THE stitching acceptance: a 4-proc run yields ONE trace id —
    launch.json's == every rank's trace shard == every rank's metrics
    dump — and trace_view merges the shards into one timeline."""
    corpus = str(_write_corpus(str(tmp_path / "c.txt")))
    out = str(tmp_path / "out.txt")
    rundir = str(tmp_path / "run")
    _mrlaunch(4, rundir, corpus, out)
    with open(out, "rb") as f:
        assert f.read() == _expected_output(corpus)
    with open(os.path.join(rundir, "launch.json")) as f:
        trace_id = json.load(f)["trace_id"]
    assert trace_id and len(trace_id) == 16
    shards = sorted(n for n in os.listdir(rundir)
                    if n.startswith("trace-r") and n.endswith(".jsonl"))
    assert shards == [f"trace-r{k}.jsonl" for k in range(4)]
    for shard in shards:
        tids = set()
        with open(os.path.join(rundir, shard)) as f:
            for line in f:
                ev = json.loads(line)
                if ev.get("trace"):
                    tids.add(ev["trace"])
        assert tids == {trace_id}, (shard, tids)
    dumps_tid = set()
    from gpu_mapreduce_tpu.obs.fleetobs import read_rank_dumps
    dumps = read_rank_dumps(rundir)
    assert sorted(dumps) == [0, 1, 2, 3]
    for doc in dumps.values():
        dumps_tid.add(doc["trace_id"])
        assert doc["reason"] == "done"          # the exit-path dump
    assert dumps_tid == {trace_id}
    # the merged timeline: every rank present, one shared clock
    tv = load_script("trace_view")
    events, nshards = tv.read_trace_dir(rundir)
    assert nshards == 4
    tl = tv.rank_timeline(events)
    assert set(tl) == {0, 1, 2, 3}
    # sync evidence exists and trace_view renders the alignment table
    syncs = tv.sync_alignment(rundir)
    assert syncs, "no spread records from an instrumented run"
    assert all(s["ranks_seen"] == 4 for s in syncs)
    report = tv.dist_report(events, rundir)
    # guard sites record their bare names ("exchange", "count_sync" —
    # the "dist." prefix is the fault-injection namespace, not the
    # observer's)
    assert "sync points" in report and "exchange" in report


@pytest.mark.slow
def test_obsdist_straggler_attribution_golden(tmp_path):
    """An injected slow (NOT dead) rank must be NAMED: delay rank 1 at
    its second exchange; every survivor's spread record for that sync
    fingers rank 1 with cause host_slow (rows were balanced)."""
    corpus = str(_write_corpus(str(tmp_path / "c.txt")))
    out = str(tmp_path / "out.txt")
    rundir = str(tmp_path / "run")
    _mrlaunch(4, rundir, corpus, out, chunks=6, env={
        "MRTPU_FAULTS":
            "site=dist.exchange;kind=delay;rank=1;after=1;n=1",
        "MRTPU_DIST_DELAY_S": "1.0",
        "MRTPU_DIST_SYNC_TIMEOUT": "60",
    })
    with open(out, "rb") as f:
        assert f.read() == _expected_output(corpus)   # slow, not wrong
    from gpu_mapreduce_tpu.obs.fleetobs import read_sync_records
    spreads = [r for r in read_sync_records(rundir)
               if r.get("kind") == "spread"
               and r.get("site") == "exchange"
               and r.get("spread_s", 0.0) >= 0.5]
    assert spreads, "the injected 1.0s delay left no spread record"
    for rec in spreads:
        assert rec["slowest"] == 1, rec
        assert rec["cause"] == "host_slow", rec
    # the straggler counter crossed MRTPU_DIST_SPREAD_WARN in at least
    # one rank's final registry dump, attributed to the same cause
    from gpu_mapreduce_tpu.obs.fleetobs import read_rank_dumps
    hit = False
    for doc in read_rank_dumps(rundir).values():
        fam = (doc.get("metrics") or {}).get(
            "mrtpu_dist_sync_straggler_total")
        if not fam:
            continue
        for s in fam["samples"]:
            if s["labels"].get("cause") == "host_slow" \
                    and s["value"] >= 1:
                hit = True
    assert hit, "mrtpu_dist_sync_straggler_total never incremented"


@pytest.mark.slow
def test_obsdist_federation_chaos_stale_not_absent(tmp_path,
                                                   monkeypatch,
                                                   obs_state):
    """Kill -9 one replica and one data-plane rank mid-run: both stay
    federation rows (up=0, stale=1), their labels stay consistent, and
    the merged counters never regress."""
    from gpu_mapreduce_tpu.serve import Router, ServeClient, Server
    root = tmp_path / "fleet"
    rundir = tmp_path / "run"
    rundir.mkdir()
    monkeypatch.setenv("MRTPU_FLEET_RUNDIR", str(rundir))
    # the doomed rank: a real process dumping on a fast cadence
    prog = (
        "import sys, time\n"
        "sys.path.insert(0, %r)\n"
        "from gpu_mapreduce_tpu.obs.fleetobs import RankMetricsDumper\n"
        "from gpu_mapreduce_tpu.obs.metrics import get_registry\n"
        "c = get_registry().counter('chaos_rows_total', 'rows')\n"
        "d = RankMetricsDumper(%r, rank=0, every_s=0.3)\n"
        "d.start()\n"
        "for _ in range(600):\n"
        "    c.inc(5)\n"
        "    time.sleep(0.1)\n" % (REPO, str(rundir)))
    rankproc = subprocess.Popen([sys.executable, "-c", prog], cwd=REPO)
    a = Server(port=0, workers=1, queue_cap=4, fleet_dir=str(root),
               replica_id="a", lease_s=5.0, heartbeat_s=0.5)
    # b's lease outlives the test on purpose: a dead-but-still-leased
    # member is the unreachable case (scrape fails, row stays) WITHOUT
    # racing a's takeover protocol, which legitimately RETIRES an
    # expired lease once the claim completes (serve/fleet.claim_done)
    b = Server(port=0, workers=1, queue_cap=4, fleet_dir=str(root),
               replica_id="b", lease_s=120.0, heartbeat_s=0.5)
    a.start()
    b.start()
    rt = Router(str(root))
    rport = rt.start()
    try:
        c = ServeClient.local(rport)
        deadline = time.monotonic() + 30.0
        doc1 = None
        while time.monotonic() < deadline:
            doc1 = c.fleet_metrics()
            by = {(m["replica"], m["rank"]): m for m in doc1["members"]}
            rank_ready = ("", "0") in by and by[("", "0")]["up"] \
                and (by[("", "0")]["metrics"] or {}).get(
                    "chaos_rows_total", {}).get("samples")
            if rank_ready and by[("a", "")]["up"] \
                    and by[("b", "")]["up"]:
                break
            time.sleep(0.2)
        by1 = {(m["replica"], m["rank"]): m for m in doc1["members"]}
        assert by1[("", "0")]["up"], "rank dump never became fresh"
        assert by1[("", "0")]["metrics"]["chaos_rows_total"]["samples"], \
            "the rank's counter never reached a cadence dump"
        v1 = by1[("", "0")]["metrics"]["chaos_rows_total"][
            "samples"][0]["value"]
        # kill -9 the rank and (simulated, lease left on disk) replica b
        rankproc.send_signal(signal.SIGKILL)
        rankproc.wait()
        b._fleet_suspended = True
        if b._listener is not None:
            b._listener.stop()
        # rank staleness: age > 3*0.3+1 = 1.9s; replica: lease + skew
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            doc2 = c.fleet_metrics()
            by2 = {(m["replica"], m["rank"]): m
                   for m in doc2["members"]}
            if by2[("", "0")]["stale"] and not by2[("b", "")]["up"]:
                break
            time.sleep(0.3)
        # stale, never absent: same member keys, honest verdicts
        assert set(by2) == set(by1)
        assert by2[("b", "")]["stale"] and not by2[("b", "")]["up"]
        r0 = by2[("", "0")]
        assert r0["stale"] and not r0["up"]
        v2 = r0["metrics"]["chaos_rows_total"]["samples"][0]["value"]
        assert v2 >= v1, "a dead rank's last counter value regressed"
        # survivor a still live and scraped
        assert by2[("a", "")]["up"] and by2[("a", "")]["metrics"]
        # the text rendering keeps every member too
        import urllib.request
        with urllib.request.urlopen(
                f"http://127.0.0.1:{rport}/metrics/fleet",
                timeout=10) as r:
            text = r.read().decode()
        assert 'mrtpu_fleet_member_up{replica="b",rank=""} 0' in text
        assert 'mrtpu_fleet_member_up{replica="",rank="0"} 0' in text
        assert 'chaos_rows_total{replica="",rank="0"}' in text
    finally:
        if rankproc.poll() is None:
            rankproc.kill()
        rt.stop()
        for srv in (a, b):
            try:
                srv.shutdown()
            except Exception:
                pass
