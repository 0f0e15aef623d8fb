"""Live metrics + flight recorder (ISSUE 3): the registry under thread
hammering, the span→metric bridge, the Prometheus endpoint round-trip,
trace-sink rotation and the flight recorder's dump paths."""

import json
import os
import signal
import threading
import urllib.request

import numpy as np
import pytest

from gpu_mapreduce_tpu import MapReduce


@pytest.fixture
def obs_state():
    """Reset the process-global tracer, registry and flight recorder
    before AND after — metric feeds must never leak across tests."""
    from gpu_mapreduce_tpu.obs import flight, get_tracer, metrics

    def _reset():
        get_tracer().reset()
        metrics.reset()
        flight.reset()

    _reset()
    yield (get_tracer(), metrics)
    _reset()


# ---------------------------------------------------------------------------
# the registry itself
# ---------------------------------------------------------------------------

def test_registry_thread_hammer():
    """Concurrent inc/observe from mapstyle-2 style worker threads must
    land exactly: the counters' final values equal the submitted work."""
    from gpu_mapreduce_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("h_total", "hammered counter", ("worker",))
    g = reg.gauge("h_gauge", "hammered gauge")
    h = reg.histogram("h_lat", "hammered histogram", ("worker",),
                      buckets=(0.001, 0.01, 1.0))
    nthreads, per = 8, 5000

    def work(w):
        lab = str(w % 2)
        for i in range(per):
            c.inc(1, worker=lab)
            g.inc(1)
            h.observe(0.0005 if i % 2 else 0.5, worker=lab)

    threads = [threading.Thread(target=work, args=(w,))
               for w in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    total = sum(s["value"] for s in c.samples())
    assert total == nthreads * per
    assert c.value(worker="0") == c.value(worker="1") == total // 2
    assert g.value() == nthreads * per
    hs = h.samples()
    assert sum(s["count"] for s in hs) == nthreads * per
    for s in hs:
        # cumulative buckets: half the observations in <=0.001
        assert s["buckets"]["0.001"] == s["count"] // 2
        assert s["buckets"]["+Inf"] == s["count"]


def test_registry_label_and_type_mismatch_raise():
    from gpu_mapreduce_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("m", "x", ("a",))
    with pytest.raises(ValueError):
        c.inc(1)                       # missing declared label
    with pytest.raises(ValueError):
        c.inc(1, a="1", b="2")         # undeclared label
    with pytest.raises(ValueError):
        c.inc(-1, a="1")               # counters only go up
    with pytest.raises(ValueError):
        reg.gauge("m")                 # re-declared under another type
    assert reg.counter("m", labelnames=("a",)) is c   # get-or-create
    h = reg.histogram("hh", buckets=(0.1, 1.0))
    with pytest.raises(ValueError):
        reg.histogram("hh", buckets=(0.5,))   # conflicting buckets
    assert reg.histogram("hh") is h           # bucket-less lookup OK


def test_prometheus_text_format():
    from gpu_mapreduce_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("c_total", "a counter", ("op",)).inc(3, op='x"y\n')
    reg.gauge("g", "a gauge").set(1.5)
    reg.histogram("h_seconds", "a histogram",
                  buckets=(0.1, 1.0)).observe(0.05)
    txt = reg.prometheus_text()
    assert "# TYPE c_total counter" in txt
    assert 'c_total{op="x\\"y\\n"} 3' in txt
    assert "# TYPE g gauge" in txt and "\ng 1.5" in txt
    assert 'h_seconds_bucket{le="0.1"} 1' in txt
    assert 'h_seconds_bucket{le="+Inf"} 1' in txt
    assert "h_seconds_count 1" in txt


# ---------------------------------------------------------------------------
# the automatic feeds: span bridge, exchange counters, stats()
# ---------------------------------------------------------------------------

def test_bridge_and_stats_metrics(obs_state):
    _, metrics = obs_state
    metrics.enable_metrics(flight=False)
    mr = MapReduce()
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.array([1, 1, 2], np.uint64), np.ones(3, np.uint64)))
    mr.compress(lambda k, v, kv, p: kv.add(k, len(v)))
    s = mr.stats()
    assert "metrics" in s
    lat = s["metrics"]["mrtpu_op_latency_seconds"]
    ops = {tuple(sorted(x["labels"].items())) for x in lat["samples"]}
    assert (("cat", "mr_op"), ("op", "map")) in ops
    assert (("cat", "mr_op"), ("op", "compress")) in ops
    # collectors refreshed the cumulative gauges + plan hit ratio
    assert "mrtpu_hbm_hiwater_bytes" in s["metrics"]
    ratio = s["metrics"]["mrtpu_plan_cache_hit_ratio"]
    assert {x["labels"]["cache"] for x in ratio["samples"]} >= {"plan"}


def test_exchange_metrics_on_mesh(obs_state):
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    _, metrics = obs_state
    metrics.enable_metrics(flight=False)
    mr = MapReduce(make_mesh(4))
    keys = np.arange(4000, dtype=np.uint64) % 97
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, keys))
    mr.aggregate()
    reg = metrics.get_registry()
    b = reg.counter("mrtpu_exchange_bytes_total", labelnames=("kind",))
    assert b.value(kind="sent") > 0
    assert b.value(kind="pad") >= 0
    assert reg.counter("mrtpu_exchanges_total").value() >= 1
    assert reg.counter("mrtpu_exchange_rows_total").value() >= 4000


def test_exchange_metrics_on_fused_plan(obs_state):
    """The fused tier must feed the same exchange counters as the eager
    one — a MRTPU_FUSE=1 run reading 'no exchange traffic' on /metrics
    would defeat the live export exactly where it matters most."""
    from gpu_mapreduce_tpu.oink.kernels import count
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    _, metrics = obs_state
    metrics.enable_metrics(flight=False)
    mr = MapReduce(make_mesh(4), fuse=1)
    keys = np.arange(4000, dtype=np.uint64) % 97
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, np.ones_like(keys)))
    with mr.pipeline():
        mr.aggregate()
        mr.convert()
        mr.reduce(count, batch=True)
    mr.kv   # property read is a plan barrier: the fused chain executes
    reg = metrics.get_registry()
    b = reg.counter("mrtpu_exchange_bytes_total", labelnames=("kind",))
    assert b.value(kind="sent") > 0
    assert reg.counter("mrtpu_exchanges_total").value() >= 1
    assert reg.counter("mrtpu_exchange_rows_total").value() >= 4000


def test_metrics_endpoint_scrape_round_trip(obs_state):
    """The acceptance path: scrape /metrics during a wordfreq-shaped
    mesh run — Prometheus text with op latency histograms, exchange
    byte counters and the plan-cache hit ratio."""
    from gpu_mapreduce_tpu.obs.httpd import MetricsServer
    from gpu_mapreduce_tpu.oink.kernels import count
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    _, metrics = obs_state
    metrics.enable_metrics(flight=False)
    srv = MetricsServer(port=0)
    port = srv.start()
    try:
        mr = MapReduce(make_mesh(4))
        keys = np.arange(2000, dtype=np.uint64) % 101
        mr.map(1, lambda i, kv, p: kv.add_batch(keys,
                                                np.ones_like(keys)))
        mr.collate()
        mr.reduce(count, batch=True)
        txt = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "# TYPE mrtpu_op_latency_seconds histogram" in txt
        assert 'mrtpu_op_latency_seconds_bucket{op="aggregate"' in txt
        assert 'mrtpu_exchange_bytes_total{kind="sent"}' in txt
        assert "mrtpu_plan_cache_hit_ratio" in txt
        assert "mrtpu_hbm_hiwater_bytes" in txt
        j = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10).read())
        assert j["mrtpu_op_latency_seconds"]["type"] == "histogram"
        # liveness/readiness split (serve fleet): no provider armed =
        # ready, JSON body
        hz = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10)
        assert hz.status == 200
        assert json.loads(hz.read()) == {"status": "ok"}
    finally:
        srv.stop()


def test_enable_metrics_concurrent_single_bridge(obs_state):
    """Racing enables (two threads constructing MapReduce(metrics_port=…))
    must subscribe the span bridge exactly once — a duplicate would
    double-count every span forever."""
    from gpu_mapreduce_tpu.obs import get_tracer, metrics
    from gpu_mapreduce_tpu.obs.sinks import CallbackSink

    threads = [threading.Thread(
        target=lambda: metrics.enable_metrics(flight=False))
        for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tr = get_tracer()
    nbridge = sum(1 for s in tr._sinks
                  if isinstance(s, CallbackSink)
                  and s.fn == metrics._bridge_emit)
    assert nbridge == 1


def test_snapshotter_env_configure_no_deadlock(tmp_path, obs_state):
    """MRTPU_METRICS_SNAP alone (no port) at import time must not
    deadlock: start_snapshotter's enable_metrics reaches get_registry,
    which takes the registry lock — they must not nest."""
    _, metrics = obs_state
    metrics._REGISTRY = None      # force the cold-start path that hung
    path = str(tmp_path / "s.jsonl")
    snap = metrics.start_snapshotter(path, every_s=3600)
    try:
        assert snap.is_alive()
        assert metrics.start_snapshotter(path, every_s=3600) is snap
    finally:
        snap.stop()


def test_snapshotter_writes_jsonl(tmp_path, obs_state):
    _, metrics = obs_state
    metrics.enable_metrics(flight=False)
    path = str(tmp_path / "snap.jsonl")
    snap = metrics.Snapshotter(path, every_s=3600)
    snap.write_once()
    snap.write_once()
    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert len(lines) == 2
    assert "mrtpu_plan_cache_hit_ratio" in lines[0]["metrics"]


# ---------------------------------------------------------------------------
# trace sink rotation
# ---------------------------------------------------------------------------

def test_jsonl_sink_rotation(tmp_path, obs_state):
    from gpu_mapreduce_tpu.obs import JsonlSink, read_jsonl
    from gpu_mapreduce_tpu.obs.metrics import get_registry

    path = str(tmp_path / "t.jsonl")
    sink = JsonlSink(path, max_bytes=1500, keep=2)
    before = get_registry().counter("mrtpu_trace_rotated_total").value()
    for i in range(200):
        sink.emit({"name": f"ev{i}", "ph": "X", "ts": i, "dur": 1.0,
                   "args": {}})
    sink.close()
    assert sink.rotations >= 2
    assert os.path.exists(path + ".1")
    assert os.path.exists(path + ".2")
    assert not os.path.exists(path + ".3")       # keep=2 bounds the set
    assert os.path.getsize(path + ".1") <= 1500 + 200
    # rotated + live files hold a contiguous tail of events, parseable
    tail = read_jsonl(path + ".2") + read_jsonl(path + ".1") \
        + read_jsonl(path)
    names = [e["name"] for e in tail]
    assert names[-1] == "ev199"
    assert names == [f"ev{i}" for i in
                     range(200 - len(names), 200)]
    assert get_registry().counter(
        "mrtpu_trace_rotated_total").value() - before == sink.rotations


def test_trace_max_mb_env(tmp_path, monkeypatch):
    from gpu_mapreduce_tpu.obs import JsonlSink
    monkeypatch.setenv("MRTPU_TRACE_MAX_MB", "0.001")  # ~1 KB
    monkeypatch.setenv("MRTPU_TRACE_KEEP", "1")
    sink = JsonlSink(str(tmp_path / "e.jsonl"))
    assert sink.max_bytes == int(0.001 * (1 << 20))
    assert sink.keep == 1
    sink.close()


def test_trace_env_malformed_falls_back(tmp_path, monkeypatch, capsys):
    """A typo'd knob warns and uses the default — it must never crash
    the run the trace was meant to observe (utils.env.env_knob)."""
    from gpu_mapreduce_tpu.obs import JsonlSink
    monkeypatch.setenv("MRTPU_TRACE_MAX_MB", "10mb")
    monkeypatch.setenv("MRTPU_TRACE_KEEP", "3files")
    sink = JsonlSink(str(tmp_path / "e.jsonl"))
    assert sink.max_bytes == 0 and sink.keep == 3
    sink.close()
    err = capsys.readouterr().err
    assert "MRTPU_TRACE_MAX_MB ignored" in err
    assert "MRTPU_TRACE_KEEP ignored" in err


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def _traced_ops():
    mr = MapReduce()
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.arange(64, dtype=np.uint64), np.ones(64, np.uint64)))
    mr.sort_keys(1)
    return mr


def test_flight_dump_on_mrerror(tmp_path, obs_state):
    """An unhandled MRError reaching the excepthook leaves the forensic
    artifact whose last spans match the trace ring."""
    import sys

    from gpu_mapreduce_tpu.core.runtime import MRError
    from gpu_mapreduce_tpu.obs import flight, get_tracer

    rec = flight.enable(dir=str(tmp_path))
    _traced_ops()
    try:
        raise MRError("induced failure")
    except MRError:
        exc_type, exc, tb = sys.exc_info()
    sys.excepthook(exc_type, exc, tb)   # what interpreter exit runs
    assert rec.last_dump and os.path.exists(rec.last_dump)
    doc = json.load(open(rec.last_dump))
    assert doc["reason"] == "unhandled:MRError"
    assert doc["counters"]["msizemax"] >= 0
    ring = get_tracer().events()
    tail = [e["name"] for e in doc["spans"]][-len(ring):]
    assert tail == [e["name"] for e in ring]
    assert "sort_keys" in tail


def test_flight_dump_on_sigusr1(tmp_path, obs_state):
    from gpu_mapreduce_tpu.obs import flight

    import time

    rec = flight.enable(dir=str(tmp_path))
    _traced_ops()
    os.kill(os.getpid(), signal.SIGUSR1)
    # the handler fires at the next bytecode boundary but hands the
    # dump to a side thread (deadlock avoidance) — wait for it
    for _ in range(500):
        if rec.last_dump:
            break
        time.sleep(0.01)
    doc = json.load(open(rec.last_dump))
    assert doc["reason"] == "SIGUSR1"
    assert any(e["name"] == "sort_keys" for e in doc["spans"])


def test_exhausted_retry_budget_dumps_flight_with_ft_span(tmp_path,
                                                          obs_state,
                                                          monkeypatch):
    """The ft/ ↔ PR-3 flight path: an exhausted retry budget raises
    MRError, and the flight-recorder artifact's trace tail contains the
    failing ``ft.retry`` span (site + outcome=exhausted) plus the
    mrtpu_retries_total counters."""
    import sys

    from gpu_mapreduce_tpu import ft
    import gpu_mapreduce_tpu.ft.retry as ftr
    from gpu_mapreduce_tpu.core.runtime import MRError
    from gpu_mapreduce_tpu.obs import flight

    _, metrics = obs_state
    metrics.enable_metrics(flight=False)
    rec = flight.enable(dir=str(tmp_path))
    monkeypatch.setattr(ftr, "_sleep", lambda s: None)
    ft.reset()
    ft.set_budget("spill.read", 2)
    try:
        _traced_ops()

        def torn_block():
            raise OSError("torn block read")

        try:
            ft.retry_call("spill.read", torn_block, detail="run-7.k.npy")
            raise AssertionError("budget should exhaust")
        except MRError:
            exc_type, exc, tb = sys.exc_info()
        sys.excepthook(exc_type, exc, tb)   # what interpreter exit runs
        doc = json.load(open(rec.last_dump))
        assert doc["reason"] == "unhandled:MRError"
        tail = doc["spans"][-3:]
        ft_spans = [e for e in tail if e["name"] == "ft.retry"]
        assert ft_spans, [e["name"] for e in doc["spans"]]
        args = ft_spans[-1]["args"]
        assert args["site"] == "spill.read"
        assert args["outcome"] == "exhausted"
        assert args["detail"] == "run-7.k.npy"
        # the same failure is counted in the registry (collector pull)
        snap = metrics.snapshot()
        got = {(s["labels"]["site"], s["labels"]["outcome"]):
               s["value"]
               for s in snap["mrtpu_retries_total"]["samples"]}
        assert got[("spill.read", "exhausted")] == 1
        assert got[("spill.read", "retry")] == 2
        assert "mrtpu_retries_total" in doc["metrics"]
    finally:
        ft.reset()


def test_flight_dump_never_raises(tmp_path, obs_state):
    from gpu_mapreduce_tpu.obs import flight

    rec = flight.enable(dir=str(tmp_path / ("no" * 200)))  # overlong path
    assert rec.dump("broken") is None    # degrade, don't mask failures


# ---------------------------------------------------------------------------
# oink dump_metrics
# ---------------------------------------------------------------------------

def test_dump_metrics_command(tmp_path, obs_state):
    from gpu_mapreduce_tpu.oink.command import run_command

    _, metrics = obs_state
    metrics.enable_metrics(flight=False)
    _traced_ops()
    out = tmp_path / "m.json"
    cmd = run_command("dump_metrics", [str(out)], screen=False)
    snap = json.load(open(out))
    assert "mrtpu_op_latency_seconds" in snap
    assert "DumpMetrics" in cmd.result_msg
    prom = tmp_path / "m.prom"
    run_command("dump_metrics", [str(prom)], screen=False)
    assert "# TYPE mrtpu_op_latency_seconds histogram" in prom.read_text()
