"""Observability parity (VERDICT r1 #6): verbosity=2 / timer=2 per-shard
histograms (reference write_histo, src/mapreduce.cpp:3251-3311), per-op
spill/comm deltas, and tier notes — plus the structured obs/ tracing
layer (spans, sinks, Chrome export, mr.stats())."""

import json
import os

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


# ---------------------------------------------------------------------------
# the host's half of a span (PR 34): CPU beside wall, JAX's own compile
# seconds as counter deltas, the per-program table
# ---------------------------------------------------------------------------

def _burn(seconds):
    """Spin until this THREAD has used ``seconds`` of CPU."""
    import time
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


def _private_tracer():
    from gpu_mapreduce_tpu.core.runtime import Counters
    from gpu_mapreduce_tpu.obs import Tracer
    return Tracer(counters=Counters()).enable()


def _sums_to_wall(ev):
    a = ev["args"]
    dur = ev["dur"] * 1e-6
    return abs(a["cpu_s"] + a["off_cpu_s"] - dur) <= max(0.01 * dur, 1e-3)


@pytest.mark.parametrize("what", ["busy", "sleep"])
def test_span_says_cpu_beside_wall(what):
    import time
    tr = _private_tracer()
    try:
        # a busy loop can lose its core to the other test workers: the
        # CPU seconds are exact, their share of the wall is best of three
        for _ in range(3):
            tr.clear()
            with tr.span(what):
                _burn(0.2) if what == "busy" else time.sleep(0.2)
            (ev,) = tr.events()
            a, wall = ev["args"], ev["dur"] * 1e-6
            assert _sums_to_wall(ev)
            if what == "sleep":
                assert a["off_cpu_s"] >= 0.18 and a["cpu_s"] < 0.05
                break
            assert 0.2 <= a["cpu_s"] <= wall + 1e-3
            if a["cpu_s"] >= 0.8 * wall:
                break
        else:
            pytest.fail(f"a busy loop read {a} of {wall} s wall")
        # a plain span costs no system call: the process-wide readings
        # are the entry category's
        assert not {"proc_cpu_s", "sys_cpu_s", "vol_switches",
                    "invol_switches"} & set(a)
    finally:
        tr.reset()


def test_a_coarse_cpu_clock_cannot_pass_the_wall(monkeypatch):
    """Where the kernel charges CPU time by the tick, a span of half a
    millisecond can be handed 10 ms: ``cpu_s`` stops at the wall."""
    import time
    ticks = iter([1.00, 1.01])
    monkeypatch.setattr(time, "thread_time", lambda: next(ticks))
    tr = _private_tracer()
    try:
        with tr.span("short"):
            pass
        (ev,) = tr.events()
        assert ev["dur"] * 1e-6 < 0.01
        assert ev["args"]["cpu_s"] == pytest.approx(ev["dur"] * 1e-6, abs=2e-6)
        assert ev["args"]["off_cpu_s"] == pytest.approx(0, abs=2e-6)
    finally:
        tr.reset()


def test_span_cpu_is_its_own_threads():
    """A span in a second thread carries that thread's CPU seconds, the
    main thread's span its own, whichever of the two was busy."""
    import threading
    import time
    tr = _private_tracer()

    def worker(busy):
        with tr.span("worker", busy=busy):
            _burn(0.2) if busy else time.sleep(0.25)

    try:
        for worker_busy in (True, False):
            t = threading.Thread(target=worker, args=(worker_busy,))
            with tr.span("main", busy=not worker_busy):
                t.start()
                time.sleep(0.25) if worker_busy else _burn(0.2)
                t.join(timeout=60)
            assert not t.is_alive()
        evs = {(e["name"], e["args"]["busy"]): e for e in tr.events()}
        assert len(evs) == 4 and all(map(_sums_to_wall, evs.values()))
        for name in ("main", "worker"):
            assert evs[name, True]["args"]["cpu_s"] >= 0.2
            assert evs[name, False]["args"]["cpu_s"] < 0.05
            assert evs[name, False]["args"]["off_cpu_s"] >= 0.18
        assert evs["main", True]["tid"] != evs["worker", True]["tid"]
    finally:
        tr.reset()


def test_entry_span_reads_the_process_and_the_switches():
    import threading
    import time
    from gpu_mapreduce_tpu.obs import names
    tr = _private_tracer()
    try:
        t = threading.Thread(target=_burn, args=(0.2,))
        with tr.span("job", cat=names.ENTRY):
            t.start()
            for _ in range(5):
                time.sleep(0.01)        # a voluntary switch each
            t.join(timeout=60)
            with tr.span("child"):
                pass
        child, job = tr.events()
        a = job["args"]
        # every thread's CPU, not only the caller's
        assert a["proc_cpu_s"] >= 0.2 > a["cpu_s"]
        assert a["vol_switches"] >= 5 and a["invol_switches"] >= 0
        # the kernel's share of the calling thread's CPU seconds (the
        # two clocks tick apart: a tick of slack)
        assert 0 <= a["sys_cpu_s"] <= a["cpu_s"] + 0.011
        # "nothing was built under this job" is stated, not left out
        assert (a["jit_lowerings"], a["jit_lower_s"], a["jit_backend_s"],
                a["jit_cache_loads"]) == (0, 0, 0, 0)
        assert "jit_lowerings" not in child["args"]
        assert "proc_cpu_s" not in child["args"]
    finally:
        tr.reset()


def _fresh_programs():
    """Two jitted functions nobody has traced yet, one calling a jitted
    ``jnp`` function."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def obs_probe_plain(x):
        return x * 3 + 1

    @jax.jit
    def obs_probe_nested(x):
        return jnp.sort(x)[::-1] - 2        # jnp.sort is jitted itself

    return obs_probe_plain, obs_probe_nested


def test_jit_seconds_land_on_the_span_and_in_the_program_table(tracer):
    import jax.numpy as jnp
    from gpu_mapreduce_tpu import obs
    plain, nested = _fresh_programs()
    x = jnp.arange(64.0)
    x.block_until_ready()
    tracer.enable()
    tracer.clear()
    with tracer.span("outer"):
        with tracer.span("first"):
            plain(x).block_until_ready()
        with tracer.span("second"):
            plain(x).block_until_ready()
        with tracer.span("nested"):
            nested(x).block_until_ready()
    args = {e["name"]: e["args"] for e in tracer.events()}
    first = args["first"]
    assert first["jit_lowerings"] == 1
    assert first["jit_lower_s"] > 0 and first["jit_backend_s"] > 0
    # a second call is a dispatch: nothing is lowered, nothing loaded
    assert not {k for k in args["second"] if k.startswith("jit_")}
    # the inner jitted function is traced into its caller: one program
    assert args["nested"]["jit_lowerings"] == 1
    # the enclosing span saw both
    assert args["outer"]["jit_lowerings"] == 2
    assert args["outer"]["jit_lower_s"] == pytest.approx(
        first["jit_lower_s"] + args["nested"]["jit_lower_s"], abs=1e-5)
    table = obs.programs()
    for fn, span in ((plain, "first"), (nested, "nested")):
        row = table["jit_" + fn.__name__]
        assert row["lowerings"] == 1
        assert row["lower_s"] == pytest.approx(args[span]["jit_lower_s"],
                                               abs=1e-5)
        assert row["backend_s"] == pytest.approx(args[span]["jit_backend_s"],
                                                 abs=1e-5)
    # mr.stats() is the operator's way to the same table and totals
    stats = MapReduce().stats()
    assert stats["programs"]["jit_obs_probe_plain"]["lowerings"] == 1
    assert stats["jit_lowerings"] >= 2 and stats["jit_backend_s"] > 0


@pytest.fixture
def tmp_compile_cache(tmp_path):
    """JAX's persistent cache in a directory of the test's own, every
    program kept; the process's settings back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path / "jaxcache"))
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], -1)
    compilation_cache.reset_cache()
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_a_load_from_the_persistent_cache_is_counted(tracer,
                                                     tmp_compile_cache):
    import jax
    import jax.numpy as jnp
    from gpu_mapreduce_tpu import obs
    plain, _ = _fresh_programs()
    x = jnp.arange(32.0)
    x.block_until_ready()
    tracer.enable()
    tracer.clear()
    with tracer.span("cold"):
        plain(x).block_until_ready()
    jax.clear_caches()              # the process forgets; the disk does not
    with tracer.span("warm"):
        plain(x).block_until_ready()
    cold, warm = (e["args"] for e in tracer.events())
    assert cold["jit_lowerings"] == 1 and "jit_cache_loads" not in cold
    # traced and lowered again, then served by the cache: what
    # requests − hits reads as 0
    assert warm["jit_lowerings"] == 1 and warm["jit_cache_loads"] == 1
    assert warm["jit_lower_s"] > 0 and warm["jit_backend_s"] > 0
    row = obs.programs()["jit_obs_probe_plain"]
    assert (row["lowerings"], row["cache_loads"]) == (2, 1)


def test_reset_clears_the_program_table_and_leaves_the_listener_inert(
        tracer):
    import jax.numpy as jnp
    from gpu_mapreduce_tpu import obs
    from gpu_mapreduce_tpu.core.runtime import global_counters
    plain, nested = _fresh_programs()
    x = jnp.arange(16.0)
    x.block_until_ready()
    tracer.enable()
    plain(x).block_until_ready()
    assert "jit_obs_probe_plain" in obs.programs()
    tracer.reset()
    assert obs.programs() == {}
    before = global_counters().snapshot()
    nested(x).block_until_ready()           # lowered with every tracer off
    after = global_counters().snapshot()
    assert obs.programs() == {}
    assert [after[k] for k in after if k.startswith("jit_")] == [
        before[k] for k in before if k.startswith("jit_")]


def test_off_means_off(tracer, tmp_path, monkeypatch):
    """With the tracer off a span site is one attribute check: no clock
    of the thread or the process is read, no rusage, and importing the
    package has registered nothing with ``jax.monitoring``."""
    import resource
    import subprocess
    import sys
    import time

    from gpu_mapreduce_tpu.obs import NULL_SPAN, names
    from gpu_mapreduce_tpu.oink.script import OinkScript

    def boom(*_a, **_kw):
        raise AssertionError("read with the tracer off")

    words = tmp_path / "w.txt"
    words.write_text("a b b c c c\n")
    monkeypatch.setattr(time, "thread_time", boom)
    monkeypatch.setattr(time, "process_time", boom)
    monkeypatch.setattr(resource, "getrusage", boom)
    assert tracer.span("x") is NULL_SPAN
    assert tracer.span(names.OINK_SCRIPT, cat=names.ENTRY) is NULL_SPAN
    with tracer.span(names.INVINDEX_RUN, cat=names.ENTRY) as sp:
        sp.set(anything=1)
    interp = OinkScript(screen=False)
    try:
        interp.run_string(f"wordfreq 2 -i {words} -o NULL NULL")
    finally:
        interp.close()
    assert tracer.events() == []
    monkeypatch.undo()

    code = (
        "import os; os.environ.pop('MRTPU_TRACE', None)\n"
        "import jax._src.monitoring as M\n"
        "import gpu_mapreduce_tpu\n"
        "from gpu_mapreduce_tpu.obs import get_tracer, NULL_SPAN\n"
        "tr = get_tracer()\n"
        "assert not tr.enabled and tr.span('x') is NULL_SPAN\n"
        "assert M.get_event_listeners() == []\n"
        "assert M.get_event_duration_listeners() == []\n"
        "tr.enable()\n"
        "assert len(M.get_event_listeners()) == 1\n"
        "assert len(M.get_event_duration_listeners()) == 1\n"
        "tr.reset(); tr.enable()\n"          # registered once a process
        "assert len(M.get_event_duration_listeners()) == 1\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", code], cwd=root,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
