"""serve/ daemon tests — admission control, session isolation, tenant
budgets, warm plan-cache sharing, journaled crash recovery (kill -9
mid-queue replay + in-flight resume), and the obs/httpd request plane
satellites (doc/serve.md)."""

import io
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from gpu_mapreduce_tpu.core.runtime import MRError
from gpu_mapreduce_tpu.serve import (AdmissionQueue, ServeClient,
                                     ServeError, Server, TenantBudgets,
                                     normalize_payload)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write_corpus(path, words, repeat):
    path.write_text((" ".join(words) + " ") * repeat)
    return str(path)


def wf_script(corpus, top=3, out=None, fuse=False):
    lines = [f"variable files index {corpus}"]
    if fuse:
        lines.append("set fuse 1")
    lines.append(f"wordfreq {top} -i v_files" +
                 (f" -o {out} wf" if out else ""))
    return "\n".join(lines) + "\n"


@pytest.fixture
def server(tmp_path):
    """One in-process daemon on an ephemeral port; always shut down."""
    srv = Server(port=0, workers=2, queue_cap=8,
                 state_dir=str(tmp_path / "state"))
    srv.start()
    try:
        yield srv
    finally:
        srv.shutdown()


def client(srv) -> ServeClient:
    return ServeClient.local(srv.port)


# ---------------------------------------------------------------------------
# units
# ---------------------------------------------------------------------------

def test_normalize_payload():
    assert normalize_payload({"script": "mr x\n"}) == "mr x\n"
    assert normalize_payload({"ops": ["mr x", "x delete"]}) == \
        "mr x\nx delete\n"
    for bad in ({}, {"script": ""}, {"ops": []}, {"ops": [1]},
                {"script": "a", "ops": ["b"]}):
        with pytest.raises(MRError):
            normalize_payload(bad)


def test_admission_queue_bounds_and_force():
    q = AdmissionQueue(2)
    assert q.offer("a") and q.offer("b")
    assert not q.offer("c")          # full → reject
    assert q.stats()["rejects"] == 1
    assert q.offer("c", force=True)  # recovery replay path
    assert [q.take(0), q.take(0), q.take(0)] == ["a", "b", "c"]
    assert q.take(0.01) is None
    q.offer("d")
    q.close()
    assert q.take(0) == "d"          # close still drains accepted work
    assert q.take(0) is None
    assert not q.offer("e")          # closed → no new admissions


def test_oink_clear_preserves_namespace_defaults():
    # serve/ sessions carry tenant budget wiring in ObjectManager
    # defaults; a script-level `clear` must not shed it
    from gpu_mapreduce_tpu.oink import OinkScript
    s = OinkScript(screen=False)
    s.obj.set_default("memsize", 7)
    s.one("clear")
    assert s.obj.defaults["memsize"] == 7


# ---------------------------------------------------------------------------
# obs/httpd request-plane satellites
# ---------------------------------------------------------------------------

def test_ensure_server_returns_bound_port():
    from gpu_mapreduce_tpu.obs import httpd
    port = httpd.ensure_server(0)
    assert isinstance(port, int) and port > 0
    # idempotent: a second call reports the SAME bound port
    assert httpd.ensure_server(0) == port
    r = urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                               timeout=5)
    assert r.status == 200


def test_metrics_server_stop_drains_inflight():
    from gpu_mapreduce_tpu.obs.httpd import (MetricsServer,
                                             register_routes,
                                             unregister_routes)
    release = threading.Event()
    entered = threading.Event()

    def slow(method, path, body, headers):
        entered.set()
        release.wait(5)
        return 200, {"ok": True}, "application/json", None

    register_routes("/t-drain/", slow)
    srv = MetricsServer(port=0)
    port = srv.start()
    got = {}

    def fetch():
        r = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/t-drain/x", timeout=10)
        got["status"] = r.status
        got["body"] = r.read()

    t = threading.Thread(target=fetch)
    t.start()
    assert entered.wait(5)
    stopper = threading.Thread(target=srv.stop)
    stopper.start()
    time.sleep(0.1)           # stop() is now waiting on the handler
    release.set()
    stopper.join(10)
    t.join(10)
    unregister_routes("/t-drain/")
    # the in-flight response completed despite the concurrent stop()
    assert got.get("status") == 200 and b"ok" in got.get("body", b"")
    assert not srv.running


# ---------------------------------------------------------------------------
# API round-trip
# ---------------------------------------------------------------------------

def test_submit_roundtrip_script_and_ops(server, tmp_path):
    c = client(server)
    corpus = write_corpus(tmp_path / "w.txt", ["to", "be", "or"], 40)
    r = c.submit(script=wf_script(corpus))
    assert r["state"] == "queued" and r["id"]
    res = c.wait(r["id"])
    assert res["status"] == "done"
    assert "1 files, 120 words, 3 unique" in res["output"]
    # the same workload as a JSON ops batch
    r2 = c.submit(ops=[f"variable files index {corpus}",
                       "wordfreq 3 -i v_files"], tenant="opsy")
    res2 = c.wait(r2["id"])
    assert res2["status"] == "done"
    assert res2["output"] == res["output"]
    # status/list/stats surfaces
    st = c.status(r["id"])
    assert st["state"] == "done" and st["tenant"] == "default"
    assert {j["id"] for j in c.jobs()} >= {r["id"], r2["id"]}
    stats = c.stats()
    assert stats["sessions"]["by_state"]["done"] >= 2
    assert stats["queue"]["cap"] == 8


def test_failed_session_reports_error(server):
    c = client(server)
    r = c.submit(script="frobnicate 1 2\n")
    res = c.wait(r["id"])
    assert res["status"] == "failed"
    assert "Unknown command" in res["error"]
    # a failed session never kills the worker: the next one runs
    r2 = c.submit(ops=["mr x", "x delete"])
    assert c.wait(r2["id"])["status"] == "done"


def test_unknown_session_404(server):
    c = client(server)
    with pytest.raises(ServeError) as ei:
        c.result("s999999")
    assert ei.value.code == 404
    with pytest.raises(ServeError) as ei:
        c.status("s999999")
    assert ei.value.code == 404


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_admission_backpressure_429(tmp_path):
    srv = Server(port=0, workers=0, queue_cap=2,
                 state_dir=str(tmp_path / "state"), paused=True)
    srv.start()
    try:
        c = client(srv)
        ids = [c.submit(ops=["mr x"])["id"] for _ in range(2)]
        assert len(ids) == 2
        with pytest.raises(ServeError) as ei:
            c.submit(ops=["mr x"])
        assert ei.value.code == 429
        assert ei.value.retry_after >= 1
        assert srv.queue.stats()["rejects"] >= 1
        st = c.stats()
        assert st["queue"]["depth"] == 2
    finally:
        srv.shutdown()


def test_drain_rejects_new_work(server, tmp_path):
    c = client(server)
    assert c.drain()["draining"]
    with pytest.raises(ServeError) as ei:
        c.submit(ops=["mr x"])
    assert ei.value.code == 503
    assert ei.value.retry_after is not None


# ---------------------------------------------------------------------------
# session isolation + tenant budgets
# ---------------------------------------------------------------------------

def test_concurrent_sessions_namespace_isolation(server, tmp_path):
    """Two tenants running the SAME script shape (`mr x`, same MR and
    variable names) concurrently: a shared namespace would fail the
    second `mr x` with "already in use" — isolation means both succeed
    with their own data."""
    c = client(server)
    ca = write_corpus(tmp_path / "a.txt", ["alpha", "beta"], 30)
    cb = write_corpus(tmp_path / "b.txt", ["gamma", "delta", "eps"], 20)

    def script(corpus):
        return (f"mr x\n"
                f"variable files index {corpus}\n"
                f"wordfreq 5 -i v_files -o NULL x2\n")

    ra = c.submit(script=script(ca), tenant="a")
    rb = c.submit(script=script(cb), tenant="b")
    res_a = c.wait(ra["id"])
    res_b = c.wait(rb["id"])
    assert res_a["status"] == "done" and res_b["status"] == "done"
    assert "60 words, 2 unique" in res_a["output"]
    assert "60 words, 3 unique" in res_b["output"]
    # per-tenant session metrics carry the right labels
    from gpu_mapreduce_tpu.obs.metrics import get_registry
    snap = get_registry().collect()
    tenants = {s["labels"]["tenant"]
               for s in snap["mrtpu_serve_sessions_total"]["samples"]}
    assert {"a", "b"} <= tenants


def test_tenant_budget_isolation_and_labels(tmp_path):
    """Tenant A outgrows its page budget and SPILLS (through the
    core/ page machinery, into its own session scratch); tenant B's
    resident pages are untouched — B spills nothing, and each tenant's
    pages gauge reads its own account."""
    budgets = TenantBudgets(pages=1, memsize=1)    # 1 MB allowance
    srv = Server(port=0, workers=2, queue_cap=8,
                 state_dir=str(tmp_path / "state"), budgets=budgets)
    srv.start()
    try:
        c = client(srv)
        big = write_corpus(tmp_path / "big.txt",
                           [f"w{i:04d}" for i in range(200)], 2000)
        small = write_corpus(tmp_path / "small.txt", ["tiny", "data"], 10)
        assert os.path.getsize(big) > 2 * (1 << 20)
        ra = c.submit(script=wf_script(big, top=2), tenant="a")
        rb = c.submit(script=wf_script(small, top=2), tenant="b")
        res_a = c.wait(ra["id"], timeout=240)
        res_b = c.wait(rb["id"])
        assert res_a["status"] == "done" and res_b["status"] == "done"
        pages_a = res_a["meta"]["pages"]
        pages_b = res_b["meta"]["pages"]
        assert pages_a["tenant"] == "a" and pages_b["tenant"] == "b"
        # A paid spill I/O for its overage; B never did
        assert pages_a["spilled_bytes"] > 0
        assert pages_b["spilled_bytes"] == 0
        # per-tenant gauge labels, independent accounts
        from gpu_mapreduce_tpu.obs.metrics import get_registry
        snap = get_registry().collect()
        by_tenant = {s["labels"]["tenant"]: s["value"]
                     for s in snap["mrtpu_tenant_pages"]["samples"]}
        assert {"a", "b"} <= set(by_tenant)
        # the server-side stats surface sees both accounts too
        st = c.stats()["tenants"]
        assert st["a"]["spilled_bytes"] > 0
        assert st["b"]["spilled_bytes"] == 0
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# warm cross-session plan cache
# ---------------------------------------------------------------------------

def test_repeated_request_hits_shared_plan_cache(server, tmp_path):
    """The acceptance assertion: an identical second request compiles
    NOTHING — the fleet-wide plan cache (PR 2's LRU) serves it, and the
    dispatch count matches the first run."""
    c = client(server)
    corpus = write_corpus(tmp_path / "w.txt",
                          ["to", "be", "or", "not"], 50)
    script = wf_script(corpus, fuse=True)
    cold = c.wait(c.submit(script=script)["id"])
    warm = c.wait(c.submit(script=script)["id"])
    assert cold["status"] == "done" and warm["status"] == "done"
    assert warm["output"] == cold["output"]
    pc_cold = cold["meta"]["plan_cache"]["plan"]
    pc_warm = warm["meta"]["plan_cache"]["plan"]
    assert pc_cold["misses"] > 0            # cold run built the plans
    assert pc_warm["misses"] == 0           # warm run recompiled nothing
    assert pc_warm["hits"] >= pc_cold["misses"]
    assert warm["meta"]["dispatches"] == cold["meta"]["dispatches"]


# ---------------------------------------------------------------------------
# journaled crash recovery
# ---------------------------------------------------------------------------

def _spawn_daemon(state, extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.Popen(
        [sys.executable, "-m", "gpu_mapreduce_tpu.serve",
         "--port", "0", "--state", state] + extra,
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL)
    line = json.loads(p.stdout.readline())
    return p, int(line["serving"])


def test_kill9_mid_queue_replay_byte_identical(tmp_path):
    """The acceptance golden: kill -9 a daemon with a populated queue;
    the restarted daemon replays the journal and produces results
    byte-identical to an uninterrupted daemon's."""
    corpora = [write_corpus(tmp_path / f"c{i}.txt",
                            [f"w{j}" for j in range(i + 2)], 30 + i)
               for i in range(3)]
    scripts = [wf_script(c, top=5, out=f"tmp.wf{i}")
               for i, c in enumerate(corpora)]

    # golden: an uninterrupted in-process daemon
    gold_srv = Server(port=0, workers=1,
                      state_dir=str(tmp_path / "golden"))
    gold_srv.start()
    try:
        gc = client(gold_srv)
        golden = [gc.wait(gc.submit(script=s)["id"]) for s in scripts]
    finally:
        gold_srv.shutdown()
    assert all(g["status"] == "done" for g in golden)

    # phase 1: paused daemon journals the queue, then SIGKILL
    state = str(tmp_path / "state")
    p, port = _spawn_daemon(state, ["--paused"])
    try:
        c = ServeClient.local(port)
        sids = [c.submit(script=s)["id"] for s in scripts]
        assert c.stats()["queue"]["depth"] == 3
    finally:
        os.kill(p.pid, signal.SIGKILL)
        p.wait()

    # phase 2: restart live; the queue replays in admission order
    p2, port2 = _spawn_daemon(state, ["--workers", "2"])
    try:
        c2 = ServeClient.local(port2)
        replayed = [c2.wait(sid, timeout=120) for sid in sids]
        for got, want in zip(replayed, golden):
            assert got["status"] == "done"
            assert got["output"] == want["output"]
            assert {k: v["sha256"] for k, v in got["files"].items()} == \
                {k: v["sha256"] for k, v in want["files"].items()}
        c2.shutdown()
        p2.wait(timeout=30)
    finally:
        if p2.poll() is None:
            p2.kill()
            p2.wait()


def test_inflight_session_resumes_from_checkpoint(tmp_path):
    """A session that died MID-RUN (journal holds begin+cmd+ckpt in its
    session dir) resumes from the checkpoint on the replayed attempt:
    the already-checkpointed command is skipped, output files come out
    byte-identical, and the result is flagged ``resumed``."""
    from gpu_mapreduce_tpu.ft.journal import Journal
    from gpu_mapreduce_tpu.oink.script import OinkScript

    corpus = write_corpus(tmp_path / "w.txt", ["p", "q", "p", "r"], 25)
    script_text = (f"variable files index {corpus}\n"
                   f"wordfreq 3 -i v_files -o tmp.wf wf\n"
                   f"print \"after-ckpt marker\"\n")

    # golden full run
    gold = Server(port=0, workers=1, state_dir=str(tmp_path / "golden"))
    gold.start()
    try:
        gc = client(gold)
        golden = gc.wait(gc.submit(script=script_text)["id"])
    finally:
        gold.shutdown()

    # manufacture the crashed in-flight session: journal + checkpoint
    # exactly as run_session would have left them mid-run
    state = str(tmp_path / "state")
    sdir = os.path.join(state, "sessions", "s000001")
    outdir = os.path.join(sdir, "out")
    os.makedirs(outdir, exist_ok=True)
    crash = OinkScript(screen=io.StringIO())
    crash._ft_journal = Journal(sdir, script_mode=True, every=1)
    crash._path_prepend = outdir
    lines = script_text.splitlines()
    crash._ft_pending_begin = (lines, "<serve>")
    for ln in lines[:2]:          # dies before the print command
        crash.one(ln)
    crash._ft_journal.close()

    boot = Server(port=0, workers=0, state_dir=state, paused=True)
    boot.start()
    try:
        assert client(boot).submit(script=script_text)["id"] == "s000001"
    finally:
        boot.shutdown()

    srv = Server(port=0, workers=1, state_dir=state)
    srv.start()
    try:
        res = client(srv).wait("s000001")
    finally:
        srv.shutdown()
    assert res["status"] == "done"
    assert res["meta"]["resumed"] is True
    # the checkpointed wordfreq was NOT re-executed: only the
    # post-checkpoint command's output replays...
    assert res["output"] == 'after-ckpt marker \n'
    # ...but the session's FILES are byte-identical to the golden run
    assert {k: v["sha256"] for k, v in res["files"].items()} == \
        {k: v["sha256"] for k, v in golden["files"].items()}


def test_clear_inside_script_reports_live_namespace(server, tmp_path):
    """`clear` swaps the interpreter's ObjectManager; the session must
    report (and account-scope-release) the LIVE namespace, not the one
    captured before the run (regression: post-clear MRs were invisible
    and their frames never deflated the tenant gauge)."""
    c = client(server)
    corpus = write_corpus(tmp_path / "w.txt", ["post", "clear"], 10)
    res = c.wait(c.submit(script=(
        f"mr pre\n"
        f"clear\n"
        f"variable files index {corpus}\n"
        f"wordfreq 2 -i v_files -o NULL after\n"))["id"])
    assert res["status"] == "done", res["error"]
    assert "after" in res["mrs"] and "pre" not in res["mrs"]


def test_budget_settings_are_pinned_against_tenant_set(tmp_path):
    """An armed tenant budget must survive the script's own `set`: a
    tenant raising maxpage past its allowance fails loudly instead of
    running unbounded (regression: `set` silently overrode the
    daemon-seeded budget defaults)."""
    budgets = TenantBudgets(pages=1, memsize=1)
    srv = Server(port=0, workers=1, queue_cap=4,
                 state_dir=str(tmp_path / "state"), budgets=budgets)
    srv.start()
    try:
        c = client(srv)
        res = c.wait(c.submit(script="set maxpage 100000\nmr x\n",
                              tenant="evil")["id"])
        assert res["status"] == "failed"
        assert "pinned" in res["error"]
        # pins survive a script-level clear too
        res2 = c.wait(c.submit(script="clear\nset memsize 4096\n",
                               tenant="evil")["id"])
        assert res2["status"] == "failed" and "pinned" in res2["error"]
    finally:
        srv.shutdown()


def test_journal_survives_torn_tail_across_restarts(tmp_path):
    """A kill -9 mid-append leaves a torn final journal line; the
    reopened journal must seal it (no merge with the next record) and
    the reader must skip it (no silent drop of later records)."""
    from gpu_mapreduce_tpu.ft.journal import Journal, read_journal
    d = str(tmp_path / "j")
    j = Journal(d, script_mode=True)
    j.append({"kind": "serve_submit", "sid": "s1"})
    j.close()
    with open(j.path, "a") as f:
        f.write('{"kind": "serve_sub')      # torn mid-append, no \n
    j2 = Journal(d, script_mode=True)       # reopen = restart
    j2.append({"kind": "serve_submit", "sid": "s2"})
    j2.close()
    kinds = [(r.get("kind"), r.get("sid")) for r in read_journal(d)]
    assert ("serve_submit", "s1") in kinds
    assert ("serve_submit", "s2") in kinds  # not merged into the tear


def test_set_prepend_stays_inside_session_dir(server, tmp_path):
    """The reference `set prepend` idiom keeps working in a session but
    re-roots UNDER the session's out dir; an absolute prepend (which
    would silently move -o files out of the sandbox and off the result)
    fails the session loudly."""
    c = client(server)
    corpus = write_corpus(tmp_path / "w.txt", ["pre", "pend"], 10)
    res = c.wait(c.submit(script=(
        f"set prepend sub\n"
        f"variable files index {corpus}\n"
        f"wordfreq 2 -i v_files -o nested.wf wf\n"))["id"])
    assert res["status"] == "done", res["error"]
    assert "sub/nested.wf" in res["files"]         # re-rooted, captured
    res2 = c.wait(c.submit(script="set prepend /tmp\nmr x\n")["id"])
    assert res2["status"] == "failed" and "pinned" in res2["error"]


def test_env_journal_does_not_break_sessions(tmp_path, monkeypatch):
    """MRTPU_JOURNAL in the daemon's environment arms a process-global
    script journal on every OinkScript — sessions must deactivate it
    (not just close it) or their first barrier op writes to a closed
    file and every job fails (regression: confirmed live repro)."""
    from gpu_mapreduce_tpu.ft import journal as ftj
    monkeypatch.setenv("MRTPU_JOURNAL", str(tmp_path / "globaljournal"))
    srv = Server(port=0, workers=1, state_dir=str(tmp_path / "state"))
    srv.start()
    try:
        c = client(srv)
        corpus = write_corpus(tmp_path / "w.txt", ["env", "j"], 20)
        res = c.wait(c.submit(script=wf_script(corpus, top=2))["id"])
        assert res["status"] == "done", res["error"]
        # and the session journaled into its OWN directory regardless
        assert os.path.exists(os.path.join(
            srv.session_dir(res["id"]), "journal.jsonl"))
    finally:
        srv.shutdown()
        ftj.reset()


# ---------------------------------------------------------------------------
# mrctl
# ---------------------------------------------------------------------------

def test_mrctl_cli(server, tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import mrctl
    finally:
        sys.path.pop(0)
    corpus = write_corpus(tmp_path / "w.txt", ["cli", "test"], 15)
    script = tmp_path / "job.oink"
    script.write_text(wf_script(corpus, top=2))
    rc = mrctl.main(["--port", str(server.port), "submit", str(script),
                     "--tenant", "ops", "--wait"])
    out = capsys.readouterr().out
    assert rc == 0
    rec = json.loads(out)
    assert rec["status"] == "done" and "30 words, 2 unique" in \
        rec["output"]
    assert mrctl.main(["--port", str(server.port), "stats"]) == 0
    stats = json.loads(capsys.readouterr().out)
    assert stats["sessions"]["by_state"]["done"] >= 1
    # state-dir discovery path (ephemeral daemon, serve.json)
    rc = mrctl.main(["--state", server.state_dir, "status"])
    assert rc == 0


# ---------------------------------------------------------------------------
# elastic-recovery satellites: quotas, priority, TTL GC, degraded mode
# ---------------------------------------------------------------------------

def test_admission_queue_priority_order():
    q = AdmissionQueue(8)
    q.offer("low1", priority=0)
    q.offer("hi", priority=5)
    q.offer("low2", priority=0)
    q.offer("mid", priority=2)
    assert [q.take(0) for _ in range(4)] == ["hi", "mid", "low1", "low2"]


def test_tenant_rate_limiter_isolated_buckets():
    from gpu_mapreduce_tpu.serve.admission import TenantRateLimiter
    rl = TenantRateLimiter(rate=1.0, burst=2)
    now = 1000.0
    assert rl.check("a", now)[0] and rl.check("a", now)[0]
    ok, ra = rl.check("a", now)          # bucket drained
    assert not ok and 0 < ra <= 1.0
    assert rl.check("b", now)[0], "tenant b must not share a's bucket"
    ok, _ = rl.check("a", now + 1.0)     # one token refilled
    assert ok
    assert TenantRateLimiter(rate=0.0).check("x")[0]   # 0 = off


def test_rate_limited_submit_429_per_tenant(tmp_path):
    """A tenant past its rate gets 429 + its OWN Retry-After; other
    tenants are untouched; decisions land in the per-tenant metric."""
    from gpu_mapreduce_tpu.serve.admission import TenantRateLimiter
    srv = Server(port=0, workers=0, paused=True,
                 state_dir=str(tmp_path / "state"))
    srv.ratelimit = TenantRateLimiter(rate=0.001, burst=1)
    srv.start()
    try:
        c = client(srv)
        assert c.submit(script="mr x\n", tenant="noisy")["id"]
        with pytest.raises(ServeError) as ei:
            c.submit(script="mr x\n", tenant="noisy")
        assert ei.value.code == 429
        assert ei.value.retry_after >= 1
        # a different tenant is admitted right through
        assert c.submit(script="mr x\n", tenant="quiet")["id"]
        from gpu_mapreduce_tpu.obs.metrics import get_registry
        m = get_registry().counter("mrtpu_serve_admission_total", "",
                                   ("outcome", "tenant"))
        assert m.value(outcome="throttled", tenant="noisy") >= 1
        assert m.value(outcome="accepted", tenant="quiet") >= 1
    finally:
        srv.shutdown()


def test_submit_priority_recorded_and_replayed(tmp_path):
    """Priority rides the journal: a paused daemon's replayed queue
    drains high-priority sessions first on restart."""
    state = str(tmp_path / "state")
    srv = Server(port=0, workers=0, paused=True, state_dir=state)
    srv.start()
    try:
        c = client(srv)
        lo = c.submit(script="mr x\n", priority=0)["id"]
        hi = c.submit(script="mr x\n", priority=7)["id"]
        assert c.status(hi)["priority"] == 7
    finally:
        srv.shutdown()
    srv2 = Server(port=0, workers=0, paused=True, state_dir=state)
    srv2.start()
    try:
        first = srv2.queue.take(0)
        assert first.sid == hi and first.priority == 7
        assert srv2.queue.take(0).sid == lo
    finally:
        srv2.shutdown()


def test_session_ttl_gc_journaled(tmp_path):
    """Done sessions past MRTPU_SERVE_TTL are swept — journaled intent
    first, dirs+result removed, dropped from the listing — and a
    restart neither lists nor replays them (the GC'd sid is terminal)."""
    state = str(tmp_path / "state")
    srv = Server(port=0, workers=1, state_dir=state)
    srv.ttl_s = 0.05
    srv.start()
    try:
        c = client(srv)
        sid = c.submit(script="mr x\n")["id"]
        assert c.wait(sid)["status"] == "done"
        sdir = srv.session_dir(sid)
        assert os.path.isdir(sdir)
        time.sleep(0.08)
        assert srv._gc_once() == 1
        assert not os.path.exists(sdir)
        assert not os.path.exists(srv.result_path(sid))
        with pytest.raises(ServeError) as ei:
            c.status(sid)
        assert ei.value.code == 404
        from gpu_mapreduce_tpu.ft.journal import read_journal
        kinds = [r["kind"] for r in read_journal(state)]
        assert "serve_gc" in kinds
    finally:
        srv.shutdown()
    # a live (queued/running) session is never GC'd and a restart
    # neither lists nor replays the swept one
    srv2 = Server(port=0, workers=0, paused=True, state_dir=state)
    srv2.start()
    try:
        assert sid not in srv2.sessions
        assert srv2.queue.depth() == 0
    finally:
        srv2.shutdown()


def test_gc_kill_mid_delete_finishes_on_restart(tmp_path):
    """kill -9 between the serve_gc intent record and the delete: the
    restart finishes the sweep instead of resurrecting the session."""
    state = str(tmp_path / "state")
    srv = Server(port=0, workers=1, state_dir=state)
    srv.start()
    try:
        c = client(srv)
        sid = c.submit(script="mr x\n")["id"]
        assert c.wait(sid)["status"] == "done"
        # intent journaled, then "killed" before _gc_files ran
        srv._journal.append({"kind": "serve_gc", "sid": sid,
                             "tenant": "default"})
    finally:
        srv.shutdown()
    assert os.path.isdir(os.path.join(state, "sessions", sid))
    srv2 = Server(port=0, workers=0, paused=True, state_dir=state)
    srv2.start()
    try:
        assert sid not in srv2.sessions
        assert not os.path.exists(os.path.join(state, "sessions", sid))
    finally:
        srv2.shutdown()


def test_degraded_restart_resumes_on_available_mesh(tmp_path):
    """Tentpole (4): a session checkpointed on a 4-shard mesh resumes
    on a daemon restarted with only 2 shards — the recovered tail's
    files are byte-identical to an uninterrupted 2-shard daemon's run,
    and the result carries ``meta.resharded``."""
    from gpu_mapreduce_tpu.ft.journal import Journal
    from gpu_mapreduce_tpu.oink.script import OinkScript
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh

    corpus = write_corpus(tmp_path / "w.txt", ["p", "q", "p", "r"], 25)
    script_text = (f"variable files index {corpus}\n"
                   f"wordfreq 3 -i v_files -o NULL wf\n"
                   f"wordfreq 2 -i v_files -o tmp.out NULL\n")

    gold = Server(port=0, workers=1, comm=make_mesh(2),
                  state_dir=str(tmp_path / "golden"))
    gold.start()
    try:
        gc = client(gold)
        golden = gc.wait(gc.submit(script=script_text)["id"])
    finally:
        gold.shutdown()
    assert golden["status"] == "done"

    # manufacture the crashed 4-shard in-flight session (checkpoint
    # after the first wordfreq, death before the output-writing one)
    state = str(tmp_path / "state")
    sdir = os.path.join(state, "sessions", "s000001")
    outdir = os.path.join(sdir, "out")
    os.makedirs(outdir, exist_ok=True)
    crash = OinkScript(comm=make_mesh(4), screen=io.StringIO())
    crash._ft_journal = Journal(sdir, script_mode=True, every=1)
    crash._path_prepend = outdir
    lines = script_text.splitlines()
    crash._ft_pending_begin = (lines, "<serve>")
    for ln in lines[:2]:
        crash.one(ln)
    crash._ft_journal.close()

    boot = Server(port=0, workers=0, state_dir=state, paused=True)
    boot.start()
    try:
        assert client(boot).submit(script=script_text)["id"] == "s000001"
    finally:
        boot.shutdown()

    srv = Server(port=0, workers=1, comm=make_mesh(2), state_dir=state)
    srv.start()
    try:
        assert srv.stats()["mesh"]["nprocs"] == 2
        res = client(srv).wait("s000001")
    finally:
        srv.shutdown()
    assert res["status"] == "done"
    assert res["meta"]["resumed"] is True
    assert res["meta"]["resharded"] is True
    assert {k: v["sha256"] for k, v in res["files"].items()} == \
        {k: v["sha256"] for k, v in golden["files"].items()}


# ---------------------------------------------------------------------------
# request-scoped tracing + exact per-request attribution (ISSUE 9)
# ---------------------------------------------------------------------------

def test_concurrent_sessions_meta_deltas_exact(tmp_path):
    """THE regression for the retired exact-only-when-idle caveat: two
    sessions run CONCURRENTLY (workers=2) — a spill-heavy one and a
    light one — and each result's meta/profile shows exactly its own
    traffic.  Before the RequestAccount scope, the light session's
    deltas bracketed process-global counters and inhaled its
    neighbor's spill bytes."""
    budgets = TenantBudgets(pages=1, memsize=1)    # force A to spill
    srv = Server(port=0, workers=2, queue_cap=8,
                 state_dir=str(tmp_path / "state"), budgets=budgets)
    srv.start()
    try:
        c = client(srv)
        big = write_corpus(tmp_path / "big.txt",
                           [f"w{i:04d}" for i in range(200)], 2000)
        small = write_corpus(tmp_path / "small.txt", ["tiny", "data"],
                             10)
        ra = c.submit(script=wf_script(big, top=2), tenant="heavy")
        rb = c.submit(script=wf_script(small, top=2), tenant="light")
        res_a = c.wait(ra["id"], timeout=240)
        res_b = c.wait(rb["id"], timeout=240)
        assert res_a["status"] == "done" and res_b["status"] == "done"
        prof_a = res_a["meta"]["profile"]
        prof_b = res_b["meta"]["profile"]
        # distinct request identities, stamped everywhere
        assert res_a["meta"]["trace_id"] != res_b["meta"]["trace_id"]
        assert prof_a["trace_id"] == res_a["meta"]["trace_id"]
        # A really spilled; B's account saw NONE of it, even though
        # both ran on one process's shared global counters
        assert prof_a["spill"]["write_bytes"] > 0
        assert prof_b["spill"]["write_bytes"] == 0
        assert prof_b["spill"]["read_bytes"] == 0
        # stage tables are per-request too
        assert "oink.wordfreq" in prof_a["stages"]
        assert "oink.wordfreq" in prof_b["stages"]
    finally:
        srv.shutdown()


@pytest.fixture
def process_tracer():
    """The process tracer, reset when the test is done: left on, every
    later test of this worker pays for spans and for the jax.monitoring
    listener that feeds them (ROADMAP C15)."""
    from gpu_mapreduce_tpu.obs import get_tracer
    yield get_tracer()
    get_tracer().reset()


def test_session_trace_id_links_every_artifact(server, tmp_path,
                                               process_tracer):
    """One request, one id: the 202, result meta, /profile, the
    session journal records, and the session's spans on any trace sink
    (the serve-worker half of the propagation goldens)."""
    import gpu_mapreduce_tpu.obs as obs
    from gpu_mapreduce_tpu.ft.journal import read_journal
    trace_path = str(tmp_path / "serve_trace.jsonl")
    process_tracer.enable(jsonl=trace_path)
    c = client(server)
    corpus = write_corpus(tmp_path / "w.txt", ["to", "be", "or"], 40)
    r = c.submit(script=wf_script(corpus), tenant="acme")
    tid = r["trace_id"]
    assert tid
    res = c.wait(r["id"])
    assert res["status"] == "done"
    assert res["meta"]["trace_id"] == tid
    assert res["meta"]["profile"]["trace_id"] == tid
    assert c.status(r["id"])["trace_id"] == tid
    # /profile serves the same id (durable once finished)
    prof = c.profile(r["id"])
    assert prof["trace_id"] == tid and prof["live"] is False
    assert prof["profile"]["stages"].get("oink.wordfreq")
    # session journal records are stamped
    recs = read_journal(os.path.join(server.state_dir, "sessions",
                                     r["id"]))
    assert recs and all(rec.get("trace") == tid for rec in recs)
    # the worker's spans carry it on the shared JSONL sink
    mine = [e for e in obs.read_jsonl(trace_path)
            if e.get("trace") == tid]
    assert any(e["name"] == "oink.wordfreq" for e in mine)
    # the serve journal's submit record carries it (replay keeps ids)
    srecs = read_journal(server.state_dir)
    sub = [x for x in srecs if x.get("kind") == "serve_submit"
           and x.get("sid") == r["id"]]
    assert sub and sub[0]["trace"] == tid


def test_events_stream_live_no_polling(server, tmp_path):
    """/v1/jobs/<id>/events: ONE streamed request observes the running
    transition, at least one top-level span, the final profile, and
    the terminal status — no client polling."""
    c = client(server)
    blocker = write_corpus(tmp_path / "blk.txt",
                           [f"w{i:03d}" for i in range(100)], 1500)
    corpus = write_corpus(tmp_path / "w.txt", ["to", "be", "or"], 40)
    # saturate both workers so the watched session stays queued until
    # the stream is attached
    rb1 = c.submit(script=wf_script(blocker, top=2))
    rb2 = c.submit(script=wf_script(blocker, top=2))
    r = c.submit(script=wf_script(corpus))
    seen = list(c.events(r["id"], timeout=120))
    kinds = [e["event"] for e in seen]
    states = [e.get("state") for e in seen if e["event"] == "status"]
    assert states[0] in ("queued", "running", "done")
    assert states[-1] == "done"                    # stream ends terminal
    if states[0] == "queued":                      # attached in time:
        assert "running" in states                # saw the transition
    assert any(e["event"] == "profile" for e in seen)
    prof = [e for e in seen if e["event"] == "profile"][-1]["profile"]
    assert prof["trace_id"] == r["trace_id"]
    c.wait(rb1["id"], timeout=240)
    c.wait(rb2["id"], timeout=240)
    # a finished session's stream replays profile THEN the terminal
    # status (the live ordering: a client stopping at the terminal
    # marker has already seen the profile) and ends
    replay = list(c.events(r["id"], timeout=60))
    assert [e["event"] for e in replay] == ["profile", "status"]
    assert replay[-1]["state"] == "done"
    # unknown session: a clean 404, not a stream
    with pytest.raises(ServeError) as ei:
        list(c.events("nope"))
    assert ei.value.code == 404


def test_slo_endpoint_and_burn(server, monkeypatch):
    import gpu_mapreduce_tpu.obs.slo as obs_slo
    monkeypatch.setenv("MRTPU_SLO",
                       "tenant=*;p99_ms=60000;err_pct=1;windows=60,600")
    obs_slo.reset()                      # re-read the env spec
    try:
        c = client(server)
        # three failing sessions for a fresh tenant → err burn >> 1
        for _ in range(3):
            r = c.submit(script="frobnicate\n", tenant="slo-t")
            assert c.wait(r["id"])["status"] == "failed"
        out = c.slo()
        assert out["objectives"], out
        assert out["burn"]["slo-t"]["60s"] > 1.0
        assert "slo-t" in out["firing"]
        # the burn gauge landed in the registry
        from gpu_mapreduce_tpu.obs.metrics import get_registry
        samples = get_registry().collect()[
            "mrtpu_slo_burn_ratio"]["samples"]
        assert any(s["labels"]["tenant"] == "slo-t" for s in samples)
    finally:
        obs_slo.reset()


def test_mrctl_profile_watch_slo(server, tmp_path, capsys):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "mrctl", os.path.join(REPO, "scripts", "mrctl.py"))
    mrctl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mrctl)
    c = client(server)
    corpus = write_corpus(tmp_path / "w.txt", ["to", "be", "or"], 40)
    r = c.submit(script=wf_script(corpus))
    c.wait(r["id"])
    port = ["--port", str(server.port)]
    assert mrctl.main(port + ["profile", r["id"]]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["trace_id"] == r["trace_id"]
    assert out["profile"]["dispatches"] >= 0
    # watch on a finished session: prints the profile and the terminal
    # status (in that order — the stop-at-terminal client still gets
    # the profile), exit 0
    assert mrctl.main(port + ["watch", r["id"]]) == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert [ln["event"] for ln in lines] == ["profile", "status"]
    assert lines[-1]["state"] == "done"
    # slo subcommand round-trips
    assert mrctl.main(port + ["slo"]) == 0
    json.loads(capsys.readouterr().out)
    # failed session → watch exits 5
    rf = c.submit(script="frobnicate\n")
    c.wait(rf["id"])
    assert mrctl.main(port + ["watch", rf["id"]]) == 5
    capsys.readouterr()
