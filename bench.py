"""Driver benchmark: InvertedIndex KV-pairs/sec on one chip.

Workload: the reference's flagship CUDA app (``cuda/InvertedIndex.cu``) —
scan HTML for ``<a href="`` URLs, emit (url, doc) pairs, shuffle, group,
count.  Corpus is synthetic deterministic HTML (~1 URL per KB, the
PUMA-style density).

Baseline: the reference's own in-code MAP-STAGE timings per 64 MB chunk on
its GPU — mark 4 ms + copy_if 14 ms + compute_url_length 8 ms + host
kv->add 18 ms = 44 ms (``cuda/InvertedIndex.cu:337,360,369,384``), i.e.
1.45 GB/s.  ``vs_baseline`` compares our map stage over the same boundary:
kernels + KV construction on device-resident data (their fread and
cudaMemcpy H2D sit outside the 44 ms; our file read and H2D likewise sit
outside the timed map stage and are reported in the detail record).

Round-2 design note: the map stage is ONE fused XLA dispatch over the
whole corpus (see apps/invertedindex.py) — mark kernel, compaction, URL
windows, u64 interning, doc ids, packing.  End-to-end wall time (also in
the detail record) includes H2D and the grouped count running on device.

Output: ONE JSON line {"metric", "value", "unit", "vs_baseline",
"backend", "engine"} on stdout; per-stage timings go to stderr as a second
JSON line.  The bench runs the engine it was asked for (``BENCH_ENGINE``,
default ``pallas``) on the device JAX finds.  A machine without a TPU, or
an engine that fails, is an error: the process exits non-zero and prints
no metric line.
"""

import json
import os
import subprocess
import sys
import tempfile
import time
import traceback

BASELINE_BYTES_PER_SEC = (64 << 20) / 0.044  # reference 64MB/44ms map stage
METRIC = "invertedindex_kv_pairs_per_sec_per_chip"
CORPUS_CACHE_VERSION = "1"   # bump on generator-affecting edits outside
                             # make_corpus's own source (ADVICE r4)


def host_id() -> str:
    """Coarse host fingerprint recorded into the bench detail.  Wall
    numbers are only comparable same-host: the bench_compare gate
    refuses to compare records whose hosts differ (a fresh run on a
    slower container must read as 'no baseline', not 'regression')."""
    import platform
    return f"{platform.node()}:{os.cpu_count()}cpu"


def tb_tail(tb_text: str, n: int) -> str:
    """Last n informative lines of a formatted traceback.  jax appends a
    traceback-filtering epilogue ('JAX has removed its internal frames
    ...'), so a naive tail records only the banner and loses the
    exception — exactly what happened to the round-4 pallas note."""
    lines = [ln for ln in tb_text.strip().splitlines()
             if "internal frames" not in ln
             and "JAX_TRACEBACK_FILTERING" not in ln
             and not ln.startswith("-----")]
    return " | ".join(lines[-n:])


def emit(value, vs_baseline, **extra):
    """The ONE stdout metric line of a completed run."""
    line = {"metric": METRIC, "value": value, "unit": "pairs/sec",
            "vs_baseline": vs_baseline,
            "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    line.update(extra)
    print(json.dumps(line))
    sys.stdout.flush()


def make_corpus(tmpdir: str, total_mb: int, nfiles: int = 4,
                skew: bool = False, dense: bool = False):
    """Deterministic synthetic HTML: filler with a URL every ~1KB.

    ``skew`` (BENCH_SKEW=1, VERDICT r2 #9): ~25% of references hit a
    64-URL hot set (RMAT-hub-style shuffle skew) and ~2% are 120–200
    byte long-tail URLs (drives the two-tier window's second gather).

    ``dense`` (BENCH_DENSE=1, VERDICT r3 #4): ~4 refs/KB — past the
    device tier's 1-href/KB capacity heuristic, so the extract MUST
    take a cap retry — and ~60% long URLs — past the cap/4 wide-window
    threshold, so the whole-corpus wide fallback MUST engage; records
    those two paths executing outside pytest.
    Returns (paths, total refs, unique urls)."""
    per_file = (total_mb << 20) // nfiles
    filler = b"<p>" + b"lorem ipsum dolor sit amet " * 36 + b"</p>\n"  # ~1KB
    if dense:
        filler = filler[:220]  # ~4 refs/KB: above the 1/KB cap heuristic
    hot = [b"http://example.org/hot/%02d" % i for i in range(64)]
    paths = []
    uid = 0
    nref = 0
    uniq = set()
    for i in range(nfiles):
        pieces = []
        size = 0
        while size < per_file:
            if dense and nref % 5 < 3:     # ~60% long: force wide windows
                u = (b"http://example.org/long/"
                     + b"p%08d/" % uid + b"x" * (96 + uid % 80))
                uid += 1
            elif skew and nref % 50 == 49:  # checked first: ~2% long tail
                u = (b"http://example.org/long/"
                     + b"p%08d/" % uid + b"x" * (96 + uid % 80))
                uid += 1
            elif skew and nref % 4 == 3:
                u = hot[(nref // 4) % len(hot)]
            else:
                u = b"http://example.org/wiki/page-%08d" % uid
                uid += 1
            url = b'<a href="' + u + b'">x</a>'
            uniq.add(u)
            nref += 1
            pieces.append(filler)
            pieces.append(url)
            size += len(filler) + len(url)
        path = os.path.join(tmpdir, f"part-{i:05d}.html")
        with open(path, "wb") as f:
            f.write(b"".join(pieces))
        paths.append(path)
    return paths, nref, len(uniq)


def corpus_cached(total_mb: int, skew: bool, dense: bool, nfiles: int = 4):
    """Reuse the deterministic corpus across bench invocations (~1 min
    of synthesis per 256 MB otherwise).

    Correctness properties: the key includes a hash of make_corpus's
    source (generator edits invalidate, and a prune of same-shape stale-
    hash siblings bounds /tmp growth); population is ATOMIC — generated
    into a per-pid sibling dir and os.rename()d into place, so two
    racing processes never interleave writes (the loser serves its own
    files); BENCH_CORPUS_CACHE=0 bypasses the cache via a self-cleaning
    tempdir."""
    import hashlib
    import inspect
    import shutil
    if os.environ.get("BENCH_CORPUS_CACHE", "1") != "1":
        import atexit
        d = tempfile.mkdtemp(prefix="bench_corpus_nocache_")
        atexit.register(shutil.rmtree, d, True)
        return make_corpus(d, total_mb, nfiles, skew, dense)
    # CACHE_VERSION covers generator-affecting edits OUTSIDE make_corpus's
    # own source (module constants, helpers) that the source hash cannot
    # see (ADVICE r4) — bump it whenever such an edit changes the corpus
    src = (CORPUS_CACHE_VERSION.encode() + b"\n"
           + inspect.getsource(make_corpus).encode())
    prefix = f"{total_mb}_{int(skew)}_{int(dense)}_{nfiles}_"
    key = prefix + hashlib.md5(src).hexdigest()[:8]
    base = os.environ.get("BENCH_CORPUS_CACHE_DIR",
                          "/tmp/bench_corpus_cache")
    d = os.path.join(base, key)
    meta = os.path.join(d, "meta.json")
    try:
        with open(meta) as f:
            m = json.load(f)
        paths = [os.path.join(d, p) for p in m["files"]]
        if all(os.path.isfile(p) for p in paths):
            return paths, m["nref"], m["nuniq"]
    except (FileNotFoundError, ValueError, KeyError):
        pass
    os.makedirs(base, exist_ok=True)
    for e in os.listdir(base):      # stale-hash siblings of this shape
        if e.startswith(prefix) and e != key and ".tmp" not in e:
            shutil.rmtree(os.path.join(base, e), ignore_errors=True)
    tmpd = f"{d}.tmp{os.getpid()}"
    shutil.rmtree(tmpd, ignore_errors=True)
    os.makedirs(tmpd)
    paths, nref, nuniq = make_corpus(tmpd, total_mb, nfiles, skew, dense)
    with open(os.path.join(tmpd, "meta.json"), "w") as f:
        json.dump({"files": [os.path.basename(p) for p in paths],
                   "nref": nref, "nuniq": nuniq}, f)
    try:
        os.rename(tmpd, d)
    except OSError:
        # lost a populate race: serve our own copy for this process's
        # lifetime, but don't leak it forever (ADVICE r4)
        import atexit
        atexit.register(shutil.rmtree, tmpd, True)
        return paths, nref, nuniq
    return ([os.path.join(d, os.path.basename(p)) for p in paths],
            nref, nuniq)


def _knobs():
    from gpu_mapreduce_tpu.apps.invertedindex import _env_knobs
    return _env_knobs()


FUSE_MODE = None   # --fuse {0,1,ab} (or BENCH_FUSE); None = skip A/B
OVERLAP_MODE = None  # --overlap {0,1,ab} (or BENCH_OVERLAP); None = skip
SERVE_MODE = False   # --serve (or BENCH_SERVE=1): daemon cold/warm A/B
ELASTIC_MODE = False  # --elastic (or BENCH_ELASTIC=1): reshard wall +
#                       MRTPU_VERIFY read-overhead advisory rows
WIRE_MODE = None   # --wire {0,1,ab} (or BENCH_WIRE): compressed-vs-raw
#                    shuffle exchange A/B on the shuffle-bound workloads
OBSDIST_MODE = False  # --obsdist (or BENCH_OBSDIST=1): 4-proc mrlaunch
#                       wordfreq with sync-site instrumentation on vs off
STREAM_MODE = False  # --stream (or BENCH_STREAM=1): incremental
#                      standing-query vs one-shot A/B + batch cadence
CACHE_MODE = None  # --cache {0,1,ab} (or BENCH_CACHE): cold-restart vs
#                    warm-store caching-tier A/B (utils/cas.py)
GATE = False       # --gate: after the run, regress-check against the
#                    BENCH_r*.json trailing baseline (scripts/
#                    bench_compare.py) and exit nonzero on a trip


def run_gate(record: dict) -> int:
    """Compare the fresh run against the trailing BENCH_r*.json
    baseline (scripts/bench_compare.py, loaded by path — scripts/ is
    not a package).  Prints the markdown verdict; returns the exit
    code (0 pass / no-baseline, 1 regression).  A gate bug must not
    turn a finished bench into a crash — errors report and pass."""
    try:
        import importlib.util
        here = os.path.dirname(os.path.abspath(__file__))
        spec = importlib.util.spec_from_file_location(
            "bench_compare", os.path.join(here, "scripts",
                                          "bench_compare.py"))
        bc = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bc)
        candidate = bc.record_metrics(record)
        if candidate is None:
            # a degenerate run (value 0) has nothing to gate; compare()
            # must not fall back to re-judging the last persisted round
            print(json.dumps({"gate": "no usable candidate metrics"}),
                  file=sys.stderr)
            return 0
        verdict = bc.compare(bc.load_series(here), candidate,
                             threshold_pct=float(
                                 os.environ.get("BENCH_GATE_PCT",
                                                bc.DEFAULT_THRESHOLD_PCT)))
        print(bc.markdown(verdict), file=sys.stderr)
        print(json.dumps({"gate": {k: verdict.get(k) for k in
                                   ("verdict", "regressions",
                                    "baseline_rounds")}}),
              file=sys.stderr)
        return 0 if verdict["ok"] else 1
    except Exception:
        print(json.dumps({"gate_error":
                          tb_tail(traceback.format_exc(), 3)[-300:]}),
              file=sys.stderr)
        return 0


def plan_ab_record(mode: str, comm) -> dict:
    """Eager-vs-fused A/B of the canonical map→aggregate→convert→reduce
    pipeline (plan/ subsystem, doc/plan.md): wall time + compiled-program
    dispatch counts per variant.  Each variant runs twice — the first
    run pays compiles (both tiers share jit caches), the second is the
    steady state the headline numbers quote; the fused second run also
    shows the plan-cache hit.  Outputs must agree across variants or the
    record carries an "error" instead of a bogus win."""
    import numpy as np
    from gpu_mapreduce_tpu.core.mapreduce import MapReduce
    from gpu_mapreduce_tpu.core.runtime import global_counters
    from gpu_mapreduce_tpu.ops.reduces import count
    from gpu_mapreduce_tpu.plan import plan_cache

    n = int(os.environ.get("BENCH_PLAN_ROWS", 1 << 20))
    keys = (np.arange(n, dtype=np.uint64) * 2654435761) % max(n // 8, 1)
    vals = np.ones(n, np.int64)

    def run(fuse: int) -> dict:
        mr = MapReduce(comm, fuse=fuse)
        mr.kv = mr._new_kv()
        mr.kv.add_batch(keys, vals)
        mr.kv.complete()
        c0 = global_counters().snapshot()["ndispatch"]
        t0 = time.perf_counter()
        mr.aggregate()
        mr.convert()
        nunique = int(mr.reduce(count, batch=True))  # int() = barrier
        dt = time.perf_counter() - t0
        d = global_counters().snapshot()["ndispatch"] - c0
        return {"wall_s": round(dt, 4), "dispatches": d,
                "nunique": nunique}

    out = {"rows": n, "mode": mode}
    results = {}
    for label, fuse in (("eager", 0), ("fused", 1)):
        if mode != "ab" and mode != str(fuse):
            continue
        first = run(fuse)
        second = run(fuse)
        results[label] = second["nunique"]
        out[label] = {**second, "first_run_wall_s": first["wall_s"]}
    if mode in ("1", "ab"):
        out["plan_cache"] = plan_cache().stats()
    if len(set(results.values())) > 1:
        out["error"] = f"variant outputs disagree: {results}"
    if mode == "ab":
        # fusion v2: per-pipeline dispatch counts on the 8-way fake
        # mesh (subprocess — the fake topology must not leak into the
        # headline process); failures stay inside the sub-record
        try:
            out["mega"] = mega_ab_record()
        except Exception:
            out["mega"] = {
                "error": tb_tail(traceback.format_exc(), 3)[-300:]}
    return out


_MEGA_PROBE = r"""
import json, os, time
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.core.runtime import global_counters
from gpu_mapreduce_tpu.ops.reduces import count
from gpu_mapreduce_tpu.parallel.mesh import make_mesh

mesh = make_mesh(8)
rows = int(os.environ.get("BENCH_MEGA_ROWS", 1 << 18))
keys = ((np.arange(rows, dtype=np.uint64) * 2654435761)
        % max(rows // 8, 1)).astype(np.uint64)
vals = np.ones(rows, np.int64)

def pipeline():
    mr = MapReduce(mesh, fuse=1)
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, vals))
    t0 = time.perf_counter()
    mr.aggregate(); mr.convert()
    n = int(mr.reduce(count, batch=True))
    return n, time.perf_counter() - t0

out = {"rows": rows}
results = {}
for label, flag in (("v1", "0"), ("v2", "1")):
    os.environ["MRTPU_MEGAFUSE"] = flag
    pipeline(); pipeline()      # compiles + arm the speculation caches
    c0 = global_counters().snapshot()["ndispatch"]
    n, wall = pipeline()        # steady state
    d = global_counters().snapshot()["ndispatch"] - c0
    results[label] = n
    out[label] = {"wall_s": round(wall, 4), "dispatches": d,
                  "nunique": n}
out["outputs_equal"] = results["v1"] == results["v2"]
out["fusion_v2_dispatches"] = out["v2"]["dispatches"]
w1, w2 = out["v1"]["wall_s"], out["v2"]["wall_s"]
out["group_wall_delta_pct"] = round((w2 - w1) / w1 * 100.0, 2) \
    if w1 else 0.0
print(json.dumps(out))
"""


def mega_ab_record() -> dict:
    """Fusion-v2 A/B (``--fuse ab``): the canonical fused pipeline on
    an 8-way fake mesh under ``MRTPU_MEGAFUSE={0,1}``, recording the
    steady-state per-pipeline dispatch count (the "1 dispatch per plan
    group" target, asserted via ``Counters.ndispatch``) and the
    group-path wall delta — the advisory ``fusion_v2_dispatches`` /
    ``group_wall_delta_pct`` rows of scripts/bench_compare.py."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "1"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    p = subprocess.run([sys.executable, "-c", _MEGA_PROBE],
                       capture_output=True, text=True, timeout=900,
                       env=env, cwd=os.path.dirname(
                           os.path.abspath(__file__)))
    if p.returncode != 0:
        raise RuntimeError(f"megafuse probe failed: {p.stderr[-400:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def overlap_ab_record(mode: str, paths) -> dict:
    """Eager-vs-overlapped A/B of the wordfreq ingest pipeline (exec/
    subsystem, doc/perf.md): the corpus streams through the serial
    chunked reader (``map_file_str`` → ``_map_chunks``) with the
    async-overlap knobs off (eager) vs on (overlapped: ingest prefetch +
    background spill + donation + deferred sync).  Each chunk tokenizes
    — the C++ tier (native.tokenize, wordfreq_interned's scanner; ctypes
    releases the GIL, so the prefetch read of chunk N+1 genuinely runs
    beside chunk N's scan) with read_words as the no-binding fallback —
    and emits one (chunk, nwords) pair, so wall time is the
    read+tokenize pipeline the prefetch overlaps and outputs stay small
    enough to compare exactly — variants must agree or the record
    carries an "error" instead of a bogus win."""
    from gpu_mapreduce_tpu import native
    from gpu_mapreduce_tpu.core.mapreduce import MapReduce
    from gpu_mapreduce_tpu.exec import exec_stats, reset_stats
    from gpu_mapreduce_tpu.utils.io import read_words

    nchunks = int(os.environ.get("BENCH_OVERLAP_CHUNKS", "256"))
    knobs = ("MRTPU_PREFETCH", "MRTPU_SPILL_BG", "MRTPU_DONATE",
             "MRTPU_DEFER_SYNC")

    if native.available():
        def tokenize(itask, chunk, kv, ptr):
            starts, _lens = native.tokenize(chunk)
            kv.add(itask, len(starts))
    else:
        def tokenize(itask, chunk, kv, ptr):
            kv.add(itask, len(read_words(chunk)))

    def run(overlapped: bool) -> dict:
        saved = {k: os.environ.get(k) for k in knobs}
        os.environ["MRTPU_PREFETCH"] = \
            os.environ.get("BENCH_PREFETCH", "2") if overlapped else "0"
        os.environ["MRTPU_SPILL_BG"] = "1" if overlapped else "0"
        os.environ["MRTPU_DONATE"] = "1" if overlapped else "0"
        os.environ["MRTPU_DEFER_SYNC"] = "1" if overlapped else "0"
        try:
            mr = MapReduce()
            t0 = time.perf_counter()
            n = mr.map_file_str(nchunks, list(paths), 0, 0, b" ", 256,
                                tokenize)
            wall = time.perf_counter() - t0
            pairs = sorted((int(k), int(v)) for fr in mr.kv.frames()
                           for k, v in fr.pairs())
            return {"wall_s": round(wall, 4), "nchunks": n,
                    "nwords": sum(v for _, v in pairs),
                    "_pairs": pairs}
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # warm the page cache so variant order doesn't decide the A/B
    for p in paths:
        with open(p, "rb") as f:
            while f.read(1 << 24):
                pass
    out = {"mode": mode,
           "corpus_bytes": int(sum(os.path.getsize(p) for p in paths))}
    results = {}
    for label, overlapped in (("eager", False), ("overlapped", True)):
        if mode != "ab" and mode != ("1" if overlapped else "0"):
            continue
        if overlapped:
            reset_stats()
        rec = run(overlapped)
        results[label] = tuple(rec.pop("_pairs"))
        out[label] = rec
        if overlapped:
            ov = exec_stats()["overlap"].get("ingest.serial")
            if ov:
                rec["overlap_ratio"] = ov["overlap_ratio"]
    if len(set(results.values())) > 1:
        out["error"] = "variant outputs disagree: " + repr(
            {k: len(v) for k, v in results.items()})
    return out


def serve_ab_record() -> dict:
    """``--serve``: submit the identical wordfreq workload TWICE through
    an in-process serve/ daemon and record cold-vs-warm wall time plus
    dispatch and plan-cache counts — the resident-daemon story: the
    second request must hit the shared plan cache and recompile nothing
    (``warm.plan_misses == 0``; doc/serve.md)."""
    import shutil
    import tempfile
    from gpu_mapreduce_tpu.serve import Server, ServeClient
    tmp = tempfile.mkdtemp(prefix="bench_serve_")
    srv = None
    try:
        corpus = os.path.join(tmp, "corpus.txt")
        with open(corpus, "w") as f:
            # deterministic ~2 MB corpus: the A/B measures compile
            # amortization across requests, not ingest throughput
            for i in range(300000):
                f.write(f"w{i % 4096} ")
        srv = Server(port=0, workers=1,
                     state_dir=os.path.join(tmp, "state"))
        port = srv.start()
        c = ServeClient.local(port)
        script = (f"variable files index {corpus}\n"
                  f"set fuse 1\n"
                  f"wordfreq 5 -i v_files\n")
        out = {}
        for phase in ("cold", "warm"):
            res = c.wait(c.submit(script=script, tenant="bench")["id"],
                         timeout=600)
            if res.get("status") != "done":
                raise RuntimeError(f"serve {phase} run failed: "
                                   f"{res.get('error')}")
            pc = res["meta"]["plan_cache"]["plan"]
            out[phase] = {"wall_s": res["meta"]["wall_s"],
                          "dispatches": res["meta"]["dispatches"],
                          "plan_misses": pc["misses"],
                          "plan_hits": pc["hits"]}
        out["warm_skipped_compiles"] = out["warm"]["plan_misses"] == 0
        return out
    finally:
        if srv is not None:
            srv.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)


def stream_ab_record() -> dict:
    """``--stream``: the standing-query A/B (stream/engine.py,
    doc/streaming.md) — ingest the same corpus INCREMENTALLY (N
    micro-batch commits, each paying the journal fsync + checkpoint
    durability tax) vs ONE SHOT over the finished file, asserting the
    snapshots are byte-identical and recording the steady-state batch
    wall (p50 over the warm tail, the compiles amortized away) and the
    sustained commit rate."""
    import shutil
    import tempfile
    from gpu_mapreduce_tpu.stream import Stream
    tmp = tempfile.mkdtemp(prefix="bench_stream_")
    try:
        src = os.path.join(tmp, "feed.txt")
        nbatches = int(os.environ.get("BENCH_STREAM_BATCHES", "12"))
        chunk = " ".join(f"w{i % 2048}" for i in range(20000)) + "\n"
        s = Stream(os.path.join(tmp, "inc"), [src],
                   settings={"fuse": 1})
        walls = []
        t0 = time.perf_counter()
        for _ in range(nbatches):
            with open(src, "a") as f:
                f.write(chunk)
            b0 = time.perf_counter()
            s.drain()
            walls.append(time.perf_counter() - b0)
        inc_wall = time.perf_counter() - t0
        inc_snap = s.snapshot()
        s.close()
        one = Stream(os.path.join(tmp, "one"), [src],
                     settings={"fuse": 1})
        b0 = time.perf_counter()
        one.drain(final=True)
        oneshot_wall = time.perf_counter() - b0
        identical = one.snapshot() == inc_snap
        one.close()
        warm = sorted(walls[2:]) or sorted(walls)
        return {"batches": nbatches,
                "incremental_wall_s": round(inc_wall, 4),
                "oneshot_wall_s": round(oneshot_wall, 4),
                "batch_p50_ms": round(warm[len(warm) // 2] * 1000, 2),
                "batches_per_sec": round(nbatches / inc_wall, 2),
                "identical": identical}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def cache_ab_record(mode: str) -> dict:
    """``--cache {0,1,ab}``: cold-restart vs warm-store A/B of the
    content-addressed caching tier (doc/perf.md#the-caching-tier).

    Each arm runs the same protocol: start a daemon, submit the
    canonical wordfreq workload, SHUT THE DAEMON DOWN (fresh state dir
    + cleared in-process plan cache = a cold restart), then resubmit
    the byte-identical script to a new daemon.  Arm ``0`` disarms the
    store (``MRTPU_CAS=0``): the restart recompiles and re-executes.
    Arm ``1`` shares one store across the restart: the second daemon
    must serve a verified memo hit — 0 plan compiles, 0 dispatches
    (``restart.memo_hit`` / ``restart.plan_misses == 0``).  Recorded
    into ``detail.cache_ab`` → the advisory ``cache_warm_restart_sec``
    / ``cache_result_hit_sec`` rows of scripts/bench_compare.py."""
    import shutil
    import tempfile
    from gpu_mapreduce_tpu.plan.cache import plan_cache
    from gpu_mapreduce_tpu.serve import Server, ServeClient
    from gpu_mapreduce_tpu.utils.cas import reset_store

    def run(arm: str) -> dict:
        tmp = tempfile.mkdtemp(prefix=f"bench_cache{arm}_")
        saved = {k: os.environ.get(k)
                 for k in ("MRTPU_CAS", "MRTPU_CAS_DIR", "MRTPU_MEMOIZE",
                           "MRTPU_JIT_PERSIST")}
        os.environ["MRTPU_CAS"] = arm
        os.environ["MRTPU_CAS_DIR"] = os.path.join(tmp, "cas")
        # the XLA disk cache stays as bench configured it globally —
        # this A/B isolates the plan/memo tiers, whose effect is
        # measurable on every backend
        os.environ["MRTPU_JIT_PERSIST"] = "0"
        reset_store()
        try:
            corpus = os.path.join(tmp, "corpus.txt")
            with open(corpus, "w") as f:
                for i in range(300000):
                    f.write(f"w{i % 4096} ")
            script = (f"variable files index {corpus}\n"
                      f"set fuse 1\n"
                      f"wordfreq 5 -i v_files\n")
            rec = {}
            for phase in ("cold", "restart"):
                # a COLD restart, in process: fresh daemon state dir
                # and a cleared in-memory plan cache — what survives
                # is exactly what the on-disk store preserved
                plan_cache().clear()
                srv = Server(port=0, workers=1,
                             state_dir=os.path.join(tmp, f"st_{phase}"))
                port = srv.start()
                try:
                    c = ServeClient.local(port)
                    res = c.wait(
                        c.submit(script=script, tenant="bench")["id"],
                        timeout=600)
                    if res.get("status") != "done":
                        raise RuntimeError(f"cache {arm}/{phase} run "
                                           f"failed: {res.get('error')}")
                    meta = res["meta"]
                    pc = meta["plan_cache"]["plan"]
                    rec[phase] = {
                        "wall_s": meta["wall_s"],
                        "dispatches": meta["dispatches"],
                        "plan_misses": pc["misses"],
                        "plan_hits": pc["hits"],
                        "memo_hit": bool((meta.get("memo") or {}
                                          ).get("hit")),
                    }
                finally:
                    srv.shutdown()
            rec["result_hit"] = rec["restart"]["memo_hit"] and \
                rec["restart"]["dispatches"] == 0 and \
                rec["restart"]["plan_misses"] == 0
            return rec
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            reset_store()
            plan_cache().clear()
            shutil.rmtree(tmp, ignore_errors=True)

    out = {}
    if mode in ("0", "ab"):
        out["store_off"] = run("0")
    if mode in ("1", "ab"):
        out["store_on"] = run("1")
    return out


def profile_ab_record() -> dict:
    """Armed-vs-disarmed cost of the request trace context
    (obs/context.py): the identical aggregate/sort micro-cycle, best of
    alternating reps with (a) MRTPU_PROFILE=0 + tracing off and (b) a
    request_scope + the tracer ring armed.  Recorded as
    ``detail.profile_ab`` → the advisory ``profile_overhead_pct``
    bench_compare row — the evidence that the disarmed context layer
    stays within bench noise (doc/observability.md)."""
    import numpy as np

    from gpu_mapreduce_tpu.core.mapreduce import MapReduce
    from gpu_mapreduce_tpu.obs import get_tracer, request_scope
    from gpu_mapreduce_tpu.obs import context as obs_context

    keys = (np.arange(400_000, dtype=np.uint64) * 2654435761) % (1 << 18)

    def cycle():
        mr = MapReduce()
        mr.map(4, lambda i, kv, p: kv.add_batch(keys, keys))
        mr.aggregate()
        mr.sort_keys(1)

    tracer = get_tracer()
    # mrlint: disable=knob-bypass — raw save/restore of the var for the
    # A/B (must keep the None-vs-"" distinction env_str collapses)
    prev_profile = os.environ.get("MRTPU_PROFILE")
    prev_enabled = tracer.enabled
    best = {"off": float("inf"), "on": float("inf")}
    try:
        cycle()                            # warm shapes/interning
        for _rep in range(3):              # alternate: ordering noise
            for mode in ("off", "on"):     # must not read as the knob
                if mode == "off":
                    os.environ["MRTPU_PROFILE"] = "0"
                    tracer.enabled = False
                    t0 = time.perf_counter()
                    cycle()
                    best["off"] = min(best["off"],
                                      time.perf_counter() - t0)
                else:
                    os.environ["MRTPU_PROFILE"] = "1"
                    tracer.enable()
                    t0 = time.perf_counter()
                    with request_scope(label="bench-profile-ab"):
                        cycle()
                    best["on"] = min(best["on"],
                                     time.perf_counter() - t0)
    finally:
        if prev_profile is None:
            os.environ.pop("MRTPU_PROFILE", None)
        else:
            os.environ["MRTPU_PROFILE"] = prev_profile
        tracer.enabled = prev_enabled
        obs_context.reset()
    off, on = best["off"], best["on"]
    return {"off_s": round(off, 4), "on_s": round(on, 4),
            "overhead_pct": round((on - off) / off * 100.0, 2)
            if off > 0 else 0.0}


_WIRE_PROBE = r"""
import json, os, time
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.ops.reduces import count
from gpu_mapreduce_tpu.parallel import shuffle
from gpu_mapreduce_tpu.parallel.mesh import make_mesh

mesh = make_mesh(8)
rows = int(os.environ.get("BENCH_WIRE_ROWS", 1 << 19))
rng = np.random.default_rng(3)
# zipf-skewed keys in a u32-ish range: the IntCount shape (maximum key
# cardinality, minimum payload) with RMAT-hub skew — the workload the
# pad tax and the wire codec both live on
zkeys = np.minimum(rng.zipf(1.3, rows), 1 << 22).astype(np.uint64)
ones32 = np.ones(rows, np.uint32)

def intcount_run():
    mr = MapReduce(mesh)
    mr.map(1, lambda i, kv, p: kv.add_batch(zkeys, ones32))
    t0 = time.perf_counter()
    mr.aggregate(); mr.convert()
    n = int(mr.reduce(count, batch=True))
    return n, time.perf_counter() - t0, mr.last_exchange

def scrunch_run():
    mr = MapReduce(mesh)
    mr.map(1, lambda i, kv, p: kv.add_batch(zkeys, ones32.astype(np.uint64)))
    t0 = time.perf_counter()
    mr.scrunch(2, np.uint64(7))
    g, n, _ = mr.kmv_stats()
    return (g, n), time.perf_counter() - t0, mr.last_exchange

mode = os.environ.get("BENCH_WIRE_MODE", "ab")
out = {"rows": rows, "mode": mode}
for name, run in (("intcount", intcount_run), ("scrunch", scrunch_run)):
    rec = {}
    results = {}
    for flag in ("0", "1"):
        if mode != "ab" and mode != flag:
            continue
        os.environ["MRTPU_WIRE"] = flag
        shuffle._SPEC_CACHE.clear()
        run()                                # warm the compiles
        res, wall, st = run()                # steady state
        results[flag] = res
        total = (st.wire_bytes if st and st.wire_bytes
                 else (st.sent_bytes + st.pad_bytes) if st else 0)
        rec["wire" + flag] = {
            "wall_s": round(wall, 4),
            "pairs_per_sec": round(rows / wall, 1),
            "sent_bytes": st.sent_bytes if st else 0,
            "pad_bytes": st.pad_bytes if st else 0,
            "wire_bytes": st.wire_bytes if st else 0,
            "exchanged_bytes": total,
            "compression_ratio": st.wire_ratio if st else 0.0,
        }
    if len(results) == 2:
        rec["outputs_equal"] = results["0"] == results["1"]
        b0 = rec["wire0"]["exchanged_bytes"]
        b1 = rec["wire1"]["exchanged_bytes"]
        rec["bytes_reduction_pct"] = round((1 - b1 / b0) * 100.0, 2) \
            if b0 else 0.0
        w0, w1 = rec["wire0"]["wall_s"], rec["wire1"]["wall_s"]
        rec["wall_delta_pct"] = round((w1 - w0) / w0 * 100.0, 2) \
            if w0 else 0.0
    out[name] = rec
print(json.dumps(out))
"""


def wire_ab_record(mode: str) -> dict:
    """``--wire {0,1,ab}``: compressed-vs-raw exchange A/B on the
    shuffle-bound workloads (zipf-skewed intcount aggregate + scrunch
    gather) over an 8-way fake mesh in a subprocess (the fake topology
    must not leak into the headline process).  Records wall, exchange
    sent/pad/wire bytes and the compression ratio into
    ``detail.wire_ab`` — the advisory ``wire_*`` rows of
    scripts/bench_compare.py."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "1"
    env["BENCH_WIRE_MODE"] = mode
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    p = subprocess.run([sys.executable, "-c", _WIRE_PROBE],
                       capture_output=True, text=True, timeout=900,
                       env=env, cwd=os.path.dirname(
                           os.path.abspath(__file__)))
    if p.returncode != 0:
        raise RuntimeError(f"wire probe failed: {p.stderr[-400:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


_ELASTIC_PROBE = r"""
import json, os, sys, time, tempfile
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
out = {}
# reshard wall: a ~2M-row aggregated KV across 4->2->8 (host-device mesh)
mr = MapReduce(make_mesh(4))
keys = (np.arange(1 << 21, dtype=np.uint64) * 2654435761) % (1 << 20)
mr.map(1, lambda i, kv, p: kv.add_batch(keys, keys))
mr.aggregate()
for w in (2, 8, 4):
    t0 = time.perf_counter()
    mr.reshard(make_mesh(w))
    out[f"reshard_to_{w}_s"] = round(time.perf_counter() - t0, 4)
out["reshard_rows"] = int(1 << 21)
# verify-on-read overhead: spill-heavy sort + checkpoint save/reload,
# MRTPU_VERIFY off vs on (stamping is always on; the knob gates reads)
tmp = tempfile.mkdtemp(prefix="bench_elastic_")
skeys = (np.arange(400_000, dtype=np.uint64) * 7919) % (1 << 40)
def cycle(tag):
    m = MapReduce(outofcore=1, memsize=1, maxpage=1,
                  fpath=os.path.join(tmp, "sp" + tag))
    m.map(1, lambda i, kv, p: kv.add_batch(skeys, skeys))
    m.sort_keys(1)
    ck = os.path.join(tmp, "ck" + tag)
    m.save(ck)
    MapReduce().load(ck)
os.environ["MRTPU_VERIFY"] = "0"
cycle("warm")                              # warm shapes + page cache
best = {"0": float("inf"), "1": float("inf")}
for rep in range(2):                       # alternate: ordering noise
    for flag in ("0", "1"):                # must not masquerade as the
        os.environ["MRTPU_VERIFY"] = flag  # knob's cost
        t0 = time.perf_counter()
        cycle(f"{flag}.{rep}")
        best[flag] = min(best[flag], time.perf_counter() - t0)
out["verify_off_s"] = round(best["0"], 4)
out["verify_on_s"] = round(best["1"], 4)
off, on = out["verify_off_s"], out["verify_on_s"]
out["verify_overhead_pct"] = round((on - off) / off * 100.0, 2) if off else 0.0
print(json.dumps(out))
"""


def elastic_record() -> dict:
    """``--elastic``: reshard wall times (4→2→8→4 on a CPU host-device
    mesh) and the MRTPU_VERIFY read-side overhead on a spill-heavy
    sort + checkpoint cycle — recorded into ``detail.elastic`` as
    advisory bench_compare rows.  Runs in a subprocess so the fake
    8-device CPU topology and the MRTPU_VERIFY toggling never leak
    into the headline measurement's process."""
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "1"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    p = subprocess.run([sys.executable, "-c", _ELASTIC_PROBE],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=os.path.dirname(
                           os.path.abspath(__file__)))
    if p.returncode != 0:
        raise RuntimeError(f"elastic probe failed: {p.stderr[-400:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def obsdist_ab_record() -> dict:
    """``--obsdist``: fleet-observability overhead A/B — the SAME
    4-process mrlaunch wordfreq run with the dist sync observer /
    per-rank trace / metrics dumper armed (the default) vs all three
    disarmed, wall-clock from each run's ``launch.json``.  Recorded
    into ``detail.obs_dist_ab`` as the advisory
    ``obs_dist_overhead_pct`` bench_compare row: arrival stamps are
    one appended JSONL line per sync per rank, so the verdict should
    sit within run-to-run noise — a drift here means the observer
    started doing work inside the collective path."""
    import random
    mrlaunch = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "scripts", "mrlaunch.py")
    tmp = tempfile.mkdtemp(prefix="bench_obsdist_")
    corpus = os.path.join(tmp, "corpus.txt")
    rng = random.Random(7)
    words = [f"w{i:04d}".encode() for i in range(500)]
    with open(corpus, "wb") as f:
        for _ in range(60_000):
            f.write(rng.choice(words))
            f.write(b" " if rng.random() < 0.85 else b"\n")
    base = dict(os.environ)
    base.pop("MRTPU_FAULTS", None)
    off_env = dict(base)
    # mrlint: disable=knob-bypass  (subprocess env assembly, not reads)
    off_env.update({"MRTPU_DIST_TRACE": "0", "MRTPU_DIST_METRICS": "0",
                    "MRTPU_DIST_SYNC_OBS": "0"})
    out = {}
    # off first, then on: a shared-host cache warmup bias would flatter
    # the instrumented side, which is the conservative direction
    for tag, env in (("off", off_env), ("on", base)):
        rundir = os.path.join(tmp, f"run-{tag}")
        p = subprocess.run(
            [sys.executable, mrlaunch, "--np", "4", "--rundir", rundir,
             "wordfreq", "--files", corpus,
             "--out", os.path.join(tmp, f"out-{tag}.txt"),
             "--chunks", "4"],
            env=env, capture_output=True, text=True, timeout=600,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        if p.returncode != 0:
            raise RuntimeError(
                f"obsdist {tag} run failed rc={p.returncode}: "
                f"{p.stderr[-400:]}")
        with open(os.path.join(rundir, "launch.json")) as f:
            out[f"{tag}_s"] = round(float(
                json.load(f)["wall_seconds"]), 4)
    off, on = out["off_s"], out["on_s"]
    out["overhead_pct"] = round((on - off) / off * 100.0, 2) if off \
        else 0.0
    return out


def run_bench(engine):
    total_mb = int(os.environ.get("BENCH_MB", "256"))
    skew = os.environ.get("BENCH_SKEW", "0") == "1"
    dense = os.environ.get("BENCH_DENSE", "0") == "1"
    import jax
    from gpu_mapreduce_tpu.apps.invertedindex import InvertedIndex
    from gpu_mapreduce_tpu.obs import aggregate_ops, get_tracer

    # subscribe to the span stream instead of hand-rolling timers: the
    # detail record's per-op rows come from the same tracer every layer
    # reports into (MRTPU_TRACE additionally streams the JSONL file)
    tracer = get_tracer().enable()

    comm = None
    if engine in ("pallas", "xla"):
        from gpu_mapreduce_tpu.parallel.mesh import make_mesh
        comm = make_mesh(1)  # 1-chip mesh: KV stays device-resident

    # corpus_cached owns file lifetime (incl. the cache-off tempdir)
    paths, nurls, nuniq = corpus_cached(total_mb, skew, dense)
    nbytes = sum(os.path.getsize(p) for p in paths)

    # warmup at FULL shapes so the timed run measures steady state
    # (first XLA/Mosaic compile is ~20-40s on TPU; jit re-specialises
    # per corpus shape, so a small-prefix warmup would not help)
    warm = InvertedIndex(engine=engine, comm=comm)
    warm.run(paths)

    idx = InvertedIndex(engine=engine, comm=comm)
    tracer.clear()             # timed run only: drop the warmup spans
    t0 = time.perf_counter()
    npairs, nunique = idx.run(paths)
    dt = time.perf_counter() - t0

    assert npairs == nurls, (npairs, nurls)
    assert nunique == nuniq, (nunique, nuniq)
    raw = idx.timer.times
    stages = {k: round(v, 4) for k, v in sorted(raw.items())}
    # the map stage over the reference's 44 ms boundary (see docstring);
    # the native tier's boundary = C++ scan + intern/kv-add (the reference's
    # host kv->add IS inside its 44 ms)
    if "map_device" in raw:
        map_time = raw["map_device"]
    elif "native_scan" in raw:
        # union wall-clock of scan+add spans across the mapstyle-2
        # worker threads: elapsed time with >=1 thread in the map stage
        # (equals the plain sum when serial; StageTimer.wall docstring)
        map_time = idx.timer.wall("map_kernels")
    else:
        map_time = raw.get("map", dt)
    map_time = max(map_time, 1e-9)
    pairs_per_sec = npairs / map_time
    map_bytes_per_sec = nbytes / map_time
    detail = {
        "npairs": npairs, "nunique": nunique, "bytes": nbytes,
        "host": host_id(),
        "corpus": {"mb": total_mb, "skew": skew, "dense": dense},
        "map_stage_sec": round(map_time, 4),
        "map_stage_bytes_per_sec": round(map_bytes_per_sec, 1),
        "end_to_end_sec": round(dt, 3),
        "end_to_end_bytes_per_sec": round(nbytes / dt, 1),
        "backend": jax.default_backend(), "engine": idx.engine,
        "stages_sec": stages,
        # knob provenance: the extract knobs this number was taken under
        "env_knobs": dict(zip(("compact", "window_bs", "mark_page_words"),
                              _knobs())),
        # device-tier batching + two-tier window machinery (VERDICT r2
        # #9: the recorded detail must show these exercised at volume)
        "map_stats": getattr(idx, "stats", {}),
        # per-span-name rows of the timed run (count/total_s/byte sums)
        # from the obs/ tracer — the machine-readable twin of stages_sec
        "trace_ops": aggregate_ops(tracer.events()),
    }
    if FUSE_MODE:
        # --fuse {0,1,ab}: eager-vs-fused plan A/B of the canonical
        # pipeline; failures must not cost the headline metric line
        ab_comm = comm
        if ab_comm is None:
            try:
                from gpu_mapreduce_tpu.parallel.mesh import make_mesh
                ab_comm = make_mesh(1)
            except Exception:
                ab_comm = None
        try:
            detail["plan_ab"] = plan_ab_record(FUSE_MODE, ab_comm)
        except Exception:
            detail["plan_ab"] = {
                "error": tb_tail(traceback.format_exc(), 3)[-300:]}
    if OVERLAP_MODE:
        # --overlap {0,1,ab}: eager-vs-overlapped ingest A/B (exec/);
        # failures must not cost the headline metric line
        try:
            detail["exec_ab"] = overlap_ab_record(OVERLAP_MODE, paths)
        except Exception:
            detail["exec_ab"] = {
                "error": tb_tail(traceback.format_exc(), 3)[-300:]}
    if SERVE_MODE:
        # --serve: cold-vs-warm daemon A/B (serve/); failures must not
        # cost the headline metric line
        try:
            detail["serve_ab"] = serve_ab_record()
        except Exception:
            detail["serve_ab"] = {
                "error": tb_tail(traceback.format_exc(), 3)[-300:]}
    if ELASTIC_MODE:
        # --elastic: reshard wall + verify-on-read overhead (advisory
        # bench_compare rows); failures must not cost the headline
        try:
            detail["elastic"] = elastic_record()
        except Exception:
            detail["elastic"] = {
                "error": tb_tail(traceback.format_exc(), 3)[-300:]}
    if WIRE_MODE:
        # --wire {0,1,ab}: compressed-vs-raw exchange A/B (parallel/
        # wire.py); failures must not cost the headline metric line
        try:
            detail["wire_ab"] = wire_ab_record(WIRE_MODE)
        except Exception:
            detail["wire_ab"] = {
                "error": tb_tail(traceback.format_exc(), 3)[-300:]}
    if OBSDIST_MODE:
        # --obsdist: 4-proc mrlaunch instrumentation on/off A/B
        # (obs/fleetobs.py); failures must not cost the headline
        try:
            detail["obs_dist_ab"] = obsdist_ab_record()
        except Exception:
            detail["obs_dist_ab"] = {
                "error": tb_tail(traceback.format_exc(), 3)[-300:]}
    if CACHE_MODE:
        # --cache {0,1,ab}: cold-restart vs warm-store caching-tier A/B
        # (utils/cas.py); failures must not cost the headline line
        try:
            detail["cache_ab"] = cache_ab_record(CACHE_MODE)
        except Exception:
            detail["cache_ab"] = {
                "error": tb_tail(traceback.format_exc(), 3)[-300:]}
    if STREAM_MODE:
        # --stream: incremental standing-query vs one-shot A/B
        # (stream/engine.py); failures must not cost the headline line
        try:
            detail["stream_ab"] = stream_ab_record()
        except Exception:
            detail["stream_ab"] = {
                "error": tb_tail(traceback.format_exc(), 3)[-300:]}
    if os.environ.get("BENCH_PROFILE_AB", "1") != "0":
        # trace-context armed-vs-disarmed micro A/B (obs/context.py):
        # cheap (~seconds), recorded on every round so the advisory
        # profile_overhead_pct series exists without a flag; failures
        # must not cost the headline metric line
        try:
            detail["profile_ab"] = profile_ab_record()
        except Exception:
            detail["profile_ab"] = {
                "error": tb_tail(traceback.format_exc(), 3)[-300:]}
    try:
        print(json.dumps({"detail": detail}), file=sys.stderr)
    except Exception:
        pass  # a broken stderr must not cost us the stdout metric line
    emit(round(pairs_per_sec, 1),
         round(map_bytes_per_sec / BASELINE_BYTES_PER_SEC, 4),
         backend=jax.default_backend(), engine=idx.engine)
    # the flat record the --gate regression check consumes
    return {"metric": METRIC, "value": round(pairs_per_sec, 1),
            "backend": jax.default_backend(), "engine": idx.engine,
            "detail": detail}


def main():
    global FUSE_MODE, OVERLAP_MODE, SERVE_MODE, ELASTIC_MODE, GATE, \
        WIRE_MODE, OBSDIST_MODE, CACHE_MODE
    argv = sys.argv[1:]
    GATE = "--gate" in argv or os.environ.get("BENCH_GATE") == "1"
    if "--fuse" in argv:
        i = argv.index("--fuse")
        FUSE_MODE = argv[i + 1] if i + 1 < len(argv) else "ab"
    else:
        FUSE_MODE = os.environ.get("BENCH_FUSE") or None
    if FUSE_MODE not in (None, "0", "1", "ab"):
        raise SystemExit(f"--fuse takes 0, 1 or ab, got {FUSE_MODE!r}")
    if "--overlap" in argv:
        i = argv.index("--overlap")
        OVERLAP_MODE = argv[i + 1] if i + 1 < len(argv) else "ab"
    else:
        OVERLAP_MODE = os.environ.get("BENCH_OVERLAP") or None
    if OVERLAP_MODE not in (None, "0", "1", "ab"):
        raise SystemExit(
            f"--overlap takes 0, 1 or ab, got {OVERLAP_MODE!r}")
    if "--wire" in argv:
        i = argv.index("--wire")
        WIRE_MODE = argv[i + 1] if i + 1 < len(argv) else "ab"
    else:
        WIRE_MODE = os.environ.get("BENCH_WIRE") or None
    if WIRE_MODE not in (None, "0", "1", "ab"):
        raise SystemExit(f"--wire takes 0, 1 or ab, got {WIRE_MODE!r}")
    if "--cache" in argv:
        i = argv.index("--cache")
        CACHE_MODE = argv[i + 1] if i + 1 < len(argv) else "ab"
    else:
        CACHE_MODE = os.environ.get("BENCH_CACHE") or None
    if CACHE_MODE not in (None, "0", "1", "ab"):
        raise SystemExit(f"--cache takes 0, 1 or ab, got {CACHE_MODE!r}")
    SERVE_MODE = "--serve" in argv or \
        os.environ.get("BENCH_SERVE") == "1"
    ELASTIC_MODE = "--elastic" in argv or \
        os.environ.get("BENCH_ELASTIC") == "1"
    OBSDIST_MODE = "--obsdist" in argv or \
        os.environ.get("BENCH_OBSDIST") == "1"
    STREAM_MODE = "--stream" in argv or \
        os.environ.get("BENCH_STREAM") == "1"
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"bench.py: needs a TPU, found platform "
                         f"{backend!r}; nothing was measured")
    # BENCH_ENGINE=xla|native runs that engine instead; whichever was
    # asked for either completes or raises — no other engine is tried
    rec = run_bench(os.environ.get("BENCH_ENGINE", "pallas"))
    if GATE:
        sys.exit(run_gate(rec))


if __name__ == "__main__":
    main()
