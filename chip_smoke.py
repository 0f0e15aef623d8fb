"""chip_smoke.py — the quickest proof that the main path runs on the TPU.

    python3 chip_smoke.py          (no arguments, from the checkout root)

One process, a mesh over every device ``jax.devices()`` returns, default
settings only.  Two legs, one after the other, each checked against a
plain reference written here:

* leg A — the flagship InvertedIndex application (the source paper's
  CUDA app) over a seeded PUMA-density HTML corpus, 256 MB per chip in
  128 MB files, through ``InvertedIndex(comm=mesh).run(paths, outdir=…)``
  with the default (Pallas) engine; reference: a regex scan.
* leg B — an OINK graph script (MR-MPI's published suite) through
  ``OinkScript(comm=mesh).run_string``: RMAT-20 at Graph500 parameters,
  ``edge_upper``, ``cc_find``, ``pagerank``; references: numpy min-label
  components and a float64 power iteration under the same stopping rule.

A machine without a TPU is an error: the script says what it found and
exits non-zero before generating anything.  The legs are importable
functions with a size argument (``tests/test_chip_smoke.py`` runs them
tiny on the fake CPU mesh); only ``main()`` insists on the chip.  The
last line of a passing run is ``{"ok": true, "device": {...}}``.
"""

import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time

import numpy as np

SEED = 20260926
FILE_BYTES = 128 << 20      # the reference's per-process file size
FILES_PER_CHIP = 2          # 256 MB a chip: one batch, one extract shape
RMAT_SCALE = 20
RMAT_EDGE_FACTOR = 8
PAGERANK_TOL = 1e-6
MAX_URL = 256               # apps/invertedindex.MAX_URL (checked in leg A)
PATTERN = b'<a href="'


class SmokeFailure(Exception):
    """A leg disagreed with its reference or broke a device assertion."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# compile-cache accounting (JAX's own monitoring events)
# ---------------------------------------------------------------------------

COMPILES = {"requests": 0, "hits": 0}


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        COMPILES["requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        COMPILES["hits"] += 1


def count_compiles() -> None:
    import jax.monitoring
    jax.monitoring.register_event_listener(_on_event)


# ---------------------------------------------------------------------------
# spread assertions (more than one device)
# ---------------------------------------------------------------------------

def check_spread(name: str, frame, ndev: int) -> None:
    """A mesh-resident KV frame must really live on every device."""
    from gpu_mapreduce_tpu.parallel.sharded import ShardedKV
    check(isinstance(frame, ShardedKV),
          f"{name}: result is {type(frame).__name__}, not mesh-resident")
    for a in (frame.key, frame.value):
        check(len(a.sharding.device_set) == ndev,
              f"{name}: array spans {len(a.sharding.device_set)} of "
              f"{ndev} devices")
    check(len(frame.counts) == ndev and int(frame.counts.min()) > 0,
          f"{name}: per-shard rows {frame.counts.tolist()}")


def check_exchange(name: str, mr) -> dict:
    ex = mr.last_exchange
    check(ex is not None and ex.rows > 0 and ex.sent_bytes > 0,
          f"{name}: the exchange moved nothing ({ex})")
    return {"rows": int(ex.rows), "sent_bytes": int(ex.sent_bytes),
            "rounds": int(ex.nrounds)}


# ---------------------------------------------------------------------------
# leg A: InvertedIndex
# ---------------------------------------------------------------------------

def make_corpus(outdir: str, nfiles: int, file_bytes: int, seed: int):
    """Seeded synthetic HTML at PUMA density: ~1 href per KB of filler;
    a quarter of the references hit a 64-URL hot set, 2 % are 130-210
    byte long-tail URLs, and one in 500 is longer than MAX_URL (the
    system must drop it, as the reference below does)."""
    filler = b"<p>" + b"lorem ipsum dolor sit amet " * 36 + b"</p>\n"
    hot = [b"http://example.org/hot/%02d" % i for i in range(64)]
    base = b"http://example.org/s%d/" % seed
    os.makedirs(outdir, exist_ok=True)
    paths, uid, nref = [], 0, 0
    for i in range(nfiles):
        pieces, size = [], 0
        while size < file_bytes:
            if nref % 500 == 499:
                u = base + b"over/p%08d/" % uid + b"y" * 300
                uid += 1
            elif nref % 50 == 49:
                u = base + b"long/p%08d/" % uid + b"x" * (96 + uid % 80)
                uid += 1
            elif nref % 4 == 3:
                u = hot[(nref // 4) % len(hot)]
            else:
                u = base + b"wiki/page-%08d" % uid
                uid += 1
            ref = PATTERN + u + b'">x</a>'
            nref += 1
            pieces.append(filler)
            pieces.append(ref)
            size += len(filler) + len(ref)
        path = os.path.join(outdir, f"part-{i:05d}.html")
        with open(path, "wb") as f:
            f.write(b"".join(pieces))
        paths.append(path)
    return paths


def index_reference(paths):
    """Plain regex scan: url -> sorted list of files naming it.  An href
    whose closing quote is not within MAX_URL bytes is dropped (the rule
    the device tier documents); files are scanned one by one, so nothing
    matches across a file boundary."""
    rx = re.compile(re.escape(PATTERN) + rb'([^"]*)"')
    index, npairs = {}, 0
    for path in paths:
        with open(path, "rb") as f:
            data = f.read()
        for m in rx.finditer(data):
            url = m.group(1)
            if len(url) >= MAX_URL:
                continue
            npairs += 1
            index.setdefault(url, set()).add(path)
    order = {p: i for i, p in enumerate(paths)}
    return ({u.decode(): sorted(fs, key=order.__getitem__)
             for u, fs in index.items()}, npairs)


def leg_invertedindex(mesh, workdir: str, file_bytes: int = FILE_BYTES,
                      files_per_chip: int = FILES_PER_CHIP,
                      seed: int = SEED) -> dict:
    from gpu_mapreduce_tpu.apps import invertedindex as app
    from gpu_mapreduce_tpu.parallel.mesh import mesh_axis_size
    check(app.MAX_URL == MAX_URL and app.PATTERN == PATTERN,
          "the reference's MAX_URL/PATTERN differ from the application's")
    ndev = mesh_axis_size(mesh)
    t0 = time.perf_counter()
    paths = make_corpus(os.path.join(workdir, "corpus"),
                        ndev * files_per_chip, file_bytes, seed)
    t1 = time.perf_counter()
    want, want_pairs = index_reference(paths)
    t2 = time.perf_counter()

    outdir = os.path.join(workdir, "index")
    idx = app.InvertedIndex(comm=mesh)          # default engine
    npairs, nunique = idx.run(paths, outdir=outdir)
    t3 = time.perf_counter()

    check(idx.engine == "pallas", f"default engine is {idx.engine!r}")
    check(npairs == want_pairs, f"npairs {npairs} != reference {want_pairs}")
    check(nunique == len(want), f"nunique {nunique} != reference {len(want)}")
    parts = sorted(glob.glob(os.path.join(outdir, "part-*")))
    got = {}
    for part in parts:
        with open(part) as f:
            for line in f:
                url, names = line.rstrip("\n").split("\t")
                check(url not in got, f"{url!r} is in two part files")
                got[url] = names.split(" ")
    check(got == want, "the part files differ from the regex reference "
          f"({len(got)} vs {len(want)} urls)")

    # which kernel ran: the flag, and the program the run dispatched
    fn, avals = idx.extract_program
    mosaic = "tpu_custom_call" in fn.lower(*avals).as_text()
    check(mosaic == (not idx.interpret),
          f"interpret={idx.interpret} but Mosaic custom call "
          f"{'present' if mosaic else 'absent'} in the extract program")

    report = {
        "files": len(paths), "file_bytes": file_bytes,
        "bytes": sum(os.path.getsize(p) for p in paths),
        "npairs": npairs, "nunique": nunique, "parts": len(parts),
        "interpret": idx.interpret, "mosaic_custom_call": mosaic,
        "map_stats": dict(idx.stats),
        "seconds": {"generate": t1 - t0, "reference": t2 - t1,
                    "run": t3 - t2},
        "stages": dict(idx.timer.times),
    }
    check(len(parts) == ndev and all(os.path.getsize(p) for p in parts),
          f"{len(parts)} part files for {ndev} shards, or an empty one")
    if ndev > 1:
        for fr in idx.mr.kv.frames():
            check_spread("invertedindex counts", fr, ndev)
        report["exchange"] = check_exchange("invertedindex", idx.mr)
    return report


# ---------------------------------------------------------------------------
# leg B: OINK graph script
# ---------------------------------------------------------------------------

def components_reference(e: np.ndarray, verts: np.ndarray) -> np.ndarray:
    """Label of every vertex in ``verts`` = smallest vertex id of its
    component: numpy min-label hooking with pointer jumping."""
    src = np.searchsorted(verts, e[:, 0])
    dst = np.searchsorted(verts, e[:, 1])
    label = np.arange(len(verts))
    while True:
        low = np.minimum(label[src], label[dst])
        new = label.copy()
        np.minimum.at(new, src, low)
        np.minimum.at(new, dst, low)
        new = new[new]
        if np.array_equal(new, label):
            return verts[label]
        label = new


def pagerank_reference(e: np.ndarray, verts: np.ndarray, iters: int,
                       damping: float = 0.85) -> tuple:
    """``iters`` steps of float64 power iteration from the uniform
    vector, dangling mass spread uniformly; returns (ranks, the largest
    rank change of each step) — the command's stopping rule reads the
    latter."""
    n = len(verts)
    src = np.searchsorted(verts, e[:, 0])
    dst = np.searchsorted(verts, e[:, 1])
    deg = np.bincount(src, minlength=n).astype(np.float64)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    r, deltas = np.full(n, 1.0 / n), []
    for _ in range(iters):
        inflow = np.bincount(dst, weights=(r * inv)[src], minlength=n)
        dangling = r[deg == 0].sum() / n
        r2 = (1.0 - damping) / n + damping * (inflow + dangling)
        deltas.append(float(np.abs(r2 - r).max()))
        r = r2
    return r, deltas


def _mr_edges(mr) -> np.ndarray:
    from gpu_mapreduce_tpu.oink.kernels import kv_keys
    rows = []
    mr.scan_kv(lambda fr, p: rows.append(kv_keys(fr)), batch=True)
    return np.concatenate(rows).astype(np.uint64)


def _pack(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(a, b) vertex pairs as one u64 each (ids are below 2**32, checked
    by the caller) — numpy sorts those far faster than rows."""
    return (a << np.uint64(32)) | b


def _read_pairs(prefix: str, dtype) -> tuple:
    """'key value' lines of an -o output: one file, or one per shard."""
    files = sorted(glob.glob(prefix + "*"))
    check(files, f"no output at {prefix}")
    rows = [np.loadtxt(f, dtype=dtype, ndmin=2) for f in files]
    rows = np.concatenate([r for r in rows if len(r)])
    order = np.argsort(rows[:, 0], kind="stable")
    return rows[order, 0], rows[order, 1]


def leg_graph(mesh, workdir: str, scale: int = RMAT_SCALE,
              seed: int = SEED) -> dict:
    import io

    from gpu_mapreduce_tpu.oink.objects import _mesh_frame
    from gpu_mapreduce_tpu.oink.script import OinkScript
    from gpu_mapreduce_tpu.parallel.mesh import mesh_axis_size
    ndev = mesh_axis_size(mesh)
    out = os.path.join(workdir, "graph")
    os.makedirs(out, exist_ok=True)
    screen = io.StringIO()
    script = OinkScript(comm=mesh, screen=screen)
    commands = [
        f"rmat {scale} {RMAT_EDGE_FACTOR} 0.57 0.19 0.19 0.05 0.0 {seed} "
        f"-o NULL mre",
        "edge_upper -i mre -o NULL mru",
        f"cc_find 0 -i mru -o {out}/cc NULL",
        f"pagerank {PAGERANK_TOL} 100 0.85 -i mre -o {out}/pr NULL",
    ]
    seconds = {}
    for line in commands:
        t0 = time.perf_counter()
        script.run_string(line)
        seconds[line.split()[0]] = time.perf_counter() - t0
    messages = screen.getvalue().strip().splitlines()

    t0 = time.perf_counter()
    mre, mru = script.obj.get_mr("mre"), script.obj.get_mr("mru")
    e = _mr_edges(mre)
    nedges = (1 << scale) * RMAT_EDGE_FACTOR
    check(e.shape == (nedges, 2), f"rmat made {e.shape} edges")
    check(int(e.max()) < (1 << scale) <= (1 << 32),
          "rmat vertex id out of range")
    check(len(np.unique(_pack(e[:, 0], e[:, 1]))) == nedges,
          "rmat edges are not unique")
    verts = np.unique(e)

    upper = _mr_edges(mru)
    keep = e[:, 0] != e[:, 1]
    packed = np.unique(_pack(e[keep].min(1), e[keep].max(1)))
    want_upper = np.stack([packed >> np.uint64(32),
                           packed & np.uint64(0xFFFFFFFF)], 1)
    check(np.array_equal(np.sort(_pack(upper[:, 0], upper[:, 1])), packed),
          f"edge_upper: {len(upper)} edges, reference {len(packed)}")

    cc_v, cc_zone = _read_pairs(os.path.join(out, "cc"), np.uint64)
    uverts = np.unique(want_upper)
    check(np.array_equal(cc_v, uverts), "cc_find: vertex set differs")
    want_zone = components_reference(want_upper, uverts)
    ncc = len(np.unique(want_zone))
    check(np.array_equal(cc_zone, want_zone),
          f"cc_find: labels differ from the reference ({ncc} components)")
    check(any(f"CC_find: {ncc} components" in m for m in messages),
          f"cc_find did not report {ncc} components: {messages}")

    pr_v, pr = _read_pairs(os.path.join(out, "pr"), np.float64)
    check(np.array_equal(pr_v.astype(np.uint64), verts),
          "pagerank: vertex set differs")
    # same rule as the command: iterate until no rank moves by more
    # than the tolerance.  The reference takes the steps the command
    # reported, and its own rank changes must agree that this was the
    # step to stop at (2 % slack: the command iterates in float32)
    found = [re.search(r"PageRank: .* (\d+) iterations", m)
             for m in messages]
    iters = [int(m.group(1)) for m in found if m]
    check(len(iters) == 1 and 0 < iters[0] < 100,
          f"pagerank did not report its iterations: {messages}")
    want_pr, deltas = pagerank_reference(e, verts, iters[0])
    check(deltas[-1] <= PAGERANK_TOL * 1.02
          and all(d > PAGERANK_TOL * 0.98 for d in deltas[:-1]),
          f"pagerank stopped after {iters[0]} iterations; the reference's "
          f"rank changes were {deltas}")
    check(bool(np.all(np.isfinite(pr))), "pagerank: non-finite rank")
    l1 = float(np.abs(pr - want_pr).sum())
    check(l1 < 1e-5, f"pagerank: L1 error {l1:.3g} against the reference")
    seconds["references"] = time.perf_counter() - t0

    report = {"scale": scale, "edges": nedges, "vertices": len(verts),
              "upper_edges": len(want_upper), "components": ncc,
              "pagerank_l1": l1, "messages": messages, "seconds": seconds}
    if ndev > 1:
        check_spread("rmat edges", _mesh_frame(mre), ndev)
        check_spread("edge_upper edges", _mesh_frame(mru), ndev)
        report["exchange"] = check_exchange("rmat", mre)
    return report


# ---------------------------------------------------------------------------

def _fmt(d: dict) -> str:
    return ", ".join(f"{k} {v:.1f}" for k, v in d.items())


def main() -> int:
    t_start = time.perf_counter()
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, found platform "
              f"{device['platform']!r} ({device['kind']}, "
              f"{device['count']} device(s)); nothing was run")
        return 1

    import gpu_mapreduce_tpu  # noqa: F401  (arms the compile cache)
    from gpu_mapreduce_tpu import native
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    count_compiles()
    print(f"device {device}; compile cache "
          f"{jax.config.jax_compilation_cache_dir}; native runtime "
          f"{'built' if native.available() else 'MISSING: '}"
          f"{native.build_error() or ''}", flush=True)

    mesh = make_mesh()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        t0 = time.perf_counter()
        a = leg_invertedindex(mesh, workdir)
        ta = time.perf_counter() - t0
        ca = dict(COMPILES)
        print(f"leg invertedindex: {a['files']} files x "
              f"{a['file_bytes'] >> 20} MB ({a['bytes']} bytes), "
              f"{a['npairs']} pairs, {a['nunique']} urls, "
              f"{a['parts']} part files, equal to the regex reference; "
              f"interpret {a['interpret']}, Mosaic custom call in the "
              f"extract program {a['mosaic_custom_call']}; map stats "
              f"{a['map_stats']}; exchange {a.get('exchange')}; "
              f"compile requests {ca['requests']} (cache {ca['hits']})\n"
              f"  smoke seconds: {_fmt(a['seconds'])}; leg wall {ta:.1f}\n"
              f"  stage seconds: {_fmt(a['stages'])}", flush=True)
        shutil.rmtree(os.path.join(workdir, "corpus"))

        t0 = time.perf_counter()
        b = leg_graph(mesh, workdir)
        tb = time.perf_counter() - t0
        print(f"leg graph: rmat {b['scale']} x {RMAT_EDGE_FACTOR} "
              f"(Graph500 a,b,c,d): {'; '.join(b['messages'])}; "
              f"{b['upper_edges']} upper edges and {b['components']} "
              f"components equal to the references, pagerank L1 error "
              f"{b['pagerank_l1']:.2g}; exchange {b.get('exchange')}; "
              f"compile requests {COMPILES['requests'] - ca['requests']} "
              f"(cache {COMPILES['hits'] - ca['hits']})\n"
              f"  command seconds: {_fmt(b['seconds'])}; leg wall {tb:.1f}",
              flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    print(f"compile requests {COMPILES['requests']}, served from the cache "
          f"{COMPILES['hits']}, compiled "
          f"{COMPILES['requests'] - COMPILES['hits']}; wall "
          f"{time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
