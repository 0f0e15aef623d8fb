"""Scale soak: RMAT graph workloads on the real chip, recording numbers
into BASELINE.json["published"] (VERDICT r1 #10 — the regression guard for
the device-tier graph iteration and the out-of-core machinery).

Runs on whatever jax.default_backend() provides (the driver's TPU, or CPU
with the fake-cluster flags).  Workloads, all through the public
framework surface:

* rmat generation (models/rmat.generate_unique — the oink rmat cull loop)
* degree: edges → collate → count on a 1-chip mesh (device tier)
* cc_find: the full OINK command on a 1-chip mesh (device-resident loop)
* pagerank: models/pagerank sharded convergence loop — edges/sec/iter,
  the BASELINE.json north-star metric (the reference's pagerank is a
  stub, oink/pagerank.cpp:53-55, so this races no reference number)

Usage:  python soak.py [--metrics-every N] [--chaos SEED] [dist|stream]
        (`soak.py stream` runs ONLY the standing-query soak: a
        feed-mode stream on an in-process daemon, publishing
        stream_batches_per_sec + stream_lag_p99_ms — doc/streaming.md)
        (`soak.py dist` runs ONLY the multi-process shrink-and-resume
        soak: a 4-process mrlaunch wordfreq with one rank SIGKILLed
        mid-run, asserting byte-identical output vs an uninterrupted
        2-process run and publishing dist_recover_seconds —
        doc/distributed.md)
        (scale from SOAK_SCALE, default 18; N also via
        SOAK_METRICS_EVERY — print a live metrics snapshot line after
        every N workloads and write a final full-registry snapshot to
        SOAK_METRICS_OUT, default soak_metrics.json, next to the log.
        --chaos SEED adds a chaos workload: the standard wordfreq +
        external-sort pipelines re-run under a small seeded fault
        schedule at every registered ft/ site with retries armed,
        asserting output equality with the fault-free run and
        publishing the retry/fault counters — doc/reliability.md)
Writes: BASELINE.json published.{rmat_edges_per_sec, degree_edges_per_sec,
        cc_find_edges_per_sec_per_iter, pagerank_edges_per_sec_per_iter}
"""

import json
import os
import sys
import time

import numpy as np


def metrics_line(n: int, name: str) -> str:
    """One compact live-metrics JSON line (a multi-hour soak window is
    watched by tailing the log; the full registry lands in the final
    snapshot file): cumulative counters + plan-cache hit ratio after
    workload #n."""
    from gpu_mapreduce_tpu.core.runtime import global_counters
    from gpu_mapreduce_tpu.plan.cache import cache_stats
    c = global_counters().snapshot()
    p = cache_stats()["plan"]
    tot = p["hits"] + p["misses"]
    return json.dumps({
        "soak_metrics": {"after": name, "workload": n,
                         "ndispatch": c["ndispatch"],
                         "shuffle_mb": round(c["cssize"] / (1 << 20), 3),
                         "pad_mb": round(c["cspad"] / (1 << 20), 3),
                         "spill_mb": round(c["wsize"] / (1 << 20), 3),
                         "hbm_hiwater_mb": round(c["msizemax"] / (1 << 20),
                                                 3),
                         "comm_s": round(c["commtime"], 3),
                         "plan_hit_ratio": round(p["hits"] / tot, 3)
                         if tot else 0.0}})


def write_final_metrics(path: str) -> None:
    """The full labeled registry snapshot + counters + cache stats, as
    one JSON document next to the soak log."""
    from gpu_mapreduce_tpu.core.runtime import global_counters
    from gpu_mapreduce_tpu.obs import metrics as _metrics
    from gpu_mapreduce_tpu.plan.cache import cache_stats
    doc = {"utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "counters": global_counters().snapshot(),
           "plan": cache_stats(),
           "metrics": _metrics.snapshot()}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, default=str)
    print(f"final metrics snapshot -> {path}")


def main():
    import jax
    jax.config.update("jax_enable_x64", True)
    from gpu_mapreduce_tpu.models.rmat import generate_unique
    from gpu_mapreduce_tpu.models.pagerank import pagerank_sharded
    from gpu_mapreduce_tpu.oink import ObjectManager, run_command
    from gpu_mapreduce_tpu.oink.kernels import count, edge_to_vertices
    from gpu_mapreduce_tpu.core.mapreduce import MapReduce
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh

    # a malformed value warns and falls back instead of killing a
    # multi-hour capture window before its first workload
    from gpu_mapreduce_tpu.utils.env import env_flag, env_knob, env_str
    scale = env_knob("SOAK_SCALE", int, 18)
    nnz = env_knob("SOAK_NNZ", int, 8)
    nmesh = env_knob("SOAK_MESH", int, 1)  # VERDICT r3 #6: P>1
    metrics_every = env_knob("SOAK_METRICS_EVERY", int, 0)
    if "--metrics-every" in sys.argv:
        i = sys.argv.index("--metrics-every")
        try:
            metrics_every = int(sys.argv[i + 1]) \
                if i + 1 < len(sys.argv) else 1
        except ValueError as e:
            print(f"--metrics-every ignored: {e!r}", file=sys.stderr)
            metrics_every = 0
    chaos_seed = env_knob("SOAK_CHAOS", int, None)
    if "--chaos" in sys.argv:
        i = sys.argv.index("--chaos")
        try:
            chaos_seed = int(sys.argv[i + 1]) \
                if i + 1 < len(sys.argv) else 0
        except ValueError as e:
            print(f"--chaos ignored: {e!r}", file=sys.stderr)
            chaos_seed = None

    backend = jax.default_backend()
    published = {}
    errors = {}

    # every workload runs under a soak.<name> span; the end-of-run
    # per-op table comes from the same tracer the library reports into
    # (MRTPU_TRACE additionally streams the JSONL trace file)
    from gpu_mapreduce_tpu.obs import get_tracer, per_op_table
    tracer = get_tracer().enable()
    if metrics_every:
        # live metrics (obs/metrics.py): span bridge + registry, so the
        # periodic lines and the final snapshot have per-op histograms
        from gpu_mapreduce_tpu.obs.metrics import enable_metrics
        enable_metrics()

    def guard(name, fn):
        """One workload failing (a Mosaic rejection, say) is recorded
        in ``errors`` and must not forfeit the other rows."""
        try:
            with tracer.span("soak." + name, cat="soak"):
                fn()
        except Exception as e:
            import traceback
            errors[name] = repr(e)[:300]
            traceback.print_exc()

    # -- rmat (fatal if it fails: every workload consumes the edges) ---
    t0 = time.perf_counter()
    edges, iters = generate_unique(seed=11, nlevels=scale, nnonzero=nnz,
                                   abcd=(0.57, 0.19, 0.19, 0.05), frac=0.1)
    dt = time.perf_counter() - t0
    nedges = len(edges)
    published["rmat_edges_per_sec"] = round(nedges / dt, 1)
    print(f"rmat scale={scale} nnz={nnz}: {nedges} edges in {iters} "
          f"rounds, {dt:.2f}s -> {nedges / dt:,.0f} edges/s")

    mesh = make_mesh(nmesh)

    def do_degree():
        # run twice at full shape: the first pass pays the XLA compiles
        # (bench.py warms the same way); recorded number = steady state
        e64 = edges.astype(np.uint64)

        def run_degree():
            mr = MapReduce(mesh)
            mr.map(1, lambda i, kv, p: kv.add_batch(
                e64, np.zeros(len(e64), np.uint8)))
            t0 = time.perf_counter()
            mr.map_mr(mr, edge_to_vertices, batch=True)
            mr.collate()
            ndeg = mr.reduce(count, batch=True)
            return ndeg, time.perf_counter() - t0

        run_degree()
        ndeg, dt = run_degree()
        published["degree_edges_per_sec"] = round(nedges / dt, 1)
        print(f"degree: {ndeg} vertices, {dt:.2f}s -> "
              f"{nedges / dt:,.0f} edges/s (warm)")

    def do_cc():
        import tempfile
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "edges.txt")
            sub = edges[: min(len(edges), 1 << (scale - 1))]
            sub = sub[sub[:, 0] != sub[:, 1]]
            np.savetxt(path, sub, fmt="%d")
            run_command("cc_find", ["0"], obj=ObjectManager(comm=mesh),
                        inputs=[path], screen=False)  # warm the compile
            obj = ObjectManager(comm=mesh)
            t0 = time.perf_counter()
            cmd = run_command("cc_find", ["0"], obj=obj, inputs=[path],
                              screen=False)
            dt = time.perf_counter() - t0
            per_iter = dt / max(1, cmd.niterate)
            published["cc_find_edges_per_sec_per_iter"] = round(
                len(sub) / per_iter, 1)
            print(f"cc_find: {cmd.ncc} components, {cmd.niterate} iters, "
                  f"{dt:.2f}s -> {len(sub) / per_iter:,.0f} edges/s/iter")

    def do_sssp():
        from gpu_mapreduce_tpu.models.sssp import prepare_bellman_ford
        nv = 1 << scale
        srcv = edges[:, 0].astype(np.int32)
        dstv = edges[:, 1].astype(np.int32)
        w = np.random.default_rng(7).uniform(0.5, 5.0, len(edges))
        bf = prepare_bellman_ford(mesh, srcv, dstv, w, nv)  # upload once
        bf(0)                                               # warm
        t0 = time.perf_counter()
        titers = 0
        for sidx in (0, 1, 2, 3):
            _, _, it = bf(sidx)
            titers += max(1, it)
        dt = time.perf_counter() - t0
        published["sssp_edges_per_sec_per_iter"] = round(
            nedges / (dt / titers), 1) if titers else 0.0
        print(f"sssp: 4 sources, {titers} total iters, {dt:.2f}s -> "
              f"{nedges / (dt / titers):,.0f} edges/s/iter")

    def do_luby():
        from gpu_mapreduce_tpu.models.luby import luby_mis_sharded
        from gpu_mapreduce_tpu.oink.commands.luby import vertex_rand
        uverts, uinv = np.unique(edges.reshape(-1), return_inverse=True)
        lsrc = uinv.reshape(-1, 2)[:, 0]
        ldst = uinv.reshape(-1, 2)[:, 1]
        keep = lsrc != ldst
        prio = vertex_rand(uverts, 99)
        luby_mis_sharded(mesh, lsrc[keep], ldst[keep], prio, len(uverts))
        t0 = time.perf_counter()
        state, lit = luby_mis_sharded(mesh, lsrc[keep], ldst[keep], prio,
                                      len(uverts))
        dt = time.perf_counter() - t0
        published["luby_edges_per_sec_per_iter"] = round(
            int(keep.sum()) / (dt / max(1, lit)), 1)
        print(f"luby: {int((state == 1).sum())} MIS vertices, {lit} "
              f"rounds, {dt:.2f}s -> "
              f"{int(keep.sum()) / (dt / max(1, lit)):,.0f} edges/s/round")

    def do_tri():
        # triangle counting is O(sum of low-degree^2) — the scale-20
        # RMAT full set is too hot-hub-heavy for one core, so soak the
        # fused engine on a smaller 2^(scale-3) edge subset (cc, with
        # its linear per-iter cost, takes 2^(scale-1))
        import tempfile
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "edges.txt")
            sub = edges[: min(len(edges), 1 << max(4, scale - 3))]
            sub = sub[sub[:, 0] != sub[:, 1]]
            np.savetxt(path, sub, fmt="%d")
            run_command("tri_find", [], obj=ObjectManager(comm=mesh),
                        inputs=[path], screen=False)  # warm the compile
            obj = ObjectManager(comm=mesh)
            t0 = time.perf_counter()
            cmd = run_command("tri_find", [], obj=obj, inputs=[path],
                              screen=False)
            dt = time.perf_counter() - t0
            published["tri_edges_per_sec"] = round(len(sub) / dt, 1)
            print(f"tri_find: {cmd.ntri} triangles over {len(sub)} edges, "
                  f"{dt:.2f}s -> {len(sub) / dt:,.0f} edges/s")

    def do_external():
        # the reference's identity: any op in a few fixed pages
        # (doc/Interface_c++.txt:39-59).  Sort 16 B/row pairs of ~8x the
        # page budget through the spill + k-way external merge and
        # record throughput AND the peak-resident/budget ratio — the
        # first published number for the out-of-core machinery
        import tempfile

        from gpu_mapreduce_tpu.core.runtime import global_counters
        rows = nedges  # same scale knob as the graph workloads
        memsize = max(1, (rows * 16) >> 23)   # budget ~ 1/8 of the data
        rng2 = np.random.default_rng(5)
        keys = rng2.integers(0, 1 << 62, rows).astype(np.uint64)
        vals = rng2.integers(0, 1 << 30, rows).astype(np.uint64)
        with tempfile.TemporaryDirectory() as tmp:
            mre = MapReduce(outofcore=1, memsize=memsize, maxpage=1,
                            fpath=tmp)
            step = max(1, rows // 8)
            mre.map(1, lambda i, kv, p: [
                kv.add_batch(keys[s:s + step], vals[s:s + step])
                for s in range(0, rows, step)])
            c = global_counters()
            c.msize = c.msizemax = 0
            t0 = time.perf_counter()
            mre.sort_keys(1)
            dt = time.perf_counter() - t0
            budget = memsize << 20
            published["external_sort_rows_per_sec"] = round(rows / dt, 1)
            published["external_sort_peak_over_budget"] = round(
                c.msizemax / budget, 2)
            print(f"external sort: {rows} rows, budget {memsize} MB, "
                  f"{dt:.2f}s -> {rows / dt:,.0f} rows/s, peak "
                  f"{c.msizemax / budget:.2f}x budget")

    def do_ingest_overlap():
        # overlapped-ingest row (exec/): the mesh chunked reader under
        # sustained load with the prefetch pipeline on — words tokenize
        # + intern per shard while the next shard's slice reads.  The
        # published number is ingest throughput; the overlap ratio of
        # the prefetch path rides along so a soak log shows whether the
        # pipeline actually hid the reads (doc/perf.md)
        import tempfile
        from gpu_mapreduce_tpu.exec import exec_stats, reset_stats
        from gpu_mapreduce_tpu.utils.io import read_words
        rng3 = np.random.default_rng(17)
        vocab = np.array([b"w%05d" % i for i in range(4096)], object)
        with tempfile.TemporaryDirectory() as tmp:
            paths = []
            nwords_per_file = 1 << max(12, scale - 2)
            for i in range(8):
                words = vocab[rng3.integers(0, len(vocab),
                                            nwords_per_file)]
                p = os.path.join(tmp, f"corpus-{i}.txt")
                with open(p, "wb") as f:
                    f.write(b" ".join(words.tolist()))
                paths.append(p)
            nbytes = sum(os.path.getsize(p) for p in paths)

            def tokenize(itask, chunk, kv, ptr):
                ws = read_words(chunk)
                kv.add_batch(ws, np.ones(len(ws), np.int64))

            def run_ingest():
                mr = MapReduce(mesh)
                t0 = time.perf_counter()
                n = mr.map_file_str(64, paths, 0, 0, b" ", 64, tokenize)
                return mr, n, time.perf_counter() - t0

            run_ingest()                 # warm (page cache + compiles)
            reset_stats()                # publish the MEASURED run's
            mr, n, dt = run_ingest()     # ratio, not warm+measured blend
            # SOAK_MESH>1 takes the mesh chunk pipeline; a 1-device
            # mesh ingests through the serial prefetch path instead
            st = exec_stats()["overlap"]
            ov = st.get("ingest.chunks") or st.get("ingest.serial", {})
            published["ingest_overlap_words_per_sec"] = round(n / dt, 1)
            published["ingest_overlap_ratio"] = ov.get("overlap_ratio",
                                                       0.0)
            print(f"ingest: {n} words / {nbytes >> 20} MB in {dt:.2f}s "
                  f"({mr.last_ingest.get('mode')}) -> {n / dt:,.0f} "
                  f"words/s, overlap ratio "
                  f"{ov.get('overlap_ratio', 0.0):.2f}")

    def do_shuffle_skew():
        # wire-codec row (parallel/wire.py): a zipf-keyed intcount-shape
        # shuffle — maximum key cardinality, RMAT-hub skew, minimum
        # payload — through aggregate/convert/count under the default
        # MRTPU_WIRE, publishing sustained shuffle throughput and the
        # exchange compression ratio the codec achieved (doc/perf.md).
        # Needs a real multi-shard mesh: a 1-wide mesh never exchanges,
        # so the row then reports ratio 0 with a note instead of lying
        from gpu_mapreduce_tpu.oink.kernels import count as count_k
        wmesh = mesh if nmesh > 1 else make_mesh(
            min(8, len(jax.devices())))
        rng6 = np.random.default_rng(29)
        rows = min(max(nedges, 1 << 16), 1 << 21)
        zkeys = np.minimum(rng6.zipf(1.3, rows),
                           1 << 22).astype(np.uint64)
        ones = np.ones(rows, np.uint32)

        def run_shuffle():
            mr = MapReduce(wmesh)
            mr.map(1, lambda i, kv, p: kv.add_batch(zkeys, ones))
            t0 = time.perf_counter()
            mr.aggregate()
            mr.convert()
            nu = mr.reduce(count_k, batch=True)
            return nu, time.perf_counter() - t0, mr.last_exchange

        run_shuffle()                       # warm the compiles
        nu, dt, st = run_shuffle()
        published["shuffle_pairs_per_sec"] = round(rows / dt, 1)
        ratio = float(getattr(st, "wire_ratio", 0.0) or 0.0)
        published["wire_compression_ratio"] = round(ratio, 4)
        from gpu_mapreduce_tpu.parallel.mesh import mesh_axis_size
        width = mesh_axis_size(wmesh)
        print(f"shuffle_skew: {rows} pairs, {nu} unique over "
              f"{width} shards in {dt:.2f}s -> {rows / dt:,.0f} "
              f"pairs/s, wire ratio {ratio:.2f}"
              + (" (1-wide mesh: no exchange)" if width == 1 else ""))

    def do_group_heavy():
        # fusion-v2 row (plan/fuser + ops/pallas/group): the canonical
        # group-bound pipeline (moderate key cardinality, every row
        # lands in a group) run fused on the mesh under
        # MRTPU_PALLAS_GROUP={0,1} — publishes sustained group-path
        # throughput for both engines so the kernel-vs-sort delta is
        # tracked across the soak series, and asserts the two engines'
        # outputs agree (the byte-identity contract of doc/perf.md)
        from gpu_mapreduce_tpu.oink.kernels import count as count_k
        wmesh = mesh if nmesh > 1 else make_mesh(
            min(8, len(jax.devices())))
        # capped below the other workloads' scale: on CPU the pallas=1
        # leg runs the kernels in interpret mode (sequential emulated
        # scatter — the honest cost of forcing them off-TPU, doc/perf.md)
        rows = min(max(nedges, 1 << 16), 1 << 18)
        gkeys = ((np.arange(rows, dtype=np.uint64) * 7919)
                 % max(rows >> 6, 97)).astype(np.uint64)
        ones = np.ones(rows, np.int64)

        def run_group():
            mr = MapReduce(wmesh, fuse=1)
            mr.map(1, lambda i, kv, p: kv.add_batch(gkeys, ones))
            t0 = time.perf_counter()
            mr.aggregate()
            mr.convert()
            nu = int(mr.reduce(count_k, batch=True))
            return nu, time.perf_counter() - t0

        # mrlint: disable=knob-bypass — A/B save/restore must keep the
        # unset-vs-empty distinction env_str collapses
        prev = os.environ.get("MRTPU_PALLAS_GROUP")
        results = {}
        try:
            for flag in ("0", "1"):
                os.environ["MRTPU_PALLAS_GROUP"] = flag
                run_group()            # compiles + arm megafuse caches
                run_group()
                nu, dt = run_group()   # steady state (megafused)
                results[flag] = nu
                published[f"group_rows_per_sec_pallas{flag}"] = round(
                    rows / dt, 1)
                print(f"group_heavy[pallas={flag}]: {rows} rows, {nu} "
                      f"groups in {dt:.2f}s -> {rows / dt:,.0f} rows/s")
        finally:
            if prev is None:
                os.environ.pop("MRTPU_PALLAS_GROUP", None)
            else:
                os.environ["MRTPU_PALLAS_GROUP"] = prev
        if results.get("0") != results.get("1"):
            raise RuntimeError(
                f"group_heavy engines disagree: {results}")
        # headline = the SHIPPED default's engine (the sort path
        # unless MRTPU_PALLAS_GROUP=1 was set for the whole run)
        from gpu_mapreduce_tpu.ops.pallas.group import \
            pallas_group_enabled
        default_leg = "1" if pallas_group_enabled() else "0"
        published["group_rows_per_sec"] = \
            published[f"group_rows_per_sec_pallas{default_leg}"]

    def do_pagerank():
        n = 1 << scale
        src = edges[:, 0].astype(np.int32)
        dst = edges[:, 1].astype(np.int32)
        pagerank_sharded(mesh, src, dst, n, tol=1e-6, maxiter=20)  # warm
        t0 = time.perf_counter()
        ranks, niter = pagerank_sharded(mesh, src, dst, n, tol=1e-6,
                                        maxiter=20)
        dt = time.perf_counter() - t0
        per_iter = dt / max(1, niter)
        published["pagerank_edges_per_sec_per_iter"] = round(
            nedges / per_iter, 1)
        print(f"pagerank: {niter} iters, {dt:.2f}s -> "
              f"{nedges / per_iter:,.0f} edges/s/iter "
              f"(sum={float(np.asarray(ranks).sum()):.4f})")

    def do_pagerank_northstar():
        # BASELINE.json's north-star metric: PageRank edges/sec/iter on
        # the RMAT-22 graph (VERDICT r4 #3 — the first current-code TPU
        # measurement of this row).  Separate from do_pagerank so the
        # base-scale row still lands if the big graph exhausts a window.
        from gpu_mapreduce_tpu.utils.env import env_knob
        prs = env_knob("SOAK_PR_SCALE", int, 0)
        if prs <= 0:
            return
        if prs == scale:
            # the base-scale pagerank row IS the north-star measurement
            # at this scale — alias it so the rmat<N> key is never
            # silently absent (r5 review)
            v = published.get("pagerank_edges_per_sec_per_iter")
            if v is not None:
                published[f"pagerank_rmat{prs}_edges_per_sec_per_iter"] = v
                print(f"pagerank rmat{prs}: aliased from base-scale row")
            return
        t0 = time.perf_counter()
        e2, _ = generate_unique(seed=13, nlevels=prs, nnonzero=nnz,
                                abcd=(0.57, 0.19, 0.19, 0.05), frac=0.1)
        print(f"rmat scale={prs}: {len(e2)} edges in "
              f"{time.perf_counter() - t0:.1f}s (north-star graph)")
        n = 1 << prs
        src = e2[:, 0].astype(np.int32)
        dst = e2[:, 1].astype(np.int32)
        pagerank_sharded(mesh, src, dst, n, tol=1e-6, maxiter=20)  # warm
        t0 = time.perf_counter()
        ranks, niter = pagerank_sharded(mesh, src, dst, n, tol=1e-6,
                                        maxiter=20)
        dt = time.perf_counter() - t0
        per_iter = dt / max(1, niter)
        published[f"pagerank_rmat{prs}_edges_per_sec_per_iter"] = round(
            len(e2) / per_iter, 1)
        print(f"pagerank rmat{prs}: {niter} iters, {dt:.2f}s -> "
              f"{len(e2) / per_iter:,.0f} edges/s/iter "
              f"(sum={float(np.asarray(ranks).sum()):.4f})")

    def do_chaos():
        # chaos round (ft/): the standard wordfreq + external-sort
        # shapes re-run under a seeded fault schedule hitting EVERY
        # registered site, with retry budgets armed; the run only
        # publishes if the faulted output equals the fault-free run —
        # the soak-scale version of tests/test_ft.py's chaos goldens
        import collections
        import tempfile
        from gpu_mapreduce_tpu import ft
        from gpu_mapreduce_tpu.ops.reduces import count as count_kernel
        from gpu_mapreduce_tpu.utils.io import read_words

        def wordfreq_pairs(files, ckpt):
            mr = MapReduce(mesh)

            def fileread(itask, fname, kv, ptr):
                with open(fname, "rb") as f:
                    ws = read_words(f.read())
                kv.add_batch(ws, np.ones(len(ws), np.int64))

            mr.map_files(files, fileread)
            mr.collate()
            mr.reduce(count_kernel, batch=True)
            mr.save(ckpt)
            return sorted((bytes(k), int(v)) for fr in mr.kv.frames()
                          for k, v in fr.pairs())

        def extsort_rows(tag, fpath):
            rng4 = np.random.default_rng(23)
            # at least 2 MB of 16 B rows: the 1 MB page budget must
            # actually spill, or the spill.* sites never probe
            rows = max(1 << 17, min(nedges, 1 << 18))
            keys = rng4.integers(0, 1 << 40, rows).astype(np.uint64)
            mre = MapReduce(outofcore=1, memsize=1, maxpage=1,
                            fpath=fpath)
            step = max(1, rows // 5)
            mre.map(1, lambda i, kv, p: [
                kv.add_batch(keys[s:s + step], keys[s:s + step])
                for s in range(0, rows, step)])
            mre.sort_keys(1)
            return [int(k) for fr in mre.kv.frames()
                    for k, _ in fr.pairs()]

        with tempfile.TemporaryDirectory() as tmp:
            rng3 = np.random.default_rng(chaos_seed)
            vocab = np.array([b"w%04d" % i for i in range(512)], object)
            files = []
            for i in range(6):
                ws = vocab[rng3.integers(0, len(vocab), 4096)]
                p = os.path.join(tmp, f"chaos-{i}.txt")
                with open(p, "wb") as f:
                    f.write(b" ".join(ws.tolist()))
                files.append(p)
            clean_wf = wordfreq_pairs(files, os.path.join(tmp, "ck0"))
            clean_es = extsort_rows("clean", os.path.join(tmp, "sp0"))
            ft.reset()
            # rate × probe counts ⇒ a handful of faults per site;
            # max_faults=3 bounds the worst case well under the budget
            # (ingest.read + ingest.tokenize share a task's budget)
            for site in ft.SITES:
                ft.schedule(site=site, rate=0.2, seed=chaos_seed,
                            max_faults=3)
                ft.set_budget(site, 8)
            try:
                chaos_wf = wordfreq_pairs(files, os.path.join(tmp,
                                                              "ck1"))
                chaos_es = extsort_rows("chaos", os.path.join(tmp,
                                                              "sp1"))
                assert chaos_wf == clean_wf, "chaos wordfreq diverged"
                assert chaos_es == clean_es, "chaos extsort diverged"
                faults = ft.fault_counts()
                retries = ft.retries_snapshot()
                # a chaos round that injected NOTHING proved nothing —
                # a schedule regression must read as a failed workload,
                # never as a green chaos_ok over two fault-free runs
                assert sum(faults.values()) >= 1, \
                    "chaos schedule injected no faults"
                published["chaos_ok"] = 1
                published["chaos_faults_injected"] = int(
                    sum(faults.values()))
                published["chaos_retries_total"] = int(sum(
                    n for (s, o), n in retries.items()
                    if o == "retry"))
                published["chaos_recovered_total"] = int(sum(
                    n for (s, o), n in retries.items()
                    if o == "recovered"))
                per_site = collections.Counter(faults)
                print(f"chaos seed={chaos_seed}: outputs identical; "
                      f"{sum(faults.values())} faults injected "
                      f"({dict(per_site)}), "
                      f"{published['chaos_retries_total']} retries, "
                      f"{published['chaos_recovered_total']} recovered")
            finally:
                ft.reset()

            # kill-and-resume-ELSEWHERE (ISSUE 8): a journaled script
            # killed mid-run by an injected fatal resumes onto a mesh
            # of a DIFFERENT width; the tail's per-shard output files
            # must be byte-identical to an uninterrupted run on that
            # target width (topology-portable checkpoints)
            from gpu_mapreduce_tpu.ft.inject import InjectedFatal
            from gpu_mapreduce_tpu.oink.script import OinkScript
            alt = max(1, nmesh // 2) if nmesh > 1 else \
                min(2, len(jax.devices()))
            if alt != nmesh:
                jdir = os.path.join(tmp, "journal")
                sc = (f"mr a\n"
                      f"wordfreq 5 -i {files[0]} -o {tmp}/kw1 NULL\n"
                      f"wordfreq 5 -i {files[1]} -o {tmp}/kw2 NULL\n")
                os.environ["MRTPU_JOURNAL"] = jdir
                os.environ["MRTPU_CKPT_EVERY"] = "1"
                ft.schedule(site="ingest.read", kind="fatal", rate=1.0,
                            after=1, max_faults=1)
                try:
                    try:
                        OinkScript(comm=mesh, screen=False
                                   ).run_string(sc)
                        raise AssertionError(
                            "chaos kill never fired")
                    except InjectedFatal:
                        pass
                finally:
                    ft.reset()
                    os.environ.pop("MRTPU_JOURNAL", None)
                    os.environ.pop("MRTPU_CKPT_EVERY", None)
                amesh = make_mesh(alt)
                s = ft.resume(jdir, mesh=amesh)
                OinkScript(comm=amesh, screen=False).run_string(
                    f"mr a\n"
                    f"wordfreq 5 -i {files[0]} -o {tmp}/cw1 NULL\n"
                    f"wordfreq 5 -i {files[1]} -o {tmp}/cw2 NULL\n")
                import glob as _glob

                def fam(prefix):
                    return {os.path.basename(p).rsplit(".", 1)[-1]:
                            open(p).read() for p in
                            sorted(_glob.glob(prefix + "*"))}
                assert fam(f"{tmp}/kw2") == fam(f"{tmp}/cw2"), \
                    "resume-elsewhere tail diverged"
                published["chaos_resume_elsewhere_ok"] = 1
                published["chaos_resume_width"] = alt
                print(f"chaos resume-elsewhere: {nmesh}→{alt} shards, "
                      f"tail byte-identical")

    def do_serve():
        # MR-as-a-service row (serve/): N concurrent clients hammer an
        # in-process daemon with the same wordfreq workload — requests
        # amortize the plan cache across tenants, 429s are retried
        # after the daemon's own Retry-After (honest backpressure), and
        # the published numbers are sustained requests/sec + tail
        # latency (doc/serve.md)
        import tempfile
        import threading

        from gpu_mapreduce_tpu.obs import slo as obs_slo
        from gpu_mapreduce_tpu.serve import Server, ServeClient, ServeError
        nclients = env_knob("SOAK_SERVE_CLIENTS", int, 4)
        nreqs = env_knob("SOAK_SERVE_REQS", int, 8)
        # arm the SLO engine with soak-scale windows: the published
        # serve_slo_burn row is the burn ratio the engine computes from
        # the very session metrics the daemon feeds (doc/observability.md)
        slo_p99_ms = env_knob("SOAK_SERVE_SLO_P99_MS", float, 30000.0)
        eng = obs_slo.configure(obs_slo.parse_slo(
            f"tenant=*;p99_ms={slo_p99_ms};err_pct=1;windows=60,300"))
        try:
            with tempfile.TemporaryDirectory() as tmp:
                corpus = os.path.join(tmp, "corpus.txt")
                rng4 = np.random.default_rng(23)
                with open(corpus, "w") as f:
                    for w in rng4.integers(0, 2048, 60000):
                        f.write(f"w{w:04d} ")
                script = (f"variable files index {corpus}\n"
                          f"set fuse 1\n"
                          f"wordfreq 5 -i v_files\n")
                srv = Server(port=0, workers=min(4, max(1, nclients)),
                             queue_cap=max(8, nclients * 2),
                             state_dir=os.path.join(tmp, "state"))
                port = srv.start()
                lat: list = []
                nrejects = [0]
                client_errors: list = []
                profiles: list = []
                lock = threading.Lock()

                def one_client(ci: int):
                    try:
                        c = ServeClient.local(port)
                        done = 0
                        while done < nreqs:
                            t0 = time.perf_counter()
                            try:
                                r = c.submit(script=script, tenant=f"c{ci}")
                            except ServeError as e:
                                if e.code != 429:
                                    raise
                                with lock:
                                    nrejects[0] += 1
                                time.sleep(min(2.0, e.retry_after or 1))
                                continue
                            res = c.wait(r["id"], timeout=300)
                            if res.get("status") != "done":
                                raise RuntimeError(res.get("error"))
                            prof = (res.get("meta") or {}).get("profile")
                            with lock:
                                lat.append(time.perf_counter() - t0)
                                if prof:
                                    profiles.append(prof)
                            done += 1
                    except Exception as e:   # noqa: BLE001 — re-raised below
                        with lock:
                            client_errors.append(f"client {ci}: {e!r}")

                t0 = time.perf_counter()
                threads = [threading.Thread(target=one_client, args=(ci,))
                           for ci in range(nclients)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                wall = time.perf_counter() - t0
                # evaluate the SLO burn BEFORE shutdown drops the daemon's
                # collector: one forced tick over the finished sessions
                burn = eng.tick(force=True)
                srv.shutdown()
                if client_errors:
                    # a dead client thread must fail the workload, not
                    # silently inflate req/s computed from the full total
                    raise RuntimeError("; ".join(client_errors[:3]))
                total = nclients * nreqs
                published["serve_requests_per_sec"] = round(total / wall, 2)
                published["serve_p50_latency_s"] = round(
                    float(np.percentile(lat, 50)), 4)
                published["serve_p99_latency_s"] = round(
                    float(np.percentile(lat, 99)), 4)
                published["serve_admission_rejects"] = nrejects[0]
                published["serve_slo_burn"] = round(max(
                    (b for per in burn.values() for b in per.values()),
                    default=0.0), 4)
                if profiles:
                    med = lambda key: round(float(np.median(  # noqa: E731
                        [key(p) for p in profiles])), 2)
                    published["serve_profile_median_dispatches"] = \
                        med(lambda p: p.get("dispatches", 0))
                    published["serve_profile_median_exchange_kb"] = \
                        med(lambda p: p.get("exchange", {})
                            .get("sent_bytes", 0) / 1024.0)
                    published["serve_profile_median_spill_kb"] = \
                        med(lambda p: p.get("spill", {})
                            .get("write_bytes", 0) / 1024.0)
                print(f"serve: {nclients} clients x {nreqs} reqs in "
                      f"{wall:.2f}s -> {total / wall:,.1f} req/s, p50 "
                      f"{np.percentile(lat, 50):.3f}s, p99 "
                      f"{np.percentile(lat, 99):.3f}s, "
                      f"{nrejects[0]} 429s retried, slo burn "
                      f"{published['serve_slo_burn']}")
        finally:
            # don't leak the soak windows into MRTPU_SLO state,
            # even when a client thread failed the workload
            obs_slo.reset()

    def do_overload():
        # self-protection row (serve/overload.py, doc/serve.md#slo-
        # burn-shedding): ONE greedy tenant burns its SLO error budget
        # with expensive failing requests while polite tenants run
        # normal work.  The daemon must shed the GREEDY tenant (429 +
        # honest Retry-After) and keep the polite tenants' p99 inside
        # the soak bound — overload protection that picks the right
        # victim, asserted then published.
        import tempfile
        import threading

        from gpu_mapreduce_tpu.obs import slo as obs_slo
        from gpu_mapreduce_tpu.serve import Server, ServeClient, ServeError
        npolite = env_knob("SOAK_OVERLOAD_POLITE", int, 3)
        nreqs = env_knob("SOAK_OVERLOAD_REQS", int, 6)
        p99_bound_ms = env_knob("SOAK_OVERLOAD_P99_MS", float, 30000.0)
        eng = obs_slo.configure(obs_slo.parse_slo(
            "tenant=*;err_pct=5;windows=60,300"))
        try:
            with tempfile.TemporaryDirectory() as tmp:
                rng6 = np.random.default_rng(41)
                big = os.path.join(tmp, "big.txt")
                with open(big, "w") as f:
                    for w in rng6.integers(0, 2048, 40000):
                        f.write(f"w{w:04d} ")
                small = os.path.join(tmp, "small.txt")
                with open(small, "w") as f:
                    for w in rng6.integers(0, 256, 4000):
                        f.write(f"w{w:03d} ")
                # expensive AND failing: real shuffle work, then a bad
                # command — the burn engine sees failures, the cost
                # profiles see an expensive tenant
                greedy_script = (f"variable files index {big}\n"
                                 f"wordfreq 5 -i v_files\n"
                                 f"frobnicate\n")
                polite_script = (f"variable files index {small}\n"
                                 f"wordfreq 5 -i v_files\n")
                srv = Server(port=0, workers=2, queue_cap=16,
                             state_dir=os.path.join(tmp, "state"))
                port = srv.start()
                try:
                    seed_c = ServeClient.local(port)
                    # phase 1 — the greedy tenant builds its own case:
                    # failed sessions feed the burn engine, their cost
                    # feeds the shed ranking.  The shedder can trip
                    # MID-SEED (admission re-evaluates the burn within
                    # ~1 s of the failures) — an early 429 IS the
                    # feature engaging, not a seed failure
                    for _ in range(4):
                        try:
                            r = seed_c.submit(script=greedy_script,
                                              tenant="greedy")
                        except ServeError as e:
                            if e.code == 429:
                                break       # already shedding
                            raise
                        seed_c.wait(r["id"], timeout=300)
                    eng.tick(force=True)
                    assert eng.burning("greedy"), \
                        "greedy tenant never started burning"
                    # phase 2 — contention: greedy hammers, polite works
                    shed = [0]
                    polite_lat: list = []
                    client_errors: list = []
                    lock = threading.Lock()
                    stop = threading.Event()

                    def greedy_client():
                        c = ServeClient.local(port)
                        while not stop.is_set():
                            try:
                                r = c.submit(script=greedy_script,
                                             tenant="greedy")
                                c.wait(r["id"], timeout=300)
                            except ServeError as e:
                                if e.code != 429:
                                    with lock:
                                        client_errors.append(
                                            f"greedy: {e!r}")
                                    return
                                with lock:
                                    shed[0] += 1
                                stop.wait(min(2.0, e.retry_after or 1))

                    def polite_client(ci):
                        try:
                            c = ServeClient.local(port)
                            for _ in range(nreqs):
                                t0 = time.perf_counter()
                                r = c.submit(script=polite_script,
                                             tenant=f"polite{ci}",
                                             retry_after_wait=60.0)
                                res = c.wait(r["id"], timeout=300)
                                if res.get("status") != "done":
                                    raise RuntimeError(res.get("error"))
                                with lock:
                                    polite_lat.append(
                                        time.perf_counter() - t0)
                        except Exception as e:  # noqa: BLE001
                            with lock:
                                client_errors.append(
                                    f"polite{ci}: {e!r}")

                    g = threading.Thread(target=greedy_client)
                    polite = [threading.Thread(target=polite_client,
                                               args=(ci,))
                              for ci in range(npolite)]
                    g.start()
                    for t in polite:
                        t.start()
                    for t in polite:
                        t.join()
                    stop.set()
                    g.join(timeout=310)
                finally:
                    srv.shutdown()
                if client_errors:
                    raise RuntimeError("; ".join(client_errors[:3]))
                assert shed[0] > 0, \
                    "greedy tenant was never shed under overload"
                p99_ms = float(np.percentile(polite_lat, 99)) * 1000.0
                assert p99_ms <= p99_bound_ms, \
                    f"polite p99 {p99_ms:.0f}ms blew the " \
                    f"{p99_bound_ms:.0f}ms bound while greedy was shed"
                published["overload_shed_total"] = shed[0]
                published["overload_polite_p99_ms"] = round(p99_ms, 1)
                print(f"overload: greedy shed {shed[0]}x while "
                      f"{npolite} polite tenants x {nreqs} reqs held "
                      f"p99 {p99_ms:.0f}ms (bound {p99_bound_ms:.0f}ms)")
        finally:
            obs_slo.reset()

    def do_fleet():
        # serve-fleet row (serve/fleet.py + serve/router.py): N
        # subprocess replicas behind the consistent-hash router; one
        # replica is kill -9'd mid-soak with accepted work on it.  The
        # fleet must finish EVERY accepted request (fleet_requests_lost
        # is asserted 0, then published) and the takeover wall lands in
        # fleet_failover_seconds (doc/serve.md#the-serve-fleet)
        import signal as _signal
        import subprocess
        import tempfile

        from gpu_mapreduce_tpu.serve import (Router, ServeClient,
                                             ServeError, ring_route)
        nreplicas = max(2, env_knob("SOAK_FLEET_REPLICAS", int, 3))
        nreqs = env_knob("SOAK_FLEET_REQS", int, 12)
        repo = os.path.dirname(os.path.abspath(__file__))
        with tempfile.TemporaryDirectory() as tmp:
            corpus = os.path.join(tmp, "corpus.txt")
            rng5 = np.random.default_rng(31)
            with open(corpus, "w") as f:
                for w in rng5.integers(0, 512, 20000):
                    f.write(f"w{w:03d} ")
            script = (f"variable files index {corpus}\n"
                      f"wordfreq 5 -i v_files\n")
            root = os.path.join(tmp, "fleet")
            rids = [f"r{i}" for i in range(nreplicas)]
            # the replicas serve a lease-failover workload, not the
            # device: the parent holds the chip (one process per chip),
            # so they get the CPU explicitly, as mrlaunch's ranks do
            env = {**os.environ, "MRTPU_FLEET_SKEW": "0.3",
                   "JAX_PLATFORMS": "cpu"}
            procs = []
            for rid in rids:
                p = subprocess.Popen(
                    [sys.executable, "-m", "gpu_mapreduce_tpu.serve",
                     "--port", "0", "--fleet", root,
                     "--replica-id", rid, "--workers", "2",
                     "--lease", "1.0", "--heartbeat", "0.25"],
                    cwd=repo, env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.DEVNULL)
                json.loads(p.stdout.readline())   # wait for "serving"
                procs.append(p)
            rt = Router(root)
            rport = rt.start()
            try:
                c = ServeClient.local(rport)
                # session keys chosen so the victim (r0) definitely
                # holds accepted work when it dies
                keys, j = [], 0
                while len(keys) < nreqs:
                    target = ring_route(f"k{j}", rids)
                    if len(keys) < 4 and target != rids[0]:
                        j += 1
                        continue
                    keys.append(f"k{j}")
                    j += 1

                def submit_one(i):
                    while True:
                        try:
                            return c.submit(script=script,
                                            tenant=f"t{i % 4}",
                                            session=keys[i])["id"]
                        except ServeError as e:
                            if e.code not in (429, 503):
                                raise
                            time.sleep(min(2.0, e.retry_after or 1))

                sids = [submit_one(i) for i in range(nreqs // 2)]
                t_kill = time.perf_counter()
                os.kill(procs[0].pid, _signal.SIGKILL)
                procs[0].wait()
                sids += [submit_one(i)
                         for i in range(nreqs // 2, nreqs)]

                def res(sid):
                    try:
                        with open(os.path.join(
                                root, "results", sid + ".json")) as f:
                            return json.load(f)
                    except (OSError, ValueError):
                        return None

                deadline = time.monotonic() + 300
                remaining = set(sids)
                failover_done = None
                while remaining and time.monotonic() < deadline:
                    for sid in list(remaining):
                        r = res(sid)
                        if r is None:
                            continue
                        remaining.discard(sid)
                        if failover_done is None and \
                                (r.get("meta") or {}).get("failed_over"):
                            failover_done = time.perf_counter()
                    time.sleep(0.1)
                assert not remaining, \
                    f"fleet lost {len(remaining)} accepted requests: " \
                    f"{sorted(remaining)}"
                bad = [s for s in sids if res(s)["status"] != "done"]
                assert not bad, f"failed sessions: {bad}"
                nfo = sum(1 for s in sids
                          if res(s)["meta"].get("failed_over"))
                failover_s = (failover_done - t_kill) \
                    if failover_done is not None else 0.0
                published["fleet_requests_lost"] = 0
                published["fleet_failover_seconds"] = round(failover_s, 2)
                published["fleet_replicas"] = nreplicas
                print(f"fleet: {nreqs} reqs over {nreplicas} replicas, "
                      f"1 killed mid-soak -> 0 lost, {nfo} failed over, "
                      f"takeover {failover_s:.2f}s")
            finally:
                rt.stop()
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                        try:
                            p.wait(timeout=10)
                        except subprocess.TimeoutExpired:
                            p.kill()
                            p.wait()

    def do_stream():
        # standing-query soak (stream/ + serve/streams.py,
        # doc/streaming.md): a feed-mode stream on an in-process daemon
        # ingests the soak corpus chunk by chunk; published numbers are
        # sustained committed micro-batches/sec and the p99 of the
        # event-time lag samples observed while data was pending
        import tempfile

        from gpu_mapreduce_tpu.serve import Server, ServeClient
        nchunks = env_knob("SOAK_STREAM_CHUNKS", int, 24)
        rng5 = np.random.default_rng(29)
        chunk = (" ".join(
            f"w{w:04d}" for w in rng5.integers(0, 512, 4000))
            + "\n").encode()
        with tempfile.TemporaryDirectory() as tmp:
            srv = Server(port=0, workers=1,
                         state_dir=os.path.join(tmp, "state"))
            port = srv.start()
            try:
                c = ServeClient.local(port)
                stid = c.stream_open(
                    batch={"rows": 2000, "wait_ms": 50})["id"]
                lags: list = []
                batches = 0
                t0 = time.perf_counter()
                for _ in range(nchunks):
                    c.stream_feed(stid, chunk)
                    # sample lag until this chunk's batch commits —
                    # the samples ARE the latency evidence
                    give_up = time.monotonic() + 60
                    while time.monotonic() < give_up:
                        st = c.stream_status(stid)["stream"]
                        lags.append(st["lag_s"] * 1000.0)
                        if st["batches"] > batches:
                            batches = st["batches"]
                            break
                        time.sleep(0.01)
                dt = time.perf_counter() - t0
                out = c.stream_close(stid)
                assert out["stream"]["rows"] == nchunks
                published["stream_batches_per_sec"] = round(
                    out["stream"]["batches"] / dt, 2)
                lags.sort()
                published["stream_lag_p99_ms"] = round(
                    lags[min(len(lags) - 1,
                             int(len(lags) * 0.99))], 2)
            finally:
                srv.shutdown()

    def do_dist():
        # multi-process data plane soak (doc/distributed.md): a real
        # 4-process mrlaunch wordfreq with rank 2 SIGKILLed mid-run —
        # the launcher must shrink to width 2, resume from the last
        # durable checkpoint, and produce output byte-identical to an
        # uninterrupted 2-process run; publishes the recovery clock
        import random
        import subprocess
        import tempfile
        repo = os.path.dirname(os.path.abspath(__file__))
        mrl = os.path.join(repo, "scripts", "mrlaunch.py")
        with tempfile.TemporaryDirectory(prefix="soak-dist-") as td:
            corpus = os.path.join(td, "corpus.txt")
            rng5 = random.Random(29)
            vocab = [f"soak{i:04d}".encode() for i in range(400)]
            with open(corpus, "wb") as f:
                for _ in range(20000):
                    f.write(rng5.choice(vocab))
                    f.write(b" " if rng5.random() < 0.85 else b"\n")

            def launch(nproc, tag, extra_env):
                out = os.path.join(td, f"out-{tag}.txt")
                env = dict(os.environ)
                env.pop("MRTPU_FAULTS", None)
                env.update(extra_env)
                r = subprocess.run(
                    [sys.executable, mrl, "--np", str(nproc),
                     "--rundir", os.path.join(td, f"run-{tag}"),
                     "wordfreq", "--files", corpus, "--out", out,
                     "--chunks", "8"],
                    env=env, cwd=repo, capture_output=True,
                    timeout=600)
                if r.returncode != 0:
                    raise RuntimeError(
                        f"mrlaunch {tag} rc={r.returncode}: "
                        f"{r.stderr.decode()[-500:]}")
                summary = json.loads(r.stdout.decode().split(
                    "mrlaunch: ", 1)[1].splitlines()[0])
                with open(out, "rb") as f:
                    return f.read(), summary

            ref, _ = launch(2, "ref", {})
            got, summary = launch(4, "chaos", {
                "MRTPU_FAULTS": "site=dist.exchange;kind=peer_kill;"
                                "rank=2;after=1;n=1",
                "MRTPU_DIST_SYNC_TIMEOUT": "20"})
            if got != ref:
                raise RuntimeError(
                    "dist shrink-and-resume output differs from the "
                    "uninterrupted narrow run")
            if summary["final_width"] != 2:
                raise RuntimeError(f"expected shrink to 2, got "
                                   f"{summary['final_width']}")
            published["dist_ok"] = 1
            published["dist_recover_seconds"] = round(
                float(summary["recover_seconds"]), 3)
            published["dist_generations"] = int(summary["generations"])
            print(f"soak dist: shrink 4->2 ok, recover "
                  f"{published['dist_recover_seconds']}s")

    workloads = [("degree", do_degree), ("cc_find", do_cc),
                 ("sssp", do_sssp), ("luby", do_luby), ("tri", do_tri),
                 ("external", do_external),
                 ("ingest", do_ingest_overlap),
                 ("shuffle_skew", do_shuffle_skew),
                 ("group_heavy", do_group_heavy),
                 ("pagerank", do_pagerank),
                 ("pagerank_northstar", do_pagerank_northstar),
                 ("serve", do_serve), ("overload", do_overload),
                 ("fleet", do_fleet), ("stream", do_stream)]
    if chaos_seed is not None:
        workloads.append(("chaos", do_chaos))
    serve_only = "serve" in sys.argv[1:]
    if serve_only:
        # `soak.py serve`: hammer ONLY the daemon (doc/serve.md)
        workloads = [("serve", do_serve)]
    if "fleet" in sys.argv[1:]:
        # `soak.py fleet`: ONLY the replicated-daemon failover soak
        workloads = [("fleet", do_fleet)]
        serve_only = True       # partial publish: merge, don't erase
    if "overload" in sys.argv[1:]:
        # `soak.py overload`: ONLY the shed-the-greedy-tenant soak
        # (doc/serve.md#slo-burn-shedding)
        workloads = [("overload", do_overload)]
        serve_only = True       # partial publish: merge, don't erase
    if "stream" in sys.argv[1:]:
        # `soak.py stream`: ONLY the standing-query micro-batch soak
        # (doc/streaming.md)
        workloads = [("stream", do_stream)]
        serve_only = True       # partial publish: merge, don't erase
    if "dist" in sys.argv[1:]:
        # `soak.py dist`: ONLY the multi-process shrink-and-resume
        # soak — kills one rank mid-run, publishes the recovery clock
        # (doc/distributed.md)
        workloads = [("dist", do_dist)]
        serve_only = True       # partial publish: merge, don't erase
    for i, (name, fn) in enumerate(workloads, 1):
        guard(name, fn)
        if metrics_every and i % metrics_every == 0:
            print(metrics_line(i, name))
    if metrics_every:
        write_final_metrics(env_str("SOAK_METRICS_OUT",
                                    "soak_metrics.json"))
    if errors:
        published["errors"] = errors

    print("\nper-op trace summary (obs/):")
    print(per_op_table(tracer.events()))

    published["backend"] = backend
    published["rmat_scale"] = scale
    published["nedges"] = nedges
    published["mesh_devices"] = nmesh
    published["notes"] = (
        "cc_find times INCLUDE device-side staging (mesh vertex "
        "ranking, parallel/staging.py; r3+) — slower on CPU fakes "
        "(single-core XLA sort) but removes the controller funnel the "
        "mesh cannot outgrow.  mesh_devices>1 rows on a CPU fake "
        "cluster time-slice ONE core across P shards while paying real "
        "collective+padding cost: they record multi-device EXECUTION, "
        "not speedup (BASELINE.md 'Soak P=1 vs P=8')")

    # backend-qualified key — never wipe records other harnesses own
    # and never let a CPU re-run clobber a previous real-TPU soak.  A
    # PARTIAL run merges over the previous record (a failed workload
    # must not erase its old row) and exits nonzero so the watcher's
    # success gate keeps retrying.
    from gpu_mapreduce_tpu.utils.publish import publish, read_published
    if env_flag("SOAK_DRY", False):
        # smoke runs must never clobber a published full-scale row
        print("SOAK_DRY=1: not publishing", json.dumps(published))
        return
    key = f"soak_{backend}" if nmesh == 1 else f"soak_{backend}_p{nmesh}"
    if errors or serve_only:
        # partial runs (a failed workload, or the serve-only mode)
        # merge over the previous record instead of erasing its rows
        for k, v in read_published(key).items():
            published.setdefault(k, v)
    publish(key, published)
    print("BASELINE.json published:", json.dumps(published))
    if errors:
        raise SystemExit(f"{len(errors)} workload(s) failed: "
                         f"{sorted(errors)}")


if __name__ == "__main__":
    main()
