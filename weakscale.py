"""Weak-scaling harness — the analogue of the reference's cuda_scale/
variant (fixed ~20×128 MB files per process, cuda_scale/InvertedIndex.cu:276)
and its Fig. 4 stage-time study (chapter_final.pdf §3.4: map/sort/reduce
stay flat as procs grow; network I/O grows).

Holds the per-shard corpus CONSTANT while the mesh grows (P=1,2,4,8 on
the CPU fake cluster, or whatever the current backend offers) and runs
the full wordfreq pipeline — map, aggregate (the network stage), convert,
reduce — printing per-stage wall time per P.  A flat map/convert row and
a growing aggregate row reproduces the reference's finding.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       python weakscale.py [mb_per_proc]
"""

import json
import os
import sys
import tempfile
import time


def make_files(tmpdir: str, nfiles: int, mb_each: float):
    import numpy as np
    rng = np.random.default_rng(0)
    vocab = [b"w%05d" % i for i in range(20000)]
    paths = []
    for i in range(nfiles):
        words = rng.choice(len(vocab), int(mb_each * (1 << 20) / 7))
        data = b" ".join(vocab[w] for w in words)
        p = os.path.join(tmpdir, f"part-{i:05d}.txt")
        with open(p, "wb") as f:
            f.write(data)
        paths.append(p)
    return paths


def main_invertedindex(mb_per_proc: float):
    """WEAKSCALE_APP=ii: the cuda_scale analog with the FLAGSHIP app —
    fixed corpus volume per proc while the mesh grows, through the
    mesh-SPMD ingestion (each shard ingests its own file slice,
    cuda_scale/InvertedIndex.cu:276 holds ~20x128 MB per proc fixed).
    Records per-P stage times + the map-stage machinery stats."""
    import jax
    from bench import make_corpus
    from gpu_mapreduce_tpu.apps.invertedindex import InvertedIndex
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh

    jax.config.update("jax_enable_x64", True)
    ndev = len(jax.devices())
    sizes = [p for p in (1, 2, 4, 8, 16) if p <= ndev]
    rows = []
    with tempfile.TemporaryDirectory() as tmpdir:
        # one file per proc so the SPMD balance gives each shard a
        # whole file; P uses the first P files (fixed volume/proc)
        paths, _, _ = make_corpus(tmpdir, int(mb_per_proc * max(sizes)),
                                  nfiles=max(sizes))
        for P in sizes:
            ii = InvertedIndex(engine="xla", comm=make_mesh(P))
            ii.run(paths[:P])                 # pay the per-mesh compiles
            ii = InvertedIndex(engine="xla", comm=make_mesh(P))
            t0 = time.time()
            npairs, nuniq = ii.run(paths[:P])
            dt = time.time() - t0
            stages = {k: round(v, 3) for k, v in
                      sorted(ii.timer.times.items())}
            rows.append({"nprocs": P, "npairs": int(npairs),
                         "nunique": int(nuniq), "total": round(dt, 3),
                         **stages, "map_stats": ii.stats})
            print(json.dumps(rows[-1]))
    record = {"weak_scaling": rows, "mb_per_proc": mb_per_proc,
              "app": "invertedindex", "backend": jax.default_backend()}
    print(json.dumps(record))
    try:
        from gpu_mapreduce_tpu.utils.publish import publish
        publish(f"weakscale_ii_{record['backend']}", record)
    except FileNotFoundError:
        pass


def main():
    import jax
    from gpu_mapreduce_tpu.core.mapreduce import MapReduce
    from gpu_mapreduce_tpu.core.runtime import Timer
    from gpu_mapreduce_tpu.oink.kernels import count, read_words
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh

    mb_per_proc = float(sys.argv[1]) if len(sys.argv) > 1 else 2.0
    ndev = len(jax.devices())
    sizes = [p for p in (1, 2, 4, 8, 16) if p <= ndev]
    rows = []
    with tempfile.TemporaryDirectory() as tmpdir:
        files = make_files(tmpdir, max(sizes), mb_per_proc)

        def run(P, counters=None):
            mr = MapReduce(make_mesh(P))
            stages = {}
            t = Timer()
            mr.map_files(files[:P], read_words)
            stages["map"] = t.elapsed()
            snap = counters.cspad if counters else 0
            t = Timer()
            mr.aggregate()          # the "network I/O" stage
            stages["aggregate"] = t.elapsed()
            if counters:
                stages["pad_mb"] = (counters.cspad - snap) / (1 << 20)
            t = Timer()
            mr.convert()
            stages["convert"] = t.elapsed()
            t = Timer()
            n = mr.reduce(count, batch=True)
            stages["reduce"] = t.elapsed()
            # r5 evidence: the generic map path ingests per shard now
            return n, stages, mr.last_ingest["mode"]

        from gpu_mapreduce_tpu.core.runtime import global_counters
        for P in sizes:
            run(P)                       # pay the per-mesh XLA compiles
            n, stages, ingest = run(P, global_counters())  # steady state
            rows.append({"nprocs": P, "nunique": int(n),
                         "ingest": ingest,
                         **{k: round(v, 3) for k, v in stages.items()}})
            print(json.dumps(rows[-1]))
    record = {"weak_scaling": rows, "mb_per_proc": mb_per_proc,
              "backend": jax.default_backend()}
    print(json.dumps(record))
    # persist like soak.py: backend-qualified, never clobbering others
    try:
        from gpu_mapreduce_tpu.utils.publish import publish
        publish(f"weakscale_{record['backend']}", record)
    except FileNotFoundError:
        pass


if __name__ == "__main__":
    import os as _os
    if _os.environ.get("WEAKSCALE_APP") == "ii":
        main_invertedindex(float(sys.argv[1]) if len(sys.argv) > 1
                           else 32.0)
    else:
        main()
