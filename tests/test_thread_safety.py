"""Concurrent-world thread hammer (VERDICT r4 weak #7).

``-partition`` runs each world's interpreter in a thread
(oink/universe.py), so the parallel tier's shared state — the
speculative-cap cache, SyncStats/ToHostStats counters — sees concurrent
exchanges.  Two worlds hammer disjoint sub-meshes (the MPI_Comm_split
layout the universe actually builds) and the telemetry must stay
consistent: no lost counter bumps, each world's ``last_exchange`` its
own exchange's and never the other world's, correct per-world results."""

import threading

import numpy as np

from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.parallel import shuffle
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
from gpu_mapreduce_tpu.parallel.sharded import SyncStats


def _world(mesh, seed, iters, results, idx, barrier):
    try:
        rng = np.random.default_rng(seed)
        barrier.wait()
        for _ in range(iters):
            mr = MapReduce(mesh)
            keys = rng.integers(0, 1 << 20, 512).astype(np.uint64)
            mr.map(1, lambda i, kv, p: kv.add_batch(
                keys, np.ones(len(keys), np.int64)))
            mr.aggregate()
            mr.convert()
            from gpu_mapreduce_tpu.ops.reduces import sum_values
            mr.reduce(sum_values, batch=True)
            got = dict(mr.kv.one_frame().to_host().pairs())
            expect = {}
            for k in keys.tolist():
                expect[k] = expect.get(k, 0) + 1
            assert got == expect, "world result corrupted"
            r = mr.last_exchange       # this world's own, never torn
            assert r.rows == len(keys)
            assert r.nrounds >= 1 and r.bucket >= 1
        results[idx] = "ok"
    except Exception as e:  # noqa: BLE001 - surface in the main thread
        results[idx] = repr(e)


def test_two_worlds_exchange_concurrently():
    all_dev = make_mesh(8)
    import jax
    devs = list(all_dev.devices.flat)
    mesh_a = make_mesh(devices=devs[:4])
    mesh_b = make_mesh(devices=devs[4:])
    iters = 6
    pulls0 = SyncStats.snapshot()
    results = [None, None]
    barrier = threading.Barrier(2)
    ta = threading.Thread(target=_world,
                          args=(mesh_a, 1, iters, results, 0, barrier))
    tb = threading.Thread(target=_world,
                          args=(mesh_b, 2, iters, results, 1, barrier))
    ta.start(); tb.start(); ta.join(120); tb.join(120)
    assert results == ["ok", "ok"], results
    # every exchange bumps pulls exactly once per sharded op; with the
    # lock no bump is lost (>= because convert/reduce pull too — the
    # invariant hammered here is "no lost updates", not an exact count)
    assert SyncStats.delta(pulls0) >= 2 * iters


def test_spec_cache_concurrent_population():
    """Hammer the speculative-cap cache dict from two threads with
    DISTINCT specs (different meshes) — entries must not be lost or
    torn (each value is a well-formed tagged exchange plan,
    parallel/wire.py)."""
    devs = list(make_mesh(8).devices.flat)
    meshes = [make_mesh(devices=devs[:4]), make_mesh(devices=devs[4:])]
    errs = []

    def pound(mesh, seed):
        try:
            rng = np.random.default_rng(seed)
            for i in range(8):
                mr = MapReduce(mesh)
                n = 128 << (i % 3)      # vary shapes → several spec keys
                keys = rng.integers(0, 1 << 16, n).astype(np.uint64)
                mr.map(1, lambda _i, kv, p: kv.add_batch(
                    keys, np.zeros(n, np.uint8)))
                mr.aggregate()
        except Exception as e:  # noqa: BLE001
            errs.append(repr(e))

    ts = [threading.Thread(target=pound, args=(m, s))
          for s, m in enumerate(meshes)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
    assert not errs, errs
    with shuffle._SPEC_LOCK:
        vals = list(shuffle._SPEC_CACHE.values())
    assert vals and all(
        isinstance(v, tuple)
        and ((v[0] == "raw" and len(v) == 4)
             or (v[0] == "wire" and len(v) == 5
                 and isinstance(v[1], tuple)))
        for v in vals)
