"""Fusion v2 (megafused single-dispatch plan groups, plan/fuser.py):
fused-vs-eager byte identity (wire on/off, chaos), the "1 dispatch per
plan group" steady-state assertion, speculation-miss fallbacks, the
kernel-launch dispatch accounting, and the fusion telemetry surfaces
(mr.stats()["plan"]["fusion"], the per-request profile)."""

import jax.numpy as jnp
import numpy as np
import pytest

from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.core.runtime import global_counters
from gpu_mapreduce_tpu.ops.reduces import (count, cull, max_values,
                                           sum_values)
from gpu_mapreduce_tpu.parallel.mesh import make_mesh


def ndispatch():
    return global_counters().snapshot()["ndispatch"]


def scan_pairs(mr):
    got = []
    mr.scan_kv(lambda k, v, p: got.append((k if isinstance(k, bytes)
                                           else int(k), int(v))))
    return sorted(got)


def run_chain(comm, fuse, kernel, keys, vals):
    mr = MapReduce(comm, fuse=fuse)
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, vals))
    mr.aggregate()
    mr.convert()
    n = mr.reduce(kernel, batch=True)
    return int(n), scan_pairs(mr)


def intcount_keys(n=8000, card=97):
    k = ((np.arange(n, dtype=np.uint64) * 7919) % card).astype(np.uint64)
    return k, np.arange(n, dtype=np.int64)


def warm_pipeline(mr, keys, vals, kernel=count):
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, vals))
    mr.aggregate()
    mr.convert()
    return int(mr.reduce(kernel, batch=True))


# ---------------------------------------------------------------------------
# kernel-launch dispatch accounting
# ---------------------------------------------------------------------------

def test_kernel_mark_launch_counts_dispatch():
    """Counters.ndispatch counts eager pallas_call launches too, so
    "1 dispatch per pipeline" cannot be faked by moving work into
    uncounted kernels: the mark kernel reports its launch."""
    from gpu_mapreduce_tpu.ops.pallas.match import mark_words_pallas
    words = jnp.zeros(1 << 10, jnp.uint32)
    d0 = ndispatch()
    mark_words_pallas(words, b'<a href="', interpret=True)
    assert ndispatch() - d0 == 1


# ---------------------------------------------------------------------------
# golden equivalence: eager == fused cold (v1) == fused warm (megafused)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", [count, sum_values, max_values, cull])
def test_megafuse_golden_all_kernels(kernel):
    keys, vals = intcount_keys()
    eager = run_chain(make_mesh(8), 0, kernel, keys, vals)
    fused_cold = run_chain(make_mesh(8), 1, kernel, keys, vals)
    fused_warm = run_chain(make_mesh(8), 1, kernel, keys, vals)
    assert eager == fused_cold == fused_warm


@pytest.mark.parametrize("wire", ["0", "1"])
def test_megafuse_golden_wire_modes(monkeypatch, wire):
    monkeypatch.setenv("MRTPU_WIRE", wire)
    keys, vals = intcount_keys()
    eager = run_chain(make_mesh(8), 0, count, keys, vals)
    run_chain(make_mesh(8), 1, count, keys, vals)
    fused_warm = run_chain(make_mesh(8), 1, count, keys, vals)
    assert eager == fused_warm


def test_megafuse_golden_kmv_chain():
    """[aggregate, convert] (collate for a host reduce) megafuses to a
    grouped KMV — output identical."""
    from gpu_mapreduce_tpu.apps.wordfreq import _sum
    keys, _ = intcount_keys()
    vals = np.ones(len(keys), np.int64)

    def wf(fuse):
        mr = MapReduce(make_mesh(8), fuse=fuse)
        mr.map(1, lambda i, kv, p: kv.add_batch(keys, vals))
        mr.collate()
        nu = mr.reduce(_sum)
        return int(nu), scan_pairs(mr)

    eager = wf(0)
    assert eager == wf(1) == wf(1)


def test_megafuse_golden_under_chaos():
    """shuffle-site chaos injection on the megafused group: the ft/
    retry re-runs the whole group and output stays byte-identical
    (the fault point sits before the single dispatch)."""
    from gpu_mapreduce_tpu import ft
    keys, vals = intcount_keys()
    mr = MapReduce(make_mesh(8), fuse=1)
    warm_pipeline(mr, keys, vals)
    clean = warm_pipeline(mr, keys, vals), scan_pairs(mr)
    ft.reset()
    try:
        ft.schedule(site="shuffle.exchange", rate=1.0, seed=3,
                    max_faults=2)
        ft.set_budget("shuffle.exchange", 4)
        chaos = warm_pipeline(mr, keys, vals), scan_pairs(mr)
        assert ft.fault_counts().get("shuffle.exchange", 0) >= 1
        assert chaos == clean
    finally:
        ft.reset()


# ---------------------------------------------------------------------------
# the dispatch-count acceptance: 1 per plan group, steady state
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wire", ["0", "1"])
def test_single_dispatch_per_pipeline(monkeypatch, wire):
    """[aggregate, convert, reduce(kernel)] under MRTPU_MEGAFUSE=1 on
    the 8-device fake mesh: ONE Counters.ndispatch per plan group once
    warm — with and without the wire codec."""
    monkeypatch.setenv("MRTPU_WIRE", wire)
    keys, vals = intcount_keys()
    mr = MapReduce(make_mesh(8), fuse=1)
    warm_pipeline(mr, keys, vals)
    n1 = warm_pipeline(mr, keys, vals)
    d0 = ndispatch()
    n2 = warm_pipeline(mr, keys, vals)
    assert ndispatch() - d0 == 1
    assert n1 == n2


def test_megafuse_off_takes_v1_dispatches(monkeypatch):
    monkeypatch.setenv("MRTPU_MEGAFUSE", "0")
    keys, vals = intcount_keys()
    mr = MapReduce(make_mesh(8), fuse=1)
    warm_pipeline(mr, keys, vals)
    warm_pipeline(mr, keys, vals)
    d0 = ndispatch()
    warm_pipeline(mr, keys, vals)
    assert ndispatch() - d0 >= 2


def test_local_group_single_dispatch():
    """[convert, reduce] on an already-sharded KV: warm = 1 dispatch
    (the compact dispatch folds into the cached-capacity program)."""
    keys, vals = intcount_keys()

    def cycle(mr):
        mr.map(1, lambda i, kv, p: kv.add_batch(keys, vals))
        mr.aggregate()
        _ = mr.kv            # barrier: aggregate replays eagerly
        mr.convert()
        return int(mr.reduce(count, batch=True))

    mr = MapReduce(make_mesh(8), fuse=1)
    cycle(mr)
    n1 = cycle(mr)
    d0 = ndispatch()
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, vals))
    mr.aggregate()
    _ = mr.kv
    dpre = ndispatch()
    mr.convert()
    n2 = int(mr.reduce(count, batch=True))
    assert ndispatch() - dpre == 1
    assert n1 == n2
    assert dpre > d0   # the eager aggregate really dispatched before


# ---------------------------------------------------------------------------
# speculation misses fall back, correctly
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_speculation_miss_pack_overflow_falls_back():
    """Warm on a narrow key range, then feed a wider one: the cached
    wire pack can't round-trip it, the megafused result is discarded
    and the v1 path re-runs — output equals eager."""
    narrow, vals = intcount_keys(card=97)
    wide = ((np.arange(8000, dtype=np.uint64) * 0x9E3779B97F4A7C15)
            % np.uint64(1 << 60)).astype(np.uint64)
    mr = MapReduce(make_mesh(8), fuse=1)
    warm_pipeline(mr, narrow, vals)
    warm_pipeline(mr, narrow, vals)          # megafuse armed for narrow
    got = warm_pipeline(mr, wide, vals), scan_pairs(mr)
    mre = MapReduce(make_mesh(8), fuse=0)
    ref = warm_pipeline(mre, wide, vals), scan_pairs(mre)
    assert got == ref


@pytest.mark.slow
def test_speculation_miss_group_growth_falls_back():
    """Warm on few distinct keys, then many: the cached group capacity
    no longer covers, detected host-side — the v1 replay keeps the
    output exact."""
    few, vals = intcount_keys(card=17)
    many, _ = intcount_keys(card=3000)
    mr = MapReduce(make_mesh(8), fuse=1)
    warm_pipeline(mr, few, vals)
    warm_pipeline(mr, few, vals)
    got = warm_pipeline(mr, many, vals), scan_pairs(mr)
    mre = MapReduce(make_mesh(8), fuse=0)
    ref = warm_pipeline(mre, many, vals), scan_pairs(mre)
    assert got == ref


# ---------------------------------------------------------------------------
# telemetry surfaces
# ---------------------------------------------------------------------------

def test_fusion_stats_in_mr_stats():
    from gpu_mapreduce_tpu.plan.cache import reset_fusion_stats
    keys, vals = intcount_keys()
    mr = MapReduce(make_mesh(8), fuse=1)
    warm_pipeline(mr, keys, vals)
    reset_fusion_stats()
    warm_pipeline(mr, keys, vals)
    fu = mr.stats()["plan"]["fusion"]
    assert fu["groups"] >= 1 and fu["fused_groups"] >= 1
    assert fu["mega_groups"] >= 1
    assert fu["dispatches_saved"] >= 4       # 5 eager − 1 megafused
    assert fu["dispatches"] <= fu["eager_dispatch_estimate"]


def test_profile_fusion_section():
    """The per-request profile (what GET /v1/jobs/<id>/profile serves)
    carries the request's own fusion effectiveness."""
    from gpu_mapreduce_tpu.obs.context import request_scope
    keys, vals = intcount_keys()
    mr = MapReduce(make_mesh(8), fuse=1)
    warm_pipeline(mr, keys, vals)            # warm outside the scope
    with request_scope(label="megafuse-test") as acct:
        warm_pipeline(mr, keys, vals)
    prof = acct.profile()
    assert prof["fusion"]["fused_groups"] >= 1
    assert prof["fusion"]["mega_groups"] >= 1
    assert prof["fusion"]["dispatches_saved"] >= 4
