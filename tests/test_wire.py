"""The exact wire codec (parallel/wire.py, MRTPU_WIRE): delta-packed
keys, narrow values, tiered per-bucket caps — compressed exchanges must
be BYTE-IDENTICAL to the raw path on every surface (eager aggregate,
fused plans, gather, reshard range exchanges, chaos retries), send
strictly fewer pad bytes on skew, and report honest telemetry."""

import collections
import os

import numpy as np
import pytest

import jax

from gpu_mapreduce_tpu import MapReduce
from gpu_mapreduce_tpu.core.column import DenseColumn
from gpu_mapreduce_tpu.core.frame import KVFrame
from gpu_mapreduce_tpu.parallel import shuffle, wire
from gpu_mapreduce_tpu.parallel.mesh import make_mesh, make_mesh2
from gpu_mapreduce_tpu.parallel.sharded import shard_frame


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8
    return make_mesh(8)


def zipf_keys(n=20000, seed=7, lim=1 << 22):
    """RMAT-hub-style skew in a u32-ish range (narrows u64→u32 on the
    wire and forces the tier ladder)."""
    rng = np.random.default_rng(seed)
    return np.minimum(rng.zipf(1.3, n), lim).astype(np.uint64)


def run_exchange(mesh, keys, vals, wire_flag, dest=("hash", None)):
    os.environ["MRTPU_WIRE"] = wire_flag
    shuffle._SPEC_CACHE.clear()
    skv = shard_frame(KVFrame(DenseColumn(keys.copy()),
                              DenseColumn(vals.copy())), mesh)
    out = shuffle.exchange(skv, dest)
    return (np.asarray(out.key), np.asarray(out.value),
            out.counts.copy(), out.exchange_stats)


# ---------------------------------------------------------------------------
# planner units (the ci.sh quick subset: codec/tiers)
# ---------------------------------------------------------------------------

def test_codec_tier_ladder_properties():
    """plan_tiers must (a) cover the raw max bucket, (b) never exceed
    the uniform schedule's slots, (c) stay within the round bound."""
    rng = np.random.default_rng(3)
    for _ in range(50):
        P = int(rng.integers(2, 16))
        counts = rng.integers(0, 5000, (P, P))
        if rng.random() < 0.5:      # inject a hub column
            counts[:, 0] = rng.integers(2000, 60000, P)
        B, nrounds, _cap, _bmax, _nc = shuffle._plan_caps(counts)
        tiers = wire.plan_tiers(counts, B, nrounds)
        bmax = int(counts.max())
        assert sum(tiers) >= bmax, (tiers, bmax)
        assert sum(tiers) <= B * nrounds, (tiers, B, nrounds)
        assert len(tiers) <= shuffle._MAX_ROUNDS
        assert all(t >= 8 and t & (t - 1) == 0 for t in tiers)


def test_codec_pack_width_planning():
    """Pack widths from bucket ranges: narrowest exact dtype, never a
    non-narrowing one, raw for over-range or empty columns."""
    counts = np.array([[3, 2], [1, 4]])
    # stats layout [P, P, 4] u64: kmin, kmax, vmin, vmax
    stats = np.zeros((2, 2, 4), np.uint64)
    stats[:, :, 0] = 100
    stats[:, :, 1] = 100 + 200          # key range 200 → uint8
    stats[:, :, 2] = 7
    stats[:, :, 3] = 7 + (1 << 20)      # value range 2^20 → uint32

    class Col:
        def __init__(self, dt):
            self.dtype = np.dtype(dt)
            self.ndim = 1
            self.shape = (8,)
    kp, vp, (kr, vr) = wire.plan_packs(Col(np.uint64), Col(np.uint64),
                                       counts, stats, (True, True))
    assert (kp, vp) == ("uint8", "uint32") and kr == 200
    # a u32 column with a 2^20 range narrows no further than uint32 —
    # which is NOT narrower than the column: ship raw
    stats2 = np.zeros((2, 2, 4), np.uint64)
    stats2[:, :, 1] = 1 << 20           # key range 2^20 on a u32 column
    kp2, _vp2, _ = wire.plan_packs(Col(np.uint32), Col(np.uint64),
                                   counts, stats2, (True, False))
    assert kp2 is None
    # empty matrix → no evidence → raw
    kp3, vp3, _ = wire.plan_packs(Col(np.uint64), Col(np.uint64),
                                  np.zeros((2, 2), int), stats,
                                  (True, True))
    assert kp3 is None and vp3 is None


def test_codec_signed_value_roundtrip(mesh, monkeypatch):
    """Signed value columns delta-pack over their int64 bit-pattern
    stats and decode exactly — including negative bases."""
    rng = np.random.default_rng(11)
    n = 4000
    keys = rng.integers(0, 1 << 16, n).astype(np.uint64)
    vals = (rng.integers(0, 50000, n) - 40000).astype(np.int64)
    k0, v0, c0, _ = run_exchange(mesh, keys, vals, "0")
    k1, v1, c1, st = run_exchange(mesh, keys, vals, "1")
    assert np.array_equal(k0, k1) and np.array_equal(v0, v1)
    assert (c0 == c1).all()
    assert st.wire_bytes > 0 and st.wire_ratio > 1.0


# ---------------------------------------------------------------------------
# goldens: compressed == raw, byte for byte
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_shape", ["flat", "2x4"])
def test_golden_zipf_exchange_byte_identical(mesh, mesh_shape):
    """On both collectives the exchange chooses between (from the mesh:
    ``shuffle._exchange_blocks``)."""
    if mesh_shape == "2x4":
        mesh = make_mesh2(2, 4)
    keys = zipf_keys()
    vals = np.arange(len(keys), dtype=np.uint64)
    k0, v0, c0, s0 = run_exchange(mesh, keys, vals, "0")
    k1, v1, c1, s1 = run_exchange(mesh, keys, vals, "1")
    assert np.array_equal(k0, k1), "compressed keys differ from raw"
    assert np.array_equal(v0, v1), "compressed values differ from raw"
    assert (c0 == c1).all()
    # the codec engaged and reported an honest ratio
    assert s1.wire_bytes > 0
    assert s1.wire_ratio == pytest.approx(
        (s1.sent_bytes + s1.pad_bytes) / s1.wire_bytes, rel=1e-3)
    assert s0.wire_bytes == 0 and s0.wire_ratio == 0.0


def test_golden_pad_tax_tiered_caps_beat_global_B(mesh):
    """The pad-tax satellite: on the zipf corpus the tier ladder must
    send STRICTLY fewer pad bytes than the raw global-B schedule, and
    the actual wire bytes must undercut the raw volume."""
    keys = zipf_keys()
    vals = np.ones(len(keys), np.uint64)
    _k0, _v0, _c0, s0 = run_exchange(mesh, keys, vals, "0")
    _k1, _v1, _c1, s1 = run_exchange(mesh, keys, vals, "1")
    assert s1.pad_bytes < s0.pad_bytes, (s1.pad_bytes, s0.pad_bytes)
    assert s1.wire_bytes < s0.sent_bytes + s0.pad_bytes
    assert s1.wire_ratio > 1.0


def test_golden_wordfreq_pipeline_eager_vs_fused(mesh, monkeypatch):
    """The full aggregate→convert→reduce pipeline (byte-keyed wordfreq
    shape) agrees across {wire on/off} × {eager/fused} — the fused
    codec program composes group/reduce on DECODED rows."""
    words = [b"w%04d" % i for i in
             np.random.default_rng(5).zipf(1.5, 4000) % 600]
    from gpu_mapreduce_tpu.ops.reduces import count

    def run(wire_flag, fuse):
        monkeypatch.setenv("MRTPU_WIRE", wire_flag)
        shuffle._SPEC_CACHE.clear()
        mr = MapReduce(mesh, fuse=fuse)
        mr.map(1, lambda i, kv, p: [kv.add(w, 1) for w in words])
        mr.aggregate()
        mr.convert()
        mr.reduce(count, batch=True)
        return sorted((bytes(k), int(v)) for fr in mr.kv.frames()
                      for k, v in fr.pairs())

    golden = run("0", 0)
    assert collections.Counter(dict(golden)) == \
        collections.Counter(words)
    assert run("1", 0) == golden
    assert run("1", 1) == golden
    assert run("0", 1) == golden


def test_golden_kmv_group_path(mesh, monkeypatch):
    """collate (the grouped ShardedKMV surface) is identical wire
    on/off — groups, sizes and multivalue runs included."""
    keys = zipf_keys(6000, seed=9, lim=1 << 14)
    vals = np.arange(6000, dtype=np.uint64)

    def grouped(wire_flag):
        monkeypatch.setenv("MRTPU_WIRE", wire_flag)
        shuffle._SPEC_CACHE.clear()
        mr = MapReduce(mesh)
        mr.map(1, lambda i, kv, p: kv.add_batch(keys, vals))
        mr.collate()
        out = {}
        mr.scan_kmv(lambda k, vs, p: out.__setitem__(
            int(k), sorted(int(v) for v in vs)))
        return out

    assert grouped("1") == grouped("0")


def test_golden_reshard_n_m_n_compressed(mesh, monkeypatch):
    """N→M→N reshard through the compressed range exchange: global row
    order (and bytes) preserved exactly — the PR 7 contract must
    survive the codec."""
    monkeypatch.setenv("MRTPU_WIRE", "1")
    shuffle._SPEC_CACHE.clear()
    keys = zipf_keys(8000, seed=13)
    mr = MapReduce(mesh)
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, keys * 5))
    mr.aggregate()

    def global_rows(fr):
        P, cap = fr.nprocs, fr.cap
        k = np.asarray(fr.key)
        v = np.asarray(fr.value)
        sel = np.concatenate(
            [np.arange(i * cap, i * cap + int(fr.counts[i]))
             for i in range(P)])
        return k[sel], v[sel]

    k0, v0 = global_rows(mr.kv.one_frame())
    mr.reshard(make_mesh(3))
    mr.reshard(make_mesh(8))
    k1, v1 = global_rows(mr.kv.one_frame())
    assert np.array_equal(k0, k1) and np.array_equal(v0, v1)


def test_chaos_golden_exchange_faults_under_wire(mesh, monkeypatch):
    """shuffle.exchange faults injected under MRTPU_WIRE=1: the ft/
    retry re-runs the WHOLE two-phase compressed exchange and the output
    stays byte-identical to the fault-free compressed run."""
    from gpu_mapreduce_tpu import ft
    monkeypatch.setenv("MRTPU_WIRE", "1")
    monkeypatch.setenv("MRTPU_DONATE", "0")   # retries need live inputs
    keys = zipf_keys(5000, seed=21)
    vals = np.arange(5000, dtype=np.uint64)

    def pipeline():
        shuffle._SPEC_CACHE.clear()
        mr = MapReduce(mesh)
        mr.map(1, lambda i, kv, p: kv.add_batch(keys, vals))
        mr.aggregate()
        fr = mr.kv.one_frame()
        return np.asarray(fr.key).copy(), fr.counts.copy()

    clean_k, clean_c = pipeline()
    ft.reset()
    try:
        ft.schedule(site="shuffle.exchange", rate=1.0, seed=3,
                    max_faults=2)
        ft.set_budget("shuffle.exchange", 4)
        chaos_k, chaos_c = pipeline()
        assert ft.fault_counts().get("shuffle.exchange", 0) >= 1
        assert np.array_equal(chaos_k, clean_k)
        assert (chaos_c == clean_c).all()
    finally:
        ft.reset()


# ---------------------------------------------------------------------------
# telemetry + speculation
# ---------------------------------------------------------------------------

def test_wire_metrics_and_request_profile(mesh, monkeypatch):
    """mrtpu_exchange_bytes_total grows a {kind=wire} series and the
    request profile rolls up wire bytes + compression ratio."""
    from gpu_mapreduce_tpu.obs import metrics as obs_metrics
    from gpu_mapreduce_tpu.obs import request_scope
    from gpu_mapreduce_tpu.obs import get_tracer
    monkeypatch.setenv("MRTPU_WIRE", "1")
    obs_metrics.reset()
    get_tracer().reset()
    try:
        obs_metrics.enable_metrics(flight=False)
        keys = zipf_keys(4000, seed=2)
        shuffle._SPEC_CACHE.clear()
        with request_scope(label="wire-test") as acct:
            # through the MR op so the byte volume ALSO flows down the
            # Counters funnel into the account (profile sent/pad bytes)
            monkeypatch.setenv("MRTPU_WIRE", "1")
            mr = MapReduce(mesh)
            mr.map(1, lambda i, kv, p: kv.add_batch(
                keys, np.ones(len(keys), np.uint64)))
            mr.aggregate()
            codec_ratio = mr.last_exchange.wire_ratio
            # a RAW exchange in the same request must not inflate the
            # reported compression (its logical bytes are excluded)
            monkeypatch.setenv("MRTPU_WIRE", "0")
            mr2 = MapReduce(mesh)
            mr2.map(1, lambda i, kv, p: kv.add_batch(
                keys, np.ones(len(keys), np.uint64)))
            mr2.aggregate()
        snap = obs_metrics.snapshot()
        kinds = {s["labels"]["kind"]: s["value"] for s in
                 snap["mrtpu_exchange_bytes_total"]["samples"]}
        assert kinds.get("wire", 0) > 0
        assert kinds["sent"] > 0 and kinds["pad"] > 0
        prof = acct.profile()["exchange"]
        assert prof["wire_bytes"] > 0
        assert prof["compression_ratio"] == pytest.approx(codec_ratio,
                                                         rel=1e-3)
        assert prof["compression_ratio"] > 1.0
    finally:
        obs_metrics.reset()
        get_tracer().reset()


def test_range_reshard_feeds_exchange_metrics(mesh, monkeypatch):
    """PR 7 regression (satellite): ("range", ...) reshard exchanges
    must feed record_exchange — sent/pad/rows/rounds — exactly like
    dest-fn exchanges, and a counters-less direct exchange() call must
    still carry byte telemetry on its per-call stats."""
    from gpu_mapreduce_tpu.obs import metrics as obs_metrics
    from gpu_mapreduce_tpu.obs import get_tracer
    obs_metrics.reset()
    get_tracer().reset()
    try:
        obs_metrics.enable_metrics(flight=False)
        keys = zipf_keys(4000, seed=17)
        mr = MapReduce(mesh)
        mr.map(1, lambda i, kv, p: kv.add_batch(keys, keys))
        mr.aggregate()
        before = obs_metrics.snapshot()

        def kinds(snap):
            return {s["labels"]["kind"]: s["value"] for s in
                    snap["mrtpu_exchange_bytes_total"]["samples"]}

        def count_of(snap, name):
            return sum(s["value"] for s in snap[name]["samples"])

        mr.reshard(make_mesh(4))           # the range exchange
        after = obs_metrics.snapshot()
        assert kinds(after)["sent"] > kinds(before)["sent"]
        assert kinds(after)["pad"] >= kinds(before)["pad"]
        assert count_of(after, "mrtpu_exchange_rows_total") > \
            count_of(before, "mrtpu_exchange_rows_total")
        assert count_of(after, "mrtpu_exchanges_total") > \
            count_of(before, "mrtpu_exchanges_total")

        # a direct exchange with NO counters still reports bytes
        monkeypatch.setenv("MRTPU_WIRE", "0")
        shuffle._SPEC_CACHE.clear()
        skv = shard_frame(KVFrame(DenseColumn(keys),
                                  DenseColumn(keys)), mesh)
        out = shuffle.exchange(skv, ("hash", None), counters=None)
        assert out.exchange_stats.sent_bytes > 0
        assert out.exchange_stats.pad_bytes >= 0
    finally:
        obs_metrics.reset()
        get_tracer().reset()


def test_wire_speculative_plan_reuse_and_overflow(mesh, monkeypatch):
    """The speculative-cap cache under the codec: a same-distribution
    repeat reuses the cached wire plan (phase 2 runs ONCE); a repeat
    whose key range outgrows the cached pack width re-runs at fresh
    widths — results exact either way."""
    monkeypatch.setenv("MRTPU_WIRE", "1")
    calls = []
    orig = shuffle._phase2_wire_jit

    def spy(mesh_, tiers, cap_out, kpack, vpack, **kw):
        calls.append((tiers, cap_out, kpack, vpack))
        return orig(mesh_, tiers, cap_out, kpack, vpack, **kw)

    monkeypatch.setattr(shuffle, "_phase2_wire_jit", spy)
    shuffle._SPEC_CACHE.clear()
    rng = np.random.default_rng(23)
    n = 4096
    small = rng.integers(0, 1 << 20, n).astype(np.uint64)
    vals = np.ones(n, np.uint64)

    def xchg(keys):
        skv = shard_frame(KVFrame(DenseColumn(keys), DenseColumn(vals)),
                          mesh)
        out = shuffle.exchange(skv, ("hash", None))
        got = collections.Counter(
            int(k) for k, _ in out.to_host().pairs())
        assert got == collections.Counter(int(k) for k in keys)
        return out.exchange_stats

    xchg(small)
    assert len(calls) == 1 and calls[0][2] == "uint32"
    st = xchg(rng.permutation(small))
    assert len(calls) == 2 and st.speculative, \
        "same-range repeat must keep the speculative wire dispatch"
    wide = small.copy()
    wide[0] = np.uint64((1 << 63) + 5)     # range outgrows uint32
    st2 = xchg(wide)
    assert len(calls) >= 4 and not st2.speculative
    assert calls[-1][2] is None            # fresh plan ships raw keys
