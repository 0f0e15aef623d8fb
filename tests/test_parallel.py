"""Fake-cluster tests for the mesh backend: 8 virtual CPU devices stand in
for 8 TPU chips (SURVEY.md §4 — the mpistubs trick, inverted)."""

import collections

import numpy as np
import pytest

import jax

from gpu_mapreduce_tpu import MapReduce
from gpu_mapreduce_tpu.parallel.mesh import make_mesh, make_mesh2
from gpu_mapreduce_tpu.parallel.sharded import ShardedKV
from gpu_mapreduce_tpu.parallel.group import reduce_sharded
from gpu_mapreduce_tpu.ops.hash import hash_u64


@pytest.fixture(scope="module")
def mesh():
    # conftest fakes 8 CPU devices; a larger fake cluster (pod-scale
    # sanity runs override the flag) still exercises the same paths
    assert len(jax.devices()) >= 8, "conftest should fake >=8 CPU devices"
    return make_mesh(8)


def emit(itask, kv, ptr):
    rng = np.random.default_rng(itask)
    keys = rng.integers(0, 97, size=500).astype(np.uint64)
    kv.add_batch(keys, keys * 10 + itask)


def oracle_pairs():
    out = []
    for itask in range(6):
        rng = np.random.default_rng(itask)
        keys = rng.integers(0, 97, size=500).astype(np.uint64)
        out.extend(zip(keys.tolist(), (keys * 10 + itask).tolist()))
    return out


def multiset(pairs):
    return collections.Counter((int(k), int(v)) for k, v in pairs)


# the one choice the exchange makes, from the mesh: one all_to_all on a
# one-axis mesh, ICI-then-DCN (shuffle._a2a_hier) on a (slice, chip) mesh
MESH_SHAPES = {"flat": lambda: make_mesh(8), "2x4": lambda: make_mesh2(2, 4)}


@pytest.fixture(params=list(MESH_SHAPES))
def either_mesh(request):
    return MESH_SHAPES[request.param]()


def test_aggregate_preserves_pairs_and_partitions(either_mesh):
    mr = MapReduce(either_mesh)
    n = mr.map(6, emit)
    assert n == 3000
    assert mr.aggregate() == 3000
    frame = mr.kv.one_frame()
    assert isinstance(frame, ShardedKV)
    # multiset of pairs is preserved
    assert multiset(frame.to_host().pairs()) == multiset(oracle_pairs())
    # every key lives on exactly one shard, and it's the lookup3 shard
    P, cap = frame.nprocs, frame.cap
    k = np.asarray(frame.key).reshape(P, cap)
    for i in range(P):
        ki = k[i, :frame.counts[i]]
        expect = hash_u64(ki) % P
        assert (expect == i).all()


def test_all2all_is_accepted_and_selects_nothing(mesh):
    """``all2all`` is the reference's MPI_Alltoallv-or-ring setting: any
    script or binding may still set it, and both values run the one
    program — same partitions, row for row."""
    frames = []
    for all2all in (1, 0):
        mr = MapReduce(mesh, all2all=all2all)
        assert mr.settings.all2all == all2all
        mr.map(6, emit)
        assert mr.aggregate() == 3000
        frames.append(mr.kv.one_frame())
    one, zero = frames
    assert one.counts.tolist() == zero.counts.tolist()
    assert np.array_equal(np.asarray(one.key), np.asarray(zero.key))
    assert np.array_equal(np.asarray(one.value), np.asarray(zero.value))
    mr.set(all2all=1)          # and as a later setting, as scripts do
    assert mr.settings.all2all == 1


def test_collate_reduce_matches_oracle(mesh):
    mr = MapReduce(mesh)
    mr.map(6, emit)
    ngroups = mr.collate()
    oracle = collections.Counter(int(k) for k, _ in oracle_pairs())
    assert ngroups == len(oracle)

    def count(frame, kv, ptr):
        kv.add_frame(reduce_sharded(frame, "count"))

    mr.reduce(count, batch=True)
    got = {}
    mr.scan_kv(lambda k, v, p: got.update({int(k): int(v)}))
    assert got == dict(oracle)


def test_reduce_sharded_sum_max_min(mesh):
    mr = MapReduce(mesh)
    mr.map(6, emit)
    mr.collate()
    groups = collections.defaultdict(list)
    for k, v in oracle_pairs():
        groups[int(k)].append(int(v))
    frame = mr.kmv.one_frame()
    for op, fn in (("sum", sum), ("max", max), ("min", min)):
        skv = reduce_sharded(frame, op)
        got = dict(skv.to_host().pairs())
        assert got == {k: fn(v) for k, v in groups.items()}, op


def test_host_reduce_on_sharded_kmv(mesh):
    """The per-group host callback tier must also work on sharded data."""
    mr = MapReduce(mesh)
    mr.map(2, emit)
    mr.collate()

    def longest(key, values, kv, ptr):
        kv.add(key, max(values))

    mr.reduce(longest)
    groups = collections.defaultdict(list)
    for itask in range(2):
        rng = np.random.default_rng(itask)
        keys = rng.integers(0, 97, size=500).astype(np.uint64)
        for k, v in zip(keys, keys * 10 + itask):
            groups[int(k)].append(int(v))
    got = dict((int(k), int(v)) for k, v in kv_pairs(mr))
    assert got == {k: max(v) for k, v in groups.items()}


def kv_pairs(mr):
    pairs = []
    mr.scan_kv(lambda k, v, p: pairs.append((k, v)))
    return pairs


def test_sort_sharded(mesh):
    mr = MapReduce(mesh)
    mr.map(6, emit)
    mr.aggregate()
    mr.sort_keys(1)
    frame = mr.kv.one_frame()
    P, cap = frame.nprocs, frame.cap
    k = np.asarray(frame.key).reshape(P, cap)
    for i in range(P):
        ki = k[i, :frame.counts[i]]
        assert (np.diff(ki.astype(np.int64)) >= 0).all()
    mr.sort_keys(-1)
    frame = mr.kv.one_frame()
    k = np.asarray(frame.key).reshape(P, cap)
    for i in range(P):
        ki = k[i, :frame.counts[i]]
        assert (np.diff(ki.astype(np.int64)) <= 0).all()


def test_sort_multivalues_sharded(mesh):
    mr = MapReduce(mesh)
    mr.map(6, emit)
    mr.collate()
    mr.sort_multivalues(1)
    for k, vals in mr.kmv.one_frame().groups():
        assert list(vals) == sorted(vals)
    mr2 = MapReduce(mesh)
    mr2.map(6, emit)
    mr2.collate()
    mr2.sort_multivalues(-1)
    for k, vals in mr2.kmv.one_frame().groups():
        assert list(vals) == sorted(vals, reverse=True)


def test_gather_on_one_shard_moves_nothing():
    """One shard holds every row already: like the reference's, ``gather``
    there returns at once: the frame's arrays are the same arrays, in the
    same order, and no exchange ran (a host frame is placed)."""
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    from gpu_mapreduce_tpu.parallel.sharded import ShardedKV, SyncStats
    mr = MapReduce(make_mesh(1))
    mr.map(6, emit)
    mr.aggregate()
    frame = mr.kv.one_frame()
    pulls = SyncStats.snapshot()
    assert mr.gather(1) == 3000 and mr.gather(4) == 3000
    assert mr.kv.one_frame() is frame and SyncStats.delta(pulls) == 0
    host = MapReduce(make_mesh(1))
    host.map(6, emit)
    assert host.gather(1) == 3000
    assert isinstance(host.kv.one_frame(), ShardedKV)
    assert multiset(host.kv.one_frame().to_host().pairs()) == multiset(
        frame.to_host().pairs())


def test_gather_and_broadcast(mesh):
    mr = MapReduce(mesh)
    mr.map(6, emit)
    mr.aggregate()
    before = multiset(mr.kv.one_frame().to_host().pairs())
    mr.gather(2)
    frame = mr.kv.one_frame()
    assert frame.counts[2:].sum() == 0 and frame.counts[:2].sum() == 3000
    assert multiset(frame.to_host().pairs()) == before

    mr.gather(1)
    frame = mr.kv.one_frame()
    assert frame.counts[0] == 3000
    n = mr.broadcast(0)
    frame = mr.kv.one_frame()
    assert (frame.counts == 3000).all()
    assert n == 3000 * 8  # every proc holds a replica (reference semantics)


def test_scrunch(mesh):
    mr = MapReduce(mesh)
    mr.map(2, emit)
    mr.scrunch(1, np.uint64(7))
    g, n, _ = mr.kmv_stats()
    assert g == 1 and n == 2 * 500 * 2  # one group, (k,v) interleaved


def test_wordfreq_interned_on_mesh(tmp_path, mesh):
    from gpu_mapreduce_tpu.apps.wordfreq import wordfreq_interned

    text = (b"alpha beta gamma alpha delta beta alpha "
            b"epsilon zeta eta theta " * 50)
    f = tmp_path / "w.txt"
    f.write_bytes(text)
    nw_s, nu_s, top_s = wordfreq_interned([str(f)], ntop=3)
    nw_m, nu_m, top_m = wordfreq_interned([str(f)], ntop=3, comm=mesh)
    assert (nw_s, nu_s) == (nw_m, nu_m)
    # compare counts only: rank 3 is a six-way tie at 50, so word identity
    # at the tail is an incidental tie-break of each execution path
    assert [c for _, c in top_s] == [c for _, c in top_m] == [150, 100, 50]


def test_skewed_exchange_multi_round(either_mesh, monkeypatch):
    """Skewed buckets force nrounds > 1 in the flow-controlled exchange;
    round-window rows must not wrap into earlier rounds (round-1 advisor
    finding: negative scatter indices wrapped before mode='drop')."""
    from gpu_mapreduce_tpu.core.frame import KVFrame
    from gpu_mapreduce_tpu.core.column import DenseColumn
    from gpu_mapreduce_tpu.parallel import shuffle
    from gpu_mapreduce_tpu.parallel.sharded import shard_frame

    # per shard: ~1 row to each dest 1..7, a pile of rows to dest 0 —
    # mean nonzero bucket << max bucket ⇒ multi-round
    rng = np.random.default_rng(99)
    hub = np.zeros(2000, np.uint64)            # dest 0 via key % 8
    tail = rng.integers(1, 8, size=56).astype(np.uint64)
    keys = np.concatenate([hub, tail])
    rng.shuffle(keys)
    vals = np.arange(len(keys), dtype=np.uint64)

    monkeypatch.setenv("MRTPU_WIRE", "0")  # the RAW schedule under test
    #                                        (wire twin: test_wire.py)
    seen = {}
    orig = shuffle._phase2_jit

    def spy(mesh_, B, nrounds, cap_out, **kw):
        seen["nrounds"] = nrounds
        return orig(mesh_, B, nrounds, cap_out, **kw)

    monkeypatch.setattr(shuffle, "_phase2_jit", spy)
    shuffle._SPEC_CACHE.clear()   # order-independent: no speculation hit
    skv = shard_frame(KVFrame(DenseColumn(keys), DenseColumn(vals)),
                      either_mesh)
    dest = ("hash", lambda k: k.astype(np.uint32))
    out = shuffle.exchange(skv, dest)
    assert seen["nrounds"] > 1, "test no longer exercises the multi-round path"
    # the public telemetry (r4: the driver dryrun asserts on this too)
    assert out.exchange_stats.nrounds == seen["nrounds"]
    assert out.exchange_stats.bucket >= 1
    assert multiset(out.to_host().pairs()) == multiset(zip(keys, vals))
    P, cap = out.nprocs, out.cap
    k = np.asarray(out.key).reshape(P, cap)
    for i in range(P):
        assert (k[i, :out.counts[i]] % P == i).all()


def test_build_send_round_window_no_wrap():
    """_build_send round r must contain EXACTLY bucket slots [rB, rB+B) —
    the round-1 advisor bug wrapped the previous round's rows (negative
    scatter indices) into this round's buffer, which XLA may keep or drop
    depending on unspecified duplicate-update order."""
    import jax.numpy as jnp
    from gpu_mapreduce_tpu.parallel.shuffle import _build_send

    nprocs, B = 4, 4
    # bucket 0: 10 rows, bucket 1: 1 row, bucket 2: 0 rows, bucket 3: 2 rows
    counts = jnp.array([10, 1, 0, 2], jnp.int32)
    rows = jnp.arange(1, 17, dtype=jnp.uint64)  # 13 real + 3 padding, no zeros
    for r in range(3):
        send = np.asarray(_build_send(nprocs, B, rows, counts, r))
        expect = np.zeros((nprocs, B), np.uint64)
        offs = [0, 10, 11, 11]
        for d in range(nprocs):
            for s in range(B):
                q0 = r * B + s
                if q0 < counts[d]:
                    expect[d, s] = rows[offs[d] + q0]
        np.testing.assert_array_equal(send, expect, err_msg=f"round {r}")


def test_sort_keys_lexicographic_after_intern():
    """sort_keys on a mesh KV whose byte keys were auto-interned must
    order by the BYTES, not the u64 intern ids (reference string sort,
    src/mapreduce.cpp:2763-2802)."""
    from gpu_mapreduce_tpu import MapReduce
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh

    words = [b"pear", b"apple", b"fig", b"zoo", b"beta", b"kiwi",
             b"mango", b"date"]
    mr = MapReduce(make_mesh(4))
    mr.map(1, lambda i, kv, p: [kv.add(w, 1) for w in words])
    mr.aggregate()
    mr.sort_keys(5)
    got = []
    mr.scan_kv(lambda k, v, p: got.append(k))
    assert got == sorted(words)
    mr.sort_keys(-5)
    got = []
    mr.scan_kv(lambda k, v, p: got.append(k))
    assert got == sorted(words, reverse=True)


def test_bytes_values_shard_and_roundtrip(mesh):
    """VERDICT r2 #4: byte-string VALUES intern and shard like keys —
    a (u64 key, bytes value) KV aggregates across the mesh, groups, and
    reduces to the serial oracle with the original value bytes intact."""
    import jax.numpy  # noqa: F401

    def emit_bv(itask, kv, ptr):
        rng = np.random.default_rng(40 + itask)
        for _ in range(200):
            k = int(rng.integers(0, 37))
            kv.add(np.uint64(k), b"doc-%03d" % rng.integers(0, 50))

    oracle = collections.defaultdict(list)
    mr0 = MapReduce()
    mr0.map(4, emit_bv)
    mr0.scan_kv(lambda k, v, p: oracle[int(k)].append(bytes(v)))

    mr = MapReduce(mesh)
    mr.map(4, emit_bv)
    mr.aggregate()
    fr = mr.kv.one_frame()
    assert isinstance(fr, ShardedKV) and fr.value_decode is not None
    # round-trip: pairs decode to the original bytes
    got = collections.defaultdict(list)
    mr.scan_kv(lambda k, v, p: got[int(k)].append(bytes(v)))
    assert {k: sorted(v) for k, v in got.items()} == \
        {k: sorted(v) for k, v in oracle.items()}
    # convert + host reduce sees decoded byte values per group
    mr.convert()
    sizes = {}
    mr.reduce(lambda k, vals, kv, p: (
        sizes.__setitem__(int(k), sorted(bytes(v) for v in vals)),
        kv.add(k, len(vals))))
    assert sizes == {k: sorted(v) for k, v in oracle.items()}


def test_bytes_keys_and_values_wordpair(mesh):
    """Both columns byte strings: (word, doc) pairs shuffle on ids for
    both sides and print/scan reconstruct bytes on both sides."""
    pairs = [(b"alpha", b"d1"), (b"beta", b"d2"), (b"alpha", b"d2"),
             (b"gamma", b"d3"), (b"beta", b"d1"), (b"alpha", b"d1")]
    mr = MapReduce(mesh)
    mr.map(1, lambda i, kv, p: [kv.add(k, v) for k, v in pairs])
    mr.aggregate()
    fr = mr.kv.one_frame()
    assert fr.key_decode is not None and fr.value_decode is not None
    got = []
    mr.scan_kv(lambda k, v, p: got.append((bytes(k), bytes(v))))
    assert sorted(got) == sorted(pairs)
    mr.convert()
    grouped = {}
    mr.scan_kmv(lambda k, vals, p: grouped.__setitem__(
        bytes(k), sorted(bytes(v) for v in vals)))
    oracle = collections.defaultdict(list)
    for k, v in pairs:
        oracle[k].append(v)
    assert grouped == {k: sorted(v) for k, v in oracle.items()}


def test_sort_interned_stays_on_device():
    """VERDICT r2 #7: sort_keys/sort_values on interned mesh columns run
    on device (rank surrogate) — no frame materialisation — and match
    the lexicographic oracle."""
    from gpu_mapreduce_tpu.parallel.sharded import ToHostStats

    words = [b"pear", b"apple", b"fig", b"zoo", b"beta", b"kiwi",
             b"mango", b"date", b"apple", b"fig"]
    mr = MapReduce(make_mesh(4))
    mr.map(1, lambda i, kv, p: [kv.add(w, np.uint64(j))
                                for j, w in enumerate(words)])
    mr.aggregate()
    snap = ToHostStats.snapshot()
    mr.sort_keys(5)
    assert ToHostStats.delta(snap) == (0, 0)
    got = []
    mr.scan_kv(lambda k, v, p: got.append(bytes(k)))
    assert got == sorted(words)
    snap = ToHostStats.snapshot()
    mr.sort_keys(-5)
    assert ToHostStats.delta(snap) == (0, 0)
    got = []
    mr.scan_kv(lambda k, v, p: got.append(bytes(k)))
    assert got == sorted(words, reverse=True)

    # interned VALUES sort by bytes too, on device
    mr2 = MapReduce(make_mesh(4))
    mr2.map(1, lambda i, kv, p: [kv.add(np.uint64(j), w)
                                 for j, w in enumerate(words)])
    mr2.aggregate()
    snap = ToHostStats.snapshot()
    mr2.sort_values(5)
    assert ToHostStats.delta(snap) == (0, 0)
    got = []
    mr2.scan_kv(lambda k, v, p: got.append(bytes(v)))
    assert got == sorted(words)


def test_one_sync_per_sharded_op(mesh):
    """VERDICT r2 #8: each sharded MR op costs exactly ONE controller
    round-trip — parity with the reference's one MPI_Allreduce per op
    (src/mapreduce.cpp:557-558).  A composed collate (aggregate+convert)
    therefore costs two, and a full composed-cc-style stage sequence
    stays at one sync per stage."""
    from gpu_mapreduce_tpu.parallel.sharded import SyncStats

    mr = MapReduce(mesh)
    mr.map(6, emit)

    snap = SyncStats.snapshot()
    mr.aggregate()
    assert SyncStats.delta(snap) == 1, "aggregate != 1 sync"

    snap = SyncStats.snapshot()
    mr.convert()
    assert SyncStats.delta(snap) == 1, "convert != 1 sync"

    from gpu_mapreduce_tpu.oink.kernels import count
    snap = SyncStats.snapshot()
    mr.reduce(count, batch=True)
    assert SyncStats.delta(snap) == 0, "batch reduce pulls mid-op"

    # correctness unchanged
    import collections
    oracle = collections.Counter(k for k, v in oracle_pairs())
    got = {}
    mr.scan_kv(lambda k, v, p: got.__setitem__(int(k), int(v)))
    assert got == dict(oracle)


def test_gather_reference_mod_layout(mesh):
    """gather(n): producing shard i's rows land on shard i % n — the
    reference's exact sender→receiver mapping ("lo procs recv from hi
    procs with same ID % numprocs", src/mapreduce.cpp:919-928)."""
    mr = MapReduce(mesh)
    keys = np.arange(64, dtype=np.uint64)
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, keys))
    mr.aggregate()
    before = mr.kv.one_frame()
    k_before = np.asarray(before.key)
    owner = {}
    for p in range(before.nprocs):
        blk = k_before[p * before.cap:p * before.cap + int(before.counts[p])]
        for k in blk.tolist():
            owner[k] = p
    mr.gather(3)            # n ∤ P: the layouts genuinely differ here
    after = mr.kv.one_frame()
    assert int(after.counts[:3].sum()) == 64
    k_after = np.asarray(after.key)
    for dest in range(3):
        blk = k_after[dest * after.cap:
                      dest * after.cap + int(after.counts[dest])]
        for k in blk.tolist():
            assert owner[k] % 3 == dest, (k, owner[k], dest)


def test_exchange_speculative_caps(mesh, monkeypatch):
    """r4 (VERDICT r3 weak #5): a repeat exchange with the same
    shapes speculates phase 2 with the cached caps so the count-matrix
    pull overlaps device work.  Three contracts: a same-distribution
    repeat runs phase 2 ONCE with the cached caps; a hub-skewed repeat
    whose buckets overflow the cached caps re-runs correctly sized
    (results always exact); sync count stays one per op."""
    from gpu_mapreduce_tpu.core.column import DenseColumn
    from gpu_mapreduce_tpu.core.frame import KVFrame
    from gpu_mapreduce_tpu.parallel import shuffle
    from gpu_mapreduce_tpu.parallel.sharded import SyncStats, shard_frame

    monkeypatch.setenv("MRTPU_WIRE", "0")  # the RAW caps under test
    #                                        (wire twin: test_wire.py)
    calls = []
    orig = shuffle._phase2_jit

    def spy(mesh_, B, nrounds, cap_out, **kw):
        calls.append((B, nrounds, cap_out))
        return orig(mesh_, B, nrounds, cap_out, **kw)

    monkeypatch.setattr(shuffle, "_phase2_jit", spy)
    shuffle._SPEC_CACHE.clear()
    rng = np.random.default_rng(5)
    n = 4096
    uni = rng.integers(0, 1 << 40, n).astype(np.uint64)
    vals = np.arange(n, dtype=np.uint64)

    def xchg(keys):
        skv = shard_frame(KVFrame(DenseColumn(keys), DenseColumn(vals)),
                          mesh)
        before = SyncStats.pulls
        out = shuffle.exchange(skv, ("hash", None))
        assert SyncStats.pulls - before == 1     # still one sync per op
        assert multiset(out.to_host().pairs()) == multiset(zip(keys, vals))

    xchg(uni)                       # cold: one fresh phase 2
    assert len(calls) == 1
    xchg(rng.permutation(uni))      # same distribution: speculation holds
    assert len(calls) == 2, "speculative hit must not re-run phase 2"
    assert calls[1] == calls[0]

    hub = uni.copy()
    hub[: n * 3 // 4] = hub[0]      # 75% on one key: cached caps overflow
    xchg(hub)
    assert len(calls) == 4, "overflowing speculation must re-run phase 2"
    assert calls[3][0] * calls[3][1] > calls[0][0] * calls[0][1]

    xchg(uni)                       # skewed caps fit uniform (Bmax small)
    spec_after = shuffle._SPEC_CACHE[next(iter(shuffle._SPEC_CACHE))]
    assert spec_after[0] == "raw"   # entries are tagged plans now
    assert len(calls) in (5, 6)     # hit (maybe oversized) or re-run
    if len(calls) == 5:             # held: cache must right-size if gross
        assert spec_after[3] <= 4 * calls[0][2]


def test_add_cross_domain_keys_group(mesh):
    """ADVICE r5 regression: a bytes-keyed dataset added to an
    object-keyed one must carry ONE id per logical key — the bytes-kind
    side re-interns through the pickle domain at concat
    (devkernels._align_domains), so equal keys group after collate."""
    mr1 = MapReduce(mesh)
    mr1.map(1, lambda i, kv, p: [kv.add(b"x", 1), kv.add(b"y", 2)])
    mr1.aggregate()
    assert mr1.kv.one_frame().key_decode.kind == "bytes"

    mr2 = MapReduce(mesh)
    # a tuple key forces the object tier, so b"x" here hashes over its
    # PICKLE — a different u64 than mr1's raw-bytes hash
    mr2.map(1, lambda i, kv, p: [kv.add(b"x", 3), kv.add((1, "t"), 4)])
    mr2.aggregate()
    assert mr2.kv.one_frame().key_decode.kind == "object"

    mr1.add(mr2)
    mr1.collate()
    groups = {}

    def take(k, vals, kv, ptr):
        key = tuple(k) if isinstance(k, (list, tuple)) else k
        groups[key] = sorted(int(v) for v in vals)
        kv.add(0, len(vals))

    mr1.reduce(take)
    assert groups[b"x"] == [1, 3]          # ONE group across both domains
    assert groups[b"y"] == [2]
    assert groups[(1, "t")] == [4]
