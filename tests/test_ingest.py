"""Per-shard mesh ingestion + dest-sharded intern tables (VERDICT r4 #4/#5).

The reference's map stage is flat under weak scaling because every rank
reads its own files (src/mapreduce.cpp:1102-1225); parallel/ingest.py is
the mesh twin: contiguous byte-balanced file slices land on their own
shard's device at map time, and byte/object keys intern into per-DEST
tables (core.column.ShardTables) so the aggregate never builds a
controller-global dict (src/mapreduce.cpp:453-473 shuffles raw bytes
fully distributed)."""

import collections
import os

import numpy as np
import pytest

from gpu_mapreduce_tpu.core.column import ShardTables, dest_of_ids
from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.oink.kernels import read_words
from gpu_mapreduce_tpu.parallel.mesh import make_mesh


@pytest.fixture
def corpus(tmp_path):
    import random
    r = random.Random(7)
    vocab = [f"w{i:03d}".encode() for i in range(120)]
    files, oracle = [], collections.Counter()
    for i in range(10):
        ws = r.choices(vocab, k=400 + 50 * i)   # uneven: balance matters
        oracle.update(ws)
        p = tmp_path / f"f{i}.txt"
        p.write_bytes(b" ".join(ws))
        files.append(str(p))
    return files, oracle


def test_mesh_map_files_per_shard(corpus):
    """read_words on an 8-shard mesh ingests per shard: the ingest stats
    show P file slices, per-shard row counts, and a ShardedKV frame with
    dest-sharded intern tables — no controller-global dict."""
    files, oracle = corpus
    mr = MapReduce(make_mesh(8))
    n = mr.map_files(files, read_words)
    assert n == sum(oracle.values())
    st = mr.last_ingest
    assert st["mode"] == "mesh"
    assert len(st["files_per_shard"]) == 8
    assert sum(st["files_per_shard"]) == len(files)
    assert sum(st["rows_per_shard"]) == n
    fr = mr.kv.one_frame()
    kd = fr.key_decode
    assert isinstance(kd, ShardTables)
    sizes = [len(t) for t in kd.tables]
    assert sum(sizes) == len(kd) == len(oracle)
    # bounded: the controller-global-table ceiling is gone — no single
    # table holds the whole vocabulary
    assert max(sizes) < len(oracle)


def test_post_aggregate_decode_locality(corpus):
    """After the hash exchange, shard d's rows decode from tables[d]
    ALONE — the per-shard output property the dest-sharding exists for."""
    files, _ = corpus
    mr = MapReduce(make_mesh(8))
    mr.map_files(files, read_words)
    mr.aggregate()
    fr = mr.kv.one_frame()
    kd = fr.key_decode
    ids = np.asarray(fr.key)
    for p in range(8):
        blk = ids[p * fr.cap: p * fr.cap + int(fr.counts[p])]
        tab = kd.tables[p]
        assert all(int(h) in tab for h in blk.tolist()), p
    # and the routing IS the exchange's hash: dest_of_ids agrees
    valid = np.concatenate([ids[p * fr.cap: p * fr.cap + int(fr.counts[p])]
                            for p in range(8)])
    d = dest_of_ids(valid.astype(np.uint64), 8)
    expect = np.concatenate([np.full(int(fr.counts[p]), p)
                             for p in range(8)])
    np.testing.assert_array_equal(d, expect)


def test_mesh_matches_serial_wordfreq(corpus):
    files, oracle = corpus
    from gpu_mapreduce_tpu.apps.wordfreq import wordfreq
    nm, num, topm = wordfreq(files, ntop=7, comm=make_mesh(8))
    ns, nus, tops = wordfreq(files, ntop=7)
    assert (nm, num) == (ns, nus) == (sum(oracle.values()), len(oracle))
    # ordering among equal counts is tie-broken by arrival order, which
    # the exchange legitimately permutes — compare against the oracle,
    # not serial's tie order
    for top in (topm, tops):
        assert [c for _, c in top] == \
            sorted(oracle.values(), reverse=True)[:7]
        assert all(oracle[w] == c for w, c in top)


def test_mesh_map_file_char_chunks(corpus, tmp_path):
    """Chunked mesh ingest: same pairs as the host path, chunk payloads
    reassemble to the original bytes per file."""
    files, oracle = corpus
    seen = []

    def cb(itask, chunk, kv, ptr):
        seen.append(bytes(chunk))
        for w in bytes(chunk).split():
            kv.add(w, 1)

    mr = MapReduce(make_mesh(8))
    n = mr.map_file_char(16, files, 0, 0, " ", 16, cb)
    assert mr.last_ingest["mode"] == "mesh"
    assert n == sum(oracle.values())        # n = KV pairs, not tasks
    assert mr.last_ingest["ntasks"] == len(seen)
    assert b"".join(seen).replace(b" ", b"") == b"".join(
        open(f, "rb").read().replace(b" ", b"") for f in files)
    mr.collate()
    from gpu_mapreduce_tpu.ops.reduces import count
    nunique = mr.reduce(count, batch=True)
    assert nunique == len(oracle)


def test_host_fallbacks(corpus):
    """addflag / outofcore / unshardable rows replay through the host
    path with identical results."""
    files, oracle = corpus
    mesh = make_mesh(8)
    # addflag=1 appends into an existing dataset → host path
    mr = MapReduce(mesh)
    mr.map_files(files[:2], read_words)
    assert mr.last_ingest["mode"] == "mesh"
    mr.map_files(files[2:], read_words, addflag=1)
    assert mr.last_ingest["mode"] == "host"
    # outofcore=1 keeps the spill machinery → host path
    mr2 = MapReduce(mesh, outofcore=1, memsize=1, maxpage=4)
    mr2.map_files(files, read_words)
    assert mr2.last_ingest["mode"] == "host"
    # a pre-built frame payload (add_frame) is not ingest traffic →
    # Unshardable → host replay, results identical to the host path
    from gpu_mapreduce_tpu.core.frame import KVFrame

    def framed(itask, fname, kv, ptr):
        kv.add_frame(KVFrame(np.arange(2, dtype=np.uint64) + itask,
                             np.zeros(2, np.uint8)))
    mr3 = MapReduce(mesh)
    n3 = mr3.map_files(files, framed)
    assert mr3.last_ingest["mode"] == "host"
    assert "fallback" in mr3.last_ingest
    assert n3 == 2 * len(files)
    # shard dtype mismatch (u32 keys on some shards, f64 on others) →
    # Unshardable; the host path legitimately promotes on concat
    def mixed_dtype(itask, fname, kv, ptr):
        if itask < 5:
            kv.add_batch(np.arange(2, dtype=np.uint32),
                         np.zeros(2, np.uint8))
        else:
            kv.add_batch(np.arange(2, dtype=np.float64),
                         np.zeros(2, np.uint8))
    mr4 = MapReduce(mesh)
    n4 = mr4.map_files(files, mixed_dtype)
    assert mr4.last_ingest["mode"] == "host"
    assert n4 == 2 * len(files)


def test_object_keys_mesh(tmp_path):
    """Arbitrary-object keys (the pickle tier) ride the mesh ingest too;
    cross-shard duplicates dedupe to one id and survive collate."""
    files = []
    for i in range(6):
        p = tmp_path / f"o{i}.txt"
        p.write_bytes(b"x" * 100)
        files.append(str(p))

    def emit(itask, fname, kv, ptr):
        kv.add(("tup", itask % 3), 1)   # tuples: object tier
        kv.add(("tup", "shared"), 1)

    mr = MapReduce(make_mesh(8))
    n = mr.map_files(files, emit)
    assert n == 12
    assert mr.last_ingest["mode"] == "mesh"
    fr = mr.kv.one_frame()
    assert fr.key_decode is not None and fr.key_decode.kind == "object"
    mr.collate()
    from gpu_mapreduce_tpu.ops.reduces import sum_values
    mr.reduce(sum_values, batch=True)
    got = dict(mr.kv.one_frame().to_host().pairs())
    assert got[("tup", "shared")] == 6
    assert got[("tup", 0)] == 2


def test_shardtables_collision_and_merge():
    t = ShardTables(4)
    ids = np.array([1, 2, 3], np.uint64)
    t.absorb(ids, [b"a", b"b", b"c"])
    with pytest.raises(ValueError, match="collision"):
        t.absorb(np.array([2], np.uint64), [b"DIFFERENT"])
    u = ShardTables(4)
    u.absorb(np.array([4], np.uint64), [b"d"])
    m = t.merge(u)
    assert len(m) == 4 and m[2] == b"b" and m[4] == b"d"
    # scalar dict protocol
    assert 3 in m and m.get(99) is None
    assert sorted(m.decode_batch(np.array([1, 4], np.uint64))) == \
        [b"a", b"d"]


def test_checkpoint_roundtrip_mesh_ingested(corpus, tmp_path):
    """save/load of a mesh-ingested dataset: the dest-sharded decode
    tables flow through to_host on save; the loaded host dataset holds
    the original byte keys and re-aggregates cleanly on a fresh mesh."""
    files, oracle = corpus
    mr = MapReduce(make_mesh(8))
    mr.map_files(files, read_words)
    assert mr.last_ingest["mode"] == "mesh"
    ckpt = str(tmp_path / "ck")
    mr.save(ckpt)
    mr2 = MapReduce(make_mesh(8))
    n = mr2.load(ckpt)
    assert n == sum(oracle.values())
    mr2.collate()
    from gpu_mapreduce_tpu.ops.reduces import count
    nunique = mr2.reduce(count, batch=True)
    assert nunique == len(oracle)
    got = dict(mr2.kv.one_frame().to_host().pairs())
    assert got == dict(oracle)


# -- ISSUE 35: the shards' interns on pool threads ----------------------------

def _map_words(files, monkeypatch, schedule: str, P: int = 4):
    """``map_files(read_words)`` on a P-way mesh with the shards' interns
    scheduled one way: everything about the result, to compare."""
    import concurrent.futures
    import threading
    from gpu_mapreduce_tpu.core import column
    mr = MapReduce(make_mesh(P))
    real = column._intern_ranges
    hashed = [threading.Event() for _ in range(P + 1)]
    hashed[P].set()
    sizes = {}          # file buffer length → its shard (one file a shard)
    for k, f in enumerate(files[:P]):
        sizes[os.path.getsize(f)] = k
    assert len(sizes) == P
    finished = []

    def reversed_hashing(buf, starts, lens):
        # shard k's hash and dedupe return only after shard k+1's have
        out = real(buf, starts, lens)
        k = sizes[len(buf)]
        assert hashed[k + 1].wait(timeout=20)
        finished.append(k)
        hashed[k].set()
        return out

    pool = None
    if schedule == "reverse":       # a worker a shard, whatever the host has
        pool = concurrent.futures.ThreadPoolExecutor(P)
        monkeypatch.setattr(column, "_intern_ranges", reversed_hashing)
    elif schedule == "one after another":
        pool = concurrent.futures.ThreadPoolExecutor(1)
    if pool is not None:
        monkeypatch.setattr(mr, "_ingest_pool", lambda: pool)
    try:
        n = mr.map_files(files[:P], read_words)
    finally:
        if pool is not None:
            pool.shutdown()
    if schedule == "reverse":
        assert finished == list(range(P))[::-1]
    fr = mr.kv.one_frame()
    return (n, mr.last_ingest, np.asarray(fr.key).tolist(),
            fr.counts.tolist(),
            [list(fr.key_decode.shard(d).items()) for d in range(P)])


@pytest.mark.parametrize("schedule", ["as it comes", "reverse"])
def test_the_tables_do_not_depend_on_which_intern_finishes_first(
        corpus, monkeypatch, schedule):
    files, _ = corpus
    want = _map_words(files, monkeypatch, "one after another")
    assert want[1]["mode"] == "mesh" and sum(map(len, want[4])) == 120
    assert _map_words(files, monkeypatch, schedule) == want


def test_wordfreq_interned_on_pool_threads_equals_the_serial_count(
        corpus, monkeypatch):
    """Under mapstyle 2 the callbacks run on the ingest pool and intern
    into ONE shared ``ShardTables``, whose lock keeps every absorb whole."""
    import functools
    from gpu_mapreduce_tpu.apps import wordfreq as app
    files, oracle = corpus
    serial = app.wordfreq_interned(files, ntop=7)
    monkeypatch.setattr(app, "MapReduce",
                        functools.partial(MapReduce, mapstyle=2))
    nwords, nunique, top = app.wordfreq_interned(files, ntop=7,
                                                 comm=make_mesh(8))
    assert (nwords, nunique) == serial[:2] == (sum(oracle.values()),
                                               len(oracle))
    assert [c for _w, c in top] == [c for _w, c in serial[2]]
    assert all(oracle[w] == c for w, c in top)


@pytest.mark.parametrize("odd_one", ["float values", "numbers", "objects"])
def test_fallbacks_with_interns_in_flight(corpus, odd_one):
    """After the first shards' interns were started in the byte domain the
    last shard turns out to hold values of another dtype (Unshardable:
    every sink replays into the host dataset, once), numbers for keys
    (Unshardable too, and the host dataset refuses the mix as it always
    did) or objects (every shard's rows move to the pickle domain).  What
    the byte domain made is thrown away, and the result is the host
    path's."""
    files, oracle = corpus
    calls = collections.Counter()

    def emit(itask, fname, kv, ptr):
        calls[itask] += 1
        with open(fname, "rb") as f:
            words = f.read().split()
        if itask < len(files) - 1:
            kv.add_batch(words, np.ones(len(words), np.int64))
        elif odd_one == "float values":
            kv.add_batch(words, np.full(len(words), 0.5))
        elif odd_one == "numbers":
            kv.add_batch(np.arange(3, dtype=np.uint64),
                         np.ones(3, np.int64))
        else:
            kv.add(("a", "tuple"), 1)
            kv.add(b"w000", 1)

    def counts(mr):
        from gpu_mapreduce_tpu.ops.reduces import sum_values
        mr.collate()
        mr.reduce(sum_values, batch=True)
        got = {}
        mr.scan_kv(lambda k, v, p: got.__setitem__(k, float(v)))
        return got

    mesh, host = MapReduce(make_mesh(4)), MapReduce()
    if odd_one == "numbers":
        for mr in (mesh, host):
            with pytest.raises(TypeError, match="byte rows with numeric"):
                mr.map_files(files, emit)
        assert set(calls.values()) == {2}       # once a run, never replayed
        return
    n = mesh.map_files(files, emit)
    assert set(calls.values()) == {1}           # every callback ran once
    assert n == host.map_files(files, emit)
    if odd_one == "float values":
        assert mesh.last_ingest["mode"] == "host"
        assert "mismatch" in mesh.last_ingest["fallback"]
    else:
        assert mesh.last_ingest["mode"] == "mesh"
        kd = mesh.kv.one_frame().key_decode
        assert kd.kind == "object" and ("a", "tuple") in list(
            dict(kd.items()).values())
    got = counts(mesh)
    assert got == counts(host) and len(got) > 100


def test_a_cross_shard_collision_under_concurrent_interns(tmp_path,
                                                          monkeypatch):
    """Four files, each clean on its own; two of their words share a
    forged id and meet in a destination table.  The shards intern at
    once, absorb in shard order, and the job fails naming both words,
    the earlier shard's first."""
    from gpu_mapreduce_tpu import native
    from gpu_mapreduce_tpu.core import column
    from gpu_mapreduce_tpu.parallel.ingest import build_sharded
    from gpu_mapreduce_tpu.core.frame import KVFrame
    from gpu_mapreduce_tpu.utils.io import word_ranges
    texts = [b"one one fine", b"left fine left", b"fine right", b"fine"]
    clash = {b"left", b"right"}

    def forged(real):
        def ids_of(buf, starts, lens, hi=0, lo=0xDEADBEEF):
            ids = real(buf, starts, lens, hi, lo)
            if (hi, lo) == (0, 0xDEADBEEF):
                for i, (s, n) in enumerate(zip(starts, lens)):
                    if bytes(buf[s:s + n]) in clash:
                        ids[i] = 99
            return ids
        return ids_of
    if not native.available():
        pytest.skip("the forgery goes through native.intern_ranges")
    monkeypatch.setattr(native, "intern_ranges", forged(native.intern_ranges))
    frames = [KVFrame(word_ranges(t), np.ones(len(t.split()), np.int64))
              for t in texts]
    for _ in range(5):
        with pytest.raises(ValueError, match="64-bit intern collision: "
                           "b'left' vs b'right'"):
            build_sharded(frames, make_mesh(4))
