"""Golden equivalence + safety tests for the async execution layer
(gpu_mapreduce_tpu/exec/): ingest prefetch, background spill with its
durability barrier, and device-buffer donation.

The overlap contract is "faster, byte-identical": every knob
(MRTPU_PREFETCH / MRTPU_SPILL_BG / MRTPU_DONATE) toggled on vs off must
produce bit-identical datasets, a background-writer crash must surface
as the original error (never as a read of a torn run), and the prefetch
pipeline must preserve source order under any scheduling."""

import collections
import os
import threading
import time

import numpy as np
import pytest

from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
from gpu_mapreduce_tpu.utils.io import read_words
from gpu_mapreduce_tpu import exec as mrexec


@pytest.fixture(autouse=True)
def _fresh_exec_stats():
    mrexec.reset_stats()
    yield
    mrexec.reset_stats()


# ---------------------------------------------------------------------------
# prefetch_iter mechanics
# ---------------------------------------------------------------------------

def test_prefetch_preserves_order_and_bounds_lookahead():
    """Items arrive in source order and the producer never runs more
    than depth+1 items ahead of the consumer (backpressure)."""
    produced = []
    consumed = []
    max_ahead = [0]

    def src():
        for i in range(40):
            produced.append(i)
            max_ahead[0] = max(max_ahead[0],
                               len(produced) - len(consumed))
            yield i

    for item in mrexec.prefetch_iter(src(), depth=2, path="t.order"):
        time.sleep(0.002)          # slow consumer: producer races ahead
        consumed.append(item)
    assert consumed == list(range(40))
    # depth slots in the queue + 1 in the producer's hand + 1 the
    # consumer holds
    assert max_ahead[0] <= 2 + 2, max_ahead[0]


def test_prefetch_threaded_production():
    """The producer really runs on its own thread (overlap exists)."""
    tids = set()

    def src():
        for i in range(5):
            tids.add(threading.get_ident())
            yield i

    out = list(mrexec.prefetch_iter(src(), depth=1, path="t.thread"))
    assert out == list(range(5))
    assert tids == {t for t in tids if t != threading.get_ident()}
    st = mrexec.exec_stats()["overlap"]["t.thread"]
    assert st["items"] == 5


def test_prefetch_zero_depth_is_passthrough():
    tids = set()

    def src():
        for i in range(5):
            tids.add(threading.get_ident())
            yield i

    out = list(mrexec.prefetch_iter(src(), depth=0, path="t.zero"))
    assert out == list(range(5))
    assert tids == {threading.get_ident()}          # no thread
    assert "t.zero" not in mrexec.exec_stats()["overlap"]


def test_prefetch_propagates_producer_error():
    def src():
        yield 1
        yield 2
        raise RuntimeError("reader died")

    got = []
    with pytest.raises(RuntimeError, match="reader died"):
        for x in mrexec.prefetch_iter(src(), depth=2, path="t.err"):
            got.append(x)
    assert got == [1, 2]


def test_prefetch_early_consumer_exit_stops_producer():
    state = {"produced": 0}

    def src():
        for i in range(10_000):
            state["produced"] += 1
            yield i

    it = mrexec.prefetch_iter(src(), depth=1, path="t.break")
    for x in it:
        if x == 3:
            break
    it.close()
    assert state["produced"] < 100    # stopped promptly, not drained


# ---------------------------------------------------------------------------
# golden equivalence: prefetch on/off
# ---------------------------------------------------------------------------

@pytest.fixture
def word_corpus(tmp_path):
    import random
    r = random.Random(31)
    vocab = [f"tok{i:04d}".encode() for i in range(300)]
    files, oracle = [], collections.Counter()
    for i in range(9):
        ws = r.choices(vocab, k=700 + 90 * i)
        oracle.update(ws)
        p = tmp_path / f"c{i}.txt"
        p.write_bytes(b" ".join(ws))
        files.append(str(p))
    return files, oracle


def _ingest_chunks(files, comm, monkeypatch, prefetch: int):
    monkeypatch.setenv("MRTPU_PREFETCH", str(prefetch))
    mr = MapReduce(comm)

    def tokenize(itask, chunk, kv, ptr):
        ws = read_words(chunk)
        kv.add_batch(ws, np.ones(len(ws), np.int64))

    n = mr.map_file_str(32, list(files), 0, 0, b" ", 32, tokenize)
    return n, mr.last_ingest, sorted(mr.kv.one_frame().to_host().pairs())


def test_golden_mesh_chunk_ingest_prefetch_on_off(word_corpus,
                                                  monkeypatch):
    """map_file_str over an 8-shard mesh: MRTPU_PREFETCH=0 vs 3 must be
    byte-identical — same pair multiset, same per-shard row counts, same
    task numbering (pair order)."""
    files, oracle = word_corpus
    n0, ing0, pairs0 = _ingest_chunks(files, make_mesh(8), monkeypatch, 0)
    n3, ing3, pairs3 = _ingest_chunks(files, make_mesh(8), monkeypatch, 3)
    assert n0 == n3 == sum(oracle.values())
    assert ing0["mode"] == ing3["mode"] == "mesh"
    assert ing0["rows_per_shard"] == ing3["rows_per_shard"]
    assert ing0["chunks_per_shard"] == ing3["chunks_per_shard"]
    assert pairs0 == pairs3
    assert collections.Counter(k for k, _ in pairs3) == oracle
    st = mrexec.exec_stats()["overlap"]
    assert st["ingest.chunks"]["items"] >= 8     # the pipeline ran


def test_golden_mesh_file_ingest_prefetch_on_off(word_corpus,
                                                 monkeypatch):
    """map_files (per-file sinks) golden under prefetch, mesh path."""
    from gpu_mapreduce_tpu.oink.kernels import read_words as rw_file
    files, oracle = word_corpus

    def run(prefetch):
        monkeypatch.setenv("MRTPU_PREFETCH", str(prefetch))
        mr = MapReduce(make_mesh(8))
        n = mr.map_files(list(files), rw_file)
        return n, mr.last_ingest, sorted(mr.kv.one_frame()
                                         .to_host().pairs())

    n0, ing0, p0 = run(0)
    n2, ing2, p2 = run(2)
    assert n0 == n2 == sum(oracle.values())
    assert ing0["mode"] == ing2["mode"] == "mesh"
    assert ing0["rows_per_shard"] == ing2["rows_per_shard"]
    assert p0 == p2


def test_golden_serial_chunk_ingest_prefetch_on_off(word_corpus,
                                                    monkeypatch):
    """The serial _map_chunks path (host backend): pair ORDER matters
    (task order is the output order) and must survive prefetch."""
    files, oracle = word_corpus

    def run(prefetch):
        monkeypatch.setenv("MRTPU_PREFETCH", str(prefetch))
        mr = MapReduce()
        out = []

        def tokenize(itask, chunk, kv, ptr):
            for w in read_words(chunk):
                kv.add(w, 1)
                out.append((itask, w))

        n = mr.map_file_str(16, list(files), 0, 0, b" ", 32, tokenize)
        return n, out, [p for fr in mr.kv.frames() for p in fr.pairs()]

    n0, order0, pairs0 = run(0)
    n2, order2, pairs2 = run(2)
    assert n0 == n2
    assert order0 == order2          # identical task payloads + order
    assert pairs0 == pairs2
    assert collections.Counter(k for k, _ in pairs0) == oracle


def test_prefetch_unshardable_fallback_golden(tmp_path, monkeypatch):
    """A mid-stream Unshardable (an add_frame payload, which per-shard
    ingest cannot assemble) must replay every sink into the host KV in
    task order — identical with the pipeline on and off."""
    from gpu_mapreduce_tpu.core.dataset import as_column
    from gpu_mapreduce_tpu.core.frame import KVFrame
    files = []
    for i in range(8):
        p = tmp_path / f"m{i}.txt"
        p.write_bytes(b"alpha beta gamma " * (i + 1))
        files.append(str(p))

    def run(prefetch):
        monkeypatch.setenv("MRTPU_PREFETCH", str(prefetch))
        mr = MapReduce(make_mesh(8))

        def mixed(itask, chunk, kv, ptr):
            ws = read_words(chunk)
            if itask % 3 == 2:   # every third chunk hands a pre-built
                kv.add_frame(KVFrame(   # frame → Unshardable mid-stream
                    as_column(ws), as_column(np.ones(len(ws), np.int64))))
            else:
                kv.add_batch(ws, np.ones(len(ws), np.int64))

        n = mr.map_file_str(16, files, 0, 0, b" ", 16, mixed)
        return n, mr.last_ingest["mode"], \
            [p for fr in mr.kv.frames() for p in fr.pairs()]

    n0, mode0, pairs0 = run(0)
    n2, mode2, pairs2 = run(2)
    assert mode0 == mode2 == "host"
    assert n0 == n2
    assert pairs0 == pairs2          # replay order = task order, both


# ---------------------------------------------------------------------------
# background spill: golden + durability barrier + crash safety
# ---------------------------------------------------------------------------

N_SPILL_ROWS = 5 * (1 << 20) // 16   # ~5 pages of 16 B rows, memsize=1


def _external_sort(tmp_path, monkeypatch, rng, bg: int):
    monkeypatch.setenv("MRTPU_SPILL_BG", str(bg))
    mr = MapReduce(outofcore=1, memsize=1, maxpage=1,
                   fpath=str(tmp_path / f"spill{bg}"))
    keys = rng.integers(0, 1 << 40, N_SPILL_ROWS).astype(np.uint64)
    vals = np.arange(len(keys), dtype=np.uint64)
    step = len(keys) // 6
    mr.map(1, lambda i, kv, p: [kv.add_batch(keys[s:s + step],
                                             vals[s:s + step])
                                for s in range(0, len(keys), step)])
    mr.sort_keys(1)
    out = [(int(k), int(v)) for fr in mr.kv.frames()
           for k, v in fr.pairs()]
    return out


def test_golden_background_spill_on_off(tmp_path, monkeypatch, rng):
    """External sort through the spill cascade: background writer on vs
    off must produce the identical sorted stream."""
    eager = _external_sort(tmp_path, monkeypatch, rng, bg=0)
    rng2 = np.random.default_rng(12345)     # same stream as `rng`
    overlapped = _external_sort(tmp_path, monkeypatch, rng2, bg=1)
    assert eager == overlapped
    assert eager == sorted(eager)
    st = mrexec.exec_stats()["overlap"]
    assert st["spill"]["items"] >= 2        # the writer thread ran


def test_spill_durability_barrier_with_slow_writer(tmp_path, monkeypatch,
                                                   rng):
    """A deliberately slow background writer must never let the merge
    read a run early: the reader blocks at the barrier and the output is
    still exactly sorted."""
    from gpu_mapreduce_tpu.exec import spill as spill_mod
    orig = spill_mod.atomic_save

    def slow_save(path, arr, allow_pickle=False):
        time.sleep(0.05)
        orig(path, arr, allow_pickle)

    monkeypatch.setattr(spill_mod, "atomic_save", slow_save)
    out = _external_sort(tmp_path, monkeypatch, rng, bg=1)
    assert out == sorted(out)
    st = mrexec.exec_stats()["overlap"]["spill"]
    assert st["wait_s"] > 0                 # the barrier actually held


def test_crash_during_background_spill_never_reads_torn_run(
        tmp_path, monkeypatch, rng):
    """A writer crash mid-file must surface as the ORIGINAL error at the
    durability barrier — never as a numpy parse of a torn .npy — and
    must leave no torn file under a final run name."""
    from gpu_mapreduce_tpu.core import external as ext
    calls = {"n": 0}
    orig = ext._save_col

    def dying_save(col, path):
        calls["n"] += 1
        if calls["n"] == 4:   # crash mid-write of the 2nd run's file
            with open(path + ".tmp", "wb") as f:
                f.write(b"\x93NUMPY-half-a-header")   # torn tmp bytes
            raise OSError("disk gone")
        orig(col, path)

    monkeypatch.setattr(ext, "_save_col", dying_save)
    monkeypatch.setenv("MRTPU_SPILL_BG", "1")
    spill_dir = tmp_path / "crash"
    mr = MapReduce(outofcore=1, memsize=1, maxpage=1,
                   fpath=str(spill_dir))
    keys = rng.integers(0, 1 << 40, N_SPILL_ROWS).astype(np.uint64)
    step = len(keys) // 6
    mr.map(1, lambda i, kv, p: [kv.add_batch(keys[s:s + step],
                                             keys[s:s + step])
                                for s in range(0, len(keys), step)])
    with pytest.raises(Exception, match="disk gone"):
        mr.sort_keys(1)
    # nothing torn survives under a FINAL run name: every remaining
    # sortrun .npy parses, the torn bytes only ever lived in a .tmp
    for name in os.listdir(spill_dir):
        if "sortrun" in name and name.endswith(".npy"):
            np.load(os.path.join(spill_dir, name), allow_pickle=True)


def test_atomic_save_leaves_no_final_on_crash(tmp_path):
    """atomic_save's contract directly: an interrupted write leaves only
    the tmp sibling, never a readable-but-wrong final path."""
    from gpu_mapreduce_tpu.exec.spill import atomic_save
    path = str(tmp_path / "run.k.npy")
    arr = np.arange(1000)
    atomic_save(path, arr)
    np.testing.assert_array_equal(np.load(path), arr)
    # an object array with allow_pickle=False dies INSIDE np.save, i.e.
    # mid-write: the final path must never appear
    path2 = str(tmp_path / "run.v.npy")
    with pytest.raises(ValueError):
        atomic_save(path2, np.array([b"a", 1], object),
                    allow_pickle=False)
    assert not os.path.exists(path2)
    assert os.path.exists(path2 + ".tmp")    # only the torn tmp remains


# ---------------------------------------------------------------------------
# donation: golden + buffers actually donated
# ---------------------------------------------------------------------------

def _pipeline(comm, monkeypatch, donate: int, fuse: int = 0):
    from gpu_mapreduce_tpu.ops.reduces import count
    monkeypatch.setenv("MRTPU_DONATE", str(donate))
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 500, 20_000).astype(np.uint64)
    vals = np.ones(len(keys), np.int64)
    mr = MapReduce(comm, fuse=fuse)
    mr.kv = mr._new_kv()
    mr.kv.add_batch(keys, vals)
    mr.kv.complete()
    mr.aggregate()
    mr.convert()
    n = int(mr.reduce(count, batch=True))
    fr = mr.kv.one_frame().to_host()
    return n, sorted(zip(np.asarray(fr.key.data).tolist(),
                         np.asarray(fr.value.data).tolist()))


def test_golden_donation_on_off_eager(monkeypatch):
    n0, p0 = _pipeline(make_mesh(8), monkeypatch, donate=0)
    n1, p1 = _pipeline(make_mesh(8), monkeypatch, donate=1)
    assert n0 == n1 == 500
    assert p0 == p1


def test_golden_donation_on_off_fused(monkeypatch):
    """The fused plan tier with donation on must match eager-no-donation
    bit for bit (composes the plan/ golden contract with exec/)."""
    n0, p0 = _pipeline(make_mesh(8), monkeypatch, donate=0, fuse=0)
    n1, p1 = _pipeline(make_mesh(8), monkeypatch, donate=1, fuse=1)
    assert n0 == n1
    assert p0 == p1


def test_exchange_donates_dead_input_buffers(monkeypatch):
    """With MRTPU_DONATE=1 the exchange's input dataset buffers are
    actually DELETED (aliased away) — the residency win exists; with =0
    they survive (the golden escape hatch)."""
    from gpu_mapreduce_tpu.core.frame import KVFrame
    from gpu_mapreduce_tpu.core.column import DenseColumn
    from gpu_mapreduce_tpu.parallel import shuffle
    from gpu_mapreduce_tpu.parallel.sharded import shard_frame

    rng = np.random.default_rng(3)
    keys = rng.integers(0, 1 << 30, 4096).astype(np.uint64)
    vals = np.arange(len(keys), dtype=np.uint64)
    oracle = sorted(zip(keys.tolist(), vals.tolist()))

    monkeypatch.setenv("MRTPU_DONATE", "0")
    skv = shard_frame(KVFrame(DenseColumn(keys), DenseColumn(vals)),
                      make_mesh(8))
    out = shuffle.exchange(skv, ("hash", None))
    assert not skv.key.is_deleted()
    got = sorted((int(k), int(v)) for k, v in out.to_host().pairs())
    assert got == oracle

    monkeypatch.setenv("MRTPU_DONATE", "1")
    skv = shard_frame(KVFrame(DenseColumn(keys), DenseColumn(vals)),
                      make_mesh(8))
    out = shuffle.exchange(skv, ("hash", None))
    assert skv.key.is_deleted() and skv.value.is_deleted()
    got = sorted((int(k), int(v)) for k, v in out.to_host().pairs())
    assert got == oracle


def test_speculative_phase2_never_donates(monkeypatch):
    """Two same-shape exchanges: the second takes the speculative path,
    whose phase-2 MUST keep its inputs alive (a failed speculation
    re-runs phase 2 on them).  The skew flip then exercises exactly that
    re-run — with donation on throughout, output stays correct."""
    from gpu_mapreduce_tpu.core.frame import KVFrame
    from gpu_mapreduce_tpu.core.column import DenseColumn
    from gpu_mapreduce_tpu.parallel import shuffle
    from gpu_mapreduce_tpu.parallel.sharded import shard_frame

    monkeypatch.setenv("MRTPU_DONATE", "1")
    shuffle._SPEC_CACHE.clear()
    mesh = make_mesh(8)
    rng = np.random.default_rng(11)
    n = 4096
    uni = rng.integers(0, 1 << 40, n).astype(np.uint64)
    vals = np.arange(n, dtype=np.uint64)

    def xchg(keys):
        skv = shard_frame(KVFrame(DenseColumn(keys), DenseColumn(vals)),
                          mesh)
        out = shuffle.exchange(skv, ("hash", None))
        got = sorted((int(k), int(v)) for k, v in out.to_host().pairs())
        assert got == sorted(zip(keys.tolist(), vals.tolist()))
        return out

    xchg(uni)                                   # cold
    out = xchg(rng.permutation(uni))            # speculative hit
    assert out.exchange_stats.speculative
    hub = uni.copy()
    hub[: n * 3 // 4] = hub[0]                  # overflow: spec re-runs
    out = xchg(hub)
    assert not out.exchange_stats.speculative


def test_donation_never_warns_unusable(monkeypatch):
    """The library only donates provably-aliasable buffers, so jax's
    'Some donated buffers were not usable' warning must never fire —
    including the count-reduce case whose value output is 1-D int64
    while the input values are narrow uint8 (the non-aliasable side is
    simply not donated)."""
    import warnings as _warnings
    from gpu_mapreduce_tpu.ops.reduces import count
    monkeypatch.setenv("MRTPU_DONATE", "1")
    n = 12347                      # odd size: fresh shapes, fresh jits
    keys = (np.arange(n, dtype=np.uint64) * 7) % 300
    with _warnings.catch_warnings(record=True) as w:
        _warnings.simplefilter("always")
        for fuse in (0, 1):
            for vdtype in (np.uint8, np.int64):
                mr = MapReduce(make_mesh(8), fuse=fuse)
                mr.kv = mr._new_kv()
                mr.kv.add_batch(keys, np.ones(n, vdtype))
                mr.kv.complete()
                mr.aggregate()
                mr.convert()
                assert int(mr.reduce(count, batch=True)) == 300
    bad = [x for x in w if "donated buffers" in str(x.message)]
    assert not bad, [str(x.message) for x in bad]


def test_copy_then_aggregate_never_corrupts_sibling(monkeypatch):
    """add_kv/copy() share ShardedKV frame OBJECTS: with donation on
    (the default), an aggregate on either MR must not delete device
    arrays the other still reads (the _shared guard)."""
    from gpu_mapreduce_tpu.ops.reduces import count
    monkeypatch.setenv("MRTPU_DONATE", "1")
    mesh = make_mesh(8)
    keys = (np.arange(1 << 12, dtype=np.uint64) * 31) % 200
    mr = MapReduce(mesh)
    mr.map(1, lambda i, kv, p: kv.add_batch(keys,
                                            np.ones(len(keys), np.int64)))
    mr.aggregate()                      # dataset now ONE sharded frame
    mr2 = mr.copy()                     # shares that frame object
    mr2.aggregate()                     # must NOT consume mr's arrays
    mr.convert()                        # reads the shared frame
    n = int(mr.reduce(count, batch=True))
    mr2.convert()
    n2 = int(mr2.reduce(count, batch=True))
    assert n == n2 == 200


@pytest.mark.parametrize("nkeys,levelled", [(3000, False), (1 << 12, True)])
def test_failed_exchange_after_donation_leaves_clean_state(monkeypatch,
                                                           nkeys, levelled):
    """A phase-2 failure after the donated phase-1 dispatch must leave
    the dataset EMPTY (clean MRError on next op), never frames holding
    deleted buffers (cryptic RuntimeError deep in XLA).  Where aggregate
    levelled its input first (PR 27: the hash spread of 4096 keys puts a
    shard over 512 rows), the exchange consumed the levelled copy and
    the installed frame is intact: the op can simply be made again."""
    from gpu_mapreduce_tpu.core.runtime import MRError
    from gpu_mapreduce_tpu.parallel import shuffle
    monkeypatch.setenv("MRTPU_DONATE", "1")

    def boom(*a, **kw):
        raise RuntimeError("phase2 exploded")

    mr = MapReduce(make_mesh(8))
    keys = np.arange(nkeys, dtype=np.uint64)
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, keys))
    mr.aggregate()                      # install the sharded frame
    from gpu_mapreduce_tpu.parallel.devkernels import level_sharded
    installed = mr.kv.one_frame()
    assert (level_sharded(installed) is not installed) == levelled
    # both phase-2 variants: the wire codec (MRTPU_WIRE, default on)
    # dispatches _phase2_wire_jit instead of _phase2_jit
    monkeypatch.setattr(shuffle, "_phase2_jit", boom)
    monkeypatch.setattr(shuffle, "_phase2_wire_jit", boom)
    shuffle._SPEC_CACHE.clear()
    with pytest.raises(RuntimeError, match="phase2 exploded"):
        mr.aggregate()                  # phase 1 donated, phase 2 died
    if levelled:
        assert not installed.key.is_deleted()
        assert mr.convert() == nkeys    # intact, no deleted array
    else:
        with pytest.raises(MRError):
            mr.convert()                # clean error, not deleted-array


def test_failed_fused_group_after_donation_leaves_clean_state(
        monkeypatch):
    """The fused plan tier honours the same contract as the eager
    exchange: a fused-program failure after the donated phase-1 frees
    the dataset to a clean MRError state."""
    from gpu_mapreduce_tpu.core.runtime import MRError
    from gpu_mapreduce_tpu.plan import fuser
    from gpu_mapreduce_tpu.ops.reduces import count
    monkeypatch.setenv("MRTPU_DONATE", "1")
    mr = MapReduce(make_mesh(8))
    keys = np.arange(1 << 12, dtype=np.uint64) % 100
    mr.kv = mr._new_kv()
    mr.kv.add_batch(keys, np.ones(len(keys), np.int64))
    mr.kv.complete()
    mr.aggregate()                      # install a ShardedKV frame

    def boom(*a, **kw):
        raise RuntimeError("fused exploded")

    monkeypatch.setattr(fuser, "_fused_exchange_jit", boom)
    mr.set(fuse=1)
    with pytest.raises(RuntimeError, match="fused exploded"):
        mr.aggregate()
        mr.convert()
        int(mr.reduce(count, batch=True))   # barrier runs the plan
    kv = mr._kv_data
    assert kv is not None and kv._frames == [] and not kv.complete_done
    mr.set(fuse=0)
    with pytest.raises(MRError):
        mr.convert()                    # clean error, not deleted-array


@pytest.mark.skipif(
    (os.cpu_count() or 1) < 2,
    reason="MapReduce._ingest_pool sizes its executor min(cpu_count, 16); "
           "on a 1-CPU container that is ONE worker, so cross-file reads "
           "cannot overlap by construction — the parallelism contract "
           "this test asserts only exists on multi-core hosts")
def test_mapstyle2_map_files_reads_in_parallel(word_corpus, monkeypatch):
    """mapstyle-2 mesh map_files must keep cross-file read parallelism:
    with ~1 file per shard, callbacks still run on several pool threads
    concurrently (the pre-exec behavior, kept under the pipeline)."""
    import threading as _threading
    files, oracle = word_corpus
    monkeypatch.setenv("MRTPU_PREFETCH", "1")
    mr = MapReduce(make_mesh(8), mapstyle=2)
    active = {"now": 0, "max": 0}
    lock = _threading.Lock()

    def cb(itask, fname, kv, ptr):
        with lock:
            active["now"] += 1
            active["max"] = max(active["max"], active["now"])
        time.sleep(0.03)                # hold the slot so overlap shows
        with open(fname, "rb") as f:
            ws = read_words(f.read())
        kv.add_batch(ws, np.ones(len(ws), np.int64))
        with lock:
            active["now"] -= 1

    n = mr.map_files(list(files), cb)
    assert n == sum(oracle.values())
    assert active["max"] > 1, "file reads serialized"


# ---------------------------------------------------------------------------
# surfacing: stats() / metrics / pool reuse
# ---------------------------------------------------------------------------

def test_stats_exec_section_and_gauge(word_corpus, monkeypatch):
    from gpu_mapreduce_tpu.obs import metrics as obs_metrics
    from gpu_mapreduce_tpu.obs.metrics import enable_metrics
    from gpu_mapreduce_tpu.obs.tracer import get_tracer
    files, _ = word_corpus
    enable_metrics(flight=False)
    try:
        monkeypatch.setenv("MRTPU_PREFETCH", "2")
        mr = MapReduce(make_mesh(8))

        def tokenize(itask, chunk, kv, ptr):
            ws = read_words(chunk)
            kv.add_batch(ws, np.ones(len(ws), np.int64))

        mr.map_file_str(16, list(files), 0, 0, b" ", 32, tokenize)
        st = mr.stats()["exec"]
        assert st["knobs"]["prefetch"] == 2
        ov = st["overlap"]["ingest.chunks"]
        assert ov["items"] > 0 and 0.0 <= ov["overlap_ratio"] <= 1.0
        snap = obs_metrics.snapshot()
        g = snap["mrtpu_overlap_ratio"]
        paths = {s["labels"]["path"] for s in g["samples"]}
        assert "ingest.chunks" in paths
    finally:
        obs_metrics.reset()
        get_tracer().reset()


def test_ingest_pool_reused_across_calls(word_corpus, monkeypatch):
    """mapstyle-2 ingest reuses ONE executor per MapReduce (the
    run_sinks satellite) instead of building one per call."""
    files, oracle = word_corpus
    monkeypatch.setenv("MRTPU_PREFETCH", "1")
    mr = MapReduce(make_mesh(8), mapstyle=2)
    from gpu_mapreduce_tpu.oink.kernels import read_words as rw_file
    n1 = mr.map_files(list(files), rw_file)
    pool1 = mr._ingest_pool_obj
    assert pool1 is not None
    n2 = mr.map_files(list(files), rw_file)
    assert mr._ingest_pool_obj is pool1
    assert n1 == n2 == sum(oracle.values())
