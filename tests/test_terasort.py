"""TeraSort (ISSUE 36): ``apps/terasort.TeraSort`` against the plain
reference (``benchmark/refs/terasort.py``: numpy only) on seeded records,
on one shard and on the CPU mesh of 4 — total order across the shards'
part files, every record once, whole; the record map, the fixed-width key
codec and the wide-row ``sort_keys`` it forced, each on its own."""

import glob
import io
import os

import jax
import numpy as np
import pytest

from benchmark import CheckFailure
from benchmark.refs import terasort as ref
from gpu_mapreduce_tpu import MapReduce, MRError
from gpu_mapreduce_tpu.apps import terasort as app
from gpu_mapreduce_tpu.core.column import (fixed_key_bytes, fixed_key_words,
                                           fixed_value_bytes,
                                           fixed_value_words)
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
from gpu_mapreduce_tpu.utils.io import RecordFormat


@pytest.fixture(scope="module")
def meshes():
    return {n: make_mesh(n) for n in (1, 2, 4, 8)}


@pytest.fixture(autouse=True)
def a_sample_smaller_than_the_records(monkeypatch):
    """The application's sample is a constant (Hadoop's 100,000), which
    at these sizes would take every key: held to 64 here, so that the
    splitters come from a strided sample as they do at a real size."""
    monkeypatch.setattr(app, "SAMPLE", 64)


def _records(rng, n):
    return rng.integers(0, 256, (n, ref.RECORD), dtype=np.uint8)


def _random(rng):
    return [_records(rng, n) for n in (700, 1300, 250, 901)]


def _prefix_twins(rng):
    """Every key equal in its first 8 bytes, different in bytes 8-9."""
    files = _random(rng)
    tails = rng.permutation(1 << 16)[:sum(map(len, files))].astype(">u2")
    at = 0
    for f in files:
        f[:, :8] = (1, 2, 3, 4, 5, 6, 7, 8)
        f[:, 8:10] = tails[at:at + len(f)].view(np.uint8).reshape(-1, 2)
        at += len(f)
    return files


def _duplicates(rng):
    """Whole keys many times over, their values different."""
    files = _random(rng)
    pool = rng.integers(0, 256, (40, ref.KEY), dtype=np.uint8)
    for f in files:
        f[:, :ref.KEY] = pool[rng.integers(0, len(pool), len(f))]
    return files


def _one_range(rng):
    """A skewed sample: nineteen records in twenty share one key, so every
    splitter is that key; the records below it fall to the first shard,
    it and the records above it to the last, and two shards of four get
    nothing."""
    files = _random(rng)
    for f in files:
        f[rng.random(len(f)) < 0.95, :ref.KEY] = 0x80
    return files


def _few_files(rng):
    """Two files on four shards: two shards read nothing."""
    return [_records(rng, 900), _records(rng, 1100)]


def _uneven_files(rng):
    """A file count that four does not divide, sizes that differ."""
    return [_records(rng, n) for n in (300, 50, 820, 1, 400, 77, 512)]


CASES = {"random": _random, "prefix_twins": _prefix_twins,
         "duplicates": _duplicates, "one_range": _one_range,
         "few_files": _few_files, "uneven_files": _uneven_files}


def _write(tmp_path, files):
    paths = []
    for i, recs in enumerate(files):
        paths.append(str(tmp_path / f"in-{i:03d}.dat"))
        recs.tofile(paths[-1])
    return paths


def _parts(outdir):
    return sorted(glob.glob(os.path.join(outdir, "part-*")))


@pytest.mark.parametrize("nshards", [1, 4])
@pytest.mark.parametrize("case", CASES)
def test_terasort_against_the_reference(case, nshards, meshes, tmp_path, rng):
    files = CASES[case](rng)
    paths = _write(tmp_path, files)
    want = ref.summary(paths)
    out = str(tmp_path / "out")
    ts = app.TeraSort(comm=meshes[nshards])
    assert ts.run(paths, outdir=out) == want["records"]
    parts = _parts(out)
    assert [os.path.basename(p) for p in parts] == [
        f"part-{p:05d}" for p in range(nshards)]
    facts = ref.validate(parts, want)       # count, order, checksum, keys
    assert ts.mr.last_ingest["mode"] == "records"
    assert ts.mr.last_ingest["shards"] == nshards
    assert want["duplicate_keys"] == (case in ("duplicates", "one_range"))
    if not want["duplicate_keys"]:
        got = np.concatenate([ref.records(p) for p in parts])
        assert np.array_equal(got, ref.sort_records(np.concatenate(files)))
    if nshards == 4:
        assert len(ts.splitters) == 3
        if case == "one_range":
            assert (ts.splitters == [0x80808080, 0x80808080,
                                     0x80800000]).all()
            assert facts["rows_per_part"][1:3] == [0, 0]
        if case == "few_files":
            assert ts.mr.last_ingest["files_per_shard"].count(0) == 2
    else:
        assert len(ts.splitters) == 0       # one shard: nothing is sampled


def test_terasort_on_the_serial_backend(tmp_path, rng):
    paths = _write(tmp_path, _prefix_twins(rng))
    out = str(tmp_path / "out")
    ts = app.TeraSort()
    ts.run(paths, outdir=out)
    assert ts.mr.last_ingest == {"mode": "host"}
    ref.validate(_parts(out), ref.summary(paths))


def test_a_sort_by_the_u64_prefix_alone_is_caught(tmp_path, rng):
    """What the generator's prefix twins are for: an order by the first 8
    key bytes that drops bytes 8-9 passes the count and the checksum and
    fails the order."""
    recs = np.concatenate(_random(rng))
    twins = np.arange(1, len(recs), 7)
    recs[twins, :8] = recs[twins - 1, :8]
    want = ref.summary([recs])
    prefix = np.ascontiguousarray(recs[:, :8]).view(">u8").ravel()
    truncated = recs[np.argsort(prefix, kind="stable")]
    with pytest.raises(CheckFailure, match="below the one before it"):
        ref.validate([truncated], want)
    ref.validate([ref.sort_records(recs)], want)


def test_the_oink_command_runs_the_application(meshes, tmp_path, rng):
    from gpu_mapreduce_tpu.oink.script import OinkScript
    files = _random(rng)
    paths = _write(tmp_path, files)
    out = str(tmp_path / "parts")
    script = OinkScript(comm=meshes[4], screen=io.StringIO())
    script.run_string(f"variable files index {' '.join(paths)}\n"
                      f"terasort -i v_files -o {out} mrs")
    assert script.screen.getvalue().strip() == (
        f"TeraSort: {sum(map(len, files))} records, 4 part files, "
        f"3 splitters")
    ref.validate(_parts(out), ref.summary(paths))
    frame = script.obj.get_mr("mrs").kv.one_frame()
    assert int(frame.counts.sum()) == sum(map(len, files))
    with pytest.raises(MRError, match="reads files"):
        script.run_string(f"terasort -i mrs -o {out} NULL")
    with pytest.raises(MRError, match="Illegal terasort"):   # no argument
        script.run_string(f"terasort 64 -i v_files -o {out} NULL")


# -- the total order: a spec of the exchange, its splitters an operand -------------

def _key_bytes(rng, n):
    return rng.integers(0, 256, (n, ref.KEY), dtype=np.uint8)


def _random_keys(rng, nshards):
    return _key_bytes(rng, 600), np.sort(ref.keys(_key_bytes(
        rng, nshards - 1)))


def _keys_on_a_splitter(rng, nshards):
    """Half of the keys are a splitter's own bytes: ties go up."""
    keys, splitters = _random_keys(rng, nshards)
    own = splitters.view(np.uint8).reshape(-1, ref.KEY)
    keys[::2] = own[rng.integers(0, len(own), len(keys[::2]))]
    return keys, splitters


def _repeated_splitters(rng, nshards):
    """Every splitter the same key: the shards between the first and the
    last get nothing."""
    keys, splitters = _random_keys(rng, nshards)
    splitters[:] = splitters[len(splitters) // 2]
    keys[:50] = splitters[:1].view(np.uint8)
    return keys, splitters


def _all_below(rng, nshards):
    """One shard's worth of keys, every one below every splitter."""
    keys, splitters = _random_keys(rng, nshards)
    keys[:, 0] = 0
    own = splitters.view(np.uint8).reshape(-1, ref.KEY)
    own[:, 0] = np.maximum(own[:, 0], 1)
    return keys, np.sort(splitters)


ORDER_CASES = {"random": _random_keys, "on_a_splitter": _keys_on_a_splitter,
               "repeated_splitters": _repeated_splitters,
               "all_below": _all_below}


@pytest.mark.parametrize("nshards", [2, 4, 8])
@pytest.mark.parametrize("case", ORDER_CASES)
def test_the_total_order_sends_a_key_where_searchsorted_says(
        case, nshards, meshes, rng):
    """``aggregate(TotalOrder(splitters))``: every row on the shard that
    ``np.searchsorted(splitters, keys, "right")`` names for its 10 key
    bytes, its value still beside it, none lost."""
    from gpu_mapreduce_tpu.parallel.shuffle import TotalOrder
    keys, splitters = ORDER_CASES[case](rng, nshards)
    want = np.searchsorted(splitters, ref.keys(keys), side="right")
    words = fixed_key_words(keys)
    value = np.arange(len(keys), dtype=np.uint32)[:, None] * np.ones(
        23, np.uint32)
    mr = MapReduce(meshes[nshards])
    mr.map(1, lambda itask, kv, ptr: kv.add_batch(words, value))
    order = TotalOrder(fixed_key_words(
        splitters.view(np.uint8).reshape(-1, ref.KEY)))
    assert np.array_equal(np.asarray(order(words)), want)   # a user hash
    mr.aggregate(order)
    assert mr.last_exchange.rows == len(keys)
    fr = mr.kv.one_frame()
    assert fr.counts.tolist() == np.bincount(
        want, minlength=nshards).tolist()
    for p in range(nshards):
        host = fr.shard_to_host(p)
        rows = np.asarray(host.value.data)[:, 0]
        assert (want[rows] == p).all()
        assert np.array_equal(np.asarray(host.key.data), words[rows])
    if case == "repeated_splitters":
        assert fr.counts[1:-1].sum() == 0
    if case == "all_below":
        assert fr.counts[0] == len(keys)


def _even(rng):
    """Four files of one size: with a sample of a third of the keys the
    shards and their buckets come out even to a few rows in a hundred,
    well inside the exchange plan's room, as at a real size (10^5 keys
    of 2 x 10^7), so a job's plan holds for the next job's records."""
    return [_records(rng, 720) for _ in range(4)]


@pytest.fixture
def a_sample_that_balances(monkeypatch):
    monkeypatch.setattr(app, "SAMPLE", 1024)


def _datasets(tmp_path, rng, n):
    """The same file sizes ``n`` times, other records: other splitters."""
    jobs = []
    for j in range(n):
        d = tmp_path / f"job{j}"
        d.mkdir()
        jobs.append(_write(d, _even(rng)))
    return jobs


def _traced(fn):
    """``fn()`` with the tracer on: its events."""
    from gpu_mapreduce_tpu.obs import get_tracer
    tracer = get_tracer()
    tracer.reset()
    tracer.enable(ring=1 << 14)
    try:
        fn()
        return tracer.events()
    finally:
        tracer.clear()
        tracer.disable()


def _traced_jobs(mesh, jobs, tmp_path):
    """``TeraSort.run`` over each of ``jobs`` on a fresh application, the
    tracer on: ``(applications, events)``."""
    apps = []

    def run():
        for j, paths in enumerate(jobs):
            apps.append(app.TeraSort(comm=mesh))
            apps[-1].run(paths, outdir=str(tmp_path / f"out{j}"))

    return apps, _traced(run)


def test_a_second_job_over_other_records_builds_no_phase_1(
        meshes, tmp_path, rng, a_sample_that_balances):
    """The splitters are data: jobs over different records on one mesh
    trace and lower phase 1 once, every exchange after the first runs
    phase 2 ahead of the count sync, and every output is the reference's.
    The second job lowers one program, the phase 2 that does not donate
    its input (a speculation that fails runs phase 2 again on the same
    rows; every exchange's first repeat builds it); the third lowers
    nothing."""
    from gpu_mapreduce_tpu import obs
    from gpu_mapreduce_tpu.obs import names
    from gpu_mapreduce_tpu.parallel import shuffle
    shuffle.PHASE1_CACHE.clear()
    shuffle._SPEC_CACHE.clear()
    jobs = _datasets(tmp_path, rng, 3)
    ran, events = _traced_jobs(meshes[4], jobs, tmp_path)
    built = obs.programs()
    assert not np.array_equal(ran[0].splitters, ran[1].splitters)
    for ts, paths in zip(ran, jobs):
        ref.validate(ts.parts, ref.summary(paths))
    ex = [e["args"] for e in events if e["name"] == names.SHUFFLE_EXCHANGE]
    assert [a["dest"] for a in ex] == ["order"] * 3
    assert [a["phase1_built"] for a in ex] == [1, 0, 0]
    assert [a["speculative"] for a in ex] == [False, True, True]
    assert built[names.SHUFFLE_PHASE1]["lowerings"] == 1
    assert built[names.TERASORT_SAMPLE_KEYS]["lowerings"] == 1
    roots = [e["args"] for e in events if e["name"] == names.TERASORT_RUN]
    assert roots[0][names.ATTR_JIT_LOWERINGS] > 0
    assert roots[1][names.ATTR_JIT_LOWERINGS] <= 1
    assert roots[2][names.ATTR_JIT_LOWERINGS] == 0
    assert shuffle.PHASE1_CACHE.stats()["size"] == 1


def test_the_caches_hold_as_much_after_five_jobs_as_after_two(
        meshes, tmp_path, rng, a_sample_that_balances):
    from gpu_mapreduce_tpu.parallel import shuffle
    paths = _write(tmp_path, _even(rng))
    sizes = []
    for job in range(5):
        recs = _even(rng)           # other records, other splitters, a job
        for path, r in zip(paths, recs):
            r.tofile(path)
        app.TeraSort(comm=meshes[4]).run(paths)
        sizes.append((len(shuffle._SPEC_CACHE), len(shuffle.PHASE1_CACHE),
                      len(shuffle.PHASE2_CACHE),
                      app._sample_jit.cache_info().currsize))
    assert sizes[1] == sizes[4]


def test_the_sample_reads_each_shards_own_rows(meshes):
    """The sample's program: no collective and no gather over the sharded
    column (an ``all-gather`` of it is 240 MB a chip at the cell's size);
    a shard's rows at an even stride."""
    import re
    mesh = meshes[4]
    sds = jax.ShapeDtypeStruct
    lowered = app._sample_jit(mesh, 16).lower(
        sds((4 * 1024, 3), np.uint32), sds((4,), np.int32),
        sds((4,), np.int32))
    from gpu_mapreduce_tpu.obs import names
    assert re.search(r"module @(\w+)", lowered.as_text()).group(1) \
        == names.TERASORT_SAMPLE_KEYS
    text = lowered.compile().as_text()
    assert not re.search(r"all-gather|all-reduce|all-to-all|"
                         r"collective-permute", text), text
    key = np.arange(4 * 1024 * 3, dtype=np.uint32).reshape(-1, 3)
    counts = np.array([1000, 0, 7, 1024], np.int32)
    take = np.array([16, 0, 7, 5], np.int32)
    got = np.asarray(app._sample_jit(mesh, 16)(key, counts, take)).reshape(
        4, 16, 3)
    for p in range(4):
        rows = p * 1024 + np.arange(take[p]) * counts[p] // max(take[p], 1)
        assert np.array_equal(got[p, :take[p]], key[rows])


def test_the_sample_is_the_constant_at_the_cells_size(meshes, monkeypatch):
    """At 5 x 10^6 rows a shard the shares and the strides are past int32
    (10^5 x 5 x 10^6; slot 24,999 x 5 x 10^6): the shares add up to the
    application's 100,000 keys, and the device program's last slot is
    the row the host's arithmetic names."""
    from gpu_mapreduce_tpu.parallel.mesh import row_sharding
    monkeypatch.setattr(app, "SAMPLE", 100_000)
    counts = np.full(4, 5_000_000, np.int32)
    take = app.sample_shares(counts)
    assert take.tolist() == [25_000] * 4 and take.dtype == np.int64
    uneven = app.sample_shares(np.array([7_000_000, 0, 12_999_999, 1],
                                        np.int32))
    assert 100_000 <= uneven.sum() <= 100_003 and uneven[1] == 0
    assert app.sample_shares(np.zeros(4, np.int32)).sum() == 0
    # the strides, at a real count over a small block: the rows asked for
    # are clipped to the block, so the last slot reads its last row when
    # the stride is computed in 64 bits and a low row when it wrapped
    mesh = meshes[4]
    key = np.arange(4 * 8 * 3, dtype=np.uint32).reshape(-1, 3)
    on_mesh = lambda a: jax.device_put(a, row_sharding(mesh))
    got = np.asarray(app._sample_jit(mesh, 25_000)(
        on_mesh(key), on_mesh(counts), on_mesh(take.astype(np.int32))))
    got = got.reshape(4, 25_000, 3)
    for p in range(4):
        rows = np.minimum(np.arange(25_000) * 5_000_000 // 25_000, 7)
        assert np.array_equal(got[p], key[p * 8 + rows])


def test_a_user_device_hash_still_runs_and_groups_as_the_reference(
        meshes, rng):
    """``aggregate(fn)`` with any other device callable keeps its
    behaviour: ``fn(keys) % nprocs``, phase 1 built for the call."""
    from gpu_mapreduce_tpu.obs import get_tracer, names
    key = rng.integers(0, 1 << 20, 500).astype(np.uint64)
    value = np.arange(500, dtype=np.uint64)
    mr = MapReduce(meshes[4])
    mr.map(1, lambda itask, kv, ptr: kv.add_batch(key, value))
    tracer = get_tracer()
    tracer.reset()
    tracer.enable(ring=1 << 12)
    try:
        mr.aggregate(lambda keys: (keys // 3).astype(np.uint32))
        (ex,) = [e["args"] for e in tracer.events()
                 if e["name"] == names.SHUFFLE_EXCHANGE]
    finally:
        tracer.clear()
        tracer.disable()
    assert (ex["dest"], ex["phase1_built"]) == ("user", 1)
    fr = mr.kv.one_frame()
    want = ((key // 3) % 4).astype(np.int64)
    assert fr.counts.tolist() == np.bincount(want, minlength=4).tolist()
    for p in range(4):
        host = fr.shard_to_host(p)
        rows = np.asarray(host.value.data)
        assert np.array_equal(np.asarray(host.key.data), key[rows])
        assert (want[rows] == p).all()


# -- the record map ---------------------------------------------------------------

def test_a_file_that_is_no_whole_number_of_records_is_refused(meshes,
                                                              tmp_path, rng):
    path = str(tmp_path / "ragged.dat")
    _records(rng, 10).ravel()[:-1].tofile(path)
    for comm in (None, meshes[1]):
        with pytest.raises(MRError, match="no whole number"):
            MapReduce(comm).map_files([path], RecordFormat(100, 10))


def test_an_armed_fault_policy_takes_the_generic_map(meshes, tmp_path, rng):
    """``onfault='skip'`` may drop a file, which a block sized beforehand
    cannot take: the same rows by ``mesh_map_files``, the reader an
    ordinary callback there."""
    paths = _write(tmp_path, _random(rng))
    out = str(tmp_path / "out")
    ts = app.TeraSort(mr=MapReduce(meshes[4], onfault="skip"))
    ts.run(paths, outdir=out)
    assert ts.mr.last_ingest["mode"] == "mesh"
    ref.validate(_parts(out), ref.summary(paths))


@pytest.mark.parametrize("width", [1, 4, 10, 12, 90])
def test_fixed_width_words_order_as_bytes_and_come_back(width, rng):
    raw = rng.integers(0, 256, (500, width), dtype=np.uint8)
    raw[:250, :max(1, width - 1)] = 9       # ties up to the last byte
    words = fixed_key_words(raw)
    assert words.dtype == np.uint32 and words.shape == (500, -(-width // 4))
    assert np.array_equal(fixed_key_bytes(words, width), raw)
    by_words = np.lexsort(words.T[::-1])
    by_bytes = np.argsort(np.ascontiguousarray(raw).view(f"S{width}").ravel(),
                          kind="stable")
    assert np.array_equal(raw[by_words], raw[by_bytes])
    carried = fixed_value_words(raw)
    assert np.array_equal(fixed_value_bytes(carried, width), raw)
    block = np.full((600, words.shape[1]), 7, np.uint32)
    fixed_key_words(raw, block[50:550])     # into a shard's block, in place
    assert np.array_equal(block[50:550], words) and (block[:50] == 7).all()


# -- sort_keys on the wide row ----------------------------------------------------

def _wide_mr(mesh, rng, n=900):
    key = rng.integers(0, 3, (n, 3)).astype(np.uint32)     # ties abound
    value = rng.integers(0, 1 << 32, (n, 23), dtype=np.uint64).astype(
        np.uint32)
    mr = MapReduce(mesh)
    mr.map(1, lambda itask, kv, ptr: kv.add_batch(key, value))
    mr.aggregate()
    return mr, key, value


@pytest.mark.parametrize("flag", [1, -1], ids=["ascending", "descending"])
@pytest.mark.parametrize("nshards", [1, 4])
def test_sort_keys_brings_the_wide_value_along(nshards, flag, meshes, rng):
    """3 key words, 23 value words (past ``RIDE_WORDS``: by the row
    index), ascending and — complemented words, no scatter — descending;
    every shard in order, every row with the value it came with."""
    mr, key, value = _wide_mr(meshes[nshards], rng)
    mr.sort_keys(flag)
    fr = mr.kv.one_frame()
    pairs = {}
    for k, v in zip(key.tolist(), value.tolist()):
        pairs.setdefault(tuple(k), []).append(tuple(v))
    seen = 0
    for p in range(nshards):
        host = fr.shard_to_host(p)
        k, v = np.asarray(host.key.data), np.asarray(host.value.data)
        order = np.lexsort(k.T[::-1])
        want = k[order] if flag > 0 else k[order][::-1]
        assert np.array_equal(k, want)
        for kr, vr in zip(k.tolist(), v.tolist()):
            assert tuple(vr) in pairs[tuple(kr)]
        seen += len(k)
    assert seen == len(key)


def test_sort_values_by_the_wide_column(meshes, rng):
    mr, key, value = _wide_mr(meshes[1], rng, n=300)
    mr.sort_values(-1)
    host = mr.kv.one_frame().shard_to_host(0)
    v = np.asarray(host.value.data)
    assert np.array_equal(v, value[np.lexsort(value.T[::-1])][::-1])


def test_the_sort_program_is_one_sort_and_no_scatter(meshes):
    import re
    from gpu_mapreduce_tpu.parallel import group
    sds = jax.ShapeDtypeStruct
    for descending in (False, True):
        text = group._sort_jit(meshes[1], "key", descending).lower(
            sds((1024, 3), np.uint32), sds((1024, 23), np.uint32),
            sds((1,), np.int32)).as_text()
        ops = re.findall(r'stablehlo\.(scatter|gather|while|sort)"?\(', text)
        assert sorted(ops) == ["gather", "sort"], ops   # the value, taken
        text = group._sort_jit(meshes[1], "key", descending).lower(
            sds((1024,), np.uint64), sds((1024, 2), np.uint32),
            sds((1,), np.int32)).as_text()
        ops = re.findall(r'stablehlo\.(scatter|gather|while|sort)"?\(', text)
        assert ops == ["sort"], ops                     # the value rode


def test_sort_keys_returns_when_the_sorted_rows_are_there(meshes, tmp_path,
                                                         rng):
    """The op's one sync, on completion: the ``sort_keys`` span runs from
    dispatch to ready, and the part writer's pull waits for no sort."""
    from gpu_mapreduce_tpu.parallel.sharded import SyncStats
    paths = _write(tmp_path, _random(rng))
    mr = MapReduce(meshes[4])
    mr.map_files(paths, RecordFormat(100, 10))
    before = SyncStats.snapshot()
    mr.sort_keys(1)
    assert SyncStats.delta(before) == 1
    frame = mr.kv.one_frame()
    assert frame.key.is_ready() and frame.value.is_ready()


def test_the_spans_of_a_job(meshes, tmp_path, rng):
    from gpu_mapreduce_tpu.obs import names
    paths = _write(tmp_path, _random(rng))
    events = _traced(lambda: app.TeraSort(comm=meshes[4]).run(
        paths, outdir=str(tmp_path / "out")))
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    (root,) = by_name[names.TERASORT_RUN]
    assert root["cat"] == names.ENTRY and root["parent"] == 0
    assert names.ATTR_PROC_CPU_S in root["args"]
    (plan,) = by_name[names.INGEST_RECORDS_PLAN]
    assert (plan["args"]["shards"], plan["args"]["files"]) == (4, len(paths))
    # four blocks of 26 words a row, the fullest shard's rows to 1024
    assert plan["args"]["block_bytes"] == 4 * 2048 * 26 * 4
    reads = by_name[names.INGEST_RECORDS_READ]
    assert [e["args"]["shard"] for e in reads] == [0, 1, 2, 3]
    assert sum(e["args"]["files"] for e in reads) == len(paths)
    assert sum(e["args"]["bytes"] for e in reads) == sum(
        os.path.getsize(p) for p in paths)
    puts = by_name[names.INGEST_RECORDS_H2D]
    assert sum(e["args"]["bytes"] for e in puts) == plan["args"]["block_bytes"]
    # the record map's spans are the layer's, and all of map_files
    (mapped,) = by_name["map_files"]
    inside = [plan] + reads + puts
    assert all(e["parent"] == mapped["id"] for e in inside)
    assert not any(e["name"].startswith("terasort.") for e in inside)
    (sample,) = by_name[names.TERASORT_SAMPLE]
    assert sample["args"]["splitters"] == 3
    assert 64 <= sample["args"]["sampled"] < 64 + 4     # a stride a shard
    # the fullest shard's share of slots a shard, 3 words a key
    assert sample["args"]["sampled"] * 12 <= sample["args"]["d2h_bytes"] \
        <= 4 * 27 * 12
    (sort,) = by_name[names.SORT_KEYS_SPAN]
    assert sort["args"][names.ATTR_RECORDS] == 3151
    assert (sort["args"][names.ATTR_KEY_WORDS],
            sort["args"][names.ATTR_RODE_WORDS],
            sort["args"][names.ATTR_TAKEN_WORDS]) == (3, 0, 23)
    assert sort["args"][names.ATTR_HBM_ROW_BYTES] >= 4 * 26
    assert sum(e["args"]["records"]
               for e in by_name[names.TERASORT_PULL]) == 3151
    assert sum(e["args"]["bytes"]
               for e in by_name[names.TERASORT_WRITE]) == 315100
    for e in events:
        assert names.ATTR_CPU_S in e["args"], e["name"]


# -- the part writer: records joined on the device, pulled window by window -------

WIDTHS = [(100, 10), (16, 4), (12, 1), (24, 11), (8, 7), (20, 8), (104, 13)]


@pytest.mark.parametrize("nrows", [0, 1, 37])
@pytest.mark.parametrize("record,key", WIDTHS)
def test_the_device_join_gives_the_hosts_bytes(record, key, nrows, rng):
    """``RecordFormat.join_words``: the u32 words whose bytes in memory
    are ``join``'s, by word arithmetic alone, in numpy and inside a
    jitted program; key bytes that end inside a word (s = 1, 2, 3) and
    on its edge (s = 0)."""
    fmt = RecordFormat(record, key)
    raw = rng.integers(0, 256, (nrows, record), dtype=np.uint8)
    raw[:nrows // 2, key - 1:key + 1] = 0xFF     # both sides of the seam
    words = fixed_key_words(raw[:, :key])
    carried = fixed_value_words(raw[:, key:])
    assert np.array_equal(fmt.join(words, carried), raw)
    for joined in (fmt.join_words(words, carried),
                   np.asarray(jax.jit(fmt.join_words)(words, carried))):
        assert joined.dtype == np.uint32
        assert joined.shape == (nrows, record // 4)
        assert np.array_equal(
            np.ascontiguousarray(joined).view(np.uint8).reshape(
                nrows, record), raw)


def _sorted_shards(mesh, counts, rng, fmt=None):
    """A sorted mesh dataset whose shard *p* holds ``counts[p]`` records
    (the first key byte is the shard, the splitters its steps), and the
    records."""
    from gpu_mapreduce_tpu.parallel import shuffle
    from gpu_mapreduce_tpu.parallel.shuffle import TotalOrder
    # the block's capacity from these counts, not from the plan another
    # test's exchange left behind
    shuffle._SPEC_CACHE.clear()
    fmt = fmt or RecordFormat(ref.RECORD, ref.KEY)
    raw = rng.integers(0, 256, (sum(counts), fmt.record_bytes),
                       dtype=np.uint8)
    raw[:, 0] = np.repeat(np.arange(len(counts)), counts)
    raw = raw[rng.permutation(len(raw))]
    key = fixed_key_words(raw[:, :fmt.key_bytes])
    value = fixed_value_words(raw[:, fmt.key_bytes:])
    mr = MapReduce(mesh)
    mr.map(1, lambda itask, kv, ptr: kv.add_batch(key, value))
    steps = np.zeros((len(counts) - 1, fmt.key_bytes), np.uint8)
    steps[:, 0] = np.arange(1, len(counts))
    mr.aggregate(TotalOrder(fixed_key_words(steps)) if len(steps) else None)
    mr.sort_keys(1)
    return mr, raw


def _span_args(events, name):
    return [e["args"] for e in events if e["name"] == name]


def _read(paths):
    out = []
    for p in paths:
        with open(p, "rb") as f:
            out.append(f.read())
    return out


# rows a window, then the shards' counts on a mesh of 1 and of 4
WRITER_CASES = {
    "whole_windows": (64, {1: [256], 4: [128, 256, 64, 192]}),
    "ragged": (64, {1: [301], 4: [130, 257, 63, 1]}),
    "a_shard_with_no_rows": (64, {1: [0], 4: [100, 0, 0, 77]}),
    "a_shard_under_one_window": (64, {1: [10], 4: [10, 200, 5, 64]}),
    # a block the window does not divide: the last window starts at
    # cap - 100 and its first rows are in the file already
    "the_last_window_steps_back": (100, {1: [1010],
                                        4: [1010, 3, 1001, 1024]}),
    "a_block_smaller_than_write_rows": (None, {1: [300],
                                               4: [300, 200, 100, 50]}),
}


@pytest.mark.parametrize("nshards", [1, 4])
@pytest.mark.parametrize("case", WRITER_CASES)
def test_the_windowed_writer_writes_the_host_joins_files(
        case, nshards, meshes, tmp_path, rng, monkeypatch):
    """``_write_parts`` on a mesh: every part file byte for byte what
    the host's ``join`` makes of the same shard's pulled rows, whatever
    the counts are to the window; the spans say which road ran and what
    crossed."""
    from gpu_mapreduce_tpu.obs import names
    rows, counts = WRITER_CASES[case]
    counts = counts[nshards]
    if rows is not None:
        monkeypatch.setattr(app, "WRITE_ROWS", rows)
    monkeypatch.setattr(app, "WRITE_AHEAD", 2)
    mr, raw = _sorted_shards(meshes[nshards], counts, rng)
    fr = mr.kv.one_frame()
    assert fr.counts.tolist() == counts
    rows = min(app.WRITE_ROWS, fr.cap)
    if case == "the_last_window_steps_back":
        assert fr.cap % rows and max(counts) > fr.cap - fr.cap % rows
    if case == "a_block_smaller_than_write_rows":
        assert rows == fr.cap < app.WRITE_ROWS
    ts = app.TeraSort(mr=mr)
    events = _traced(lambda: ts._write_parts(str(tmp_path)))
    got = _read(ts.parts)
    assert [os.path.basename(p) for p in ts.parts] == [
        f"part-{p:05d}" for p in range(nshards)]
    for p in range(nshards):
        host = fr.shard_to_host(p)      # no rows: no columns to join
        assert got[p] == (ts.format.join(
            np.asarray(host.key.data), np.asarray(host.value.data)).tobytes()
            if counts[p] else b"")
    assert [len(g) for g in got] == [n * ref.RECORD for n in counts]
    order = np.lexsort(raw[:, :ref.KEY].T[::-1])
    assert b"".join(got) == raw[order].tobytes()
    # a pull and a write a window; only windows below a shard's count
    windows = sum(-(-n // rows) for n in counts)
    pulls = _span_args(events, names.TERASORT_PULL)
    writes = _span_args(events, names.TERASORT_WRITE)
    assert len(pulls) == len(writes) == windows
    assert {a["joined"] for a in writes} <= {"device"}
    assert sum(a["windows"] for a in writes) == windows
    assert [a["shard"] for a in writes] == sorted(a["shard"] for a in writes)
    assert sum(a["bytes"] for a in writes) == sum(counts) * ref.RECORD
    assert sum(a["records"] for a in pulls) == sum(counts)
    # what crossed: whole windows of records, nothing of the capacity
    # block's padding and no row above a window that holds a valid one
    crossed = sum(a["d2h_bytes"] for a in pulls)
    assert crossed == windows * rows * ref.RECORD
    if case == "whole_windows":
        assert crossed == sum(counts) * ref.RECORD
    assert crossed < (sum(counts) + rows * nshards) * ref.RECORD


def test_a_record_of_no_whole_words_has_no_device_join(
        meshes, tmp_path, rng, monkeypatch):
    """10-byte records with 3-byte keys: no word form, so ``join_words``
    refuses, and with it a mesh frame's part writer (nothing is written
    in another record's shape); the serial backend's ``join`` takes any
    width."""
    from gpu_mapreduce_tpu import MRError
    fmt = RecordFormat(10, 3)
    raw = rng.integers(0, 256, (201, 10), dtype=np.uint8)
    key, value = fixed_key_words(raw[:, :3]), fixed_value_words(raw[:, 3:])
    assert np.array_equal(fmt.join(key, value), raw)
    with pytest.raises(MRError, match="no word form of a 10-byte record"):
        fmt.join_words(key, value)
    monkeypatch.setattr(app, "RECORD_BYTES", 10)
    monkeypatch.setattr(app, "KEY_BYTES", 3)
    mr, _ = _sorted_shards(meshes[4], [130, 0, 64, 7], rng, fmt)
    with pytest.raises(MRError, match="no word form of a 10-byte record"):
        app.TeraSort(mr=mr)._write_parts(str(tmp_path))


def test_the_serial_backend_is_joined_on_the_host(tmp_path, rng,
                                                  monkeypatch):
    from gpu_mapreduce_tpu.obs import names
    monkeypatch.setattr(app, "WRITE_ROWS", 1000)
    paths = _write(tmp_path, _random(rng))
    ts = app.TeraSort()
    events = _traced(lambda: ts.run(paths, outdir=str(tmp_path / "out")))
    ref.validate(ts.parts, ref.summary(paths))
    (write,) = _span_args(events, names.TERASORT_WRITE)
    (pull,) = _span_args(events, names.TERASORT_PULL)
    assert (write["joined"], write["windows"], write["bytes"]) == (
        "host", 4, 315100)
    assert (pull["records"], pull["d2h_bytes"]) == (3151, 0)


def test_a_job_on_a_mesh_joins_its_records_on_the_device(meshes, tmp_path,
                                                         rng, monkeypatch):
    """Whole jobs on four shards: the writer's program is built once a
    (cap, window) pair (the exchange's plan may give the first job of a
    process another capacity than the jobs after it), and what crosses
    for the part files is the records' own bytes up to a window a
    shard."""
    from gpu_mapreduce_tpu import obs
    from gpu_mapreduce_tpu.obs import names
    monkeypatch.setattr(app, "WRITE_ROWS", 256)
    app._join_jit.cache_clear()
    jobs = _datasets(tmp_path, rng, 3)
    ran, events = _traced_jobs(meshes[4], jobs, tmp_path)
    for ts, paths in zip(ran, jobs):
        ref.validate(ts.parts, ref.summary(paths))
    caps = [ts.mr.kv.one_frame().cap for ts in ran]
    assert caps[1] == caps[2]
    assert obs.programs()[names.TERASORT_JOIN_RECORDS]["lowerings"] \
        == len(set(caps))
    assert app._join_jit.cache_info().currsize == 1
    writes = _span_args(events, names.TERASORT_WRITE)
    pulls = _span_args(events, names.TERASORT_PULL)
    assert {a["joined"] for a in writes} == {"device"}
    assert sum(a["bytes"] for a in writes) == 3 * 2880 * ref.RECORD
    crossed = sum(a["d2h_bytes"] for a in pulls)
    assert 3 * 2880 * ref.RECORD <= crossed == len(pulls) * 256 * ref.RECORD
    assert crossed < (3 * 2880 + 3 * 4 * 256) * ref.RECORD
