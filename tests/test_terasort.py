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
    return {1: make_mesh(1), 4: make_mesh(4)}


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
    from gpu_mapreduce_tpu.obs import get_tracer, names
    paths = _write(tmp_path, _random(rng))
    tracer = get_tracer()
    tracer.reset()
    tracer.enable(ring=1 << 14)
    try:
        app.TeraSort(comm=meshes[4]).run(paths,
                                         outdir=str(tmp_path / "out"))
        events = tracer.events()
    finally:
        tracer.clear()
        tracer.disable()
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
