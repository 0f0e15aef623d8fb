"""ISSUE 30: a file map hands its words to the dataset as ranges of the
file's buffer (``BytesColumn.from_ranges``), not as one Python object per
word.  The ranges path against the object path (ids, per-shard tables,
counts, top 10), with and without the native library; OINK ``wordfreq`` and
``apps/wordfreq`` against ``collections.Counter`` on the serial backend and
on a four-device mesh; collisions; the three tokenizers on every byte."""

import collections
import io

import numpy as np
import pytest

from gpu_mapreduce_tpu import native
from gpu_mapreduce_tpu.apps.wordfreq import wordfreq, wordfreq_interned
from gpu_mapreduce_tpu.core import column
from gpu_mapreduce_tpu.core.column import (BytesColumn, ShardTables, concat,
                                           dest_of_ids)
from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.oink import kernels
from gpu_mapreduce_tpu.oink.commands import wordfreq as wordfreq_cmd
from gpu_mapreduce_tpu.oink.script import OinkScript
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
from gpu_mapreduce_tpu.utils.io import WHITESPACE, read_words, word_ranges


def zipf_text(seed: int, nbytes: int, vocabulary: int = 1 << 15) -> bytes:
    """A small copy of ``benchmark/gen/text.py``'s rule: ranks by
    ``round(exp(u ln(2V+1)) / 2)``, a word spelled from its rank (short for
    frequent ones), one token in a hundred a url of 32-200 bytes, spaces
    with a newline where a line passes 80 bytes and a few tabs."""
    rng = np.random.default_rng(seed)
    out, size, line = [], 0, 0
    while size < nbytes:
        r = int(np.clip(np.rint(0.5 * np.exp(
            rng.random() * np.log(2.0 * vocabulary + 1.0))), 1, vocabulary))
        digits, v = [], r - 1
        while True:
            digits.append(b"bcdfghjklmnpqrstvwxz"[v % 20])
            v //= 20
            if not v:
                break
        word = b"".join(bytes([c]) + b"aeiou"[(r + i) % 5:(r + i) % 5 + (
            (r >> i) & 1)] for i, c in enumerate(reversed(digits)))
        if r > 64 and r % 50 == 0:
            word = b"http://en.wikipedia.org/wiki/" + word + b"_" * (
                3 + r % 160)
        size += len(word) + 1
        sep = b" "
        if size // 80 != line:
            sep, line = b"\n", size // 80
        elif r % 97 == 0:
            sep = b"\t"
        out.append(word + sep)
    return b"".join(out)


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    d = tmp_path_factory.mktemp("zipf")
    paths = []
    for i in range(4):
        p = d / f"part-{i}.txt"
        p.write_bytes(zipf_text(100 + i, 60_000))
        paths.append(str(p))
    return paths


@pytest.fixture(scope="module")
def oracle(files):
    c = collections.Counter()
    for f in files:
        with open(f, "rb") as fh:
            c.update(fh.read().split())
    assert len(c) > 3000 and max(map(len, c)) > 100 and min(map(len, c)) == 1
    assert 0.05 < c.most_common(1)[0][1] / sum(c.values()) < 0.12
    return c


def object_read_words(itask, filename, kv, ptr):
    """``oink/kernels.read_words`` as it was before the ranges: a list of
    one ``bytes`` object per word."""
    with open(filename, "rb") as f:
        words = f.read().split()
    if ptr is not None:
        ptr.append(filename)
    kv.add_batch(words, np.zeros(len(words), np.uint8))


# -- the tokenizers --------------------------------------------------------------

@pytest.mark.parametrize("byte", sorted(set(WHITESPACE) | {
    0x00, 0x08, 0x0e, 0x1c, 0x1f, 0x21, 0x7f, 0x85, 0xa0, 0xff}))
def test_every_byte_splits_alike_in_all_tokenizers(byte, library):
    c = bytes([byte])
    text = b"ab" + c + b"cd" + c + c + b"e" + c
    want = text.split()
    assert (len(want) == 3) == (byte in WHITESPACE)
    assert word_ranges(text).tolist() == want
    assert word_ranges(c + text).tolist() == (c + text).split()
    assert read_words(text) == want
    if library == "native":
        starts, lens = native.tokenize(np.frombuffer(text, np.uint8))
        assert [text[s:s + n] for s, n in zip(starts, lens)] == want


def test_whitespace_set_is_bytes_split_s():
    assert sorted(WHITESPACE) == [b for b in range(256)
                                  if not bytes([b]).split()]


@pytest.mark.parametrize("text", [b"", b" \n\t ", b"x", b" x", b"x ",
                                  b"a" * 200 + b" b " + b"c" * 33])
def test_word_ranges_edges_and_long_tokens(text, library):
    col = word_ranges(text)
    assert col.ranges is not None and col.tolist() == text.split()
    assert len(col) == len(text.split())
    assert col.nbytes() == sum(map(len, text.split()))


# -- the column -----------------------------------------------------------------

def test_ranges_column_is_a_bytes_column(files):
    with open(files[0], "rb") as f:
        raw = f.read()
    col, obj = word_ranges(raw), BytesColumn(raw.split())
    assert len(col) == len(obj) and col.nbytes() == obj.nbytes()
    assert col._data is None                    # nothing materialised yet
    idx = np.array([5, 0, len(col) - 1, 5])
    assert col.take(idx).tolist() == obj.take(idx).tolist()
    assert col.slice(3, 40).tolist() == obj.slice(3, 40).tolist()
    both = concat([col.slice(0, 10), col.slice(20, 25), word_ranges(b"z y")])
    assert both.ranges is not None and both._data is None
    assert both.tolist() == obj.data[:10].tolist() + obj.data[20:25].tolist(
        ) + [b"z", b"y"]
    mixed = concat([col.slice(0, 3), BytesColumn([b"q"])])
    assert mixed.tolist() == obj.data[:3].tolist() + [b"q"]
    assert col.tolist() == obj.tolist()         # now it has the objects


def test_ranges_intern_equals_object_intern(files, library):
    tables_r, tables_o = ShardTables(4), ShardTables(4)
    for f in files:
        with open(f, "rb") as fh:
            raw = fh.read()
        col, obj = word_ranges(raw), BytesColumn(raw.split())
        ids_r, table_r = col.intern()
        ids_o, table_o = obj.intern()
        assert np.array_equal(ids_r.data, ids_o.data)
        assert dict(table_r) == dict(table_o) and table_r.kind == "bytes"
        assert list(table_r) == list(table_o)       # and in the same order
        assert np.array_equal(col.intern_sharded(tables_r).data,
                              obj.intern_sharded(tables_o).data)
        assert col._data is None                # no object per word
    for d in range(4):
        assert dict(tables_r.shard(d)) == dict(tables_o.shard(d))
        assert list(tables_r.shard(d)) == list(tables_o.shard(d))


def test_ids_do_not_depend_on_the_library(files, monkeypatch):
    if not native.available():
        pytest.skip("no native library")
    with open(files[1], "rb") as fh:
        raw = fh.read()
    with_lib = word_ranges(raw).intern()
    monkeypatch.setattr(native, "_lib", None)
    without = word_ranges(raw).intern()
    assert np.array_equal(with_lib[0].data, without[0].data)
    assert list(with_lib[1].items()) == list(without[1].items())


def test_unique_ranges_is_the_argsort_dedupe(files):
    if not native.available():
        pytest.skip("no native library")
    with open(files[2], "rb") as fh:
        buf, starts, lens = word_ranges(fh.read()).ranges
    ids = native.intern_ranges(buf, starts, lens)
    first = native.unique_ranges(buf, starts, lens, ids)
    assert np.array_equal(first, np.sort(np.unique(
        ids, return_index=True)[1]))             # order of appearance
    got = column._intern_ranges(buf, starts, lens)
    want = column._unique_first(ids, lambda: native.intern_ranges(
        buf, starts, lens, *column._ALT_SEEDS), None)
    assert all(np.array_equal(a, b) for a, b in zip(got, want))
    empty = np.zeros(0, np.int64)
    assert len(native.unique_ranges(buf, empty, empty,
                                    np.zeros(0, np.uint64))) == 0


# -- collisions -----------------------------------------------------------------

def _collapse_ids(monkeypatch, keep_bits: int):
    """Make the intern family weak: only ``keep_bits`` bits of an id vary,
    so that different words share one; the check family stays whole."""
    mask = np.uint64((1 << keep_bits) - 1)
    if native.available():
        real = native.intern_ranges

        def weak(buf, starts, lens, hi=0, lo=0xDEADBEEF):
            ids = real(buf, starts, lens, hi, lo)
            return ids & mask if (hi, lo) == (0, 0xDEADBEEF) else ids
        monkeypatch.setattr(native, "intern_ranges", weak)
    else:
        real = column.hash_bytes64_batch

        def weak(strings, hi=0, lo=0xDEADBEEF):
            ids = real(strings, hi, lo)
            return ids & mask if (hi, lo) == (0, 0xDEADBEEF) else ids
        monkeypatch.setattr(column, "hash_bytes64_batch", weak)


def test_a_collision_inside_a_file_fails_the_intern(files, library,
                                                    monkeypatch):
    _collapse_ids(monkeypatch, 4)
    with open(files[0], "rb") as fh:
        col = word_ranges(fh.read())
    with pytest.raises(ValueError, match="64-bit intern collision"):
        col.intern()
    with pytest.raises(ValueError, match="64-bit intern collision"):
        col.intern_sharded(ShardTables(4))


@pytest.mark.parametrize("backend", ["serial", "mesh"])
def test_a_forced_collision_fails_the_job(files, library, monkeypatch,
                                          backend):
    """Through the OINK command, on a mesh (interned in the file map) —
    and through ``wordfreq_interned`` on the serial backend (interned in
    its callback): never two words merged into one count."""
    _collapse_ids(monkeypatch, 6)
    with pytest.raises(ValueError, match="64-bit intern collision"):
        if backend == "mesh":
            _oink_wordfreq(make_mesh(4), files)
        else:
            wordfreq_interned(files, ntop=10)


def test_a_collision_across_shards_fails_in_absorb(tmp_path, monkeypatch):
    """Each file is clean on its own; the two words that share an id meet
    in the destination shard's table."""
    a, b = tmp_path / "a.txt", tmp_path / "b.txt"
    a.write_bytes(b"left left left\n")
    b.write_bytes(b"right right\n")
    _collapse_ids(monkeypatch, 0)
    with pytest.raises(ValueError, match="64-bit intern collision"):
        _oink_wordfreq(make_mesh(2), [str(a), str(b)])


# -- the pipelines ----------------------------------------------------------------

def _oink_wordfreq(comm, files, ntop=10):
    s = OinkScript(comm=comm, screen=io.StringIO())
    s.run_string("variable files index " + " ".join(files))
    s.run_string(f"wordfreq {ntop} -i v_files -o NULL mrw")
    return s


def _shards(mr):
    """Per shard: (ids, counts, that shard's table) of a mesh-resident MR."""
    fr = mr.kv.one_frame()
    out = []
    for p in range(fr.nprocs):
        k = np.asarray(fr.key).reshape(fr.nprocs, -1)[p][:fr.counts[p]]
        v = np.asarray(fr.value).reshape(fr.nprocs, -1)[p][:fr.counts[p]]
        out.append((k, v, dict(fr.key_decode.shard(p))))
    return out


def _check_top(top, oracle, ntop=10):
    assert [c for _w, c in top] == sorted(oracle.values(),
                                          reverse=True)[:ntop]
    assert all(oracle[w] == c for w, c in top)
    assert len({w for w, _c in top}) == len(top)


@pytest.mark.parametrize("backend", ["serial", "mesh"])
@pytest.mark.parametrize("impl", [wordfreq, wordfreq_interned])
def test_apps_wordfreq_matches_counter(files, oracle, impl, backend, library):
    comm = make_mesh(4) if backend == "mesh" else None
    nwords, nunique, top = impl(files, ntop=10, comm=comm)
    assert (nwords, nunique) == (sum(oracle.values()), len(oracle))
    _check_top(top, oracle)


@pytest.mark.parametrize("backend", ["serial", "mesh"])
def test_oink_wordfreq_matches_counter_exactly(files, oracle, backend,
                                               library):
    s = _oink_wordfreq(make_mesh(4) if backend == "mesh" else None, files)
    cmd_top = [ln.split() for ln in s.screen.getvalue().splitlines()[1:]]
    assert s.screen.getvalue().splitlines()[0] == (
        f"WordFreq: 4 files, {sum(oracle.values())} words, "
        f"{len(oracle)} unique")
    _check_top([(w.encode(), int(c)) for c, w in cmd_top], oracle)
    got = {}
    s.obj.get_mr("mrw").scan_kv(lambda k, v, p: got.__setitem__(k, int(v)))
    assert got == dict(oracle)                  # every word, every count


def test_every_word_is_in_one_shard_and_decoded_from_its_table(files, oracle):
    s = _oink_wordfreq(make_mesh(4), files)
    seen = {}
    for p, (ids, counts, table) in enumerate(_shards(s.obj.get_mr("mrw"))):
        assert len(ids) > 0 and np.all(dest_of_ids(ids, 4) == p)
        assert set(ids.tolist()) <= set(table)      # its OWN table
        for h, c in zip(ids.tolist(), counts.tolist()):
            assert table[h] not in seen
            seen[table[h]] = c
    assert seen == dict(oracle)
    assert max(map(len, seen)) > 100                # the long tokens too


def test_ranges_path_equals_object_path_through_the_command(
        files, library, monkeypatch):
    """ids, per-shard tables, counts, messages and top 10: all equal."""
    by_ranges = _oink_wordfreq(make_mesh(4), files)
    assert kernels.read_words is wordfreq_cmd.read_words
    monkeypatch.setattr(wordfreq_cmd, "read_words", object_read_words)
    by_objects = _oink_wordfreq(make_mesh(4), files)
    assert by_ranges.screen.getvalue() == by_objects.screen.getvalue()
    for (k1, v1, t1), (k2, v2, t2) in zip(
            _shards(by_ranges.obj.get_mr("mrw")),
            _shards(by_objects.obj.get_mr("mrw"))):
        assert np.array_equal(k1, k2) and np.array_equal(v1, v2)
        assert t1 == t2 and list(t1) == list(t2)


def test_ranges_path_equals_object_path_on_the_serial_backend(files):
    def run(callback):
        mr = MapReduce()
        mr.map_files(files, callback, [])
        mr.collate()
        mr.reduce(kernels.count, batch=True)
        rows = []
        mr.scan_kv(lambda k, v, p: rows.append((k, int(v))))
        return rows
    assert run(kernels.read_words) == run(object_read_words)   # and in order


def test_the_file_map_builds_no_object_per_word(files, monkeypatch):
    """On a mesh the words go from the callback to the ids, and the distinct
    ones on into the shards' tables, as ranges: no ``bytes`` object is asked
    for, per word or (since ISSUE 35) per distinct word."""
    if not native.available():
        pytest.skip("without the library the rows are sliced to hash them")
    asked = []
    real = column._range_rows

    def counting(buf, starts, lens):
        asked.append(len(starts))
        return real(buf, starts, lens)
    monkeypatch.setattr(column, "_range_rows", counting)
    mr = MapReduce(make_mesh(4))
    nwords = mr.map_files(files, kernels.read_words, [])
    assert mr.last_ingest["mode"] == "mesh"
    assert nwords > 0 and asked == []
    fr = mr.kv.one_frame()
    assert sum(asked) == 0 and len(fr.key_decode) > 0
    top = fr.key_decode.decode_batch(np.asarray(fr.key)[:3])
    assert sum(asked) == 3 and all(isinstance(w, bytes) for w in top)
