"""ISSUE 35: a byte-kind ``ShardTables`` holds its entries as arrays (ids,
offsets, one blob a destination), not one ``bytes`` object an entry, and
takes a shard's distinct words as ranges.  Held here to a plain ``dict`` a
destination: the same ids, the same entries in the same order, the same
answers from every reader; every collision still raised with both words;
threads sharing one table; object-kind tables as they were."""

import concurrent.futures
import pickle
import sys

import numpy as np
import pytest

from gpu_mapreduce_tpu import native
from gpu_mapreduce_tpu.core import column
from gpu_mapreduce_tpu.core.column import (BytesColumn, InternTable,
                                           ObjectColumn, ShardTables,
                                           dest_of_ids)
from gpu_mapreduce_tpu.ops.hash import hash_bytes64_batch
from gpu_mapreduce_tpu.utils.io import word_ranges


def vocabulary(rng, n: int) -> list:
    """n distinct words without whitespace: most short, the empty word
    left out (a tokenizer never yields it), a few of 200 bytes."""
    words = {bytes(rng.integers(97, 123, size=int(k)).astype(np.uint8))
             for k in rng.integers(1, 12, size=n)}
    words |= {bytes([65 + i]) * 200 for i in range(5)}
    return sorted(words)


def batches(rng, vocab: list, nbatches: int, size: int) -> list:
    """Batches that overlap heavily: each draws with replacement from a
    window of the vocabulary that moves on by a quarter of its width."""
    width = max(8, len(vocab) // 3)
    out = []
    for b in range(nbatches):
        lo = (b * width // 4) % (len(vocab) - width + 1)
        out.append([vocab[lo + int(i)]
                    for i in rng.integers(0, width, size=size)])
    return out


class DictOracle:
    """What the tables were before: one ``dict`` a destination, entries
    inserted in the order of each call (ascending id for an intern)."""

    def __init__(self, P: int):
        self.P, self.shards = P, [{} for _ in range(P)]

    def absorb(self, ids, rows, by_id: bool):
        order = np.argsort(ids, kind="stable") if by_id else range(len(ids))
        dests = dest_of_ids(np.asarray(ids, np.uint64), self.P)
        for i in order:
            self.shards[dests[i]].setdefault(int(ids[i]), rows[i])

    def intern(self, words):
        ids = hash_bytes64_batch(words)
        self.absorb(ids, words, by_id=True)
        return ids


def same_as(tables: ShardTables, oracle: DictOracle):
    assert len(tables) == sum(map(len, oracle.shards))
    for d, want in enumerate(oracle.shards):
        got = tables.shard(d)
        assert got is tables.tables[d] and len(got) == len(want)
        assert dict(got) == want and got == want
        assert list(got) == list(want) == list(got.keys())
        assert list(got.items()) == list(want.items())
        assert list(got.values()) == list(want.values())
    flat = [kv for want in oracle.shards for kv in want.items()]
    assert list(tables.items()) == flat
    assert list(tables.keys()) == [h for h, _ in flat]
    if flat:
        ids = np.array([h for h, _ in flat][::-1], np.uint64)
        assert tables.decode_batch(ids) == [w for _, w in flat][::-1]
        h, w = flat[len(flat) // 2]
        assert tables[h] == w and h in tables and tables.get(h) == w
        assert tables.shard(int(dest_of_ids(ids[:1], oracle.P)[0]))[
            int(ids[0])] == flat[-1][1]
    missing = 12345
    assert missing not in tables and tables.get(missing, b"?") == b"?"
    assert all(missing not in t and t.get(missing) is None
               and -1 not in t and "a" not in t and 1 << 64 not in t
               for t in tables.tables)
    with pytest.raises(KeyError):
        tables[missing]
    with pytest.raises(KeyError):
        tables.decode_batch(np.array([missing], np.uint64))


@pytest.mark.parametrize("how", ["ranges", "objects", "absorb"])
@pytest.mark.parametrize("seed,P", [(0, 1), (1, 2), (2, 4), (3, 7)])
def test_byte_tables_are_the_dicts_they_replaced(seed, P, how, library):
    rng = np.random.default_rng(seed)
    vocab = vocabulary(rng, 400)
    tables, oracle = ShardTables(P), DictOracle(P)
    for words in batches(rng, vocab, 9, 300):
        want = oracle.intern(words) if how != "absorb" else None
        if how == "ranges":
            got = word_ranges(b" ".join(words)).intern_sharded(tables).data
        elif how == "objects":
            got = BytesColumn(words).intern_sharded(tables).data
        else:       # the caller's pairs in the caller's order, ids unique
            distinct = list(dict.fromkeys(words))
            ids = hash_bytes64_batch(distinct)
            oracle.absorb(ids, distinct, by_id=False)
            added, checked = tables.absorb(ids, distinct)
            assert added + checked == len(distinct)
            continue
        assert np.array_equal(got, want)
    same_as(tables, oracle)
    assert all(isinstance(t, column._ByteTable) for t in tables.tables)
    # a round trip through pickle (ft/ checkpoints a frame with its tables)
    again = pickle.loads(pickle.dumps(tables, pickle.HIGHEST_PROTOCOL))
    same_as(again, oracle)
    more = [b"after", b"the", b"pickle"]
    oracle.intern(more)
    BytesColumn(more).intern_sharded(again)
    same_as(again, oracle)


@pytest.mark.parametrize("other", ["dict", "interntable", "shardtables",
                                   "empty"])
def test_merge_of_byte_tables(other, library):
    rng = np.random.default_rng(5)
    vocab = vocabulary(rng, 300)
    a_words, b_words = batches(rng, vocab, 2, 200)
    tables, oracle = ShardTables(4), DictOracle(4)
    oracle.intern(a_words)
    BytesColumn(a_words).intern_sharded(tables)
    if other == "empty":
        b_words = []
    ids = hash_bytes64_batch(b_words) if b_words else np.zeros(0, np.uint64)
    if other == "shardtables":
        rhs = ShardTables(4)
        word_ranges(b" ".join(b_words)).intern_sharded(rhs)
        rhs_oracle = DictOracle(4)
        rhs_oracle.intern(b_words)
        for shard in rhs_oracle.shards:     # merged table by table
            oracle.absorb(np.array(list(shard), np.uint64),
                          list(shard.values()), by_id=False)
    else:
        rhs = dict(zip(ids.tolist(), b_words))
        if other == "interntable":
            rhs = InternTable(rhs, kind="bytes")
        oracle.absorb(np.array(list(rhs), np.uint64), list(rhs.values()),
                      by_id=False)
    before = list(tables.items())
    merged = tables.merge(rhs)
    assert merged is not tables and merged.kind == "bytes"
    assert list(tables.items()) == before       # the operands are left alone
    same_as(merged, oracle)


COLLIDE = [b"left", b"right"]


def _forge(monkeypatch):
    """Every word gets the same id from the intern family."""
    if native.available():
        real = native.intern_ranges
        monkeypatch.setattr(
            native, "intern_ranges",
            lambda buf, starts, lens, hi=0, lo=0xDEADBEEF: (
                real(buf, starts, lens, hi, lo) * np.uint64(
                    (hi, lo) != (0, 0xDEADBEEF)) + np.uint64(7)))
    else:
        real = column.hash_bytes64_batch
        monkeypatch.setattr(
            column, "hash_bytes64_batch",
            lambda strings, hi=0, lo=0xDEADBEEF: (
                real(strings, hi, lo) * np.uint64(
                    (hi, lo) != (0, 0xDEADBEEF)) + np.uint64(7)))


@pytest.mark.parametrize("where", ["one batch", "one absorb",
                                   "two absorbs", "two interns",
                                   "two lengths", "setitem"])
def test_a_forged_collision_names_both_words(where, library, monkeypatch):
    tables = ShardTables(3)
    with pytest.raises(ValueError, match="64-bit intern collision") as e:
        if where == "one batch":        # the intern's own dedupe meets it
            _forge(monkeypatch)
            word_ranges(b"left left right").intern_sharded(tables)
        elif where == "one absorb":     # a caller's batch repeats an id
            tables.absorb(np.array([9, 4, 9], np.uint64),
                          [b"left", b"mid", b"right"])
        elif where == "two absorbs":
            tables.absorb(np.array([4, 9], np.uint64), [b"mid", b"left"])
            assert tables.absorb(np.array([9], np.uint64),
                                 [b"left"]) == (0, 1)
            tables.absorb(np.array([9], np.uint64), [b"right"])
        elif where == "two interns":    # each column clean on its own
            _forge(monkeypatch)
            word_ranges(b"left left").intern_sharded(tables)
            BytesColumn([b"right"]).intern_sharded(tables)
        elif where == "two lengths":    # a prefix is another word
            tables.absorb(np.array([9], np.uint64), [b"left"])
            tables.absorb(np.array([9], np.uint64), [b"leftright"])
        else:
            tables.shard(1)[9] = b"left"
            tables.shard(1)[9] = b"left"            # the same word: fine
            tables.shard(1)[9] = b"right"
    assert all(w.decode() in str(e.value) for w in COLLIDE)


def test_a_repeated_id_with_one_word_is_one_entry(library):
    tables = ShardTables(2)
    assert tables.absorb(np.array([9, 4, 9, 9], np.uint64),
                         [b"w", b"x", b"w", b"w"]) == (2, 2)
    assert dict(tables.items()) == {9: b"w", 4: b"x"}
    assert tables.absorb(np.array([4, 9, 1], np.uint64),
                         [b"x", b"w", b""]) == (1, 2)
    assert len(tables) == 3 and tables[1] == b""


@pytest.mark.parametrize("rows", [
    [], [b""], [b"", b"", b"x"], [b"y" * 200], [b"a", b"b" * 200, b""],
    [bytes([i]) for i in range(256)]])
def test_rows_of_every_size(rows, library):
    tables, oracle = ShardTables(4), DictOracle(4)
    for _ in range(2):          # the second time everything is checked
        ids = BytesColumn(rows).intern_sharded(tables).data
        assert np.array_equal(ids, oracle.intern(rows) if rows else ids)
        same_as(tables, oracle)
    assert tables.decode_batch(ids) == rows


def test_threads_share_one_table(library):
    """More threads than cores intern overlapping columns into one
    ``ShardTables`` with the switch interval cut short: the absorbs run
    under the tables' lock, so no entry is lost, none is filed twice and
    every reader's view is whole."""
    rng = np.random.default_rng(11)
    vocab = vocabulary(rng, 600)
    work = batches(rng, vocab, 48, 400)
    tables, oracle = ShardTables(4), DictOracle(4)
    for words in work:
        oracle.intern(words)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with concurrent.futures.ThreadPoolExecutor(24) as pool:
            futs = [pool.submit(BytesColumn(w).intern_sharded, tables)
                    for w in work]
            done, pending = concurrent.futures.wait(futs, timeout=120)
            assert not pending
            got = [f.result().data for f in futs]
    finally:
        sys.setswitchinterval(old)
    assert all(np.array_equal(g, hash_bytes64_batch(w))
               for g, w in zip(got, work))
    for d, want in enumerate(oracle.shards):    # as sets: the order of the
        t = tables.shard(d)                     # batches was the threads'
        assert len(t) == len(want) and dict(t) == want
        assert np.array_equal(np.sort(t.ids), t._sorted)
        assert np.array_equal(t.ids[t._pos], t._sorted)


@pytest.mark.parametrize("rows", [
    [("tup", 1), ("tup", 2), ("tup", 1)],
    [b"bytes", ("and", "objects"), b"bytes", None, 3.5],
    [[1, 2], [1, 2], {"k": "v"}]])
def test_object_kind_tables_are_dicts_as_before(rows):
    tables = ShardTables(3, kind="object")
    ids = ObjectColumn(rows).intern_sharded(tables).data
    assert all(type(t) is InternTable and t.kind == "object"
               for t in tables.tables)
    pickles = [pickle.dumps(r, protocol=4) for r in rows]
    assert np.array_equal(ids, hash_bytes64_batch(pickles))
    assert tables.decode_batch(ids) == rows
    assert len(tables) == len(set(pickles))
    assert tables.probes_for(ids) == pickles
    again = pickle.loads(pickle.dumps(tables))
    assert again.decode_batch(ids) == rows and again.kind == "object"
    # bytes rows promoted into the object domain compare by pickle there
    merged = tables.merge(InternTable({5: b"five"}, kind="bytes"))
    assert merged.kind == "object" and merged[5] == b"five"
    with pytest.raises(ValueError, match="64-bit intern collision"):
        merged.absorb(np.array([5], np.uint64), [b"six"])
    with pytest.raises(TypeError, match="object-kind"):
        BytesColumn([b"raw"]).intern_sharded(tables)


def test_native_gather_and_differ_refuse_ranges_outside_the_buffer():
    if not native.available():
        pytest.skip("no native library")
    buf = np.frombuffer(b"abcdef", np.uint8)
    one = np.array([1], np.int64)
    assert native.gather_ranges(buf, one, one * 3, 3).tobytes() == b"bcd"
    assert native.differ_ranges(buf, one, buf, one, one * 5) == -1
    assert native.differ_ranges(buf, one * 0, buf, one, one * 2) == 0
    for starts, lens in [(one * 4, one * 3), (-one, one), (one, -one)]:
        with pytest.raises(ValueError, match="outside"):
            native.gather_ranges(buf, starts, lens, 3)
        with pytest.raises(ValueError, match="outside"):
            native.differ_ranges(buf, one, buf, starts, lens)
