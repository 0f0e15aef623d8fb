"""Native C++ runtime parity tests — every mrnative entry point against
its Python/numpy reference implementation (the reference's equivalent
host paths: src/hash.cpp, oink/map_read_*.cpp, cpu/InvertedIndex.cpp)."""

import random
import re

import numpy as np
import pytest

from gpu_mapreduce_tpu import native
from gpu_mapreduce_tpu.ops.hash import (hash_bytes64, hash_bytes64_batch,
                                        hashlittle)

pytestmark = pytest.mark.skipif(
    not native.available(),
    reason=f"native lib unavailable: {native.build_error()}")


def test_hashlittle_parity_random():
    rnd = random.Random(7)
    for _ in range(300):
        data = bytes(rnd.randrange(256) for _ in range(rnd.randrange(50)))
        iv = rnd.randrange(2 ** 32)
        assert native.hashlittle(data, iv) == hashlittle(data, iv)


def test_hashlittle_batch_and_intern():
    words = [b"alpha", b"", b"x" * 13, b"mixed bytes\x00\xff", b"q"]
    buf = b"".join(words)
    offs = np.cumsum([0] + [len(w) for w in words]).astype(np.int64)
    assert native.hashlittle_batch(buf, offs, 9).tolist() == \
        [hashlittle(w, 9) for w in words]
    assert native.intern64_batch(buf, offs).tolist() == \
        [hash_bytes64(w) for w in words]


def test_hash_bytes64_batch_routes_native():
    words = [bytes([i]) * (i % 7) for i in range(64)]
    got = hash_bytes64_batch(words)
    assert got.tolist() == [hash_bytes64(w) for w in words]


def test_parse_table_rejects_overflow_and_partial_tokens():
    # > 2^64-1 must error (the numpy fallback raises OverflowError)
    with pytest.raises(ValueError):
        native.parse_table(b"99999999999999999999999 1\n",
                           (np.uint64, np.uint64))
    with pytest.raises(ValueError):
        native.parse_table(b"1 1.5abc\n", (np.uint64, np.float64))
    with pytest.raises(ValueError):
        native.parse_table(b"1 0x10\n", (np.uint64, np.float64))


def test_invertedindex_native_engine(tmp_path):
    from gpu_mapreduce_tpu.apps.invertedindex import InvertedIndex
    html = b'<a href="http://a/1">x</a><p><a href="http://b/2">y</a>'
    f = tmp_path / "part-00000"
    f.write_bytes(html)
    app = InvertedIndex(engine="native")
    nhits, nurls = app.run([str(f)], outdir=str(tmp_path / "out"))
    assert (nhits, nurls) == (2, 2)
    lines = sorted((tmp_path / "out").glob("*"))
    text = "".join(p.read_text() for p in lines)
    assert "http://a/1" in text and "http://b/2" in text


def test_parse_table_u64_exact_and_f64():
    tbl = b"1 2 3.5\n18446744073709551615 7 0.25\n 0 0 1e3 "
    u1, u2, f = native.parse_table(tbl, (np.uint64, np.uint64, np.float64))
    assert u1.tolist() == [1, 18446744073709551615, 0]   # 2^64-1 exact
    assert u2.tolist() == [2, 7, 0]
    assert f.tolist() == [3.5, 0.25, 1000.0]
    with pytest.raises(ValueError):
        native.parse_table(b"1 2\n3\n", (np.uint64, np.uint64))
    with pytest.raises(ValueError):
        native.parse_table(b"1 x\n", (np.uint64, np.uint64))


def test_parse_table_capacity_retry():
    n = 5000
    tbl = b"\n".join(b"%d %d" % (i, i * 2) for i in range(n))
    a, b = native.parse_table(tbl, (np.uint64, np.uint64))
    assert a.tolist() == list(range(n))
    assert b.tolist() == [2 * i for i in range(n)]


def test_find_hrefs_matches_regex():
    rnd = random.Random(11)
    parts = []
    urls = []
    for i in range(100):
        u = b"http://site%d/p%d" % (i, rnd.randrange(1000))
        urls.append(u)
        parts.append(b'<p>junk<a href="%s">t</a>' % u)
    html = b"<html>" + b"".join(parts) + b'<a href="noquote'
    s, l = native.find_hrefs(html)
    got = [html[a:a + b] for a, b in zip(s, l)]
    # lookahead regex: every match position, like the device mark kernel
    oracle = [m.group(1) for m in
              re.finditer(rb'(?=<a href="([^"]*)")', html)]
    assert got == oracle == urls


def test_find_hrefs_overlapping_matches():
    # a pattern occurrence *inside* a prior URL span must still match
    # (device mark kernel marks every position)
    html = b'<a href="aaa<a href="bar">x</a>'
    s, l = native.find_hrefs(html)
    got = [html[a:a + b] for a, b in zip(s, l)]
    oracle = [m.group(1) for m in
              re.finditer(rb'(?=<a href="([^"]*)")', html)]
    assert got == oracle == [b'aaa<a href=', b'bar']


def test_parse_table_inf_nan_plus_like_fallback():
    u, f = native.parse_table(b"+5 inf\n007 -nan\n1 -infinity\n",
                              (np.uint64, np.float64))
    assert u.tolist() == [5, 7, 1]
    assert f[0] == np.inf and np.isnan(f[1]) and f[2] == -np.inf
    # zero-padded beyond 20 chars still parses (fallback does too)
    u2, = native.parse_table(b"0000000000000000000000042\n", (np.uint64,))
    assert u2.tolist() == [42]


def test_kernels_parse_cols_native_path(tmp_path):
    from gpu_mapreduce_tpu.oink.kernels import _parse_cols
    p = tmp_path / "e.txt"
    p.write_text("5 6 1.5\n18446744073709551615 2 0.25\n")
    vi, vj, w = _parse_cols(str(p), (np.uint64, np.uint64, np.float64))
    assert vi.tolist() == [5, 18446744073709551615]
    assert vj.tolist() == [6, 2]
    assert w.tolist() == [1.5, 0.25]


def test_intern_ranges_matches_batch():
    """Zero-copy range interning must agree with the packed-buffer intern
    and the seeded alt family must differ from the default family."""
    rnd = random.Random(5)
    data = bytes(rnd.randrange(256) for _ in range(4096))
    buf = np.frombuffer(data, np.uint8)
    starts = np.array([0, 10, 100, 1000, 4000], np.int64)
    lens = np.array([5, 0, 33, 300, 96], np.int64)
    ids = native.intern_ranges(buf, starts, lens)
    pieces = [data[s:s + l] for s, l in zip(starts, lens)]
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    expect = native.intern64_batch(b"".join(pieces), offs)
    np.testing.assert_array_equal(ids, expect)
    alt = native.intern_ranges(buf, starts, lens, 0x9E3779B9, 0x85EBCA6B)
    assert not np.array_equal(ids, alt)


def test_find_hrefs_edge_positions():
    # pattern flush at start / end-of-buffer, quote at last byte,
    # unterminated tail, '<' density
    html = b'<a href="x"' + b"<<<<" + b'<a href="yy"'
    s, l = native.find_hrefs(html)
    got = [html[a:a + b] for a, b in zip(s, l)]
    assert got == [b"x", b"yy"]
    assert native.find_hrefs(b'<a href="')[0].size == 0   # no quote
    assert native.find_hrefs(b"")[0].size == 0
    assert native.find_hrefs(b"<" * 64)[0].size == 0


def test_intern_ranges2_matches_two_single_family_passes():
    rng = np.random.default_rng(3)
    buf = rng.integers(0, 256, 4096, dtype=np.uint8)
    starts = np.sort(rng.choice(3800, 40, replace=False)).astype(np.int64)
    lens = rng.integers(0, 200, 40, dtype=np.int64)  # incl. len 0 and >12
    ah, al = 0x9E3779B9, 0x85EBCA6B
    ids, alts = native.intern_ranges2(buf, starts, lens, ah, al)
    assert ids.tolist() == native.intern_ranges(buf, starts, lens).tolist()
    assert alts.tolist() == \
        native.intern_ranges(buf, starts, lens, ah, al).tolist()


def test_so_is_keyed_by_source_and_stale_sibling_not_loaded(tmp_path,
                                                            monkeypatch):
    """What loads is built from the mrnative.cpp that is there now: the
    artifact's name carries the source hash, and a sibling built from
    other source (or the old unkeyed name) is deleted, never loaded —
    an mtime says nothing after a copy or a checkout."""
    import os
    import shutil
    src = tmp_path / "mrnative.cpp"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    tag = native._TAG
    # stale siblings, NEWER than the source: the old loader's mtime rule
    # would have loaded the first of them
    legacy = tmp_path / f"mrnative-{tag}.so"
    other = tmp_path / f"mrnative-{'0' * 16}-{tag}.so"
    legacy.write_bytes(b"not a shared object")
    other.write_bytes(b"not a shared object")
    lib = native._load()
    assert lib is not None, native.build_error()
    first = native._so_path()
    assert os.path.exists(first)
    assert not legacy.exists() and not other.exists()
    assert lib.mr_hashlittle(native._u8(b"abc"), 3, 0) == \
        native.hashlittle(b"abc")
    # the source changes → another artifact; the previous one goes
    with open(src, "a") as f:
        f.write("\n// edited\n")
    assert native._so_path() != first
    assert native._load() is not None
    assert os.path.exists(native._so_path())
    assert not os.path.exists(first)
