"""InvertedIndex pipeline vs a regex oracle; mark kernel (pallas interpret +
xla twin) equivalence."""

import re

import numpy as np
import pytest

import jax.numpy as jnp

from gpu_mapreduce_tpu.apps.invertedindex import InvertedIndex, PATTERN
from gpu_mapreduce_tpu.ops.pallas.match import (compact_matches, mark_pallas,
                                                mark_xla, url_lengths)

HTML = (b'<html><body><a href="http://a.com/x">x</a>'
        b'<p>no link</p><a href="http://b.org/long/path?q=1">y</a>'
        b'<A HREF="http://case.sensitive/">skip</A>'
        b'<a href="http://a.com/x">dup</a></body></html>')


def oracle_urls(data: bytes):
    return re.findall(rb'<a href="([^"]*)"', data)


def test_mark_xla_vs_pallas_interpret():
    rng = np.random.default_rng(0)
    noise = rng.integers(0, 256, size=100_000, dtype=np.uint8)
    data = noise.tobytes() + HTML * 7 + noise.tobytes()
    buf = jnp.asarray(np.frombuffer(data, np.uint8))
    m1 = np.asarray(mark_xla(buf, PATTERN))
    m2 = np.asarray(mark_pallas(buf, PATTERN, interpret=True))
    np.testing.assert_array_equal(m1.astype(np.int8), m2)
    # ground truth from python
    expect = np.zeros(len(data), np.int8)
    start = 0
    while True:
        i = data.find(PATTERN, start)
        if i < 0:
            break
        expect[i] = 1
        start = i + 1
    np.testing.assert_array_equal(m2, expect)


def test_mark_cross_lane_boundaries():
    # place the pattern at every offset mod 128+rows to cross lane/row edges
    for off in (0, 1, 119, 120, 126, 127, 128, 255, 256, 1000):
        data = b"x" * off + b'<a href="u">' + b"y" * 300
        buf = jnp.asarray(np.frombuffer(data, np.uint8))
        m = np.asarray(mark_pallas(buf, PATTERN, interpret=True))
        assert m.sum() == 1 and m[off] == 1, off


def test_compact_and_lengths():
    data = HTML
    buf = jnp.asarray(np.frombuffer(data, np.uint8))
    mask = mark_xla(buf, PATTERN)
    starts, n = compact_matches(mask.astype(jnp.int8), 16)
    assert int(n) == 3  # lowercase '<a href="' occurrences
    starts = starts + len(PATTERN)
    lengths, windows = url_lengths(buf, starts, ord('"'), 128)
    urls = [bytes(np.asarray(windows[i][: int(lengths[i])]))
            for i in range(int(n))]
    assert urls == oracle_urls(data)


def test_unterminated_href_dropped(tmp_path):
    f = tmp_path / "bad.html"
    f.write_bytes(b'<a href="http://ok/">fine</a><a href="no-close-quote')
    ii = InvertedIndex()
    nhits, nurl = ii.run([str(f)])
    assert nhits == 1 and nurl == 1
    assert list(ii.urls.values()) == [b"http://ok/"]


def test_empty_href_kept(tmp_path):
    # length 0 is a real empty URL, distinct from "no terminator"
    f = tmp_path / "e.html"
    f.write_bytes(b'<a href="">empty</a><a href="http://x/">x</a>')
    ii = InvertedIndex()
    nhits, nurl = ii.run([str(f)])
    assert (nhits, nurl) == (2, 2)
    assert sorted(ii.urls.values()) == [b"", b"http://x/"]


@pytest.fixture
def html_corpus(tmp_path):
    rng = np.random.default_rng(7)
    hosts = [b"http://site%d.org/p%d" % (i % 5, i) for i in range(40)]
    files = []
    for fi in range(6):
        parts = [b"<html>"]
        for _ in range(rng.integers(5, 30)):
            u = hosts[rng.integers(0, len(hosts))]
            parts.append(b'<a href="' + u + b'">link</a>' +
                         bytes(rng.integers(32, 127, size=50, dtype=np.uint8)))
        parts.append(b"</html>")
        p = tmp_path / f"part-{fi:05d}.html"
        p.write_bytes(b"".join(parts))
        files.append(str(p))
    return files


def test_pipeline_matches_regex_oracle(html_corpus, tmp_path):
    import collections

    index = collections.defaultdict(set)
    total = 0
    for f in html_corpus:
        data = open(f, "rb").read()
        for u in oracle_urls(data):
            index[u].add(f)
            total += 1
    ii = InvertedIndex()
    outdir = str(tmp_path / "out")
    nhits, nurl = ii.run(html_corpus, outdir=outdir)
    assert nhits == total
    assert nurl == len(index)
    # output file lines reconstruct the oracle index
    got = {}
    with open(f"{outdir}/part-00000") as fh:
        for line in fh:
            url, names = line.rstrip("\n").split("\t")
            got[url.encode()] = set(names.split(" "))
    assert got == dict(index)


def test_pipeline_on_mesh(html_corpus):
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh

    ii1 = InvertedIndex()
    n1 = ii1.run(html_corpus)
    ii2 = InvertedIndex(comm=make_mesh())
    n2 = ii2.run(html_corpus)
    assert n1 == n2


def test_mesh_chunked_h2d_and_paged_mark(html_corpus, monkeypatch):
    """r4 large-shape hardening: bounded H2D messages (MR_H2D_CHUNK_WORDS)
    and fixed-page mark dispatches (MR_MARK_PAGE_WORDS) must be invisible
    in the results — forced tiny here so even a KB-scale corpus crosses
    both seams.  The knobs key the builder caches (_env_knobs), so no
    cache management is needed around the env toggles."""
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh

    ii1 = InvertedIndex()
    n1 = ii1.run(html_corpus)
    monkeypatch.setenv("MR_H2D_CHUNK_WORDS", "32")
    monkeypatch.setenv("MR_MARK_PAGE_WORDS", "256")
    ii2 = InvertedIndex(engine="pallas", comm=make_mesh())
    n2 = ii2.run(html_corpus)
    assert n1 == n2
    assert ii1.urls == ii2.urls


def test_long_url_second_tier(tmp_path):
    """URLs longer than the 64-byte first-tier window take the 256-byte
    re-gather path; ones beyond MAX_URL still drop."""
    long_url = b"http://example.org/" + b"x" * 150          # tier 2
    giant = b"http://example.org/" + b"y" * 400             # > MAX_URL: drop
    short = b"http://e/"
    f = tmp_path / "long.html"
    f.write_bytes(b'<a href="%s">a</a><a href="%s">b</a><a href="%s">c</a>'
                  % (short, long_url, giant))
    ii = InvertedIndex()
    nhits, nurl = ii.run([str(f)])
    assert (nhits, nurl) == (2, 2)
    assert sorted(ii.urls.values()) == sorted([short, long_url])


def test_long_url_dense_corpus_wide_fallback(tmp_path):
    """More long URLs than the long-tail capacity → the wide (full-window)
    fallback must engage and still match the oracle."""
    urls = [b"http://example.org/" + bytes([97 + i % 26]) * 120
            for i in range(40)]
    f = tmp_path / "dense.html"
    f.write_bytes(b"".join(b'<a href="%s">x</a>' % u for u in urls))
    ii = InvertedIndex()
    nhits, nurl = ii.run([str(f)])
    assert nhits == len(urls)
    assert nurl == len(set(urls))
    assert sorted(set(ii.urls.values())) == sorted(set(urls))


@pytest.mark.slow
def test_multi_batch_corpus(html_corpus, monkeypatch):
    """Force the per-corpus byte cap below one file so every file becomes
    its own batch — counts and url dict must match the single-batch run."""
    ii1 = InvertedIndex()
    n1 = ii1.run(html_corpus)
    monkeypatch.setattr(InvertedIndex, "_BATCH_BYTES", 4096)
    ii2 = InvertedIndex()
    n2 = ii2.run(html_corpus)
    assert n1 == n2
    assert ii1.urls == ii2.urls
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    ii3 = InvertedIndex(comm=make_mesh(1))
    n3 = ii3.run(html_corpus)
    assert n3 == n1


def test_single_file_over_cap_raises(tmp_path, monkeypatch):
    p = tmp_path / "big.html"
    p.write_bytes(b"x" * 8192)
    monkeypatch.setattr(InvertedIndex, "_BATCH_BYTES", 4096)
    with pytest.raises(ValueError, match="exceeds the device corpus cap"):
        InvertedIndex().run([str(p)])


def test_pipeline_on_single_device_mesh(html_corpus):
    """The one-chip benchmark cell's tier: P=1 mesh → zero-copy ShardedKV from the
    fused extract, aggregate early-out, device convert, batch count
    reduce (emit_batch) — must agree with the serial path."""
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    from gpu_mapreduce_tpu.parallel.sharded import ShardedKV

    ii1 = InvertedIndex()
    n1 = ii1.run(html_corpus)
    ii2 = InvertedIndex(comm=make_mesh(1))
    n2 = ii2.run(html_corpus)
    assert n1 == n2
    # the reduced KV must still be device-resident (count per url id)
    fr = ii2.mr.kv.one_frame()
    assert isinstance(fr, ShardedKV)
    import numpy as np
    counts = {int(k): int(v) for k, v in fr.to_host().pairs()}
    ref = {int(k): int(v) for k, v in ii1.mr.kv.one_frame().pairs()}
    assert counts == ref


def test_mesh_ingestion_no_controller_funnel(html_corpus):
    """VERDICT r2 #2: per-device ingestion — every shard extracts its own
    file slice on its own device and the whole map/aggregate/convert/
    reduce pipeline runs with ZERO device→host frame materialisations."""
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    from gpu_mapreduce_tpu.parallel.sharded import ShardedKV, ToHostStats

    ii1 = InvertedIndex()
    n1 = ii1.run(html_corpus)
    ii2 = InvertedIndex(comm=make_mesh())
    snap = ToHostStats.snapshot()
    n2 = ii2.run(html_corpus)
    assert ToHostStats.delta(snap) == (0, 0)
    assert n2 == n1
    fr = ii2.mr.kv.one_frame()
    assert isinstance(fr, ShardedKV)
    counts = {int(k): int(v) for k, v in fr.to_host().pairs()}
    ref = {int(k): int(v) for k, v in ii1.mr.kv.one_frame().pairs()}
    assert counts == ref
    # the url dict built from per-shard host slices matches the serial one
    assert ii2.urls == ii1.urls


def test_mesh_multi_round_batches(html_corpus, monkeypatch):
    """Per-shard corpora above the int32 cap process in rounds (one
    ShardedKV frame per round) and still match the serial oracle."""
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh

    ii1 = InvertedIndex()
    n1 = ii1.run(html_corpus)
    monkeypatch.setattr(InvertedIndex, "_BATCH_BYTES", 4096)
    ii2 = InvertedIndex(comm=make_mesh())
    n2 = ii2.run(html_corpus)
    assert n2 == n1
    assert ii1.urls == ii2.urls


def test_map_stats_multi_batch_and_wide(html_corpus, tmp_path, monkeypatch):
    """``InvertedIndex.stats`` surfaces the batching + two-tier window
    machinery: forced multi-batch shows nbatches > 1;
    a long-URL-dense corpus shows a wide fallback."""
    monkeypatch.setattr(InvertedIndex, "_BATCH_BYTES", 4096)
    ii = InvertedIndex()
    ii.run(html_corpus)
    assert ii.stats["nbatches"] > 1, ii.stats
    monkeypatch.undo()

    urls = [b"http://example.org/" + bytes([97 + i % 26]) * 120
            for i in range(40)]
    f = tmp_path / "dense.html"
    f.write_bytes(b"".join(b'<a href="%s">x</a>' % u for u in urls))
    ii2 = InvertedIndex()
    ii2.run([str(f)])
    assert ii2.stats["wide_fallbacks"] >= 1, ii2.stats
    assert ii2.stats["nlong_max"] > 0


def test_fold_id_check_detects_collisions_within_and_across_batches():
    """u64 intern collision safety on the no-url-dict path: one id
    carrying two alt-family values must raise at compaction — whether
    the pairs sit in one batch or span batches (the r4 append-only
    hot loop + doubling-trigger compaction rework of _fold_id_check;
    run() always compacts at map close)."""
    import numpy as np
    import pytest
    from gpu_mapreduce_tpu.apps.invertedindex import InvertedIndex

    idx = InvertedIndex(engine="native")
    ids = np.array([5, 7, 5], np.uint64)
    alts = np.array([1, 2, 9], np.uint64)
    idx._fold_id_check(ids, alts)   # append only; checked at compaction
    with pytest.raises(ValueError, match="collision"):
        idx._compact_chk_runs()

    idx = InvertedIndex(engine="native")
    idx._fold_id_check(np.array([5, 7], np.uint64),
                       np.array([1, 2], np.uint64))
    idx._fold_id_check(np.array([8, 5], np.uint64),
                       np.array([3, 9], np.uint64))  # 5 -> 9 vs 1: deferred
    with pytest.raises(ValueError, match="collision"):
        idx._compact_chk_runs()

    # benign duplicates (same id, same alt) across batches survive
    idx = InvertedIndex(engine="native")
    idx._fold_id_check(np.array([5, 7], np.uint64),
                       np.array([1, 2], np.uint64))
    idx._fold_id_check(np.array([5, 8], np.uint64),
                       np.array([1, 3], np.uint64))
    idx._compact_chk_runs()
    ri, ra = idx._chk_sorted
    assert ri.tolist() == [5, 7, 8] and ra.tolist() == [1, 2, 3]


@pytest.mark.parametrize("engine", ["xla", "native"])
def test_mesh_outdir_writes_per_shard_parts(html_corpus, tmp_path, engine):
    """VERDICT r3 #7: an 8-device run writes 8 part-<shard> files from
    per-shard data (url bytes decoded from the destination shard's own
    dict on the device tier), and their union matches the serial
    oracle's single output file."""
    import collections
    import os

    from gpu_mapreduce_tpu.parallel.mesh import make_mesh

    oracle = collections.defaultdict(set)
    for f in html_corpus:
        for u in oracle_urls(open(f, "rb").read()):
            oracle[u].add(f)

    ii = InvertedIndex(engine=engine, comm=make_mesh(8))
    outdir = str(tmp_path / f"out_{engine}")
    nhits, nurl = ii.run(html_corpus, outdir=outdir)
    parts = sorted(os.listdir(outdir))
    assert parts == [f"part-{p:05d}" for p in range(8)]
    assert nurl == len(oracle)
    got = {}
    for part in parts:
        with open(os.path.join(outdir, part)) as fh:
            for line in fh:
                url, names = line.rstrip("\n").split("\t")
                assert url.encode() not in got   # each key on ONE shard
                got[url.encode()] = set(names.split(" "))
    assert got == dict(oracle)
    if engine == "xla":
        # the device tier never built a controller-global dict
        assert ii.shard_urls is not None
        assert sum(len(d) for d in ii.shard_urls) == len(oracle)
        assert ii._urls == {}


def test_fold_id_check_thread_hammer():
    """4 threads interleave batches (shared hot ids + disjoint tails)
    while doubling-trigger compactions race the appends; the final
    compacted run must be exactly the global unique pair set."""
    import threading

    idx = InvertedIndex(engine="native")
    idx._CHK_MIN_COMPACT = 256          # force many mid-stream compactions
    rng = np.random.default_rng(3)
    hot = np.arange(100, dtype=np.uint64)
    batches = []
    for t in range(4):
        for b in range(30):
            tail = (np.arange(200, dtype=np.uint64)
                    + 1000 * (1 + t * 30 + b))
            ids = np.concatenate([hot, tail])
            rng.shuffle(ids)
            batches.append((t, ids))
    expect = set()
    for _, ids in batches:
        expect.update(ids.tolist())

    def work(t):
        for bt, ids in batches:
            if bt == t:
                idx._fold_id_check(ids, ids + np.uint64(7))  # alt = id+7

    threads = [threading.Thread(target=work, args=(t,)) for t in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    idx._compact_chk_runs()
    ri, ra = idx._chk_sorted
    assert idx._chk_tails == []
    assert set(ri.tolist()) == expect
    assert (ra == ri + np.uint64(7)).all()
    assert (np.diff(ri.astype(np.int64)) > 0).all()   # sorted, deduped

    # and a collision smuggled in by one thread still surfaces
    idx._fold_id_check(np.array([5], np.uint64), np.array([99], np.uint64))
    with pytest.raises(ValueError, match="collision"):
        idx._compact_chk_runs()


# -- the four-chip deployment's shapes, tiny (benchmark/configs/
# puma-invindex-4chip.json): exact against a regex scan written here ----------

def _puma_corpus(d, nfiles=8, file_bytes=6000):
    """PUMA-density shapes at toy size: one href per filler, a quarter of
    the references to a 64-URL hot set, 2 % long URLs of 130-210 bytes,
    some over MAX_URL (dropped), and an href without its closing quote at
    the end of every file (dropped)."""
    filler = b"<p>" + b"lorem ipsum dolor sit amet " * 3 + b"</p>\n"
    hot = [b"http://example.org/hot/%02d" % i for i in range(64)]
    paths, uid, nref = [], 0, 0
    for i in range(nfiles):
        pieces, size = [], 0
        while size < file_bytes:
            if nref % 100 == 99:
                u = b"http://example.org/over/p%08d/" % uid + b"y" * 300
                uid += 1
            elif nref % 50 == 49:
                u = b"http://example.org/long/p%08d/" % uid \
                    + b"x" * (96 + uid % 80)
                uid += 1
            elif nref % 4 == 3:
                u = hot[(nref // 4) % len(hot)]
            else:
                u = b"http://example.org/wiki/page-%08d" % uid
                uid += 1
            nref += 1
            pieces += [filler, PATTERN + u + b'">x</a>']
            size += len(filler) + len(pieces[-1])
        pieces.append(filler + PATTERN + b"http://example.org/cut-off/%d" % i)
        p = d / f"part-{i:05d}.html"
        p.write_bytes(b"".join(pieces))
        paths.append(str(p))
    return paths


@pytest.mark.parametrize("rounds", [1, 2])
@pytest.mark.parametrize("P", [1, 4])
def test_puma_shapes_on_a_mesh_match_a_regex_scan_exactly(
        tmp_path, monkeypatch, P, rounds):
    """The multi-process deployment through the normal path: every url in
    exactly one part file, in the part file of ``default_hash(id) % P``,
    P non-empty part files, npairs and nunique equal.  Integers: no
    tolerance."""
    import os

    from gpu_mapreduce_tpu.apps.invertedindex import MAX_URL, _GAP
    from gpu_mapreduce_tpu.ops.hash import hash_bytes64
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    from gpu_mapreduce_tpu.parallel.shuffle import default_hash

    paths = _puma_corpus(tmp_path)
    want, npairs, dropped = {}, 0, 0
    for f in paths:
        data = open(f, "rb").read()
        assert data.count(PATTERN) == len(oracle_urls(data)) + 1  # cut off
        for u in oracle_urls(data):
            if len(u) >= MAX_URL:
                dropped += 1
                continue
            npairs += 1
            want.setdefault(u, set()).add(f)
    assert dropped and any(130 <= len(u) <= 210 for u in want)

    if rounds > 1:
        # a shard's contiguous slice of 8 / P files in `rounds` batches
        per_batch = len(paths) // P // rounds
        sizes = [os.path.getsize(f) + _GAP for f in paths]
        cap = per_batch * max(sizes)
        assert (per_batch + 1) * min(sizes) > cap
        monkeypatch.setenv("MR_BATCH_BYTES", str(cap))
    idx = InvertedIndex(comm=make_mesh(P))
    outdir = str(tmp_path / "out")
    assert idx.run(paths, outdir=outdir) == (npairs, len(want))
    assert idx.stats["nbatches"] == P * rounds

    parts = sorted(os.listdir(outdir))
    assert parts == [f"part-{p:05d}" for p in range(P)]
    got = {}
    for p, part in enumerate(parts):
        lines = open(os.path.join(outdir, part), "rb").read().splitlines()
        assert lines                                  # P non-empty files
        urls = [ln.split(b"\t")[0] for ln in lines]
        ids = np.asarray([hash_bytes64(u) for u in urls], np.uint64)
        assert (np.asarray(default_hash(ids)) % P == p).all()
        for ln in lines:
            url, names = ln.split(b"\t")
            assert url not in got                     # in ONE part file
            got[url] = set(names.decode().split(" "))
    assert got == want


# -- the part files from arrays (PR 45): `_part_file` plans a shard's lines
# with numpy and copies them by one range gather; the per-group loop the
# program ran until then is kept here as its oracle, byte for byte ------------

def _loop_part_file(path, hf, lookup, docs):
    """The loop `_write_parts_sharded` was (text mode, UTF-8)."""
    with open(path, "w", encoding="utf-8") as out:
        for k, vals in hf.groups():
            url = lookup[int(k)].decode(errors="replace")
            names = " ".join(docs[int(v)] for v in sorted(set(vals)))
            out.write(f"{url}\t{names}\n")


def _shard(groups, urls=None, ndocs=4, docs=None):
    """A host shard as `shard_to_host` returns it (`groups`: one list of
    file indices a group, keys random u64 in ascending order), its url
    dict and the file names; the dict holds a few urls of other shards."""
    from gpu_mapreduce_tpu.core.column import DenseColumn
    from gpu_mapreduce_tpu.core.frame import KMVFrame

    rng = np.random.default_rng(len(groups))
    ids = np.unique(rng.integers(0, 2 ** 64, len(groups) + 3, dtype=np.uint64))
    assert len(ids) == len(groups) + 3
    keys, other = ids[1:-2], ids[[0, -2, -1]]     # below, above, the u64 top
    if urls is None:
        urls = [b"http://example.org/wiki/page-%06d" % i
                for i in range(len(groups))]
    lookup = dict(zip(keys.tolist(), urls))
    lookup.update((k, b"http://other.shard/%d" % k) for k in other.tolist())
    items = list(lookup.items())                  # a dict in no order
    lookup = dict(items[i] for i in rng.permutation(len(items)))
    nv = np.asarray([len(g) for g in groups], np.int64)
    offsets = np.concatenate([[0], np.cumsum(nv)]).astype(np.int64)
    values = np.asarray([v for g in groups for v in g], np.int32)
    if docs is None:
        docs = [f"/corpus/dir-{i % 3}/part-{i:05d}.html" for i in range(ndocs)]
    return KMVFrame(DenseColumn(keys), nv, offsets, DenseColumn(values)), \
        lookup, docs


def _case_unsorted_repeated():
    return _shard([[3, 0, 3, 1, 0, 0], [2, 2], [1, 3, 1, 2], [0]]), 0


def _case_hot_group_beside_groups_of_one():
    rng = np.random.default_rng(7)
    hot = rng.integers(0, 8, 5000).tolist()
    ones = [[int(v)] for v in rng.integers(0, 8, 700)]
    return _shard(ones[:300] + [hot] + ones[300:] + [hot[:2048]],
                  ndocs=8), 0


def _case_a_group_naming_every_file():
    return _shard([[1], list(range(16))[::-1] * 2, [15, 0]], ndocs=16), 0


def _case_one_group():
    return _shard([[2, 1]]), 0


def _case_no_group():
    return _shard([]), 0


def _case_urls_of_0_1_and_max_bytes():
    from gpu_mapreduce_tpu.apps.invertedindex import MAX_URL
    urls = [b"", b"/", b"h" * (MAX_URL - 1), b"x", b"y" * (MAX_URL - 1), b"z"]
    return _shard([[0], [1, 0], [3], [2, 2], [0, 1, 2, 3], [1]], urls), 0


def _case_valid_utf8_in_a_url():
    urls = [b"http://a/plain", "http://b/café/中".encode(),
            b"http://c/plain", "ü".encode()]
    return _shard([[0], [1, 0], [2], [3, 1]], urls), 2


def _case_invalid_utf8_in_a_url():
    urls = [b"", b"http://a/\xff\xfe/x", b"http://b/plain",
            b"http://c/\xe4\xb8", "http://d/é".encode(), b"\x80"]
    return _shard([[0], [1, 0], [2], [3, 1], [2, 2], [1]], urls), 4


def _case_a_file_name_that_is_not_ascii():
    docs = ["/corpus/part-0.html", "/corpus/süd/été 1.html",
            "/corpus/中文.html"]
    return _shard([[2, 1], [0], [1], [2, 0, 1]], docs=docs), 0


_PART_CASES = [f for n, f in sorted(globals().items())
               if n.startswith("_case_")]


@pytest.mark.parametrize("native_lib", [True, False],
                         ids=["native", "numpy"])
@pytest.mark.parametrize("case", _PART_CASES,
                         ids=[f.__name__[6:] for f in _PART_CASES])
def test_part_file_from_arrays_equals_the_per_group_loop(
        case, native_lib, tmp_path, monkeypatch):
    from gpu_mapreduce_tpu import native
    from gpu_mapreduce_tpu.apps.invertedindex import _part_file

    if native_lib:
        assert native.available(), native.build_error()
    else:
        monkeypatch.setattr(native, "available", lambda: False)
    (hf, lookup, docs), want_recoded = case()
    _loop_part_file(tmp_path / "loop", hf, lookup, docs)
    want = (tmp_path / "loop").read_bytes()
    assert want.count(b"\n") == len(hf)

    before = dict(lookup)
    lines, pieces, recoded = _part_file(hf, lookup, docs)
    assert lines.dtype == np.uint8 and lines.tobytes() == want
    pairs = sum(len(set(vals)) for _, vals in hf.groups())
    assert pieces == 2 * len(hf) + pairs
    assert recoded == want_recoded
    assert (b"\xef\xbf\xbd" in want) == ("invalid" in case.__name__)
    assert lookup == before                     # the dict is read, not recoded


@pytest.mark.parametrize("table", ["without_the_key", "empty"])
def test_a_group_key_missing_from_the_table_raises_and_writes_nothing(
        table, tmp_path):
    """`lookup[int(k)]` raised KeyError; a searchsorted that is not tested
    for equality would write the neighbour's URL."""
    import os
    import types

    (hf, lookup, docs), _ = _case_unsorted_repeated()
    gone = int(hf.key.data[2])
    lookup = {} if table == "empty" else {
        k: u for k, u in lookup.items() if k != gone}
    idx = InvertedIndex()
    idx.docs, idx.shard_urls = docs, [lookup]
    fr = types.SimpleNamespace(nprocs=1, shard_to_host=lambda p: hf)
    with pytest.raises(KeyError) as err:
        idx._write_parts_sharded(str(tmp_path), fr)
    assert err.value.args == ((int(hf.key.data[0]) if table == "empty"
                               else gone),)
    assert os.listdir(tmp_path) == []


def test_a_value_that_is_no_file_index_raises():
    from gpu_mapreduce_tpu.apps.invertedindex import _part_file

    for bad in (4, -1):
        hf, lookup, docs = _shard([[0, 1], [bad, 2]])
        with pytest.raises(IndexError):
            _part_file(hf, lookup, docs)


def test_part_write_spans_say_pieces_and_recoded(tmp_path):
    """The words of `parts.write` (doc/observability.md): `pieces` ranges
    gathered, `recoded` URLs spelt again, beside `groups` and `bytes`."""
    import types

    from gpu_mapreduce_tpu.obs import get_tracer, names

    shards = [_case_invalid_utf8_in_a_url()[0], _case_no_group()[0],
              _case_hot_group_beside_groups_of_one()[0]]
    docs = shards[2][2]
    idx = InvertedIndex()
    idx.docs, idx.shard_urls = docs, [s[1] for s in shards]
    fr = types.SimpleNamespace(nprocs=3,
                               shard_to_host=lambda p: shards[p][0])
    tr = get_tracer()
    was = tr.enabled
    tr.enable(ring=1 << 10)
    tr.clear()
    try:
        idx._write_parts_sharded(str(tmp_path), fr)
        spans = [e["args"] for e in tr.events()
                 if e["name"] == names.PARTS_WRITE]
    finally:
        tr.clear()
        if not was:
            tr.disable()
    assert [a["shard"] for a in spans] == [0, 1, 2]
    assert [a["recoded"] for a in spans] == [4, 0, 0]
    for p, (a, (hf, lookup, _)) in enumerate(zip(spans, shards)):
        _loop_part_file(tmp_path / "loop", hf, lookup, docs)
        want = (tmp_path / "loop").read_bytes()
        assert (tmp_path / f"part-{p:05d}").read_bytes() == want
        assert a["groups"] == len(hf) and a["bytes"] == len(want)
        assert a["pieces"] == 2 * len(hf) + sum(
            len(set(v)) for _, v in hf.groups())
