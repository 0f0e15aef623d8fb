"""Rows added to a dataset that lives on a mesh go UP and are appended
there (ISSUE 27): ``KeyValue.one_frame`` places the dense host frames of a
mixed dataset on the mesh and pulls nothing; the append is a copy (a select
over a shifted view: no scatter, no sort) whose capacity is the fullest
shard's, not the sum of the inputs'; ``rmat`` / ``rmat2`` hand over device
frames on the mesh backend and build the same graph as the serial one."""

import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpu_mapreduce_tpu import MapReduce
from gpu_mapreduce_tpu.core.frame import KVFrame
from gpu_mapreduce_tpu.obs import get_tracer, names
from gpu_mapreduce_tpu.parallel import devkernels, sharded
from gpu_mapreduce_tpu.parallel.mesh import (make_mesh, mesh_axis_size,
                                             row_sharding)
from gpu_mapreduce_tpu.parallel.sharded import (ShardedKV, ToHostStats,
                                                fill_counts, place_rows,
                                                round_cap,
                                                shard_frame_with_counts)


@pytest.fixture(scope="module", params=[1, 4], ids=["P1", "P4"])
def mesh(request):
    return make_mesh(request.param)


@pytest.fixture
def traced():
    tr = get_tracer()
    was = tr.enabled
    tr.enable(ring=1 << 16)
    tr.clear()
    yield tr
    tr.clear()
    if not was:
        tr.disable()


def _spans(tr, name):
    return [e["args"] for e in tr.events() if e["name"] == name]


def _rows(fr):
    """Sorted (key..., value) rows of a host or sharded frame."""
    host = fr if isinstance(fr, KVFrame) else fr.to_host()
    k = np.asarray(host.key.data).reshape(len(host), -1)
    v = np.asarray(host.value.data).reshape(len(host), -1)
    return sorted(map(tuple, np.concatenate([k, v.astype(k.dtype)], 1)))


def _skv(mesh, rng, counts, cap, width=2):
    """A front-packed ShardedKV of random u64[.., width] keys and 1-byte
    values with the given per-shard counts, garbage-free padding."""
    P = mesh_axis_size(mesh)
    shape = (P * cap, width) if width > 1 else (P * cap,)
    k = rng.integers(1, 1 << 60, size=shape, dtype=np.uint64)
    v = rng.integers(1, 255, size=P * cap, dtype=np.uint8)
    live = (np.arange(cap)[None, :] < np.asarray(counts)[:, None]).reshape(-1)
    k[~live], v[~live] = 0, 0
    sh = row_sharding(mesh)
    return ShardedKV(mesh, jax.device_put(k, sh), jax.device_put(v, sh),
                     np.asarray(counts, np.int32))


def _host(rng, n, width=2):
    shape = (n, width) if width > 1 else (n,)
    return KVFrame(rng.integers(1, 1 << 60, size=shape, dtype=np.uint64),
                   rng.integers(1, 255, size=n, dtype=np.uint8))


# -- (a) the mixed dataset is assembled on the mesh ---------------------------

def test_mixed_dataset_is_appended_on_the_mesh(mesh, traced, monkeypatch):
    rng = np.random.default_rng(3)
    P = mesh_axis_size(mesh)
    acc = _skv(mesh, rng, rng.integers(0, 40, P), 64)
    new = _host(rng, 57)
    want = sorted(_rows(acc) + _rows(new))

    mr = MapReduce(mesh)
    mr.open()
    mr.kv.add_frame(acc)
    mr.kv.add_frame(new)
    mr.close()
    pulls = []
    monkeypatch.setattr(ShardedKV, "to_host",
                        lambda self: pulls.append(len(self)))
    before = ToHostStats.snapshot()
    mr.aggregate()
    out = mr.kv.one_frame()
    monkeypatch.undo()
    assert pulls == [] and ToHostStats.delta(before) == (0, 0)
    assert isinstance(out, ShardedKV) and out.mesh == mesh
    assert _rows(out) == want
    (span,) = _spans(traced, names.AGGREGATE_ONE_FRAME)
    assert span["frames"] == 2 and span["rows"] == len(want)
    assert span["to_host_bytes"] == 0
    assert span["to_device_bytes"] == new.nbytes() > 0
    # only the new rows went up: nothing was sharded a second time
    assert _spans(traced, names.AGGREGATE_SHARD) == []


def test_one_frame_places_host_rows_on_the_short_shards(mesh):
    rng = np.random.default_rng(4)
    P = mesh_axis_size(mesh)
    have = rng.integers(0, 30, P)
    acc, new = _skv(mesh, rng, have, 32), _host(rng, 32 * P - int(have.sum()))
    mr = MapReduce(mesh)
    mr.open()
    mr.kv.add_frame(new)        # the host frame first: order is free
    mr.kv.add_frame(acc)
    mr.close()
    out = mr.kv.one_frame()
    assert out.counts.tolist() == [32] * P and out.cap == 32
    assert _rows(out) == sorted(_rows(acc) + _rows(new))


# -- (f) an interned host frame still goes through the host -------------------

def test_byte_keyed_host_frame_keeps_the_host_path(mesh, traced):
    """Words interned onto the mesh, then more words added on the host:
    the new rows' ids are not in the sharded frame's space, so the dataset
    compacts through the host as before, and the span says so."""
    first = [b"ant", b"bee", b"cat", b"ant", b"dog"]
    more = [b"bee", b"eel", b"fox"]
    mr = MapReduce(mesh)
    mr.map(1, lambda i, kv, p: [kv.add(w, 1) for w in first])
    mr.aggregate()
    acc = mr.kv.one_frame()
    assert isinstance(acc, ShardedKV) and acc.key_decode is not None
    mr.map(1, lambda i, kv, p: [kv.add(w, 2) for w in more], addflag=1)
    before = ToHostStats.snapshot()
    moved = {}
    out = mr.kv.one_frame(moved)
    assert isinstance(out, KVFrame) and ToHostStats.delta(before) == (1, 0)
    assert moved == {"to_host_bytes": acc.nbytes(), "to_device_bytes": 0}
    want = sorted([(w, 1) for w in first] + [(w, 2) for w in more])
    assert sorted((bytes(k), int(v)) for k, v in out.pairs()) == want
    # and aggregate says so on its span, and still gives the right rows
    traced.clear()
    mr.aggregate()
    (span,) = _spans(traced, names.AGGREGATE_ONE_FRAME)
    assert span["frames"] == 2
    assert span["to_host_bytes"] == acc.nbytes() and \
        span["to_device_bytes"] == 0
    got = []
    mr.scan_kv(lambda k, v, p: got.append((bytes(k), int(v))))
    assert sorted(got) == want


# -- (b) the append against the pack form it replaces --------------------------

def _pack_concat(a: ShardedKV, b: ShardedKV):
    """The form before PR 27: concatenate the two blocks of a shard and
    front-pack the valid rows.  ``devkernels._pack`` did that by a prefix
    sum and two scatters then and does it by one payload sort since PR 49
    (``tests/test_pack.py``); the oracle for ``_append`` is neither: numpy's
    own indexing, the kept rows first and zero rows after."""
    P = a.nprocs
    ks, vs, cs = [], [], []
    for i in range(P):
        blk = lambda x, cap: np.asarray(x)[i * cap:(i + 1) * cap]
        valid = np.concatenate([np.arange(a.cap) < a.counts[i],
                                np.arange(b.cap) < b.counts[i]])
        at = np.flatnonzero(valid)
        for out, xa, xb in ((ks, a.key, b.key), (vs, a.value, b.value)):
            rows = np.concatenate([blk(xa, a.cap), blk(xb, b.cap)])
            packed = np.zeros_like(rows)
            packed[:len(at)] = rows[at]
            out.append(packed)
        cs.append(len(at))
    return ks, vs, cs


CASES = {
    # name: (counts a, counts b, cap a, cap b) as functions of P
    "random": lambda P, r: (r.integers(0, 33, P), r.integers(0, 17, P), 32, 16),
    "a_empty": lambda P, r: (np.zeros(P, int), r.integers(0, 17, P), 8, 16),
    "b_empty": lambda P, r: (r.integers(0, 33, P), np.zeros(P, int), 32, 8),
    "both_empty": lambda P, r: (np.zeros(P, int), np.zeros(P, int), 8, 8),
    "fills_cap": lambda P, r: (np.full(P, 20), np.full(P, 12), 32, 16),
    "b_wider_cap": lambda P, r: (r.integers(0, 5, P), r.integers(0, 9, P), 8, 64),
    "a_oversized": lambda P, r: (r.integers(0, 5, P), r.integers(0, 9, P), 128, 16),
    "by_shard": lambda P, r: (np.arange(P) * 7 % 30, (P - np.arange(P)) * 5 % 16,
                              32, 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("width", [1, 2], ids=["u64", "u64x2"])
def test_append_equals_the_pack_form(mesh, case, width):
    rng = np.random.default_rng(sorted(CASES).index(case) * 2 + width)
    P = mesh_axis_size(mesh)
    ca, cb, cap_a, cap_b = CASES[case](P, rng)
    a = _skv(mesh, rng, ca, cap_a, width)
    b = _skv(mesh, rng, cb, cap_b, width)
    out = devkernels.concat_sharded(a, b)
    ks, vs, cs = _pack_concat(a, b)
    assert out.counts.tolist() == cs == (np.asarray(ca) + cb).tolist()
    # (d) the capacity is the fullest shard's, rounded: not cap_a + cap_b
    assert out.cap == round_cap(max(cs))
    if case in ("fills_cap", "a_oversized", "b_wider_cap", "both_empty"):
        assert out.cap < cap_a + cap_b
    assert out.value.dtype == np.uint8 and out.key.dtype == np.uint64
    key, value = np.asarray(out.key), np.asarray(out.value)
    for i in range(P):
        got_k = key[i * out.cap:(i + 1) * out.cap]
        got_v = value[i * out.cap:(i + 1) * out.cap]
        n = min(out.cap, len(ks[i]))
        # identical rows in identical order, zero rows after them
        assert np.array_equal(got_k[:n], ks[i][:n])
        assert np.array_equal(got_v[:n], vs[i][:n])
        assert not got_k[cs[i]:].any() and not got_v[cs[i]:].any()


def test_append_lowers_to_a_copy(mesh):
    """(c) no scatter, no sort, no gather in the program — and the pack
    form it replaces does hold a sort (the check can fail): exactly one,
    with no scatter and no prefix sum beside it since PR 49, and one gather
    where a float64 value cannot ride."""
    u64, u8, i32 = jnp.uint64, jnp.uint8, jnp.int32
    P = mesh_axis_size(mesh)
    SDS = jax.ShapeDtypeStruct
    args = (SDS((P * 32, 2), u64), SDS((P * 32,), u8), SDS((P,), i32),
            SDS((P * 16, 2), u64), SDS((P * 16,), u8), SDS((P,), i32))
    text = devkernels._concat_jit(mesh, 32).lower(*args).as_text()
    assert re.search(r"module @(\w+)", text).group(1) == names.CONCAT_ROWS
    for op in ("scatter", "sort", "gather"):
        assert op not in text, op
    assert "dynamic_slice" in text and "select" in text
    packed = jax.jit(devkernels._pack).lower(
        SDS((48, 2), u64), SDS((48,), u8), SDS((48,), jnp.bool_)).as_text()
    for op in ("scatter", "gather", "cumsum", "reduce_window"):
        assert op not in packed, op
    assert packed.count('"stablehlo.sort"') == 1
    by_index = jax.jit(devkernels._pack).lower(
        SDS((48, 2), u64), SDS((48,), jnp.float64),
        SDS((48,), jnp.bool_)).as_text()
    for op in ("scatter", "cumsum", "reduce_window"):
        assert op not in by_index, op
    assert by_index.count('"stablehlo.sort"') == 1
    assert by_index.count('"stablehlo.gather"') == 1
    placed = sharded._place_rows_jit(mesh, 32).lower(
        SDS((64, 2), u64), SDS((64,), u8), SDS((P,), i32),
        SDS((P,), i32)).as_text()
    for op in ("scatter", "sort", "gather"):
        assert op not in placed, op


# -- the counts that keep the capacity ------------------------------------------

@pytest.mark.parametrize("have,n,want", [
    ([0, 0, 0, 0], 8, [2, 2, 2, 2]),
    ([0, 0, 0, 0], 10, [2, 2, 3, 3]),
    ([5, 1, 3, 3], 4, [0, 2, 1, 1]),
    ([5, 1, 3, 3], 8, [0, 4, 2, 2]),
    ([9, 1, 1, 1], 3, [0, 1, 1, 1]),
    ([9, 1, 1, 1], 1, [0, 0, 0, 1]),
    ([7], 5, [5]),
    ([3, 4], 0, [0, 0]),
])
def test_fill_counts_fills_the_short_shards_first(have, n, want):
    have = np.asarray(have)
    give = fill_counts(have, n)
    assert give.sum() == n and give.min() >= 0
    assert sorted(give.tolist()) == sorted(want)
    # no split ends with a lower fullest shard: the level is the lowest
    # that takes all n rows, and only shards below it were given any
    level = next(L for L in range(int(have.max()) + n + 1)
                 if np.maximum(L - have, 0).sum() >= n)
    assert (have + give).max() == max(level, have.max())
    assert not give[have >= level].any()


def test_fill_counts_keeps_rmat_at_one_capacity():
    """The four-chip cell's case: 2^23 rows in all, the accumulated
    shards a little uneven, every shard ends exactly full at 2^21."""
    rng = np.random.default_rng(0)
    have = (1 << 21) - rng.integers(200_000, 203_000, 4)
    give = fill_counts(have, (1 << 23) - int(have.sum()))
    assert (have + give).tolist() == [1 << 21] * 4


# -- levelling before an exchange ---------------------------------------------------

def _meshes():
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh2
    return {"P4": lambda: make_mesh(4), "P8": lambda: make_mesh(8),
            "2x2": lambda: make_mesh2(2, 2), "2x4": lambda: make_mesh2(2, 4)}


@pytest.mark.parametrize("shape", sorted(_meshes()))
@pytest.mark.parametrize("case", ["one_over", "rmat_late", "many_over",
                                  "one_holds_all", "ragged"])
def test_level_moves_the_excess_to_the_short_shards(shape, case):
    m = _meshes()[shape]()
    P = mesh_axis_size(m)
    rng = np.random.default_rng(P + len(case))
    counts = {
        "one_over": np.r_[70, np.full(P - 1, 20)],
        # every shard near the level, some over: the four-chip cell's
        # rounds 4 to 6 (64 a shard in all, hash spread of a few rows)
        "rmat_late": 64 + np.resize([3, -1, -2, 0, 2, -2, 1, -1], P),
        "many_over": np.resize([100, 3, 90, 0], P),
        "one_holds_all": np.r_[np.zeros(P - 1, int), 128],
        "ragged": rng.integers(0, 129, P),
    }[case]
    a = _skv(m, rng, counts, 128)
    out = devkernels.level_sharded(a)
    level = -(-int(counts.sum()) // P)
    if round_cap(level) >= round_cap(int(counts.max())):
        assert out is a         # nothing to win: left as it is
        return
    assert out.counts.sum() == counts.sum() and out.counts.max() <= level
    assert out.cap == round_cap(int(out.counts.max())) < round_cap(
        int(counts.max()))
    # shards at or under the level kept their rows, in place and in order
    k_in = np.asarray(a.key).reshape(P, a.cap, 2)
    k_out = np.asarray(out.key).reshape(P, out.cap, 2)
    for i in range(P):
        keep = min(int(counts[i]), level)
        assert np.array_equal(k_out[i, :keep], k_in[i, :keep])
        assert not k_out[i, out.counts[i]:].any()
    assert _rows(out) == _rows(a)
    assert out.key.sharding == a.key.sharding
    text = devkernels._level_jit(m, 64, 8, 8).lower(
        a.key, a.value, *(jax.ShapeDtypeStruct((P,), jnp.int32),) * 2,
        *(jax.ShapeDtypeStruct((P, P), jnp.int32),) * 2).as_text()
    assert re.search(r"module @(\w+)", text).group(1) == names.LEVEL_ROWS
    assert not re.search(r"stablehlo\.(scatter|sort|gather)\b", text)
    assert "all_gather" in text


def test_aggregate_levels_its_input_and_only_there(traced):
    """Before the exchange the rows over the even level move (they are
    re-homed anyway); ``one_frame`` alone never moves a sharded row
    (``convert`` after ``add`` counts on equal keys sharing a shard)."""
    m = make_mesh(4)
    rng = np.random.default_rng(11)
    a = _skv(m, rng, [70, 20, 20, 18], 128)
    b = _skv(m, rng, [0, 0, 0, 0], 8)
    want = _rows(a)
    mr = MapReduce(m)
    mr.open()
    mr.kv.add_frame(a)
    mr.kv.add_frame(b)
    mr.close()
    assert mr.kv.one_frame().counts.tolist() == [70, 20, 20, 18]
    mr.aggregate()
    (span,) = _spans(traced, names.AGGREGATE_ONE_FRAME)
    assert span["cap"] == 32 and span["to_host_bytes"] == 0
    assert _rows(mr.kv.one_frame()) == want
    # the exchange had rows to send home: the ones that were moved
    assert mr.last_exchange.rows == 128 and mr.last_exchange.sent_bytes > 0


def test_place_rows_lays_device_rows_over_the_mesh(mesh):
    rng = np.random.default_rng(6)
    P = mesh_axis_size(mesh)
    m = 64
    key = jnp.asarray(rng.integers(1, 1 << 60, (m, 2), dtype=np.uint64))
    val = jnp.asarray(rng.integers(1, 255, m, dtype=np.uint8))
    for counts in (fill_counts(np.zeros(P), 50), fill_counts(np.zeros(P), m),
                   np.r_[np.zeros(P - 1, int), 9]):
        before = ToHostStats.snapshot()
        fr = place_rows(mesh, key, val, counts)
        n = int(np.sum(counts))
        assert fr.cap == round_cap(int(np.max(counts)))
        assert fr.counts.tolist() == list(counts)
        host = fr.to_host()      # shard after shard = the first n rows in order
        assert np.array_equal(np.asarray(host.key.data), np.asarray(key)[:n])
        assert np.array_equal(np.asarray(host.value.data), np.asarray(val)[:n])
        assert ToHostStats.delta(before) == (1, 0)
        k = np.asarray(fr.key).reshape(P, fr.cap, 2)
        for i in range(P):      # zero rows behind the valid ones
            assert not k[i, counts[i]:].any()
    # the same frame as the host route's
    same = shard_frame_with_counts(
        KVFrame(np.asarray(key)[:50], np.asarray(val)[:50]), mesh,
        fill_counts(np.zeros(P), 50))
    fr = place_rows(mesh, key, val, fill_counts(np.zeros(P), 50))
    assert np.array_equal(np.asarray(fr.key), np.asarray(same.key))
    assert np.array_equal(np.asarray(fr.value), np.asarray(same.value))


def test_frames_made_on_the_mesh_say_their_row_sharding(mesh):
    """On a one-device mesh jax would hand back the replicated inputs'
    sharding; the spec is part of every later program's compile-cache
    key, so the new programs state the one ``shard_frame`` gives."""
    rng = np.random.default_rng(7)
    P = mesh_axis_size(mesh)
    want = shard_frame_with_counts(_host(rng, 8 * P), mesh,
                                   np.full(P, 8)).key.sharding
    key = jnp.asarray(rng.integers(1, 9, (64, 2), dtype=np.uint64))
    placed = place_rows(mesh, key, jnp.zeros(64, jnp.uint8),
                        fill_counts(np.zeros(P), 40))
    both = devkernels.concat_sharded(placed, placed)
    for fr in (placed, both):
        assert fr.key.sharding == want and fr.value.sharding == want
        assert fr.key.sharding.spec == want.spec


# -- (e) rmat / rmat2 on the mesh backend -----------------------------------------

def _run_rmat(comm, command):
    """(sorted edges, messages, frames pulled to the host by the command)."""
    from gpu_mapreduce_tpu.oink.script import OinkScript
    s = OinkScript(comm=comm, screen=io.StringIO())
    before = ToHostStats.snapshot()
    s.run_string(f"{command} 7 8 0.57 0.19 0.19 0.05 0.0 1 -o NULL mre")
    pulled = ToHostStats.delta(before)
    rows = []
    s.obj.get_mr("mre").scan_kv(
        lambda k, v, p: rows.append((int(k[0]), int(k[1]))))
    return (sorted(rows), s.screen.getvalue().replace("RMAT2", "RMAT"),
            pulled)


@pytest.fixture(scope="module")
def serial_rmat():
    return _run_rmat(None, "rmat")[:2]


@pytest.mark.parametrize("command", ["rmat", "rmat2"])
def test_rmat_on_the_mesh_builds_the_serial_graph(mesh, serial_rmat, traced,
                                                  command, monkeypatch):
    appended = []
    concat = devkernels.concat_sharded
    monkeypatch.setattr(
        devkernels, "concat_sharded",
        lambda a, b: appended.append((a.counts, concat(a, b)))
        or appended[-1][1])
    rows, said, pulled = _run_rmat(mesh, command)
    assert pulled == (0, 0)         # the loop's dataset never came down
    want_rows, want_said = serial_rmat
    assert rows == want_rows and len(set(rows)) == 128 * 8
    assert said == want_said
    niterate = int(re.search(r"(\d+) iterations", said).group(1))
    gen = _spans(traced, names.RMAT_GENERATE)
    assert len(gen) == niterate
    assert all(a["d2h_bytes"] == 0 for a in gen)
    one = _spans(traced, names.AGGREGATE_ONE_FRAME)
    assert one and all(a["to_host_bytes"] == 0 and a["to_device_bytes"] == 0
                       for a in one)
    if command == "rmat":
        # rounds 2.. hand aggregate the accumulated frame and the new one
        assert sum(a["frames"] == 2 for a in one) == niterate - 1
        assert {a["rows"] for a in one} == {128 * 8}
        # ... whose rows filled the short shards first: no shard ends
        # fuller than the level (or than the fullest already was)
        assert len(appended) == niterate - 1
        level = -(-128 * 8 // mesh_axis_size(mesh))
        for had, out in appended:
            assert out.counts.sum() == 128 * 8
            assert out.counts.max() == max(level, had.max())
            assert out.cap == round_cap(int(out.counts.max()))
        # ... and aggregate levels what the hash spread left over it: one
        # capacity in every round, the even split's (as the host route)
        assert {a["cap"] for a in one} == {round_cap(level)}


def test_rmat_serial_backend_reports_its_pull(traced):
    _run_rmat(None, "rmat")
    gen = _spans(traced, names.RMAT_GENERATE)
    assert gen and all(a["d2h_bytes"] == a["rows"] * 16 for a in gen)


def test_rmat_commands_agree_with_generate_unique(mesh, serial_rmat):
    """The same number of unique in-range edges as the host driver of the
    same generator (it accepts first-come over whole batches, so its
    rounds and edge set are its own)."""
    from gpu_mapreduce_tpu.models.rmat import generate_unique
    want, _niter = generate_unique(1, 7, 8, (0.57, 0.19, 0.19, 0.05))
    rows = _run_rmat(mesh, "rmat")[0]
    assert len(rows) == len(want) == len(set(rows)) == 128 * 8
    assert max(max(r) for r in rows) < 128 and int(want.max()) < 128
    assert rows == serial_rmat[0]


def test_a_dropped_script_frees_its_datasets_at_once(mesh):
    """No reference cycle through the script: the device memory of its
    MRs goes with the last reference, not with the cyclic collector
    (a window of fast jobs otherwise piles up their results)."""
    import gc
    import weakref

    from gpu_mapreduce_tpu.oink.objects import _mesh_frame
    from gpu_mapreduce_tpu.oink.script import OinkScript
    s = OinkScript(comm=mesh, screen=io.StringIO())
    s.run_string("rmat 5 4 0.57 0.19 0.19 0.05 0.0 1 -o NULL mre")
    assert s.variables.specials["nprocs"]() == mesh_axis_size(mesh)
    key = weakref.ref(_mesh_frame(s.obj.get_mr("mre")).key)
    assert key() is not None
    gc.disable()
    try:
        del s
        assert key() is None
    finally:
        gc.enable()
