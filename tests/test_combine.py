"""The combiner (``MapReduce.compress`` by a registered segment reduce over
a mesh frame of few distinct keys: ``parallel/group.combine_sharded``,
program ``jit_combine``) against ``convert()`` + ``reduce(fn)`` as its
oracle: the same keys in the same order, the same values bit for bit, the
same counts, on CPU meshes of one and four; and the rule that sends a call
down one road or the other, read off the ``compress`` op span."""

import numpy as np
import pytest

from gpu_mapreduce_tpu import MapReduce
from gpu_mapreduce_tpu.core.column import as_column
from gpu_mapreduce_tpu.core.frame import KVFrame
from gpu_mapreduce_tpu.obs import names
from gpu_mapreduce_tpu.ops import reduces
from gpu_mapreduce_tpu.parallel import devkernels, group
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
from gpu_mapreduce_tpu.parallel.sharded import (ShardedKV, round_cap,
                                                shard_frame_with_counts)

EDGE = group.COMBINE_GROUPS
FNS = {"sum": reduces.sum_values, "count": reduces.count,
       "min": reduces.min_values, "max": reduces.max_values}
ROWS = 96           # a shard's


def _mesh(p):
    return make_mesh(p)


def _frame(mesh, key, value, counts=None):
    """``key`` / ``value`` ``[P * rows, ...]``: shard p holds rows
    ``[p * rows, (p + 1) * rows)`` of them."""
    p = int(np.prod(list(mesh.shape.values())))
    counts = (np.full(p, len(key) // p, np.int32) if counts is None
              else np.asarray(counts, np.int32))
    return shard_frame_with_counts(
        KVFrame(as_column(key), as_column(value)), mesh, counts)


def _mr(mesh, frame):
    mr = MapReduce(mesh)
    mr.kv = mr._new_kv()
    mr.kv.add_frame(frame)
    mr.kv.complete()
    return mr


def _pairs(mr):
    """``(counts, [each shard's (key rows, value rows)])`` of mr's KV."""
    fr = mr.kv.one_frame()
    assert isinstance(fr, ShardedKV)
    shards = []
    for p in range(fr.nprocs):
        host = fr.shard_to_host(p)
        shards.append((np.asarray(host.key.data), np.asarray(host.value.data)))
    return fr.counts.copy(), shards


def _same(a, b):
    (ca, sa), (cb, sb) = a, b
    assert ca.tolist() == cb.tolist()
    for (ka, va), (kb, vb) in zip(sa, sb):
        assert ka.dtype == kb.dtype and va.dtype == vb.dtype
        assert ka.tolist() == kb.tolist()
        assert va.tolist() == vb.tolist()


def _both(mesh, make, fn, traced):
    """compress(fn) and convert + reduce(fn) over two frames from
    ``make()``; returns the ``compress`` span's attrs."""
    oracle = _mr(mesh, make())
    oracle.convert()
    oracle.reduce(fn, batch=True)
    mr = _mr(mesh, make())
    n, events = traced(lambda: mr.compress(fn, batch=True))
    _same(_pairs(mr), _pairs(oracle))
    assert n == int(_pairs(oracle)[0].sum())
    (span,) = [e for e in events if e["name"] == names.COMPRESS_SPAN]
    return span["args"]


def _keys(rng, nshards, groups, words):
    """``[nshards * ROWS(, 2)]`` keys, ``groups`` distinct ones a shard
    (every row its own when ``groups`` is ROWS), high words set, each
    shard's from its own range."""
    out = []
    for p in range(nshards):
        ids = rng.permutation(1 << 20)[:groups].astype(np.uint64) \
            + np.uint64(p << 24)
        pick = ids[np.r_[np.arange(groups),
                         rng.integers(0, groups, ROWS - groups)]]
        out.append(rng.permutation(pick))
    k = np.concatenate(out)
    if words == 1:
        return k * np.uint64(0x100000001)       # both halves of a u64 used
    return np.stack([(k >> np.uint64(10)).astype(np.uint32),
                     (k & np.uint64(1023)).astype(np.uint32)], 1)


@pytest.mark.parametrize("nshards", [1, 4])
@pytest.mark.parametrize("groups", [1, 4, EDGE - 1, EDGE, EDGE + 1, ROWS])
@pytest.mark.parametrize("op", sorted(FNS))
def test_the_combiner_is_convert_and_reduce(rng, traced, nshards, groups, op):
    mesh = _mesh(nshards)
    key = _keys(rng, nshards, groups, 2)
    value = rng.integers(-2 ** 62, 2 ** 62, (len(key), 2))
    args = _both(mesh, lambda: _frame(mesh, key, value), FNS[op], traced)
    assert args[names.ATTR_COMBINED] == int(groups <= EDGE)
    assert args[names.ATTR_ROWS] == len(key)
    assert args[names.ATTR_KEY_WORDS] == 2
    assert args[names.ATTR_VALUE_WORDS] == 4
    if groups <= EDGE:
        assert args[names.ATTR_GROUPS] == groups * nshards
        assert 1 <= args[names.ATTR_GROUP_ROWS_MAX] <= ROWS - groups + 1


@pytest.mark.parametrize("tile, sample", [(100, 32), (64, 8), (96, 200)])
@pytest.mark.parametrize("groups", [3, EDGE, EDGE + 1, ROWS])
def test_tiles_that_do_not_divide_the_block_and_a_short_sample(
        rng, traced, monkeypatch, tile, sample, groups):
    """A block of 128 rows in tiles of 100 (the last starts inside the one
    before it and leaves its rows out), 64 and 96; the sample at the head
    that is asked first sees more than the rule's keys (32 rows of 96
    keys: nothing else is probed), or too few to tell (8), or is the
    whole block."""
    monkeypatch.setattr(group, "COMBINE_TILE", tile)
    monkeypatch.setattr(group, "COMBINE_SAMPLE", sample)
    group._combine_jit.cache_clear()
    try:
        mesh = _mesh(4)
        key = _keys(rng, 4, groups, 2)
        value = rng.integers(-2 ** 40, 2 ** 40, (len(key), 3))
        for op in ("sum", "min", "count"):
            args = _both(mesh, lambda: _frame(mesh, key, value), FNS[op],
                         traced)
            assert args[names.ATTR_COMBINED] == int(groups <= EDGE)
            if groups <= EDGE:
                assert args[names.ATTR_GROUPS] == 4 * groups
    finally:
        monkeypatch.undo()
        group._combine_jit.cache_clear()


@pytest.mark.parametrize("dtype", ["int64", "uint32", "int32"])
@pytest.mark.parametrize("width", [0, 1, 2, 3, 4, 5, 6])
def test_every_value_width_sums_exactly(rng, traced, dtype, width):
    """``width`` 0: a one-dimensional value.  Sums wrap as the dtype does
    on both roads; int64 never passes through a float."""
    mesh = _mesh(4)
    key = _keys(rng, 4, 5, 1)
    info = np.iinfo(dtype)
    shape = (len(key),) if width == 0 else (len(key), width)
    value = rng.integers(info.min // 2, info.max // 2, shape).astype(dtype)
    for op in ("sum", "max"):
        args = _both(mesh, lambda: _frame(mesh, key, value), FNS[op], traced)
        assert args[names.ATTR_COMBINED] == 1
        assert args[names.ATTR_KEY_WORDS] == 2      # a u64 is two operands
        assert args[names.ATTR_VALUE_WORDS] == max(width, 1) * (
            np.dtype(dtype).itemsize // 4)


@pytest.mark.parametrize("nshards", [1, 4])
def test_an_empty_frame_combines_to_nothing(traced, nshards):
    mesh = _mesh(nshards)
    key = np.zeros((8 * nshards, 2), np.uint32)
    value = np.zeros((8 * nshards, 3), np.int64)
    make = lambda: _frame(mesh, key, value, counts=[0] * nshards)
    args = _both(mesh, make, reduces.sum_values, traced)
    assert args[names.ATTR_COMBINED] == 1 and args[names.ATTR_GROUPS] == 0


@pytest.mark.parametrize("op", sorted(FNS))
def test_rows_past_the_count_do_not_appear(rng, traced, op):
    """Each shard's count is under its capacity, and the rows past it hold
    other keys (and, for min / max, more extreme values)."""
    mesh = _mesh(4)
    cap = round_cap(ROWS)
    key = np.zeros((4 * cap,), np.uint64)
    value = np.zeros((4 * cap, 2), np.int64)
    counts = [ROWS, 17, 1, 0]
    for p, c in enumerate(counts):
        lo = p * cap
        key[lo:lo + c] = rng.integers(10, 13, c)
        value[lo:lo + c] = rng.integers(-1000, 1000, (c, 2))
        key[lo + c:lo + cap] = rng.integers(0, 10 ** 6, cap - c)
        value[lo + c:lo + cap] = rng.choice([-10 ** 15, 10 ** 15],
                                            (cap - c, 2))

    def make():
        fr = _frame(mesh, key, value)
        fr.counts = np.asarray(counts, np.int32)
        return fr
    args = _both(mesh, make, FNS[op], traced)
    assert args[names.ATTR_COMBINED] == 1
    assert args[names.ATTR_ROWS] == sum(counts)
    assert args[names.ATTR_GROUPS] <= 3 * 3


def test_a_key_on_one_shard_only(rng, traced):
    mesh = _mesh(4)
    key = np.tile(np.arange(3, dtype=np.uint64), 4 * ROWS // 3)
    key[ROWS + 5] = 99          # shard 1 alone holds it, once
    value = rng.integers(0, 100, len(key))
    args = _both(mesh, lambda: _frame(mesh, key, value), reduces.count,
                 traced)
    assert args[names.ATTR_GROUPS] == 3 * 4 + 1
    mr = _mr(mesh, _frame(mesh, key, value))
    mr.compress(reduces.sum_values, batch=True)
    counts, shards = _pairs(mr)
    assert counts.tolist() == [3, 4, 3, 3]
    assert shards[1][0].tolist() == [0, 1, 2, 99]
    assert shards[1][1][3] == value[ROWS + 5]


def test_the_output_is_local_to_a_shard(rng):
    """Nothing is exchanged: a key that several shards hold comes out of
    each of them (``collate`` + ``reduce`` merge them afterwards)."""
    mesh = _mesh(4)
    key = np.full(4 * ROWS, 7, np.uint64)
    mr = _mr(mesh, _frame(mesh, key, np.ones(len(key), np.int64)))
    assert mr.compress(reduces.sum_values, batch=True) == 4
    counts, shards = _pairs(mr)
    assert counts.tolist() == [1, 1, 1, 1]
    assert [s[1].tolist() for s in shards] == [[ROWS]] * 4
    mr.collate()
    assert mr.reduce(reduces.sum_values, batch=True) == 1
    assert sum(s[1].sum() for s in _pairs(mr)[1]) == 4 * ROWS


def _forbid_the_combiner(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("the combiner ran")
    monkeypatch.setattr(group, "combine_sharded", refuse)


@pytest.mark.parametrize("what", ["float", "cull", "callback", "ptr",
                                  "not_batch", "serial", "fused"])
def test_everything_else_takes_the_convert_road(rng, traced, monkeypatch,
                                                what):
    """A float value, ``cull``, a user callback (with or without ``ptr``:
    ``sssp``'s two ``compress`` calls), the per-group host tier, a host
    frame and a recorded plan are ``convert`` + ``reduce`` as they were;
    the op span carries no ``combined`` 1."""
    _forbid_the_combiner(monkeypatch)
    mesh = None if what == "serial" else _mesh(4)
    n = 4 * ROWS
    key = rng.integers(0, 3, n).astype(np.uint64)
    value = rng.integers(0, 100, n)
    if what == "float":
        value = value.astype(np.float64)
    seen = []

    def callback(fr, kv, ptr):
        seen.append(ptr)
        reduces.sum_values(fr, kv)
    fn, kw = reduces.sum_values, {"batch": True}
    if what == "cull":
        fn = reduces.cull
    elif what in ("callback", "ptr"):
        fn = callback
        kw["ptr"] = "open" if what == "ptr" else None
    elif what == "not_batch":
        fn, kw = (lambda k, vals, kv, ptr: kv.add(k, sum(vals))), {}

    def make():
        if mesh is None:
            mr = MapReduce()
            mr.map(1, lambda i, kv, p: kv.add_batch(key, value))
            return mr
        return _mr(mesh, _frame(mesh, key, value))
    oracle = make()
    oracle.convert()
    oracle.reduce(fn, **kw)
    mr = make()
    if what == "fused":
        mr.settings.fuse = 1
    n_out, events = traced(lambda: int(mr.compress(fn, **kw)))
    assert n_out == oracle.kv.nkv
    got, want = [], []
    mr.scan_kv(lambda k, v, p: got.append((int(k), float(v))))
    oracle.scan_kv(lambda k, v, p: want.append((int(k), float(v))))
    assert sorted(got) == sorted(want) and len(got) in (3, 12)
    (span,) = [e for e in events if e["name"] == names.COMPRESS_SPAN]
    assert not span["args"].get(names.ATTR_COMBINED)
    ran = [e["name"] for e in events]
    assert ("plan.execute" if what == "fused" else names.CONVERT_SPAN) in ran
    if what == "ptr":
        assert set(seen) == {"open"}


def test_many_keys_say_combined_0_and_sort(rng, traced):
    mesh = _mesh(4)
    key = _keys(rng, 4, EDGE + 1, 1)
    value = rng.integers(0, 9, len(key))
    mr = _mr(mesh, _frame(mesh, key, value))
    _, events = traced(lambda: mr.compress(reduces.sum_values, batch=True))
    by = {e["name"]: e["args"] for e in events}
    assert by[names.COMPRESS_SPAN][names.ATTR_COMBINED] == 0
    assert names.ATTR_GROUPS not in by[names.COMPRESS_SPAN]
    assert by[names.COMBINE_COUNT_SYNC]["groups"] == 4 * (EDGE + 1)
    assert by[names.CONVERT_SPAN][names.ATTR_GROUPS] == 4 * (EDGE + 1)


# -- a deferred scan (devkernels.skv_keep) -------------------------------------

def _odd_rows_dev(k, v, c, bound):
    """Rows under ``bound`` in their first value column, keyed by that
    column's parity, their value both columns widened."""
    import jax.numpy as jnp
    keep = (v[:, 0] < bound) & (jnp.arange(k.shape[0]) < c)
    return v[:, 0] % 2, v.astype(jnp.int64) * 3, keep


@pytest.mark.parametrize("nshards", [1, 4])
def test_a_deferred_scan_is_folded_where_the_rows_lie(rng, traced, nshards,
                                                      monkeypatch):
    import jax.numpy as jnp
    mesh = _mesh(nshards)
    n = nshards * ROWS
    key = rng.integers(0, 10 ** 6, n).astype(np.uint64)
    value = rng.integers(0, 1000, (n, 2)).astype(np.uint32)
    table = _frame(mesh, key, value)
    before = (np.asarray(table.key).copy(), np.asarray(table.value).copy())
    bound = (jnp.uint32(600),)
    oracle = _mr(mesh, devkernels.skv_scan(table, _odd_rows_dev, extra=bound))
    kept = len(oracle.kv.one_frame())
    assert kept == int((value[:, 0] < 600).sum())
    oracle.convert()
    oracle.reduce(reduces.sum_values, batch=True)
    # the deferred frame counts as the scan's result and is never made
    scanned = devkernels.skv_keep(table, _odd_rows_dev, extra=bound)
    assert isinstance(scanned, devkernels.ScannedKV)
    assert len(scanned) == kept and scanned.nbytes() == 0
    monkeypatch.setattr(devkernels, "skv_scan", None)
    mr = _mr(mesh, scanned)
    assert mr.kv.nkv == kept
    _, events = traced(lambda: mr.compress(reduces.sum_values, batch=True))
    _same(_pairs(mr), _pairs(oracle))
    (span,) = [e for e in events if e["name"] == names.COMPRESS_SPAN]
    assert span["args"][names.ATTR_COMBINED] == 1
    assert span["args"][names.ATTR_ROWS] == kept
    assert span["args"][names.ATTR_VALUE_WORDS] == 4
    assert (np.asarray(table.key) == before[0]).all()
    assert (np.asarray(table.value) == before[1]).all()


def test_a_deferred_scan_read_row_by_row_is_the_scan(rng):
    """Any reader but the combiner gets the plain frame ``skv_scan``
    makes, once."""
    import jax.numpy as jnp
    mesh = _mesh(4)
    n = 4 * ROWS
    table = _frame(mesh, rng.integers(0, 99, n).astype(np.uint64),
                   rng.integers(0, 1000, (n, 2)).astype(np.uint32))
    bound = (jnp.uint32(500),)
    plain = devkernels.skv_scan(table, _odd_rows_dev, extra=bound)
    scanned = devkernels.skv_keep(table, _odd_rows_dev, extra=bound)
    assert scanned.cap == table.cap
    mr = _mr(mesh, scanned)
    mr.sort_values(1)           # not the combiner: the rows are made
    oracle = _mr(mesh, plain)
    oracle.sort_values(1)
    _same(_pairs(mr), _pairs(oracle))
    assert scanned.scan is None and scanned.nbytes() > 0
    assert scanned.cap == plain.cap


def test_the_rules_vocabulary():
    assert group.COMBINE_OPS == ("sum", "count", "min", "max")
    assert {f.segment_op for f in FNS.values()} == set(group.COMBINE_OPS)
    assert not hasattr(reduces.cull, "segment_op")
    assert EDGE <= round_cap(EDGE) and EDGE >= 4
