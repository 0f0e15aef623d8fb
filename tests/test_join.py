"""``MapReduce.join``: the keyed inner join of two datasets, held to a
plain dict join (``benchmark/refs/tpch.join``) on every backend the
library has, and its device programs held to the design
(``doc/internals.md``): two sorts that carry nothing, then four gathers of
the joined rows alone; no scatter, no ``while``."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.refs import tpch as ref
from gpu_mapreduce_tpu import MapReduce, MRError
from gpu_mapreduce_tpu.obs import get_tracer, names
from gpu_mapreduce_tpu.parallel import group
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
from gpu_mapreduce_tpu.parallel.sharded import ShardedKV, SyncStats

BACKENDS = ["serial", "mesh1", "mesh4"]
KEYS = ["u64", "u32x3"]
SIZES = ["empty_probe", "empty_build", "no_match", "all_match", "partial",
         "garbage"]


def _comm(backend):
    return {"serial": None, "mesh1": make_mesh(1),
            "mesh4": make_mesh(4)}[backend]


def _keys(kind, ids):
    """Key rows of the numbers ``ids``: u64[n], or three u32 words that
    differ from row to row in every word."""
    ids = np.asarray(ids, np.uint64)
    if kind == "u64":
        return ids * np.uint64(0x9E3779B97F4A7C15) >> np.uint64(3)
    return np.stack([ids % 5, ids // 7, ids], 1).astype(np.uint32)


def _sides(kind, size, wp, wb, seed):
    rng = np.random.default_rng(seed)
    nb, n = 40, 300
    build = rng.permutation(200)[:nb]
    probe = {"no_match": rng.integers(200, 400, n),
             "all_match": rng.choice(build, n)}.get(
                 size, rng.integers(0, 200, n))
    if size == "empty_probe":
        probe = probe[:0]
    if size == "empty_build":
        build = build[:0]
    words = lambda m, w: rng.integers(0, 1 << 32, (m, w), dtype=np.uint32)
    return (_keys(kind, probe), words(len(probe), wp),
            _keys(kind, build), words(len(build), wb))


def _mr(comm, key, value):
    mr = MapReduce(comm)
    mr.open()
    mr.kv.add_batch(key, value)
    mr.close()
    if comm is not None and len(key):
        mr.aggregate()          # onto the mesh (on one shard: placed)
    return mr


def _rows(mr):
    """(key tuple, value tuple) of every pair, and the frame."""
    fr = mr.kv.one_frame()
    host = fr.to_host()
    k = np.asarray(host.key.to_host().data)
    v = np.asarray(host.value.to_host().data)
    return [(tuple(np.atleast_1d(a).tolist()), tuple(np.atleast_1d(b).tolist()))
            for a, b in zip(k, v)], fr


def _tuples(a):
    return [tuple(np.atleast_1d(r).tolist()) for r in a]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("wb", [1, 3])
@pytest.mark.parametrize("wp", [1, 4, 9])
@pytest.mark.parametrize("kind", KEYS)
def test_join_equals_the_dict_join(kind, wp, wb, size, backend):
    comm = _comm(backend)
    pk, pv, bk, bv = _sides(kind, size, wp, wb, seed=wp * 10 + wb)
    probe, build = _mr(comm, pk, pv), _mr(comm, bk, bv)
    if size == "garbage" and comm is not None:
        # rows past the count hold keys of the other side: they are no rows
        for mr, other in ((probe, bk), (build, pk)):
            fr = mr.kv.one_frame()
            counts = fr.counts.copy()
            counts[0] -= min(2, counts[0])
            mr.kv._frames = [ShardedKV(fr.mesh, fr.key, fr.value, counts)]
            mr.kv.nkv = int(counts.sum())
        (prow, _), (brow, _) = _rows(probe), _rows(build)
    else:
        prow = list(zip(_tuples(pk), _tuples(pv)))
        brow = list(zip(_tuples(bk), _tuples(bv)))
    before, _ = _rows(build)
    want = ref.join([k for k, _ in prow], [v for _, v in prow],
                    [k for k, _ in brow], [v for _, v in brow])
    pulls = SyncStats.snapshot()
    n = probe.join(build)
    got, fr = _rows(probe)
    assert n == len(want) == len(got)
    assert sorted(got) == sorted(want)
    # rows of one key keep the probe's order
    for key in {k for k, _ in want[:20]}:
        assert [v for k, v in got if k == key] == [
            v for k, v in want if k == key]
    assert _rows(build)[0] == before          # the build side as it was
    if comm is not None and len(pk) and len(bk):
        # one program a shard and one pull; on four shards each side's
        # exchange has its own count sync
        assert SyncStats.delta(pulls) == (1 if backend == "mesh1" else 3)
        assert isinstance(fr, ShardedKV)
        assert fr.value.shape[1:] == (wp + wb,)


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_build_key_that_occurs_twice_is_an_error(backend):
    comm = _comm(backend)
    pk, pv, bk, bv = _sides("u64", "partial", 2, 2, seed=3)
    bk[7] = bk[30]
    probe, build = _mr(comm, pk, pv), _mr(comm, bk, bv)
    before, _ = _rows(probe)
    with pytest.raises(MRError, match="occur more than once"):
        probe.join(build)
    assert _rows(probe)[0] == before          # nothing was chosen


@pytest.mark.parametrize("backend", BACKENDS)
def test_sides_of_different_keys_or_values_are_refused(backend):
    comm = _comm(backend)
    pk, pv, bk, bv = _sides("u64", "partial", 2, 2, seed=4)
    other, _, _, _ = _sides("u32x3", "partial", 2, 2, seed=4)
    with pytest.raises(MRError, match="keys differ"):
        _mr(comm, other, pv).join(_mr(comm, bk, bv))
    with pytest.raises(MRError, match="values differ"):
        _mr(comm, pk, pv).join(_mr(comm, bk, bv.astype(np.int64)))
    with pytest.raises(MRError, match="fixed-width"):
        words = MapReduce(comm)
        words.open()
        words.kv.add(b"a", 1)
        words.close()
        words.join(_mr(comm, bk, bv))
    with pytest.raises(MRError, match="without completed KeyValue"):
        MapReduce(comm).join(_mr(comm, bk, bv))


@pytest.mark.parametrize("backend", ["mesh1", "mesh4"])
def test_a_resident_probe_source_is_left_bit_for_bit(backend):
    """A table that maps its rows into the probe side (``map_mr``) and a
    build side that is joined against twice: the arrays of both are the
    same arrays, with the same bits, after two joins."""
    comm = _comm(backend)
    pk, pv, bk, bv = _sides("u32x3", "partial", 4, 3, seed=5)
    table, build = _mr(comm, pk, pv), _mr(comm, bk, bv)
    frames = [mr.kv.one_frame() for mr in (table, build)]
    bits = [(np.asarray(f.key).copy(), np.asarray(f.value).copy())
            for f in frames]
    for _ in range(2):
        probe = MapReduce(comm)
        probe.map_mr(table, lambda fr, kv, ptr: kv.add_frame(fr), batch=True)
        assert probe.join(build) > 0
    for mr, f, (k, v) in zip((table, build), frames, bits):
        now = mr.kv.one_frame()
        assert now is f and not f.key.is_deleted()
        np.testing.assert_array_equal(np.asarray(now.key), k)
        np.testing.assert_array_equal(np.asarray(now.value), v)


def test_the_join_span_says_its_rows_words_and_bytes():
    comm = make_mesh(1)
    pk, pv, bk, bv = _sides("u64", "partial", 4, 3, seed=6)
    probe, build = _mr(comm, pk, pv), _mr(comm, bk, bv)
    tracer = get_tracer()
    tracer.enable()
    try:
        tracer.clear()
        n = probe.join(build)
        (span,) = [e for e in tracer.events()
                   if e["name"] == names.JOIN_SPAN]
    finally:
        tracer.disable()
    a = span["args"]
    assert (a[names.ATTR_PROBE_ROWS], a[names.ATTR_BUILD_ROWS],
            a[names.ATTR_MATCHED_ROWS]) == (300, 40, n)
    # a u64 key is two operands and the rows' tag a third; nothing rides
    # the sort: the seven value words are taken for the joined rows
    assert (a[names.ATTR_KEY_WORDS], a[names.ATTR_RODE_WORDS],
            a[names.ATTR_TAKEN_WORDS]) == (3, 0, 7)
    assert a[names.ATTR_HBM_ROW_BYTES] > 0
    assert a["join_in_bytes"] > a["join_out_bytes"] > 0
    assert span["cat"] == "mr_op"


# -- the program --------------------------------------------------------------

def _ops(text):
    return re.findall(r'stablehlo\.(scatter|gather|while|sort)"?\(', text)


SDS = jax.ShapeDtypeStruct
# Q3's two joins (two key words; 4 + 1 and 2 + 2 value words), a u64 key,
# and values past RIDE_WORDS: the programs are the same whatever the values
PROGRAM_SHAPES = [("orders_customer", ("u4", 2), 4, 1),
                  ("lineitem_orders", ("u4", 2), 2, 2),
                  ("u64_key", ("u8", None), 3, 3),
                  ("wide_row", ("u4", 3), 9, 3)]


@pytest.mark.parametrize("P", [1, 4], ids=["mesh1", "mesh4"])
@pytest.mark.parametrize("shape", PROGRAM_SHAPES,
                         ids=[s[0] for s in PROGRAM_SHAPES])
def test_join_lowers_to_two_bare_sorts_and_four_small_gathers(shape, P):
    """The chip's rule (PERF.md §6, PRs 25-43) for the join: both sides'
    keys ordered by ONE sort that carries nothing (a sort's compile grows
    with its operands), the joined rows' positions brought to the front by
    a second sort of one operand, and every value taken afterwards, for
    the joined rows alone; no scatter, no ``while`` (a ``searchsorted`` is
    a gather a round)."""
    mesh = make_mesh(P)
    _, (kdtype, kw), wp, wb = shape
    key = lambda n: SDS((P * n,) if kw is None else (P * n, kw), kdtype)
    val = lambda n, w: SDS((P * n, w), jnp.uint32)
    cnt, idx = SDS((P,), jnp.int32), SDS((P * 80,), jnp.int32)
    text = group._join_jit(mesh).lower(key(64), cnt, key(16), cnt).as_text()
    assert re.search(r"module @(\w+)", text).group(1) == names.JOIN_ROWS
    assert sorted(_ops(text)) == ["sort", "sort"]
    operands = [len(re.findall(r"%arg\d+: tensor", m)) // 2 for m in
                re.findall(r"stablehlo\.sort.*?\}\) :", text, re.S)]
    assert sorted(operands) == [1, (1 if kw is None else kw) + 1]
    text = group._join_take_jit(mesh, 8).lower(
        idx, SDS((P * 80, 2), jnp.int32), key(64), val(64, wp),
        val(16, wb)).as_text()
    assert re.search(r"module @(\w+)", text).group(1) == names.JOIN_TAKE
    # four takes (jax outlines those of one shape into one function)
    assert set(_ops(text)) == {"gather"} and len(re.findall(
        r"call @_take|stablehlo\.gather\"", text)) >= 4
