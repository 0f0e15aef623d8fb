"""``parallel/devkernels._pack``: the device mappers bring the rows a kernel
body keeps to the front of their shard by ONE payload sort
(``ops/sort.sort_carrying``, ISSUE 49), where a prefix sum and two scatters
ran.  Held to numpy here: the kept rows in their emission order, zero rows
from the count on, the count as ``int32[1]``, whatever rides the sort and
whatever comes by the sorted row index; then through ``skv_map`` and
``skmv_map`` on a mesh of four devices beside the serial backend."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpu_mapreduce_tpu import MapReduce
from gpu_mapreduce_tpu.oink import kernels
from gpu_mapreduce_tpu.oink.commands import cc
from gpu_mapreduce_tpu.ops import sort as sortops
from gpu_mapreduce_tpu.parallel import devkernels
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
from gpu_mapreduce_tpu.parallel.sharded import ShardedKV

N = 1003                        # not a power of two
WIDE = sortops.RIDE_WORDS + 1

# (dtype, width) of a key or a value block
KEYS = {"u64": ("u8", None), "u64x2": ("u8", 2), "u32x3": ("u4", 3)}
VALUES = {"u8": ("u1", None), "u64": ("u8", None), "u64x2": ("u8", 2),
          "float64_by_index": ("f8", None), "wide_by_index": ("u4", WIDE)}


# which rows a body keeps: name -> (rng, n) -> bool[n]
KEEP = {
    "none": lambda rng, n: np.zeros(n, bool),
    "all": lambda rng, n: np.ones(n, bool),
    "every_other": lambda rng, n: np.arange(n) % 2 == 0,
    "random_1pct": lambda rng, n: rng.random(n) < 0.01,
    "random_50pct": lambda rng, n: rng.random(n) < 0.5,
    "random_99pct": lambda rng, n: rng.random(n) < 0.99,
    "last_row_only": lambda rng, n: np.arange(n) == n - 1,
}


def _block(rng, dtype, width, n=N):
    shape = (n,) if width is None else (n, width)
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return rng.uniform(-5, 5, shape).astype(dt)
    # never zero, so that a zero row can only be a dropped one
    return rng.integers(1, 250, shape).astype(dt)


def _assert_packed(got, ok, ov, valid):
    """``got`` = ``_pack(ok, ov, valid)`` against numpy's indexing."""
    okey, oval, count = (np.asarray(x) for x in got)
    at = np.flatnonzero(valid)
    assert count.dtype == np.int32 and count.shape == (1,)
    assert count[0] == len(at)
    for out, src in ((okey, ok), (oval, ov)):
        assert out.dtype == src.dtype and out.shape == src.shape
        # identical rows in identical order, zero rows from the count on
        assert np.array_equal(out[:len(at)], src[at])
        assert not out[len(at):].any()


_pack = jax.jit(devkernels._pack)


@pytest.mark.parametrize("pattern", KEEP, ids=list(KEEP))
@pytest.mark.parametrize("value", VALUES, ids=list(VALUES))
@pytest.mark.parametrize("key", KEYS, ids=list(KEYS))
def test_pack_equals_numpy(key, value, pattern):
    rng = np.random.default_rng(49)
    ok, ov = _block(rng, *KEYS[key]), _block(rng, *VALUES[value])
    valid = KEEP[pattern](rng, N)
    _assert_packed(_pack(ok, ov, valid), ok, ov, valid)


@pytest.mark.parametrize("value", VALUES, ids=list(VALUES))
def test_pack_rides_what_sort_carrying_lets_ride(value):
    """The branch is ``riding``'s, read off the arrays: a float64 value
    and a row past ``RIDE_WORDS`` come by index, everything else rides."""
    rng = np.random.default_rng(5)
    ok, ov = _block(rng, "u8", 2), _block(rng, *VALUES[value])
    text = _pack.lower(ok, ov, np.ones(N, bool)).as_text()
    by_index = value.endswith("_by_index")
    assert sortops.riding((ok, ov)) == [True, not by_index]
    assert text.count('"stablehlo.sort"') == 1 and "scatter" not in text
    assert text.count('"stablehlo.gather"') == (1 if by_index else 0)


@pytest.mark.parametrize("ndev", [1, 4], ids=["P1", "P4"])
def test_the_edge_upper_mapper_lowers_to_one_sort_and_no_scatter(ndev):
    """The program of the two graph-build cells, ``jit_kv_map_edge_upper``."""
    mesh = make_mesh(ndev)
    SDS = jax.ShapeDtypeStruct
    text = devkernels._skv_map_jit(mesh, devkernels.edge_upper_dev, (), 0).lower(
        SDS((ndev * 64, 2), jnp.uint64), SDS((ndev * 64,), jnp.uint8),
        SDS((ndev,), jnp.int32)).as_text()
    assert "module @jit_kv_map_edge_upper" in text
    assert text.count('"stablehlo.sort"') == 1
    for op in ("scatter", "gather", "cumsum", "reduce_window"):
        assert op not in text, op


@pytest.mark.parametrize("thin", [False, True], ids=["all", "even_keys"])
@pytest.mark.parametrize("count", [0, 1, 37, 64])
def test_pack_behind_a_body_that_emits_two_rows_a_row(count, thin):
    """``edge_both_directions_dev``: ``2n`` rows out of ``n``, the second
    half's emission order behind the first's."""
    n = 64
    k = _block(np.random.default_rng(count), "u8", 2, n)

    def body(k, v, c):
        okey, oval, valid = devkernels.edge_both_directions_dev(k, v, c)
        if thin:
            valid = valid & (okey % 2 == 0)
        return (okey, oval, valid) + devkernels._pack(okey, oval, valid)

    ok, ov, valid, *got = (np.asarray(x) for x in jax.jit(body)(
        k, np.zeros(n, np.uint8), np.int32(count)))
    assert len(valid) == 2 * n
    assert valid.sum() == 2 * count or thin
    _assert_packed(got, ok, ov, valid)


# -- through the mappers, on a mesh of four devices ---------------------------

def _edges(rng, n=203):
    """Edges with self loops and duplicates over a few ids, u64[n, 2]."""
    e = rng.integers(1, 40, (n, 2)).astype(np.uint64)
    e[::9, 1] = e[::9, 0]
    return e


def _edge_mr(comm, e):
    mr = MapReduce(comm)
    mr.map(1, lambda i, kv, p: kv.add_batch(e, np.zeros(len(e), np.uint8)))
    mr.aggregate()
    return mr


def _zoned_mr(comm, e):
    """Edge : zone rows, two an edge, as ``cc_find``'s composition has
    them before ``zone_winner``."""
    mr = MapReduce(comm)
    z = np.concatenate([e[:, 0], np.minimum(e[:, 0], e[:, 1])])
    mr.map(1, lambda i, kv, p: kv.add_batch(np.concatenate([e, e]), z))
    mr.aggregate()
    return mr


def _rows_of(mr, on_mesh: bool):
    """Sorted (key..., value...) rows of the MR's KV dataset; on the mesh
    every frame must be a front-packed ``ShardedKV``: zero rows from each
    shard's count on."""
    rows = []
    for fr in mr.kv.frames():
        assert isinstance(fr, ShardedKV) == on_mesh
        if on_mesh:
            key, value = np.asarray(fr.key), np.asarray(fr.value)
            for p, c in enumerate(fr.counts):
                lo, hi = p * fr.cap, (p + 1) * fr.cap
                assert not key[lo + c:hi].any()
                assert not value[lo + c:hi].any()
        host = fr.to_host() if on_mesh else fr
        k = np.asarray(host.key.to_host().data).reshape(len(host), -1)
        v = np.asarray(host.value.to_host().data).reshape(len(host), -1)
        rows.append(np.concatenate([k.astype(np.float64),
                                    v.astype(np.float64)], 1))
    rows = np.concatenate(rows)
    return rows[np.lexsort(rows.T[::-1])]


def _mapped(comm, source, body):
    src = source(comm, _edges(np.random.default_rng(7)))
    out = MapReduce(comm)
    out.map_mr(src, body, batch=True)
    return out


def _reduced(comm, source, pre, body):
    mr = source(comm, _edges(np.random.default_rng(8)))
    if pre is not None:
        src, mr = mr, MapReduce(comm)
        mr.map_mr(src, pre, batch=True)
    mr.collate()
    mr.reduce(body, batch=True)
    return mr


MAPPERS = {
    # skv_map: rows dropped (self loops), the NULL value riding
    "skv_map-edge_upper": lambda comm: _mapped(
        comm, _edge_mr, kernels.edge_upper),
    # skv_map: 2n rows out, a u64[2n, 3] value at RIDE_WORDS' edge
    "skv_map-edge_vert_tagged": lambda comm: _mapped(
        comm, _edge_mr, cc.edge_vert_tagged),
    # skv_map: a float64 value, by index
    "skv_map-add_weight": lambda comm: _mapped(
        comm, _edge_mr, kernels.add_weight),
    # skmv_map: a row a group
    "skmv_map-self_zone": lambda comm: _reduced(
        comm, _edge_mr, kernels.edge_to_vertices, cc.self_zone),
    # skmv_map: only the groups whose zones differ are kept
    "skmv_map-zone_winner": lambda comm: _reduced(
        comm, _zoned_mr, None, cc.zone_winner),
}


@pytest.mark.parametrize("case", MAPPERS, ids=list(MAPPERS))
def test_mappers_on_a_mesh_equal_the_serial_backend(case):
    serial = _rows_of(MAPPERS[case](None), False)
    meshed = _rows_of(MAPPERS[case](make_mesh(4)), True)
    assert len(serial) > 0
    assert np.array_equal(meshed, serial)
