"""parallel/staging.py against ``np.unique(..., return_inverse=True)``
(ISSUE 25): the vertex table and the ranks that come out of the one
payload-carrying sort, over mesh sizes, ``drop_self`` and the inputs
that have bitten before (padding rows in every shard, ids around 2^32
and above 2^63, a single edge); the sentinel refusal and the n == 0
result; the staging programs hold no loop (the binary search of
``searchsorted``, a ``while`` of gathers, is gone and stays gone); and
the host's ranking (``stage_graph_host``) against ``rank_graph``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpu_mapreduce_tpu.core.frame import KVFrame
from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.parallel import staging
from gpu_mapreduce_tpu.parallel.mesh import make_mesh, row_sharding
from gpu_mapreduce_tpu.parallel.sharded import (round_cap, shard_frame,
                                                shard_frame_with_counts)

U64 = np.uint64


def _random(rng, nprocs):
    # few ids, so duplicates and self loops are certain
    e = rng.integers(0, 40, (300, 2)).astype(U64)
    assert (e[:, 0] == e[:, 1]).any()
    return e, None


def _ragged(rng, nprocs):
    # a different count in every shard and none at the capacity's power
    # of two, so padding rows exist in every shard
    counts = np.asarray([3 + 2 * (p % 5) for p in range(nprocs)], np.int32)
    e = rng.integers(0, 25, (int(counts.sum()), 2)).astype(U64)
    return e, counts


def _wide(rng, nprocs):
    base = np.asarray([2 ** 32 - 1, 2 ** 32, 2 ** 32 + 1, 2 ** 63,
                       2 ** 63 + 5, 2 ** 64 - 2, 0, 7], U64)
    e = base[rng.integers(0, len(base), (64, 2))]
    return e, None


def _single(rng, nprocs):
    return np.asarray([[2 ** 63 + 1, 3]], U64), None


INPUTS = {"random": _random, "ragged": _ragged, "wide": _wide,
          "single": _single}


def _frame(mesh, e, counts=None):
    kv = KVFrame(e, np.zeros(len(e), np.uint8))
    if counts is None:
        return shard_frame(kv, mesh)
    return shard_frame_with_counts(kv, mesh, counts)


def _row_mask(fr):
    counts = np.asarray(fr.counts)
    cap = fr.key.shape[0] // len(counts)
    return (np.arange(cap)[None, :] < counts[:, None]).reshape(-1)


@pytest.mark.parametrize("drop_self", [False, True], ids=["keep", "drop"])
@pytest.mark.parametrize("kind", list(INPUTS))
@pytest.mark.parametrize("nprocs", [1, 4, 8])
def test_rank_graph_matches_np_unique(nprocs, kind, drop_self):
    mesh = make_mesh(nprocs)
    e, counts = INPUTS[kind](np.random.default_rng(7), nprocs)
    fr = _frame(mesh, e, counts)
    verts, n, src, dst, valid = staging.rank_graph(fr, drop_self=drop_self)

    keep = np.ones(len(e), bool)
    if drop_self:
        keep = e[:, 0] != e[:, 1]
    want_verts, inv = np.unique(e[keep].reshape(-1), return_inverse=True)
    assert n == len(want_verts)
    # the table: sorted uniques, sentinel-padded to round_cap(n), replicated
    got_verts = np.asarray(verts)
    assert got_verts.dtype == U64 and got_verts.shape == (round_cap(n),)
    np.testing.assert_array_equal(got_verts[:n], want_verts)
    assert (got_verts[n:] == staging.SENTINEL).all()
    assert verts.sharding.is_fully_replicated

    # the edges: int32 ranks and the row mask, laid out as the frame is
    shard = row_sharding(mesh)
    for a in (src, dst, valid):
        assert a.shape == (fr.key.shape[0],)
        assert a.sharding.is_equivalent_to(shard, 1)
    assert src.dtype == jnp.int32 and dst.dtype == jnp.int32
    assert valid.dtype == jnp.bool_
    rows = _row_mask(fr)
    want_valid = rows.copy()
    want_valid[rows] = keep
    np.testing.assert_array_equal(np.asarray(valid), want_valid)
    got = np.stack([np.asarray(src), np.asarray(dst)], 1)
    np.testing.assert_array_equal(got[want_valid], inv.reshape(-1, 2))
    # an invalid row's rank is unspecified, but it indexes an [n + 1]
    # segment table (models/cc._propagate)
    assert got.min() >= 0 and got.max() <= n


@pytest.mark.parametrize("kind", list(INPUTS))
@pytest.mark.parametrize("nprocs", [1, 4])
def test_ranked_columns_feed_the_pagerank_loop(nprocs, kind):
    """``pagerank`` takes ``src``/``dst``/``valid`` as ``rank_graph``
    leaves them (ISSUE 44): self loops and duplicates kept, the padding
    rows of every shard behind the mask with whatever rank they carry.
    The ranks are those of the same edges ranked by ``np.unique``."""
    from gpu_mapreduce_tpu.models.pagerank import pagerank, pagerank_staged
    mesh = make_mesh(nprocs)
    e, counts = INPUTS[kind](np.random.default_rng(11), nprocs)
    fr = _frame(mesh, e, counts)
    # ("wide" fills its shards to the row, as the benchmark's 2^23 edges
    # do; the others leave padding rows)
    assert _row_mask(fr).all() == (kind == "wide")
    _, n, src, dst, valid = staging.rank_graph(fr)
    assert src.dtype == dst.dtype == jnp.int32
    got, iters = pagerank_staged(mesh, src, dst, valid, n, tol=1e-7,
                                 maxiter=200)
    verts, inv = np.unique(e.reshape(-1), return_inverse=True)
    inv = inv.reshape(-1, 2)
    want, want_iters = pagerank(inv[:, 0], inv[:, 1], len(verts), tol=1e-7,
                                maxiter=200)
    assert n == len(verts) and iters == int(want_iters)
    assert np.abs(got - np.asarray(want)).sum() < 1e-6
    assert abs(got.sum() - 1.0) < 1e-5


def test_sentinel_vertex_is_refused():
    mesh = make_mesh(4)
    e = np.asarray([[1, 2], [3, staging.SENTINEL], [2, 5]], U64)
    fr = _frame(mesh, e)
    with pytest.raises(ValueError, match="reserved as the device staging"):
        staging.rank_graph(fr)


@pytest.mark.parametrize("nprocs", [1, 8])
def test_no_vertices_left(nprocs):
    """Only self loops and ``drop_self``: n == 0, and ``stage_graph``
    hands its caller the empty result without ranked columns."""
    mesh = make_mesh(nprocs)
    e = np.asarray([[4, 4], [9, 9], [4, 4]], U64)
    fr = _frame(mesh, e)
    verts, n, _, _, valid = staging.rank_graph(fr, drop_self=True)
    assert n == 0 and not np.asarray(valid).any()
    assert (np.asarray(verts) == staging.SENTINEL).all()

    mr = MapReduce(mesh)
    mr.map(1, lambda i, kv, p: kv.add_batch(e, np.zeros(len(e), np.uint8)))
    sg = staging.stage_graph(mr, mesh, drop_self=True)
    assert sg.n == 0 and sg.verts.shape == (0,) and sg.src is None


def _unranked(verts, src, dst, valid, weights=None):
    """The edge list a staging result stands for, sorted: its valid rows
    with the ranks turned back into ids (and each row's weight)."""
    src, dst, valid = (np.asarray(a) for a in (src, dst, valid))
    cols = [np.asarray(verts)[src[valid]], np.asarray(verts)[dst[valid]]]
    if weights is not None:
        cols.append(np.asarray(weights)[valid].astype(np.float64)
                    .view(U64))
    rows = np.stack(cols, 1) if valid.any() else np.zeros((0, len(cols)),
                                                          U64)
    return rows[np.lexsort(rows.T[::-1])]


def _identity(e):
    """An edge list as a staging result of its own: np.unique's."""
    verts, inv = np.unique(e.reshape(-1), return_inverse=True)
    inv = inv.reshape(-1, 2)
    return verts, inv[:, 0], inv[:, 1], np.ones(len(inv), bool)


_LOOPS = np.asarray([[4, 4], [9, 9], [4, 4]], U64)
HOST_CASES = {
    # id: (edges, drop_self, weighted)
    "plain": (lambda: _random(np.random.default_rng(3), 4)[0], False, False),
    "drop_self": (lambda: _random(np.random.default_rng(4), 4)[0], True,
                  False),
    "weighted": (lambda: _wide(np.random.default_rng(5), 4)[0], False, True),
    "weighted_drop_self": (lambda: _random(np.random.default_rng(6), 4)[0],
                           True, True),
    "self_loops_only": (lambda: _LOOPS, True, False),
    "self_loops_kept": (lambda: _LOOPS, False, False),
}


@pytest.mark.parametrize("case", list(HOST_CASES))
def test_host_ranking_matches_rank_graph(case):
    """The two ways a command's edges are ranked give one graph: the same
    vertex table, the same ``n``, the same edges once the ranks are turned
    back into ids (the device keeps invalid rows behind a mask, the host
    drops them) — and the same weight on each."""
    make, drop_self, weighted = HOST_CASES[case]
    e = make()
    w = (np.arange(len(e), dtype=np.float64) * 0.5 + 1.0 if weighted
         else np.zeros(len(e), np.uint8))
    mesh = make_mesh(4)
    mrs = []
    for comm in (None, mesh):
        mr = MapReduce(comm)
        mr.map(1, lambda i, kv, p: kv.add_batch(e, w))
        mrs.append(mr)
    host = staging.stage_graph_host(mrs[0], drop_self=drop_self,
                                    need_weights=weighted)
    dev = staging.stage_graph(mrs[1], mesh, drop_self=drop_self,
                              need_weights=weighted)
    assert host.n == dev.n == len(host.verts)
    assert host.verts.dtype == U64
    np.testing.assert_array_equal(host.verts, dev.verts)
    for a in (host.src, host.dst, host.valid):
        assert isinstance(a, np.ndarray) and a.shape == host.src.shape
    assert host.valid.all()
    assert (host.weights is None) == (not weighted)
    if host.n == 0:
        assert len(host.src) == 0 and dev.src is None
        return
    np.testing.assert_array_equal(
        _unranked(host.verts, host.src, host.dst, host.valid, host.weights),
        _unranked(dev.verts, dev.src, dev.dst, dev.valid, dev.weights))
    # and both are the input's own rows
    keep = e[:, 0] != e[:, 1] if drop_self else np.ones(len(e), bool)
    np.testing.assert_array_equal(
        _unranked(host.verts, host.src, host.dst, host.valid, host.weights),
        _unranked(*_identity(e[keep]), w[keep] if weighted else None))


@pytest.mark.parametrize("comm", [None, 4], ids=["serial", "mesh"])
def test_host_ranking_of_an_empty_edge_list(comm):
    """No edges at all: ``stage_graph`` does not apply (None) and the
    host's ranking is the empty graph, whatever the backend."""
    mesh = make_mesh(comm) if comm else None
    mr = MapReduce(mesh)
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.zeros((0, 2), U64), np.zeros(0, np.float64)))
    assert staging.stage_graph(mr, mesh, need_weights=True) is None
    sg = staging.stage_graph_host(mr, need_weights=True)
    assert sg.n == 0 and sg.verts.shape == (0,) and sg.verts.dtype == U64
    assert sg.src.shape == sg.dst.shape == sg.valid.shape == (0,)
    assert sg.weights.shape == (0,)


@pytest.mark.parametrize("nprocs", [1, 8])
def test_no_staging_program_loops(nprocs):
    """No ``stablehlo.while`` in any staging program, so none with a
    gather in its body either: the ranks are not searched for."""
    mesh = make_mesh(nprocs)
    sds = jax.ShapeDtypeStruct
    lowered = [
        staging._rank_fn(mesh, 64, drop_self).lower(
            sds((64, 2), jnp.uint64), sds((nprocs,), jnp.int32))
        for drop_self in (False, True)]
    lowered.append(staging._trim_fn(mesh, 8).lower(sds((128,), jnp.uint64)))
    for low in lowered:
        assert "stablehlo.while" not in low.as_text()

    # what the check is there to see: searchsorted is a loop of gathers
    text = jax.jit(jnp.searchsorted).lower(
        sds((8,), jnp.uint64), sds((64,), jnp.uint64)).as_text()
    assert "stablehlo.while" in text and "stablehlo.gather" in text
