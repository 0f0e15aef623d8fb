"""PageRank vs a dense numpy power-iteration oracle, single-chip and
sharded (8 virtual CPU devices).  The reference ships only the pagerank
skeleton (oink/pagerank.cpp:53-55); these goldens pin our designed-from-
pattern implementation."""

import jax
import numpy as np
import pytest

from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.core.runtime import MRError
from gpu_mapreduce_tpu.models.pagerank import (
    pagerank, pagerank_sharded, pagerank_staged, pad_edges_for_mesh)
from gpu_mapreduce_tpu.oink import ObjectManager, run_command
from gpu_mapreduce_tpu.parallel import staging
from gpu_mapreduce_tpu.parallel.mesh import make_mesh, row_sharding


def dense_oracle(src, dst, n, damping=0.85, iters=200):
    A = np.zeros((n, n))
    for a, b in zip(src, dst):
        A[a, b] += 1.0
    deg = A.sum(1)
    # rows of dangling vertices stay 0 (without ``out`` they are whatever
    # the allocator left there: a NaN now and then)
    P = np.divide(A, deg[:, None], out=np.zeros_like(A),
                  where=deg[:, None] > 0)
    x = np.full(n, 1.0 / n)
    for _ in range(iters):
        dangling = x[deg == 0].sum()
        x = (1 - damping) / n + damping * (P.T @ x + dangling / n)
    return x


@pytest.fixture
def graph(rng):
    n = 50
    src = rng.integers(0, n, 400).astype(np.int32)
    dst = rng.integers(0, n, 400).astype(np.int32)
    return src, dst, n


def test_pagerank_matches_dense_oracle(graph):
    src, dst, n = graph
    ranks, iters = pagerank(src, dst, n, tol=1e-7, maxiter=200)
    ranks = np.asarray(ranks)
    want = dense_oracle(src, dst, n)
    np.testing.assert_allclose(ranks, want, atol=1e-5)
    np.testing.assert_allclose(ranks.sum(), 1.0, rtol=1e-4)
    assert 1 <= int(iters) <= 200


def test_pagerank_with_dangling_vertices():
    # vertex 3 is dangling (never a source); chain 0->1->2->3
    src = np.array([0, 1, 2], np.int32)
    dst = np.array([1, 2, 3], np.int32)
    ranks, _ = pagerank(src, dst, 4, tol=1e-7, maxiter=300)
    want = dense_oracle(src, dst, 4, iters=300)
    np.testing.assert_allclose(np.asarray(ranks), want, atol=1e-5)


def test_pagerank_sharded_matches_single_chip(graph):
    src, dst, n = graph
    mesh = make_mesh(8)
    got, _ = pagerank_sharded(mesh, src, dst, n, tol=1e-7, maxiter=200)
    want = dense_oracle(src, dst, n)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_pad_edges_for_mesh():
    src = np.arange(5, dtype=np.int32)
    dst = np.arange(5, dtype=np.int32)
    s, d, v = pad_edges_for_mesh(src, dst, 4)
    assert len(s) == len(d) == len(v) == 8
    assert v.sum() == 5 and v[:5].all() and not v[5:].any()


# ---------------------------------------------------------------------------
# the command stages on the device (ISSUE 44): on a mesh the edge KV is ranked
# by ``stage_graph`` where it lies and the loop takes the ranked columns as
# they are; the host path is the serial backend's, and the reference here
# ---------------------------------------------------------------------------

U64 = np.uint64
MESHES = pytest.mark.parametrize("nprocs", [1, 4])
PARAMS = ["1e-6", "100", "0.85"]
DANGLING = 99


def _nasty_edges():
    """Everything the staging has to keep: duplicate edges, self loops, a
    vertex that is never a source, ids above 2^32 and 2^63, and 37 rows:
    no shard of 1 or 4 is filled to its capacity (a power of two)."""
    rng = np.random.default_rng(7)
    ids = np.asarray([0, 3, 7, 2 ** 32 - 1, 2 ** 32, 2 ** 32 + 9,
                      2 ** 40 + 1, 2 ** 63 + 5, 11, 12], U64)
    e = ids[rng.integers(0, len(ids), (29, 2))]
    e = np.concatenate([
        e, e[:4], np.asarray([[7, 7], [2 ** 32, 2 ** 32]], U64),
        np.asarray([[3, DANGLING], [2 ** 40 + 1, DANGLING]], U64)])
    assert len(e) == 37 and len(np.unique(e, axis=0)) < len(e)
    assert (e[:, 0] == e[:, 1]).any() and DANGLING not in e[:, 0]
    return e


def _edge_mr(comm, e, values=None):
    mr = MapReduce(comm)
    v = np.zeros(len(e), np.uint8) if values is None else values
    mr.map(1, lambda i, kv, p: kv.add_batch(e, v))
    if comm is not None:
        mr.aggregate()          # mesh-resident, as rmat leaves its edges
    return mr


def _run(comm, source):
    return run_command("pagerank", PARAMS, obj=ObjectManager(comm=comm),
                       inputs=[source], screen=False)


def _ranks(cmd, verts):
    return np.asarray([cmd.ranks[int(v)] for v in verts])


def _no_host(monkeypatch):
    """The O(E) columns may not reach the host: any scan of a KV and the
    host's ranking fail the test."""
    def refuse(*_a, **_k):
        raise AssertionError("the edge list was pulled to the host")
    monkeypatch.setattr(MapReduce, "scan_kv", refuse)
    monkeypatch.setattr(staging, "stage_graph_host", refuse)


@MESHES
def test_staged_loop_masks_padding_rows(nprocs):
    """Rows behind the mask add nothing, whatever rank they carry in
    [0, n] (``rank_graph``'s padding rows; until ISSUE 44 the loop only
    saw a mask of ones up to a multiple of the mesh size)."""
    rng = np.random.default_rng(3)
    n, m = 23, 64
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    dst[src == 5] = 6               # a vertex of one target ...
    src[src == 6] = 5               # ... that is never a source
    valid = rng.random(m) < 0.6
    valid[:4] = False               # a shard's first rows, and its last
    valid[-4:] = True
    junk = rng.integers(0, n + 1, m).astype(np.int32)
    junk[np.flatnonzero(~valid)[:3]] = n    # one past the table, too
    s, d = np.where(valid, src, junk), np.where(valid, dst, junk[::-1])
    mesh = make_mesh(nprocs)
    s, d, v = (jax.device_put(a, row_sharding(mesh)) for a in (s, d, valid))
    got, iters = pagerank_staged(mesh, s, d, v, n, tol=1e-7, maxiter=200)
    want, want_iters = pagerank(src[valid], dst[valid], n, tol=1e-7,
                                maxiter=200)
    assert np.abs(got - np.asarray(want)).sum() < 1e-6
    assert iters == int(want_iters)
    np.testing.assert_allclose(
        got, dense_oracle(src[valid], dst[valid], n), atol=1e-5)


@MESHES
def test_command_on_a_mesh_stages_on_the_device(nprocs, monkeypatch):
    """Over a mesh-resident edge MR the command pulls no edge column, and
    its ranks are the host-staged path's: the same mesh with
    ``stage_graph`` declining, and the serial backend."""
    e = _nasty_edges()
    verts = np.unique(e)
    mesh = make_mesh(nprocs)
    with monkeypatch.context() as mp:
        mp.setattr(staging, "stage_graph", lambda *a, **k: None)
        host_mesh = _run(mesh, _edge_mr(mesh, e))
    serial = _run(None, _edge_mr(None, e))

    mr = _edge_mr(mesh, e)
    fr = staging.mesh_kv_frame(mr)
    cap = fr.key.shape[0] // nprocs
    assert (np.asarray(fr.counts) < cap).all()      # padding in every shard
    _no_host(monkeypatch)
    dev = _run(mesh, mr)

    assert dev.nvert == len(verts) and set(dev.ranks) == set(verts.tolist())
    got = _ranks(dev, verts)
    for ref in (host_mesh, serial):
        assert np.abs(got - _ranks(ref, verts)).sum() < 1e-6
        assert dev.niterate == ref.niterate > 1
    assert abs(got.sum() - 1.0) < 1e-5
    # the dangling vertex holds rank and hands it on evenly
    inv = np.searchsorted(verts, e)
    np.testing.assert_allclose(
        got, dense_oracle(inv[:, 0], inv[:, 1], len(verts)), atol=1e-5)


def test_serial_comm_takes_the_host_path(monkeypatch):
    e = _nasty_edges()
    calls = []
    host = staging.stage_graph_host
    monkeypatch.setattr(staging, "stage_graph_host",
                        lambda mr, **k: calls.append(k) or host(mr, **k))

    def refuse(*_a, **_k):
        raise AssertionError("a device ranking without a mesh")
    monkeypatch.setattr(staging, "rank_graph", refuse)
    cmd = _run(None, _edge_mr(None, e))
    assert calls == [{}]
    verts = np.unique(e)
    inv = np.searchsorted(verts, e)
    np.testing.assert_allclose(
        _ranks(cmd, verts),
        dense_oracle(inv[:, 0], inv[:, 1], len(verts)), atol=1e-5)


@MESHES
def test_weighted_file_gives_the_plain_files_ranks(nprocs, tmp_path,
                                                   monkeypatch):
    """``vi vj wt`` (the reference's ``in.pagerank``) and ``vi vj``: the
    weights are the frame's value and are not read, on the device path."""
    e = _nasty_edges()
    plain, weighted = tmp_path / "plain.txt", tmp_path / "weighted.txt"
    plain.write_text("".join(f"{a} {b}\n" for a, b in e.tolist()))
    weighted.write_text("".join(
        f"{a} {b} {0.5 + i}\n" for i, (a, b) in enumerate(e.tolist())))
    mesh = make_mesh(nprocs)
    _no_host(monkeypatch)
    a, b = _run(mesh, str(plain)), _run(mesh, str(weighted))
    verts = np.unique(e)
    assert a.niterate == b.niterate
    assert np.abs(_ranks(a, verts) - _ranks(b, verts)).sum() < 1e-6


@MESHES
def test_interned_values_do_not_send_the_command_to_the_host(nprocs,
                                                             monkeypatch):
    """Byte values shard as interned ids (``value_decode``): ``sssp``
    must decline them, ``pagerank`` reads no value and does not."""
    e = _nasty_edges()
    mesh = make_mesh(nprocs)
    mr = MapReduce(mesh)
    mr.map(1, lambda i, kv, p: [kv.add(row, b"label-%d" % (j % 3))
                                for j, row in enumerate(e)])
    mr.aggregate()
    assert staging.mesh_kv_frame(mr).value_decode is not None
    assert staging.stage_graph(mr, mesh, need_weights=True) is None
    want = _run(None, _edge_mr(None, e))
    _no_host(monkeypatch)
    got = _run(mesh, mr)
    verts = np.unique(e)
    assert got.niterate == want.niterate
    assert np.abs(_ranks(got, verts) - _ranks(want, verts)).sum() < 1e-6


@pytest.mark.parametrize("nprocs", [None, 1, 4],
                         ids=["serial", "mesh1", "mesh4"])
def test_empty_edge_list_keeps_its_words(nprocs):
    comm = make_mesh(nprocs) if nprocs else None
    with pytest.raises(MRError, match="pagerank: empty edge list"):
        _run(comm, _edge_mr(comm, np.zeros((0, 2), U64)))


@MESHES
def test_reserved_vertex_id_is_an_mrerror_naming_it(nprocs):
    e = np.concatenate([_nasty_edges(),
                        np.asarray([[3, staging.SENTINEL]], U64)])
    mesh = make_mesh(nprocs)
    with pytest.raises(MRError, match="pagerank: vertex id "
                       "18446744073709551615 is reserved") as err:
        _run(mesh, _edge_mr(mesh, e))
    assert isinstance(err.value.__cause__, ValueError)
