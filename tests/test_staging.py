"""parallel/staging.py against ``np.unique(..., return_inverse=True)``
(ISSUE 25): the vertex table and the ranks that come out of the one
payload-carrying sort, over mesh sizes, ``drop_self`` and the inputs
that have bitten before (padding rows in every shard, ids around 2^32
and above 2^63, a single edge); the sentinel refusal and the n == 0
result; and the staging programs hold no loop (the binary search of
``searchsorted``, a ``while`` of gathers, is gone and stays gone)."""

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
