"""PageRank vs a dense numpy power-iteration oracle, single-chip and
sharded (8 virtual CPU devices).  The reference ships only the pagerank
skeleton (oink/pagerank.cpp:53-55); these goldens pin our designed-from-
pattern implementation."""

import numpy as np
import pytest

from gpu_mapreduce_tpu.models.pagerank import (
    pagerank, pagerank_sharded, pad_edges_for_mesh)
from gpu_mapreduce_tpu.parallel.mesh import make_mesh


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
