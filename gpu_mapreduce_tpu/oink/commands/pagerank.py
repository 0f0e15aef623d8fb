"""pagerank — damped PageRank over a directed edge list.

The reference names this command but ships an empty iteration body
(``oink/pagerank.cpp:53-56``, SURVEY.md §2.5) — it reads weighted edges,
builds the vertex list, loops ``maxiter`` times doing nothing, and prints
the *edges*.  This implementation supplies the real algorithm from the
composition pattern, backed by the flagship TPU model
(:mod:`gpu_mapreduce_tpu.models.pagerank`): dense ranks, on-device
``lax.while_loop`` convergence, mesh-sharded edges + one ICI psum per
iteration when the ObjectManager carries a mesh.

Where the vertex ranking runs: on a mesh the edge KV is ranked on the
device where it lies (``parallel/staging.stage_graph``, the program
``jit_stage_rank_graph``, as ``cc_find``) and its ranked int32 columns go
straight into the loop; the O(E) columns never reach the host, which
pulls ``n``, the [n] vertex table and the [n] ranks.  On the serial
backend (or where ``stage_graph`` does not apply) the host ranks them
(``stage_graph_host``: ``scan_kv`` + ``np.unique``).  Vertex id 2^64-1 is
the device staging's sentinel: on a mesh it is refused by name.

Script syntax (reference ``PageRank::params``): ``pagerank tol maxiter
alpha``.  Edge weights are accepted in the input ('vi vj [wt]') for
script parity but rank follows link structure only (classic PageRank).
Output: 'v rank' per vertex (``print_vertex_rank``, ``%d %.8g``: a
declared template, so the file is formatted from the two columns);
self.ranks = {v: rank}, a dict made from those columns when first read.
"""

from __future__ import annotations

import functools

import numpy as np

from ...core.runtime import MRError
from ..command import Command, command
from ..kernels import print_vertex_rank, read_edge, read_edge_weight
from ...models.pagerank import (PSUMS_PER_ITERATION, pagerank,
                                pagerank_sharded, pagerank_staged)


def _read_edges_sniff(itask, filename, kv, ptr):
    """'vi vj' or 'vi vj wt' lines → key=[vi,vj], value=NULL — the command
    accepts both the reference's weighted input and plain edge lists."""
    first = []
    with open(filename, "rb") as f:
        for line in f:
            first = line.split()
            if first:
                break
    if len(first) == 3:
        read_edge_weight(itask, filename, kv, ptr)
    else:
        read_edge(itask, filename, kv, ptr)


@command("pagerank")
class PageRankCommand(Command):
    """pagerank tol maxiter alpha (oink/pagerank.cpp:67-75)."""

    ninputs = 1
    noutputs = 1

    def params(self, args):
        if len(args) != 3:
            raise MRError("Illegal pagerank command")
        self.tolerance = float(args[0])
        self.maxiter = int(args[1])
        self.alpha = float(args[2])

    @functools.cached_property
    def ranks(self) -> dict:
        """{vertex: rank} of the last run, made when first read."""
        return dict(zip(self._verts.tolist(), self._ranks.tolist()))

    def run(self):
        obj = self.obj
        mre = obj.input(1, _read_edges_sniff)

        from jax.sharding import Mesh
        mesh = obj.comm if isinstance(obj.comm, Mesh) else None
        # device staging, as cc_find's: the edge KV is ranked where it
        # lies and the ranked columns stay there for the loop; only n and
        # the [n] id table come to the host.  The values (weights, or
        # interned bytes) are not read, so they never decide the path.
        from ...obs import get_tracer, names
        from ...parallel.mesh import allreduce_bytes, mesh_axis_size
        from ...parallel.staging import stage_graph, stage_graph_host
        tr = get_tracer()
        with tr.span(names.PAGERANK_STAGE, cat=names.HOST) as sp:
            try:
                sg = stage_graph(mre, obj.comm)
            except ValueError as e:     # the reserved vertex id 2^64-1
                raise MRError(f"pagerank: {e}") from e
            on_device = sg is not None
            if on_device:
                nedges = int(mre.kv.nkv)
            else:
                sg = stage_graph_host(mre)
                nedges = len(sg.src)
            verts, n = sg.verts, sg.n
            shards = mesh_axis_size(mesh) if mesh is not None else 1
            edge_rows = sg.rows
            sp.set(n=n, edges=nedges, on_device=int(on_device),
                   shards=shards, edge_rows=edge_rows)
            if n == 0:
                raise MRError("pagerank: empty edge list")

        # the fused loop, from dispatch (on the host path: from the
        # edges' transfer) to the pull that ends it
        with tr.span(names.PAGERANK_ENGINE, cat=names.ENGINE, n=n,
                     edges=nedges, edge_rows=edge_rows) as sp:
            params = dict(tol=self.tolerance, maxiter=self.maxiter,
                          damping=self.alpha)
            if on_device:
                ranks, iters = pagerank_staged(mesh, sg.src, sg.dst,
                                               sg.valid, n, **params)
            elif mesh is not None:
                ranks, iters = pagerank_sharded(mesh, sg.src, sg.dst, n,
                                                **params)
            else:
                ranks, iters = pagerank(sg.src, sg.dst, n, **params)
                ranks, iters = np.asarray(ranks), int(iters)
            sp.set(iters=iters, shards=shards,
                   allreduce_bytes=allreduce_bytes(
                       shards, n, iters * PSUMS_PER_ITERATION))

        with tr.span(names.PAGERANK_EMIT, cat=names.HOST, n=n):
            self._verts, self._ranks = verts, ranks.astype(np.float64)
            vars(self).pop("ranks", None)
            self.niterate = iters
            self.nvert = n
            mrr = obj.create_mr()
            mrr.map(1, lambda i, kv, p: kv.add_batch(verts, self._ranks))
        obj.output(1, mrr, print_vertex_rank)
        self.message(f"PageRank: {n} vertices, {nedges} edges, "
                     f"{iters} iterations")
        obj.cleanup()
