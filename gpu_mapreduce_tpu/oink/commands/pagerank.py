"""pagerank — damped PageRank over a directed edge list.

The reference names this command but ships an empty iteration body
(``oink/pagerank.cpp:53-56``, SURVEY.md §2.5) — it reads weighted edges,
builds the vertex list, loops ``maxiter`` times doing nothing, and prints
the *edges*.  This implementation supplies the real algorithm from the
composition pattern, backed by the flagship TPU model
(:mod:`gpu_mapreduce_tpu.models.pagerank`): dense ranks, on-device
``lax.while_loop`` convergence, mesh-sharded edges + one ICI psum per
iteration when the ObjectManager carries a mesh.

Script syntax (reference ``PageRank::params``): ``pagerank tol maxiter
alpha``.  Edge weights are accepted in the input ('vi vj [wt]') for
script parity but rank follows link structure only (classic PageRank).
Output: 'v rank' per vertex; self.ranks = {v: rank}.
"""

from __future__ import annotations

import numpy as np

from ...core.runtime import MRError
from ..command import Command, command
from ..kernels import read_edge, read_edge_weight
from ...models.pagerank import pagerank, pagerank_sharded


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

    def run(self):
        obj = self.obj
        mre = obj.input(1, _read_edges_sniff)

        from ...obs import get_tracer, names
        from ...parallel.staging import stage_graph_host
        tr = get_tracer()
        with tr.span(names.PAGERANK_STAGE, cat=names.HOST) as sp:
            # compact arbitrary u64 ids to dense 0..n-1 for the dense-rank
            # model
            sg = stage_graph_host(mre)
            verts, n, src, dst = sg.verts, sg.n, sg.src, sg.dst
            sp.set(n=n, edges=len(src))
            if n == 0:
                raise MRError("pagerank: empty edge list")

        from jax.sharding import Mesh
        mesh = obj.comm if isinstance(obj.comm, Mesh) else None
        # the fused loop, from the edges' transfer to the pull that ends it
        with tr.span(names.PAGERANK_ENGINE, cat=names.ENGINE, n=n,
                     edges=len(src)) as sp:
            if mesh is not None:
                ranks, iters = pagerank_sharded(
                    mesh, src, dst, n, tol=self.tolerance,
                    maxiter=self.maxiter, damping=self.alpha)
            else:
                ranks, iters = pagerank(src, dst, n, tol=self.tolerance,
                                        maxiter=self.maxiter,
                                        damping=self.alpha)
                ranks, iters = np.asarray(ranks), int(iters)
            sp.set(iters=iters)

        with tr.span(names.PAGERANK_EMIT, cat=names.HOST, n=n):
            self.ranks = {int(v): float(r) for v, r in zip(verts, ranks)}
            self.niterate = iters
            self.nvert = n
            mrr = obj.create_mr()
            mrr.map(1, lambda i, kv, p: kv.add_batch(
                verts, ranks.astype(np.float64)))
        obj.output(1, mrr, lambda k, v, fp: fp.write(f"{k} {v:.8g}\n"))
        self.message(f"PageRank: {n} vertices, {len(src)} edges, "
                     f"{iters} iterations")
        obj.cleanup()
