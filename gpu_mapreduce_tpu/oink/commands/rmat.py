"""rmat / rmat2 — R-MAT matrix generation commands.

Reference: ``oink/rmat.cpp:37-96`` (generate → collate → cull loop until
2^N·Nz unique edges) and ``oink/rmat2.cpp:36-76`` (variant that aggregates
each round into a separate MR and ``add``s it into the accumulator —
demonstrating the aggregate/convert decomposition).  Generation itself is
the vectorised device kernel ``models/rmat.py`` instead of the reference's
serial drand48 walk."""

from __future__ import annotations

import jax
import numpy as np

from ...core.frame import KVFrame
from ...core.runtime import MRError
from ...models.rmat import rmat_edge_rows, rmat_edges
from ...obs import get_tracer, names
from ...parallel.mesh import mesh_axis_size, replicated
from ...parallel.sharded import fill_counts, place_rows
from ..command import Command, command
from ..kernels import cull, print_edge


class _RmatBase(Command):
    noutputs = 1

    def params(self, args):
        if len(args) != 8:
            raise MRError(f"Illegal {self.name} command")
        self.nlevels = int(args[0])
        self.nnonzero = int(args[1])
        self.abcd = tuple(float(a) for a in args[2:6])
        self.frac = float(args[6])
        self.seed = int(args[7])
        if abs(sum(self.abcd) - 1.0) > 1e-12:
            raise MRError("RMAT a,b,c,d must sum to 1")
        if self.frac >= 1.0:
            raise MRError("RMAT fraction must be < 1")
        self.order = 1 << self.nlevels

    def _generate(self, mr, key, nremain: int, append: bool = False):
        """One round of device edge generation as the frame ``mr`` adds:
        the first nremain generated rows, NULL values.  The generation
        shape is the SAME every round (pow2 of the total edge count, not
        of the shrinking remainder) so the jitted generator compiles once
        per command, not once per cull round.

        When ``mr`` runs on a mesh the rows never leave it: the key and
        value blocks are formed on the device and laid row-sharded over
        the mesh as a ShardedKV whose counts keep the same first nremain
        rows — beside what ``mr`` already holds there when the rows
        ``append`` to it, so the short shards fill first and the dataset
        keeps its capacity.  On the serial backend it is a host frame."""
        m = max(8, 1 << (self.order * self.nnonzero - 1).bit_length())
        mesh = getattr(mr.backend, "mesh", None)
        with get_tracer().span(names.RMAT_GENERATE, cat=names.HOST,
                               rows=nremain) as sp:
            if mesh is not None:
                key = jax.device_put(key, replicated(mesh))
            vi, vj = rmat_edges(key, m, self.nlevels,
                                np.asarray(self.abcd), self.frac,
                                noisy=self.frac > 0.0)
            if mesh is None:
                edges = np.stack([np.asarray(vi)[:nremain],
                                  np.asarray(vj)[:nremain]], axis=1)
                sp.set(d2h_bytes=edges.nbytes)
                return KVFrame(edges, np.zeros(nremain, np.uint8))
            sp.set(d2h_bytes=0)
            # wait for the generator where the host path pulls: a program's
            # buffers are allocated when it is dispatched, and the host is
            # a whole round ahead here, so the row blocks below would be
            # allocated beside the previous round's sort and layout (the
            # build's peak on one chip: 0.88 GiB without the three waits of
            # this path, 0.64 with them as on the parent; PERF.md §6, PR 27)
            jax.block_until_ready(vi)
            have = np.zeros(mesh_axis_size(mesh), np.int64)
            if append:
                mr._flush_plan()    # a deferred cull must land before we read
                if mr.kv is not None:
                    have = mr.kv.shard_rows(mesh)
            return place_rows(mesh, *rmat_edge_rows(vi, vj),
                              fill_counts(have, nremain))


@command("rmat")
class RMAT(_RmatBase):
    """rmat N Nz a b c d frac seed (oink/rmat.cpp)."""

    def run(self):
        obj = self.obj
        mr = obj.create_mr()
        ntotal = self.order * self.nnonzero
        nremain = ntotal
        niterate = 0
        root = jax.random.PRNGKey(self.seed)
        while nremain:
            niterate += 1
            root, sub = jax.random.split(root)
            edges = self._generate(mr, sub, nremain, append=True)
            mr.map(1, lambda i, kv, p: kv.add_frame(edges), addflag=1)
            del edges       # the dataset owns the frame: free with it
            nunique = mr.collate()
            mr.reduce(cull, batch=True)
            nremain = ntotal - nunique
        self.nunique = ntotal
        self.niterate = niterate
        get_tracer().annotate(rounds=niterate)    # on the oink.<command> span
        obj.output(1, mr, print_edge)
        self.message(f"RMAT: {self.order} rows, {ntotal} non-zeroes, "
                     f"{niterate} iterations")
        obj.cleanup()


@command("rmat2")
class RMAT2(_RmatBase):
    """rmat2 N Nz a b c d frac seed (oink/rmat2.cpp): per-round aggregate
    into a fresh MR, add into the accumulator, convert+cull."""

    def run(self):
        obj = self.obj
        mr = obj.create_mr()
        mrnew = obj.create_mr()
        ntotal = self.order * self.nnonzero
        nremain = ntotal
        niterate = 0
        root = jax.random.PRNGKey(self.seed)
        while nremain:
            niterate += 1
            root, sub = jax.random.split(root)
            edges = self._generate(mrnew, sub, nremain)
            mrnew.map(1, lambda i, kv, p: kv.add_frame(edges))
            del edges
            mrnew.aggregate()
            mr.add(mrnew)
            nunique = mr.convert()
            mr.reduce(cull, batch=True)
            nremain = ntotal - nunique
        self.nunique = ntotal
        self.niterate = niterate
        get_tracer().annotate(rounds=niterate)    # on the oink.<command> span
        obj.output(1, mr, print_edge)
        self.message(f"RMAT2: {self.order} rows, {ntotal} non-zeroes, "
                     f"{niterate} iterations")
        obj.cleanup()
