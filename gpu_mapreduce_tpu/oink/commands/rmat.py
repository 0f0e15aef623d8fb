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

from ...core.runtime import MRError
from ...models.rmat import rmat_edges
from ...obs import get_tracer, names
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

    def _generate(self, key, nremain: int) -> np.ndarray:
        """One round of device edge generation, trimmed to nremain rows.
        The generation shape is the SAME every round (pow2 of the total
        edge count, not of the shrinking remainder) so the jitted
        generator compiles once per command, not once per cull round."""
        m = max(8, 1 << (self.order * self.nnonzero - 1).bit_length())
        with get_tracer().span(names.RMAT_GENERATE, cat=names.HOST,
                               rows=nremain):
            vi, vj = rmat_edges(key, m, self.nlevels,
                                np.asarray(self.abcd), self.frac,
                                noisy=self.frac > 0.0)
            return np.stack([np.asarray(vi)[:nremain],
                             np.asarray(vj)[:nremain]], axis=1)


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
            edges = self._generate(sub, nremain)
            mr.map(1, lambda i, kv, p: kv.add_batch(
                edges, np.zeros(len(edges), np.uint8)), addflag=1)
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
            edges = self._generate(sub, nremain)
            mrnew.map(1, lambda i, kv, p: kv.add_batch(
                edges, np.zeros(len(edges), np.uint8)))
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
