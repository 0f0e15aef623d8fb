"""TeraSort — the Sort Benchmark's 100-byte records ordered by their
10-byte keys, as Hadoop runs it (TeraGen / TeraSort / TeraValidate): read
the files, range-partition by sampled splitters so that shard *i*'s keys
all precede shard *i+1*'s, sort each shard, write one part file a shard.

In MR-MPI's terms, and built from ``MapReduce`` operations only:

* ``map_files`` with a :class:`~..utils.io.RecordFormat` — the record
  map: a file is ``n`` records, the key bytes become dense u32 words
  whose order is the bytes' ``memcmp`` order, the value bytes travel
  beside them; no tokenizer, no intern, no table;
* ``aggregate(range partitioner)`` — the reference's ``aggregate`` takes
  a user hash (``src/mapreduce.cpp:469-472``), and a total-order
  partitioner is one: the number of sampled splitters a key is not
  below.  On one shard it is MR-MPI's no-op;
* ``sort_keys(1)`` — one device sort a shard
  (``parallel/group.sort_sharded``: the key words are the sort's keys,
  the value comes by the row index);
* the part writer: each shard's rows pulled once and written as they
  were read, ``part-%05d``, 100 bytes a record.

Private to the application: the record format's numbers, the splitter
sampling and the binary writer.  Ties come out in any order (the Sort
Benchmark's Indy rules ask for no stable sort).
"""

from __future__ import annotations

import collections
import os
from typing import Callable, Optional, Sequence

import numpy as np

from ..core.frame import KVFrame
from ..core.mapreduce import MapReduce
from ..obs import get_tracer, names
from ..utils.io import RecordFormat, findfiles

RECORD_BYTES = 100
KEY_BYTES = 10
SAMPLE = 100_000        # keys sampled for the splitters: Hadoop's default
WRITE_ROWS = 1 << 20    # records put together and written at a time
WRITE_AHEAD = 4         # blocks the pool joins ahead of the write


def range_partitioner(splitters: np.ndarray) -> Callable:
    """The user hash of a total-order ``aggregate``: ``keys [n, w]`` (the
    record map's key words) → the number of ``splitters [s, w]`` (sorted)
    each key is not below, so that destination *i* takes the keys in
    ``[splitter i-1, splitter i)``.  Every key is compared with every
    splitter (a ``searchsorted`` is a gather a round on the chip,
    ``parallel/shuffle._dest_fn``); the words compare as unsigned
    numbers, the first the most significant."""
    import jax.numpy as jnp
    splitters = np.ascontiguousarray(splitters, np.uint32)

    def dest(keys):
        k, s = keys[:, None, :], jnp.asarray(splitters)[None, :, :]
        w = splitters.shape[1]
        ge = k[..., w - 1] >= s[..., w - 1]
        for j in range(w - 2, -1, -1):
            ge = (k[..., j] > s[..., j]) | ((k[..., j] == s[..., j]) & ge)
        return jnp.sum(ge, axis=1, dtype=jnp.uint32)
    return dest


class TeraSort:
    """``TeraSort(comm=mesh).run(paths, outdir=...)``; ``comm=None`` runs
    the same operations on the serial backend.  ``mr``: the MapReduce
    object to run on (the OINK command hands its own).  After ``run``,
    ``mr`` holds the sorted dataset, ``parts`` the part files' paths and
    ``splitters`` the partitioner's key words."""

    def __init__(self, comm=None, mr: Optional[MapReduce] = None):
        self.mr = mr if mr is not None else MapReduce(comm)
        self.format = RecordFormat(RECORD_BYTES, KEY_BYTES)
        self.parts: list = []
        self.splitters = np.zeros((0, self.format.key_words), np.uint32)

    def run(self, paths: Sequence[str], outdir: Optional[str] = None) -> int:
        """Sort the records of ``paths``; with ``outdir``, write them as
        ``outdir/part-<shard>`` (closed when this returns).  Returns the
        number of records."""
        # the job's root span: its CPU and off-CPU seconds, context
        # switches and what JAX built under it (doc/observability.md)
        with get_tracer().span(names.TERASORT_RUN, cat=names.ENTRY):
            return self._run(paths, outdir)

    def _run(self, paths, outdir) -> int:
        mr = self.mr
        self.parts = []
        nrecords = mr.map_files(findfiles(list(paths)), self.format)
        mr.aggregate(self._partitioner())
        mr.sort_keys(1)
        if outdir is not None:
            os.makedirs(outdir, exist_ok=True)
            self._write_parts(outdir)
        return int(nrecords)

    # -- the splitters --------------------------------------------------------
    def _partitioner(self) -> Optional[Callable]:
        """The range partitioner of this dataset, from ``SAMPLE`` of its
        keys at an even stride over every shard's rows; None on one
        shard, where nothing is partitioned and nothing is sampled."""
        nshards = self.mr.backend.nprocs
        if nshards == 1:
            return None
        with get_tracer().span(names.TERASORT_SAMPLE, cat=names.HOST) as sp:
            keys = self._sample_keys()
            if len(keys):
                # ascending, the first word the most significant
                keys = keys[np.lexsort(keys.T[::-1])]
                at = (np.arange(1, nshards) * len(keys)) // nshards
                self.splitters = keys[at]
            sp.set(sampled=len(keys), splitters=len(self.splitters))
        return range_partitioner(self.splitters)

    def _sample_keys(self) -> np.ndarray:
        fr = self.mr.kv.one_frame()
        if isinstance(fr, KVFrame):     # the map fell back to the host
            counts, cap = np.array([len(fr)]), len(fr)
            key = np.asarray(fr.key.data)
        else:
            counts, cap, key = fr.counts, fr.cap, fr.key
        total = int(counts.sum())
        rows = []
        for p, c in enumerate(counts.tolist()):
            take = min(c, -(-SAMPLE * c // max(total, 1)))
            if take:
                rows.append(p * cap + (np.arange(take) * c) // take)
        if not rows:
            return np.zeros((0, self.format.key_words), np.uint32)
        rows = np.concatenate(rows)
        if isinstance(key, np.ndarray):
            return key[rows]
        import jax.numpy as jnp
        return np.asarray(jnp.take(key, jnp.asarray(rows), axis=0))

    # -- the part files -------------------------------------------------------
    def _write_parts(self, outdir: str) -> None:
        """``part-<shard>`` from each shard's own rows, in their order:
        one pull a shard, then the records put together again and
        written block by block (the pool joins the blocks ahead of the
        write)."""
        fr = self.mr.kv.one_frame()
        tracer = get_tracer()
        on_host = isinstance(fr, KVFrame)   # the serial backend's frame
        nshards = 1 if on_host else fr.nprocs
        for p in range(nshards):
            with tracer.span(names.TERASORT_PULL, cat=names.HOST,
                             shard=p) as sp:
                host = fr if on_host else fr.shard_to_host(p)
                key = np.asarray(host.key.data)
                value = np.asarray(host.value.data)
                sp.set(records=len(key), d2h_bytes=(
                    0 if on_host
                    else (fr.key.nbytes + fr.value.nbytes) // nshards))
            path = os.path.join(outdir, f"part-{p:05d}")
            with tracer.span(names.TERASORT_WRITE, cat=names.HOST, shard=p,
                             records=len(key)) as sp:
                self._write_part(path, key, value)
                sp.set(bytes=os.path.getsize(path))
            self.parts.append(path)

    def _write_part(self, path: str, key: np.ndarray,
                    value: np.ndarray) -> None:
        pool = self.mr._ingest_pool()
        joined = collections.deque()    # blocks being put together, in order
        with open(path, "wb") as out:
            for lo in range(0, len(key), WRITE_ROWS):
                joined.append(pool.submit(
                    self.format.join, key[lo:lo + WRITE_ROWS],
                    value[lo:lo + WRITE_ROWS]))
                if len(joined) > WRITE_AHEAD:
                    joined.popleft().result().tofile(out)
            while joined:
                joined.popleft().result().tofile(out)
