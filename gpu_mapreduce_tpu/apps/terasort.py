"""TeraSort — the Sort Benchmark's 100-byte records ordered by their
10-byte keys, as Hadoop runs it (TeraGen / TeraSort / TeraValidate): read
the files, range-partition by sampled splitters so that shard *i*'s keys
all precede shard *i+1*'s, sort each shard, write one part file a shard.

In MR-MPI's terms, and built from ``MapReduce`` operations only:

* ``map_files`` with a :class:`~..utils.io.RecordFormat` — the record
  map: a file is ``n`` records, the key bytes become dense u32 words
  whose order is the bytes' ``memcmp`` order, the value bytes travel
  beside them; no tokenizer, no intern, no table;
* ``aggregate(TotalOrder(splitters))`` — the reference's ``aggregate``
  takes a user hash (``src/mapreduce.cpp:469-472``); the total-order
  partitioner is handed in as one and is a destination spec of the
  exchange in its own right (``parallel/shuffle.TotalOrder``): the
  number of sampled splitters a key is not below, the splitters an
  operand of the cached phase 1, so that every job of a process runs one
  program whatever its records.  The splitters come from ``SAMPLE`` keys,
  each shard's share read at an even stride over its own rows by one
  small device program.  On one shard it is MR-MPI's no-op;
* ``sort_keys(1)`` — one device sort a shard
  (``parallel/group.sort_sharded``: the key words are the sort's keys,
  the value comes by the row index);
* the part writer: each shard's rows put together again as records by
  one small device program, pulled a window at a time and written as
  they were read, ``part-%05d``, 100 bytes a record.

Private to the application: the record format's numbers, the splitter
sampling and the binary writer.  Ties come out in any order (the Sort
Benchmark's Indy rules ask for no stable sort).
"""

from __future__ import annotations

import collections
import functools
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.frame import KVFrame
from ..core.mapreduce import MapReduce
from ..obs import get_tracer, names
from ..parallel.mesh import row_sharding, row_spec
from ..parallel.sharded import shard_blocks
from ..parallel.shuffle import TotalOrder
from ..utils.io import RecordFormat, findfiles

RECORD_BYTES = 100
KEY_BYTES = 10
SAMPLE = 100_000        # keys sampled for the splitters: Hadoop's default
WRITE_ROWS = 1 << 18    # records put together and written at a time (the
#                         device join's temporaries are 512 bytes a row)
WRITE_AHEAD = 4         # windows (the serial backend: blocks) joined ahead
#                         of the one being written


def sample_shares(counts) -> np.ndarray:
    """Keys each shard gives to the sample: its share of ``SAMPLE`` by its
    share of the rows, rounded up, and no more than it holds."""
    # 10^5 keys times a shard's 5 x 10^6 rows is past the counts' int32
    counts = np.asarray(counts, np.int64)
    total = max(int(counts.sum()), 1)
    return np.minimum(counts, -(-SAMPLE * counts // total))


@functools.lru_cache(maxsize=8)
def _sample_jit(mesh, slots: int):
    """The sample's device program: ``slots`` key rows a shard, slot *i*
    the shard's row ``i * count // take`` — an even stride over its own
    rows, read where they lie (no gather over the sharded column).  A
    shard's slots past its ``take`` hold rows the host drops."""
    spec = row_spec(mesh)

    @jax.jit
    def terasort_sample(key, count, take):
        def body(k, c, t):
            with jax.named_scope("sample"):
                at = jnp.arange(slots, dtype=jnp.int64) * c[0] \
                    // jnp.maximum(t[0], 1)
                return jnp.take(k, at, axis=0, mode="clip")
        return jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec)(key, count, take)

    return terasort_sample


@functools.lru_cache(maxsize=8)
def _join_jit(record_bytes: int, key_bytes: int, rows: int):
    """The part writer's device program, over ONE shard's own block on
    that shard's chip: ``rows`` rows from row ``start`` on (a traced
    offset: one program whatever the window), put together as the
    records' own bytes (``RecordFormat.join_words``: shifts, masks and ORs
    of the words the shard holds; nothing is ordered, gathered or
    scattered, and the sorted dataset stays as it is).  The window comes
    out FLAT, ``u32[rows * record words]``, record after record: the chip
    keeps a ``[rows, words]`` array with the rows minor and its host copy
    keeps that order, so only a flat array reaches the host as the file
    has it."""
    fmt = RecordFormat(record_bytes, key_bytes)

    @jax.jit
    def join_records(key, value, start):
        with jax.named_scope("join"):
            return fmt.join_words(
                lax.dynamic_slice_in_dim(key, start, rows),
                lax.dynamic_slice_in_dim(value, start, rows)).reshape(-1)

    return join_records


def _drawn_ahead(items, ahead: int):
    """``items`` in their order, each handed on when ``ahead`` more have
    been drawn behind it (or none are left): work that starts as an item
    is drawn runs ``ahead`` items ahead of whoever consumes them."""
    drawn = collections.deque()
    for item in items:
        drawn.append(item)
        if len(drawn) > ahead:
            yield drawn.popleft()
    while drawn:
        yield drawn.popleft()


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
    def _partitioner(self) -> Optional[TotalOrder]:
        """The total order of this dataset, from ``SAMPLE`` of its keys at
        an even stride over every shard's rows; None on one shard, where
        nothing is partitioned and nothing is sampled."""
        nshards = self.mr.backend.nprocs
        if nshards == 1:
            return None
        with get_tracer().span(names.TERASORT_SAMPLE, cat=names.HOST) as sp:
            keys, pulled = self._sample_keys()
            if len(keys):
                # ascending, the first word the most significant
                keys = keys[np.lexsort(keys.T[::-1])]
                at = (np.arange(1, nshards) * len(keys)) // nshards
                self.splitters = keys[at]
            sp.set(sampled=len(keys), splitters=len(self.splitters),
                   d2h_bytes=pulled)
        return TotalOrder(self.splitters)

    def _sample_keys(self) -> tuple:
        """``(keys [n, words], bytes pulled from the mesh)``: each shard's
        share of ``SAMPLE`` by its share of the rows."""
        fr = self.mr.kv.one_frame()
        on_host = isinstance(fr, KVFrame)   # the map fell back to the host
        counts = np.array([len(fr)]) if on_host else np.asarray(fr.counts)
        take = sample_shares(counts)
        if not take.any():
            return np.zeros((0, self.format.key_words), np.uint32), 0
        if on_host:
            rows = np.arange(take[0]) * counts[0] // take[0]
            return np.asarray(fr.key.data)[rows], 0
        slots = int(take.max())
        on_mesh = functools.partial(jax.device_put,
                                    device=row_sharding(fr.mesh))
        got = np.asarray(_sample_jit(fr.mesh, slots)(
            fr.key, on_mesh(counts.astype(np.int32)),
            on_mesh(take.astype(np.int32))))
        got = got.reshape(len(take), slots, -1)
        return (np.concatenate([got[p, :t] for p, t in enumerate(take)]),
                got.nbytes)

    # -- the part files -------------------------------------------------------
    def _write_parts(self, outdir: str) -> None:
        """``part-<shard>`` from each shard's own rows, in their order.  A
        mesh frame's records are put together on the device and come back
        as the file's bytes, window by window; the serial backend's frame
        is on the host already and goes through the host's ``join``."""
        fr = self.mr.kv.one_frame()
        if isinstance(fr, KVFrame):
            self._write_part_host(outdir, fr)
        else:
            self._write_windows(outdir, fr)

    def _write_windows(self, outdir: str, fr) -> None:
        """One queue over every (shard, window) in file order: this thread
        waits for the oldest window on its way (``terasort.pull``) and
        writes its rows below the shard's count (``terasort.write``), while
        the next ``WRITE_AHEAD`` cross behind it, the last of a shard
        beside the first of the next shard's, from another chip.  A file
        is written front to back by this one thread and closed with its
        last window."""
        tracer = get_tracer()
        rows = min(WRITE_ROWS, fr.cap)
        windows = self._joined_windows(fr, rows)
        for p, n in enumerate(int(c) for c in fr.counts):
            path = os.path.join(outdir, f"part-{p:05d}")
            with open(path, "wb") as out:   # no rows: an empty file
                for lo in range(0, n, rows):
                    with tracer.span(
                            names.TERASORT_PULL, cat=names.HOST, shard=p,
                            d2h_bytes=rows * self.format.record_bytes) as sp:
                        records = next(windows)
                        sp.set(records=len(records))
                    with tracer.span(names.TERASORT_WRITE, cat=names.HOST,
                                     shard=p, records=len(records),
                                     joined="device", windows=1) as sp:
                        sp.set(bytes=out.write(records))
                        if lo + rows >= n:
                            out.close()     # inside the last write's span
            self.parts.append(path)

    def _joined_windows(self, fr, rows: int):
        """The records ``u32[n, record words]`` on the host of every window
        of ``rows`` rows of every shard, in file order, the rows from the
        shard's count on and the windows past it left out; the next
        ``WRITE_AHEAD`` windows are joined and on their way to the host
        when one is waited for."""
        join = _join_jit(self.format.record_bytes, self.format.key_bytes,
                         rows)
        keys = shard_blocks(fr.key, fr.nprocs)
        values = shard_blocks(fr.value, fr.nprocs)

        def dispatched():
            for p, n in enumerate(int(c) for c in fr.counts):
                for lo in range(0, n, rows):
                    # the last window starts where a whole one still
                    # fits: its first rows are the window's before it
                    at = min(lo, fr.cap - rows)
                    window = join(keys[p], values[p], np.int32(at))
                    window.copy_to_host_async()
                    yield window, lo - at, min(lo + rows, n) - at

        for window, first, stop in _drawn_ahead(dispatched(), WRITE_AHEAD):
            yield np.asarray(window).reshape(rows, -1)[first:stop]

    def _write_part_host(self, outdir: str, fr: KVFrame) -> None:
        """The serial backend's one part file: the frame's rows put
        together again by the host's ``join`` and written block by block
        (the pool joins the blocks ahead of the write)."""
        tracer = get_tracer()
        with tracer.span(names.TERASORT_PULL, cat=names.HOST, shard=0,
                         d2h_bytes=0) as sp:   # nothing crosses
            key = np.asarray(fr.key.data)
            value = np.asarray(fr.value.data)
            sp.set(records=len(key))
        path = os.path.join(outdir, "part-00000")
        with tracer.span(names.TERASORT_WRITE, cat=names.HOST, shard=0,
                         records=len(key), joined="host",
                         windows=-(-len(key) // WRITE_ROWS)) as sp:
            self._write_part(path, key, value)
            sp.set(bytes=os.path.getsize(path))
        self.parts.append(path)

    def _write_part(self, path: str, key: np.ndarray,
                    value: np.ndarray) -> None:
        pool = self.mr._ingest_pool()
        blocks = (pool.submit(self.format.join, key[lo:lo + WRITE_ROWS],
                              value[lo:lo + WRITE_ROWS])
                  for lo in range(0, len(key), WRITE_ROWS))
        with open(path, "wb") as out:
            for block in _drawn_ahead(blocks, WRITE_AHEAD):
                block.result().tofile(out)
