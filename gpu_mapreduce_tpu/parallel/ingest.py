"""Per-shard mesh ingestion for the generic file map path (VERDICT r4 #4).

The reference's map stage is flat under weak scaling because every MPI
rank reads its own files on its own node (``src/mapreduce.cpp:1102-1225``;
chapter Fig. 4).  Round 4 built that shape for InvertedIndex only
(``apps/invertedindex._map_corpus_mesh``); this module generalises it to
``map_files`` / ``map_file_char`` / ``map_file_str`` — wordfreq and every
file-driven OINK command — on a mesh backend:

* the file list splits into P CONTIGUOUS byte-balanced slices (the
  reference's consecutive per-proc file ranges);
* every task's callback runs into a private sink (a thread pool overlaps
  the file reads — CPython releases the GIL for I/O and numpy parsing);
* each shard's sinks assemble into ONE host frame whose rows go to that
  shard's device — a ``ShardedKV`` is born at map time, rows already
  living on the shard that read them;
* byte/object keys and values intern into DEST-SHARDED decode tables
  (``core.column.ShardTables``): each (id, bytes) entry lives in the
  table of the shard the aggregate will route the id to, so the exchange
  moves u64 ids and shard d's output later decodes from table d alone —
  no controller-global dict (the reference shuffles raw bytes fully
  distributed, ``src/mapreduce.cpp:453-473``);
* the shards' interns run on pool threads, each from the moment its
  shard's frame is assembled (hash, dedupe, split and gather are array
  and native work that releases the GIL), beside the reader of the next
  shard; the tables take the shards' batches in shard order, so nothing
  depends on which thread finished first.

Anything unshardable (mixed dtypes across shards, frames added via
``add_frame``, out-of-core datasets) falls back to replaying the recorded
sinks into the host KV — bit-identical to the pre-r5 behavior, and the
callbacks never run twice.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import os
import threading
import time
from typing import Callable, List, Sequence

import jax
import numpy as np

from ..core.column import (BytesColumn, DenseColumn, ObjectColumn,
                           ShardTables)
from ..core.frame import KVFrame

# per-message cap for generic ingest H2D, in BYTES (apps/invertedindex
# caps its corpus transfers the same way)
H2D_CHUNK_BYTES = 32 << 20


class Unshardable(Exception):
    """Raised when per-shard frames cannot form one mesh dataset; the
    caller replays the sinks into the host KV instead."""


def balance_by_bytes(names: Sequence[str], P: int):
    """Split files into P contiguous chunks of ~equal bytes (the
    reference's consecutive per-proc file ranges).  Returns
    ``[(first_index, files, sizes)] * P`` — the ONE balancing policy;
    apps/invertedindex._balance_files delegates here (r5 review: the
    two ingest paths must not diverge)."""
    sizes = np.array([os.path.getsize(f) for f in names], np.int64)
    total = max(int(sizes.sum()), 1)
    mid = np.cumsum(sizes) - sizes // 2
    assign = np.minimum((mid * P) // total, P - 1)  # non-decreasing
    out = []
    i = 0
    for p in range(P):
        j = i
        while j < len(names) and assign[j] == p:
            j += 1
        out.append((i, list(names[i:j]), sizes[i:j]))
        i = j
    return out


def run_sinks(payloads, call: Callable, threaded: bool = True,
              base: int = 0, pool=None, onfault: str = "fail",
              shard=None):
    """Run ``call(base+i, payload, sink)`` for every payload into
    private _TaskSink buffers; returns the sinks in task order.
    Threaded by default (the per-rank parallel read the reference gets
    from MPI); assembly order is by task index either way, so the
    result is deterministic regardless of scheduling.

    Every task runs through the ft/ ingest policy (``ft.retry
    .ingest_task``): fault points, bounded retries into a fresh private
    buffer per attempt (a retried task can never duplicate pairs or
    reorder — sinks are positional), OSError→MRError wrapping naming
    file/shard/task, and quarantine-skip under ``onfault="skip"``.

    ``pool``: a shared ThreadPoolExecutor (``MapReduce._ingest_pool`` —
    one pool per MapReduce instead of a fresh executor per call); when
    None a private pool capped at ``min(nworkers, len(payloads))`` is
    built and torn down here (standalone callers)."""
    import contextlib
    from concurrent.futures import ThreadPoolExecutor
    from ..core.mapreduce import _TaskSink
    from ..ft.retry import ingest_task
    from ..obs import get_tracer
    sinks = [_TaskSink() for _ in payloads]
    where = {} if shard is None else {"shard": shard}
    with get_tracer().span("ingest.read", cat="ingest",
                           ntasks=len(payloads), threaded=threaded, **where):
        if not threaded or len(payloads) <= 1:
            for i, p in enumerate(payloads):
                ingest_task(call, base + i, p, sinks[i],
                            onfault=onfault, shard=shard)
            return sinks
        # one submit/drain loop for both executors: a shared pool stays
        # open (nullcontext), a private one tears down here
        if pool is not None:
            ctx = contextlib.nullcontext(pool)
        else:
            nworkers = max(1, min((os.cpu_count() or 4), 16,
                                  len(payloads)))
            ctx = ThreadPoolExecutor(nworkers)
        # pool workers run the SUBMITTING request's trace context
        # (obs/context.py): their ft fault-point spans and any counter
        # traffic charge the request, and the pool is shared across
        # sessions so each task must carry its own binding
        from ..obs.context import bind as _ctx_bind
        task = _ctx_bind(ingest_task)
        with ctx as ex:
            futs = [ex.submit(task, call, base + i, p, sinks[i],
                              onfault=onfault, shard=shard)
                    for i, p in enumerate(payloads)]
            for f in futs:
                f.result()   # propagate callback exceptions
    return sinks


def _sink_frame(sinks) -> KVFrame:
    """One host KVFrame from a shard's sinks (task order).  add/add_batch
    traffic only — add_frame/add_kv payloads (pre-built or sharded
    frames) don't belong to a file-ingest callback and fall back."""
    from ..core.dataset import _coerce_rows, _merge_frames, as_column
    frames = []
    for s in sinks:
        buf_k: list = []
        buf_v: list = []
        for name, *args in s._calls:
            if name == "add":
                buf_k.append(args[0])
                buf_v.append(args[1])
                continue
            if buf_k:
                frames.append(KVFrame(_coerce_rows(buf_k),
                                      _coerce_rows(buf_v)))
                buf_k, buf_v = [], []
            if name != "add_batch":
                raise Unshardable(name)
            fr = KVFrame(as_column(args[0]), as_column(args[1]))
            if len(fr):
                frames.append(fr)
        if buf_k:
            frames.append(KVFrame(_coerce_rows(buf_k), _coerce_rows(buf_v)))
    if not frames:
        from ..core.frame import empty_kv
        return empty_kv()
    try:
        return _merge_frames(frames)
    except TypeError as e:       # mixed byte/numeric rows across tasks
        raise Unshardable(str(e))


class _InOrder:
    """Turns for the shards' absorbs.  A shard's thread hashes, dedupes
    and splits when it likes, then waits here for every ticket before
    its own: the tables take the shards' batches in shard order, so the
    tables, a collision's message and the job's digest do not depend on
    which thread finished first.  A ticket is a task's place in the
    order of submission, and the pool starts tasks in that order, so a
    waiting ticket only ever waits for tasks that are running or done."""

    def __init__(self):
        self._cv = threading.Condition()
        self._next = 0

    @contextlib.contextmanager
    def turn(self, k: int):
        with self._cv:
            self._cv.wait_for(lambda: self._next >= k)
        try:
            yield
        finally:
            self.done(k)

    def done(self, k: int) -> None:
        """Ticket k has had its turn, or will never take it (its task
        failed first): nobody waits for it any more."""
        with self._cv:
            self._next = max(self._next, k + 1)
            self._cv.notify_all()


def _intern_shard(shard: int, col, tables: ShardTables, order: _InOrder,
                  ticket: int):
    """One shard's column interned into the shared tables, on a pool
    thread: ``(id column, seconds)``."""
    from ..obs import get_tracer, names
    t0 = time.perf_counter()
    try:
        # the column says what it absorbed: unique, added, checked,
        # table_bytes
        with get_tracer().span(names.INGEST_INTERN, cat=names.HOST,
                               shard=shard, words=len(col)):
            ids = col.intern_sharded(tables, turn=order.turn(ticket))
    finally:
        order.done(ticket)
    return ids, time.perf_counter() - t0


class _SideInterns:
    """One side (the keys, or the values) of the shards' frames,
    interned into shared dest-sharded tables by pool threads.

    ``add`` takes shard k's column as soon as its frame exists and, while
    every shard so far holds byte rows, starts its intern at once, so it
    runs beside the tokenizer of shard k+1 and beside the other shards'
    interns.  ``finish`` decides as a whole, when every shard is in.
    All-or-nothing: one shard emitting bytes while another emits numbers
    is two incompatible key spaces (Unshardable → host fallback), and
    one shard emitting objects moves EVERY shard's rows into the pickle
    domain; what was interned in the byte domain by then is thrown away,
    never patched."""

    def __init__(self, P: int, submit: Callable):
        self.P = P
        self.submit = submit        # (fn, *args) → Future, on the pool
        self.cols: list = []
        self.futs: dict = {}        # shard → its intern, in flight or done
        self.tables = ShardTables(P)
        self.order = _InOrder()
        self.bytes_so_far = True
        self.busy_s = 0.0

    def _start(self, shard: int, col) -> None:
        self.futs[shard] = self.submit(_intern_shard, shard, col, self.tables,
                                       self.order, len(self.futs))

    def add(self, col) -> None:
        self.bytes_so_far &= isinstance(col, BytesColumn) or not len(col)
        if self.bytes_so_far and len(col):
            self._start(len(self.cols), col)
        self.cols.append(col)

    def discard(self) -> None:
        """Let what is in flight run out (a cancelled task would never
        take its turn) and forget it."""
        concurrent.futures.wait(self.futs.values())
        self.futs = {}

    def finish(self):
        """(new columns, tables-or-None) of the whole side."""
        cols = self.cols
        stringy = [isinstance(c, (BytesColumn, ObjectColumn))
                   for c in cols if len(c)]
        if not any(stringy):
            return cols, None
        if not all(stringy):
            self.discard()
            raise Unshardable("mixed byte and numeric rows across shards")
        if not self.bytes_so_far:
            # one shard emitted objects: EVERY shard's rows must hash in
            # the pickle domain, or the same logical bytes key would get
            # two ids (host concat() promotes the same way — r5 review)
            self.discard()
            self.tables, self.order = ShardTables(self.P, "object"), _InOrder()
            cols = [ObjectColumn(c.data) if isinstance(c, BytesColumn) else c
                    for c in cols]
        for shard, c in enumerate(cols):    # an empty byte column; all of
            if shard not in self.futs and isinstance(   # them after a discard
                    c, (BytesColumn, ObjectColumn)):
                self._start(shard, c)
        concurrent.futures.wait(self.futs.values())
        out = [DenseColumn(np.zeros(0, np.uint64))] * len(cols)
        for shard in sorted(self.futs):     # the first shard's error, if any
            out[shard], seconds = self.futs[shard].result()
            self.busy_s += seconds
        self.futs = {}
        return out, self.tables


def _common_spec(arrs: List[np.ndarray]):
    """(dtype, row-shape) every shard must share; empty shards defer."""
    spec = None
    for a in arrs:
        if a.shape[0] == 0:
            continue
        s = (a.dtype, a.shape[1:])
        if spec is None:
            spec = s
        elif spec != s:
            raise Unshardable(f"shard dtype/shape mismatch: {spec} vs {s}")
    return spec or (np.dtype(np.uint8), ())


def _put_block(block: np.ndarray, dev, budget: int):
    """One shard's row block onto its device, in messages of at most
    ``budget`` bytes joined there."""
    host = np.ascontiguousarray(block)
    cap = host.shape[0]
    rowbytes = max(1, int(host.nbytes // max(1, cap)))
    chunk = max(1, budget // rowbytes)
    if cap <= chunk:
        return jax.device_put(host, dev)
    import jax.numpy as jnp
    return jnp.concatenate([jax.device_put(host[o:o + chunk], dev)
                            for o in range(0, cap, chunk)])


def _shard_devices(mesh, cap: int):
    """(row sharding, the device of every shard in shard order) of
    arrays ``[P*cap, ...]`` sharded by rows."""
    from .mesh import mesh_axis_size, row_sharding
    sharding = row_sharding(mesh)
    dmap = sharding.addressable_devices_indices_map(
        (mesh_axis_size(mesh) * cap,))
    return sharding, sorted(dmap, key=lambda dev: dmap[dev][0].start or 0)


def _put_blocks(blocks: List[np.ndarray], cap: int, mesh):
    """Device-put per-shard row blocks [cap,...] each onto ITS device in
    bounded messages (mesh.h2d_chunk_bytes — honors MR_H2D_CHUNK_WORDS
    like every other chunked-transfer site); assemble the row-sharded
    global [P*cap,...]."""
    from ..obs import get_tracer
    from .mesh import h2d_chunk_bytes
    sharding, devices = _shard_devices(mesh, cap)
    shape = (len(blocks) * cap,) + blocks[0].shape[1:]
    budget = h2d_chunk_bytes(H2D_CHUNK_BYTES)
    with get_tracer().span("ingest.h2d", cat="ingest", shards=len(blocks),
                           bytes=int(sum(b.nbytes for b in blocks))):
        shards = [_put_block(blocks[p], dev, budget)
                  for p, dev in enumerate(devices)]
        return jax.make_array_from_single_device_arrays(shape, sharding,
                                                        shards)


class _ShardedBuilder:
    """Per-shard host frames → one ShardedKV, interning byte/object
    columns into dest-sharded tables.  ``add`` takes the shards' frames
    in shard order, each as soon as it is assembled, and starts its
    interns on ``pool`` (a private one for standalone callers, torn down
    on exit); ``build`` waits for them and places the rows."""

    def __init__(self, mesh, pool=None):
        from ..obs.context import bind
        from .mesh import mesh_axis_size
        self.mesh = mesh
        P = mesh_axis_size(mesh)
        self._own = None
        if pool is None:
            pool = self._own = concurrent.futures.ThreadPoolExecutor(
                max(1, min(os.cpu_count() or 4, P)),
                thread_name_prefix="mrtpu-intern")

        def submit(fn, *args):
            # the worker runs the SUBMITTING request's trace context, so
            # its ingest.intern span lands in the job's trace
            return pool.submit(bind(fn), *args)
        self.keys = _SideInterns(P, submit)
        self.values = _SideInterns(P, submit)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.keys.discard()
        self.values.discard()
        if self._own is not None:
            self._own.shutdown()
        return False

    def add(self, frame: KVFrame) -> None:
        self.keys.add(frame.key)
        self.values.add(frame.value)

    def build(self):
        """Rows normally stay on the shard whose file slice produced
        them — EXCEPT a severely lopsided ingest (max shard > 2× the even
        share, e.g. one file on an 8-shard mesh), which re-splits rows
        evenly: the padded cap tracks the fullest shard, so keeping the
        skew would move ~P× the real rows through every downstream
        collective.  Raises Unshardable when the frames cannot agree."""
        from ..obs import get_tracer
        from .sharded import ShardedKV, round_cap, _pad_rows
        mesh = self.mesh
        kcols, ktables = self.keys.finish()
        vcols, vtables = self.values.finish()
        # thread-seconds of the shards' interns, beside the wall their
        # spans cover: onto the enclosing map_files / map_file_* span
        get_tracer().annotate(intern_busy_s=round(
            self.keys.busy_s + self.values.busy_s, 6))
        P = len(kcols)
        karrs = [np.asarray(c.to_host().data) for c in kcols]
        varrs = [np.asarray(c.to_host().data) for c in vcols]
        kdt, kshape = _common_spec(karrs)
        vdt, vshape = _common_spec(varrs)
        counts = np.array([a.shape[0] for a in karrs], np.int32)
        total = int(counts.sum())
        if P > 1 and total and int(counts.max()) > 2 * (-(-total // P)):
            # lopsided ingest (fewer files than shards — e.g. one edge file
            # on an 8-shard mesh): the padded cap tracks the FULLEST shard,
            # so every downstream collective would move ~P x the real rows.
            # Re-split evenly — free on a single controller (the bytes are
            # already in host RAM), and order-preserving.  A multi-host
            # runtime would keep locality instead; with one file only one
            # host has the data anyway (r5 P=8 soak regression).
            kall = np.concatenate([a.astype(kdt, copy=False)
                                   .reshape((-1,) + kshape) for a in karrs])
            vall = np.concatenate([a.astype(vdt, copy=False)
                                   .reshape((-1,) + vshape) for a in varrs])
            per = -(-total // P)
            starts = np.minimum(np.arange(P) * per, total)
            ends = np.minimum(starts + per, total)
            karrs = [kall[s:e] for s, e in zip(starts, ends)]
            varrs = [vall[s:e] for s, e in zip(starts, ends)]
            counts = (ends - starts).astype(np.int32)
        cap = round_cap(int(counts.max()) if counts.max() else 0)
        kb = [_pad_rows(a.astype(kdt, copy=False).reshape((-1,) + kshape), cap)
              for a in karrs]
        vb = [_pad_rows(a.astype(vdt, copy=False).reshape((-1,) + vshape), cap)
              for a in varrs]
        key = _put_blocks(kb, cap, mesh)
        value = _put_blocks(vb, cap, mesh)
        return ShardedKV(mesh, key, value, counts,
                         key_decode=ktables, value_decode=vtables)


def build_sharded(frames: List[KVFrame], mesh, pool=None):
    """One ShardedKV of per-shard host frames that all exist already
    (see :class:`_ShardedBuilder`)."""
    with _ShardedBuilder(mesh, pool) as builder:
        for frame in frames:
            builder.add(frame)
        return builder.build()


def _frames_to_kv(mr, kv, sink_stream, stats: dict) -> dict:
    """The consumer half of both mesh map paths: every shard's sinks
    become its frame, the frames one mesh dataset in ``kv``.  When they
    cannot (Unshardable), every sink replays into the host ``kv`` in
    task order instead, once, and ``stats`` says so."""
    shard_sinks: List[list] = []      # kept for the fallback
    failed = skv = None
    with _ShardedBuilder(mr.backend.mesh, mr._ingest_pool()) as builder:
        for sinks in sink_stream:
            shard_sinks.append(sinks)
            if failed is None:
                try:
                    builder.add(_sink_frame(sinks))
                except Unshardable as e:
                    failed = str(e)[:200]
        if failed is None:
            try:
                skv = builder.build()
            except Unshardable as e:
                failed = str(e)[:200]
    if failed is not None:
        for sinks in shard_sinks:
            for s in sinks:
                s.replay(kv)
        stats["mode"] = "host"
        stats["fallback"] = failed
        return stats
    kv.add_frame(skv)
    stats["rows_per_shard"] = skv.counts.tolist()
    return stats


def _balanced_shards(names: Sequence[str], P: int,
                     onfault: str) -> List[List[str]]:
    """balance_by_bytes under the ft/ discovery policy — ONE copy for
    both mesh map paths: a file that vanished between findfiles and
    the byte balance gets the SAME disposition a task-time failure
    would (MRError naming it, or quarantine-drop + rebalance under
    onfault="skip"), so which stage notices a bad input never decides
    whether the run survives it."""
    from ..ft.retry import quarantine_or_raise
    names = list(names)
    while True:
        try:
            return [files for _, files, _ in balance_by_bytes(names, P)]
        except OSError as e:
            bad = getattr(e, "filename", None)
            if bad in names:
                quarantine_or_raise(e, bad, onfault)
                names.remove(bad)
            else:
                quarantine_or_raise(e, bad, "fail")


def _shard_sink_stream(shards_payloads, call: Callable, threaded: bool,
                       pool, onfault: str = "fail"):
    """Generator of per-shard sink lists: ``run_sinks`` over each
    shard's payloads in turn, with GLOBAL task numbering (cumulative
    base).  This is the producer half the prefetch pipeline runs in its
    background thread — read + tokenize shard N+1 while the consumer
    assembles/interns shard N's frame.  A retry inside ``run_sinks``
    happens WITHIN a task slot, so the producer can never reorder
    frames (the chaos golden contract)."""
    itask = 0
    for sidx, payloads in enumerate(shards_payloads):
        sinks = run_sinks(payloads, call, threaded=threaded, base=itask,
                          pool=pool, onfault=onfault, shard=sidx)
        itask += len(payloads)
        yield sinks


def _pooled_file_sink_stream(shards, call: Callable, pool,
                             onfault: str = "fail"):
    """mapstyle-2 map_files producer: EVERY file's task submits to the
    shared pool up front (the full cross-file parallelism the pre-exec
    single run_sinks had — a P-shard mesh with ~1 file per shard must
    not serialize its reads), then per-shard sink groups yield in task
    order as their futures complete, so the consumer assembles shard N
    while shards > N are still reading."""
    from ..core.mapreduce import _TaskSink
    from ..ft.retry import ingest_task
    from ..obs import get_tracer
    names = [f for files in shards for f in files]
    shard_of = [s for s, files in enumerate(shards) for _ in files]
    sinks = [_TaskSink() for _ in names]
    from ..obs.context import bind as _ctx_bind
    task = _ctx_bind(ingest_task)   # shared pool: each task carries the
    #                                 submitting request's trace context
    with get_tracer().span("ingest.read", cat="ingest",
                           ntasks=len(names), threaded=True):
        futs = [pool.submit(task, call, i, name, sinks[i],
                            onfault=onfault, shard=shard_of[i])
                for i, name in enumerate(names)]
        i = 0
        for files in shards:
            for f in futs[i:i + len(files)]:
                f.result()   # propagate callback exceptions, task order
            yield sinks[i:i + len(files)]
            i += len(files)


def mesh_map_files(mr, kv, names: Sequence[str], call: Callable) -> dict:
    """The mesh map_files path: per-shard ingest + dest-sharded intern.
    Returns the ingest stats record ({"mode": "mesh"|"host", ...});
    either way every callback has run exactly once and its pairs are in
    ``kv``.

    Shards pipeline through the exec/ prefetch: the reader/tokenizer
    produce shard N+1's sinks while shard N's frame assembles (task ids
    and replay order stay global file order — output is bit-identical
    to the unprefetched path)."""
    from ..exec import prefetch_iter
    from .mesh import mesh_axis_size
    P = mesh_axis_size(mr.backend.mesh)
    onfault = mr.settings.onfault
    shards = _balanced_shards(names, P, onfault)
    stats = {"mode": "mesh", "shards": P,
             "files_per_shard": [len(s) for s in shards]}
    threaded = mr.settings.mapstyle == 2
    if threaded:
        # all files in flight on the shared pool at once (cross-file
        # parallelism), groups stream out in shard order
        stream = _pooled_file_sink_stream(shards, call,
                                          mr._ingest_pool(),
                                          onfault=onfault)
    else:
        stream = _shard_sink_stream(shards, call, False, None,
                                    onfault=onfault)
    return _frames_to_kv(mr, kv, prefetch_iter(stream, path="ingest.files"),
                         stats)


# rows a shard's block of fixed-width records is rounded up to: a whole
# number of the chip's 1024-element tiles, and NOT ``round_cap``'s power
# of two — 10^7 records a shard would be held, moved and sorted as 2^24
RECORD_ROWS = 1024


def mesh_map_records(mr, kv, names: Sequence[str], reader) -> dict:
    """The mesh path of ``map_files`` over fixed-width records
    (``reader``: a ``utils/io.RecordFormat``), on one shard or many: no
    tokenizer, no intern, no sink.  A file is ``n`` records by its size,
    so every shard's block is sized before a byte is read; the pool cuts
    each file's keys and values straight into its rows of the block
    (every file in flight at once), and a shard's block goes to its
    device when its last file is in, beside the reads of the later
    shards.  A ``ShardedKV`` is born with the rows on the shard that read
    them, the key words ordered as the key bytes are.

    Under an armed fault policy (retries, ``onfault="skip"``) a file may
    contribute nothing, which a block sized beforehand cannot take: those
    runs go through :func:`mesh_map_files` with the reader as an ordinary
    callback, the same rows by the generic path."""
    from ..ft.retry import ingest_active, ingest_task
    from ..obs import get_tracer, names as obs
    from ..obs.context import bind
    from .mesh import h2d_chunk_bytes, mesh_axis_size
    from .sharded import ShardedKV
    onfault = mr.settings.onfault
    if ingest_active(onfault):
        return mesh_map_files(mr, kv, names, reader)
    mesh = mr.backend.mesh
    P = mesh_axis_size(mesh)
    tracer = get_tracer()
    with tracer.span(obs.INGEST_RECORDS_PLAN, cat=obs.HOST, shards=P,
                     files=len(names)) as sp:
        try:
            shards = balance_by_bytes(names, P)
        except OSError as e:
            from ..ft.retry import input_unreadable
            raise input_unreadable(e) from e
        rows = [[reader.rows(f, int(b)) for f, b in zip(files, sizes)]
                for _, files, sizes in shards]
        counts = np.array([sum(r) for r in rows], np.int32)
        cap = max(RECORD_ROWS, -(-int(counts.max(initial=0)) // RECORD_ROWS)
                  * RECORD_ROWS)
        pool = mr._ingest_pool()
        task = bind(ingest_task)
        blocks, pending = [], []
        for p, (first, files, _) in enumerate(shards):
            key = np.empty((cap, reader.key_words), np.uint32)
            value = np.empty((cap, reader.value_words), np.uint32)
            key[counts[p]:] = 0         # the rows past the count: padding
            value[counts[p]:] = 0
            blocks.append((key, value))
            futs, at = [], 0
            for i, (fname, n) in enumerate(zip(files, rows[p])):
                def cut(_itask, f, _sink, k=key[at:at + n],
                        v=value[at:at + n]):
                    reader.read_into(f, k, v)
                futs.append(pool.submit(task, cut, first + i, fname, None,
                                        onfault=onfault, shard=p,
                                        private_sink=False))
                at += n
            pending.append(futs)
        sp.set(block_bytes=P * (key.nbytes + value.nbytes))
    budget = h2d_chunk_bytes(H2D_CHUNK_BYTES)
    sharding, devices = _shard_devices(mesh, cap)
    kparts, vparts = [], []
    try:
        for p, ((_, files, sizes), futs) in enumerate(zip(shards, pending)):
            with tracer.span(obs.INGEST_RECORDS_READ, cat=obs.HOST, shard=p,
                             files=len(files), bytes=int(sizes.sum())):
                for f in futs:
                    f.result()      # the first file's error, in file order
            key, value = blocks[p]
            with tracer.span(obs.INGEST_RECORDS_H2D, cat=obs.HOST, shard=p,
                             bytes=key.nbytes + value.nbytes):
                kparts.append(_put_block(key, devices[p], budget))
                vparts.append(_put_block(value, devices[p], budget))
                if p == P - 1:
                    # the map's one wait, for every shard's blocks, once
                    # all are on their way: a program's buffers are
                    # allocated when it is dispatched, so the sort behind
                    # this map would stand beside the messages of a block
                    # still being joined (PERF.md §6, PR 36: 8.06 GiB
                    # against 5.98 at 2 x 10^7 records)
                    jax.block_until_ready((kparts, vparts))
                # the host copy goes as soon as the device has it (a
                # transfer holds its own reference until then)
                key = value = blocks[p] = None
    except BaseException:
        for futs in pending:
            for f in futs:
                f.cancel()
        raise
    skv = ShardedKV(mesh, *(
        jax.make_array_from_single_device_arrays(
            (P * cap, width), sharding, parts)
        for width, parts in ((reader.key_words, kparts),
                             (reader.value_words, vparts))), counts)
    kv.add_frame(skv)
    return {"mode": "records", "shards": P,
            "files_per_shard": [len(files) for _, files, _ in shards],
            "rows_per_shard": counts.tolist()}


def mesh_map_chunks(mr, kv, names: Sequence[str], per_file: int, sep: bytes,
                    delta: int, call: Callable) -> dict:
    """Mesh path for map_file_char/str: files balance across shards, each
    file splits into its ~per_file chunks (utils.io.file_chunks — same
    chunking as the host path, so callbacks see identical payloads and
    task ids stay global file-then-chunk order).

    Shards process ONE AT A TIME: a shard's raw chunk payloads are
    generated, consumed into its frame, and released before the next
    shard reads — peak raw-bytes residency is ~one shard's slice per
    in-flight pipeline stage, not the whole corpus (the host path's
    lazy-window property, kept; the exec/ prefetch pipeline holds at
    most MRTPU_PREFETCH extra shards' tokenized sinks)."""
    from ..exec import prefetch_iter
    from ..ft.retry import ingest_read
    from ..utils.io import file_chunks
    from .mesh import mesh_axis_size
    P = mesh_axis_size(mr.backend.mesh)
    onfault = mr.settings.onfault
    shards = _balanced_shards(names, P, onfault)
    stats = {"mode": "mesh", "shards": P,
             "files_per_shard": [len(s) for s in shards],
             "chunks_per_shard": []}
    threaded = mr.settings.mapstyle == 2
    pool = mr._ingest_pool() if threaded else None
    counts = {"ntasks": 0}

    def shard_payloads():
        # producer side: the raw chunk bytes of one shard materialize,
        # tokenize through the callbacks, and release before the next
        # shard reads (run_sinks happens in _shard_sink_stream).  Each
        # file reads under the ft/ ingest.read policy: retry budget,
        # MRError naming the file, quarantine-skip under onfault=skip
        for sidx, chunk_files in enumerate(shards):
            payloads = []
            for fname in chunk_files:
                chunks = ingest_read(
                    lambda f=fname: list(file_chunks(f, per_file, sep,
                                                     delta)),
                    file=fname, onfault=onfault, shard=sidx)
                if chunks is not None:
                    payloads.extend(chunks)
            stats["chunks_per_shard"].append(len(payloads))
            counts["ntasks"] += len(payloads)
            yield payloads

    _frames_to_kv(mr, kv, prefetch_iter(
        _shard_sink_stream(shard_payloads(), call, threaded, pool,
                           onfault=onfault),
        path="ingest.chunks"), stats)
    stats["ntasks"] = counts["ntasks"]
    return stats
