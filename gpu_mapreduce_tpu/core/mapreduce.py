"""The MapReduce class — the ~30-method op algebra of the reference
(``src/mapreduce.h:59-131``), re-designed TPU-first.

Semantics follow ``doc/Interface_c++.txt`` and the call stacks in SURVEY.md
§3.  Key differences from the reference, by design (SURVEY.md §7):

* Data is columnar (frames of dense arrays / byte strings), not byte-packed
  pages.  Every op has a vectorised *batch* path (callbacks receive whole
  columns, run jitted on device) next to the per-pair *host* path (callbacks
  receive python scalars — the reference's serial-callback model, kept for
  parity and arbitrary-object support like python/mrmpi.py's pickled KVs).
* Parallelism is a pluggable backend: the default :class:`SerialBackend`
  is the analogue of the reference's mpistubs/ serial MPI (1-proc semantics,
  ``mpistubs/mpi.cpp:244-395``); the mesh backend (``parallel/``) runs the
  same ops sharded over a ``jax.sharding.Mesh`` with ICI collectives.
* ``aggregate()`` early-outs with one proc exactly like the reference
  (``src/mapreduce.cpp:403-406``).

Every mutating op returns the *global* pair count, like the reference's
MPI_Allreduce'd returns (``src/mapreduce.cpp:557-558``).
"""

from __future__ import annotations

import copy as _copymod
import functools
import sys
import time
from typing import Callable, List, Optional, Sequence, Union

import numpy as np

from ..ops.segment import group_frame
from ..ops.sort import argsort_column
from ..utils.io import RecordFormat, file_chunks, findfiles
from .column import BytesColumn, Column, DenseColumn, as_column, concat
from .dataset import KeyMultiValue, KeyValue
from .frame import BlockedMultivalue, KMVFrame, KVFrame
from .runtime import Counters, Error, MRError, Settings, Timer, global_counters


class SerialBackend:
    """1-proc backend: all distributed ops are local no-ops or renames.

    This is the moral equivalent of linking against ``mpistubs/`` — the
    reference's complete single-process MPI fake (``mpistubs/mpi.cpp``):
    the same program text runs serial or parallel unchanged."""

    nprocs = 1
    me = 0

    def aggregate(self, mr: "MapReduce", hash_fn) -> None:
        return  # nprocs==1 early-out, src/mapreduce.cpp:403-406

    def gather(self, mr: "MapReduce", nprocs: int) -> None:
        return

    def broadcast(self, mr: "MapReduce", root: int) -> None:
        return

    def allreduce_sum(self, x):
        return x


class _TaskSink:
    """Per-task KV stand-in for mapstyle-2 worker threads: records the
    callback's add traffic, replayed into the real KeyValue in task order
    once all workers finish (KeyValue's append buffers are not
    thread-safe, and serial replay keeps output order deterministic)."""

    __slots__ = ("_calls",)

    def __init__(self):
        self._calls: list = []

    def add(self, key, value):
        self._calls.append(("add", key, value))

    def add_batch(self, keys, values):
        self._calls.append(("add_batch", keys, values))

    def add_frame(self, frame):
        self._calls.append(("add_frame", frame))

    def add_kv(self, other):
        self._calls.append(("add_kv", other))

    def replay(self, kv: KeyValue):
        for name, *args in self._calls:
            getattr(kv, name)(*args)
        self._calls.clear()


def _fusible(fn):
    """Defer this op into the plan recorder (plan/) when one is active —
    either an explicit ``with mr.pipeline():`` block or the ``fuse=1``
    setting (MRTPU_FUSE).  The deferred call returns a lazy
    :class:`~..plan.recorder.PendingCount`; barriers (maps, gather,
    scans, print, stats, save/load, copy) flush the plan, and the fuser
    replays any non-fusible stage through the undeferred method —
    ``_plan_replaying`` guards that re-entry."""
    op = fn.__name__

    @functools.wraps(fn)
    def wrapper(self, *args, **kw):
        if not self._plan_replaying:
            if not _defer_ok(op, args, kw):
                # user-callback ops (host reduces, ptr-carrying calls,
                # comparator sorts) can have arbitrary Python side
                # effects the caller observes right after the call —
                # the sssp shape reduce(f, ptr=open_mr), closure
                # counters — and they never fuse anyway: they are a
                # barrier, not a recorded stage
                self._flush_plan()
                return fn(self, *args, **kw)
            rec = self._plan
            if rec is None and self.settings.fuse:
                from ..plan.recorder import PlanRecorder
                rec = self._plan = PlanRecorder(self, auto=True)
            if rec is not None:
                return rec.record(op, args, kw)
        return fn(self, *args, **kw)
    return wrapper


def _defer_ok(op: str, args: tuple, kw: dict) -> bool:
    """Only ops that could possibly fuse are worth deferring: aggregate,
    convert, int-flag sorts and registered-kernel reduces.  Anything
    carrying a user callback runs as a barrier instead."""
    if op in ("sort_keys", "sort_values"):
        arg = args[0] if args else kw.get("flag_or_cmp", 1)
        return not callable(arg)
    if op != "reduce":
        return True          # aggregate / convert
    if kw.get("ptr") is not None or (len(args) > 1 and args[1] is not None):
        return False
    fn = args[0] if args else kw.get("func")
    from ..plan.fuser import _kernel_op
    return fn is not None and _kernel_op(fn) is not None


def _traced(fn):
    """Wrap an MR op in a tracer span (gpu_mapreduce_tpu/obs): wall
    time, counter deltas (shuffle/pad/spill bytes, HBM hi-water) and the
    returned global pair count land as span attributes; nesting follows
    the call structure (collate parents aggregate+convert, compress
    parents convert+reduce, the shuffle/ingest child spans hang under
    their op).  Disabled tracing costs one attribute check."""
    op = fn.__name__

    @functools.wraps(fn)
    def wrapper(self, *args, **kw):
        tr = self.tracer
        if not tr.enabled:
            return fn(self, *args, **kw)
        with tr.span(op, cat="mr_op",
                     shards=self.backend.nprocs) as sp:
            out = fn(self, *args, **kw)
            if isinstance(out, int):
                sp.set(npairs=out)
            if op.startswith("map_file"):
                sp.set(ingest=self.last_ingest.get("mode"))
            return out
    return wrapper


class MapReduce:
    """One MapReduce object owns at most one KV and/or one KMV
    (reference src/mapreduce.h:43-44)."""

    def __init__(self, comm=None, trace=None, metrics_port=None,
                 **settings):
        self.error = Error()
        self.settings = Settings(**settings)
        self.settings.validate(self.error)
        self.counters = global_counters()
        # fault-tolerance knobs (ft/): apply MRTPU_FAULTS / MRTPU_RETRY
        # when they changed — two getenv+compare when they did not
        from ..ft import configure_from_env as _ft_env
        _ft_env()
        # tracing is process-global (obs/): `trace=path` turns on the
        # JSONL sink (the MRTPU_TRACE env var does the same without a
        # code change); `trace=True` enables the in-memory ring only
        from ..obs import get_tracer
        self.tracer = get_tracer()
        if trace:
            self.tracer.enable(jsonl=trace if isinstance(trace, str)
                               else None)
        # live metrics are process-global too: `metrics_port=N` arms the
        # registry + span bridge and serves /metrics on localhost:N (the
        # MRTPU_METRICS_PORT env var does the same; obs/httpd.py).  A
        # bind failure (port already taken by a sibling process) warns
        # instead of killing the constructor — metrics must never fail
        # the app they observe
        if metrics_port is not None:
            try:
                from ..obs.httpd import ensure_server
                ensure_server(int(metrics_port))
            except Exception as e:
                self.error.warning(
                    f"metrics server on port {metrics_port!r} failed "
                    f"({e!r}); continuing without live export")
        if comm is None or comm == 1 or (isinstance(comm, int)):
            self.backend = SerialBackend()
        else:
            # a jax.sharding.Mesh → distributed backend (parallel/)
            from ..parallel.backend import MeshBackend
            self.backend = MeshBackend(comm)
        self._kv_data: Optional[KeyValue] = None
        self._kmv_data: Optional[KeyMultiValue] = None
        self._open = False
        self._last_stats: dict = {}
        self._plan = None              # active plan recorder (plan/)
        self._plan_replaying = False   # fuser is replaying a stage
        self.last_exchange = None      # per-call ExchangeCallStats
        # which path the last file map took ({"mode": "mesh"|"host", …},
        # parallel/ingest.py); None-mode until a file map runs
        self.last_ingest: dict = {"mode": None}
        self._ingest_pool_obj = None   # shared ingest executor (lazy)

    # ------------------------------------------------------------------
    # settings passthrough (reference exposes them as public members)
    # ------------------------------------------------------------------
    def __getattr__(self, name):
        s = self.__dict__.get("settings")
        if s is not None and hasattr(s, name):
            return getattr(s, name)
        raise AttributeError(name)

    def set(self, **kw):
        candidate = _copymod.deepcopy(self.settings)
        for k, v in kw.items():
            if not hasattr(candidate, k):
                self.error.all(f"unknown setting {k!r}")
            setattr(candidate, k, v)
        candidate.validate(self.error)  # raises before touching live settings
        self.settings = candidate
        # turning fusion off is a barrier: an active fuse=1 auto
        # recorder must not keep deferring past the user's fuse=0
        if not candidate.fuse and self._plan is not None and self._plan.auto:
            self._flush_plan()
        return self

    # ------------------------------------------------------------------
    # datasets: reading kv/kmv is a plan barrier (plan/) — any pending
    # deferred chain materializes first, so direct readers (apps, oink
    # commands, checkpoint, user code) never see stale/None state under
    # fuse=1.  During plan execution the recorder's stage list is empty
    # (recorder.flush swaps it out first), so these reads cost nothing.
    # ------------------------------------------------------------------
    @property
    def kv(self) -> Optional[KeyValue]:
        rec = self.__dict__.get("_plan")
        if rec is not None and rec.stages:
            self._flush_plan()
        return self._kv_data

    @kv.setter
    def kv(self, value: Optional[KeyValue]) -> None:
        # writes are barriers too: pending deferred ops were issued
        # against the OLD dataset — eager semantics would have run them
        # before the caller's assignment, so run them now
        rec = self.__dict__.get("_plan")
        if rec is not None and rec.stages:
            self._flush_plan()
        self._kv_data = value

    @property
    def kmv(self) -> Optional[KeyMultiValue]:
        rec = self.__dict__.get("_plan")
        if rec is not None and rec.stages:
            self._flush_plan()
        return self._kmv_data

    @kmv.setter
    def kmv(self, value: Optional[KeyMultiValue]) -> None:
        rec = self.__dict__.get("_plan")
        if rec is not None and rec.stages:
            self._flush_plan()
        self._kmv_data = value

    # ------------------------------------------------------------------
    # lazy pipeline recording (plan/)
    # ------------------------------------------------------------------
    def pipeline(self):
        """Record the ops issued inside the block and run them fused::

            with mr.pipeline():
                mr.aggregate(); mr.convert(); mr.reduce(count, batch=True)

        Exit (or any barrier op) fuses maximal device-tier runs into
        single compiled programs via the plan cache; non-fusible stages
        fall back to the eager path.  The same recording starts
        implicitly per-op under ``fuse=1`` / ``MRTPU_FUSE=1``."""
        import contextlib

        @contextlib.contextmanager
        def _ctx():
            from ..plan.recorder import PlanRecorder
            prev = self._plan
            rec = self._plan = PlanRecorder(self)
            if prev is not None:
                # adopt a pre-existing recorder's pending stages (e.g.
                # fuse=1 deferred an aggregate before this block) so
                # they execute in issue order — and may fuse with ours
                rec.stages, prev.stages = prev.stages, []
                if prev.auto:
                    prev = None
            try:
                yield rec
            except BaseException:
                # abort, don't run heavy deferred compute mid-unwind or
                # let a replay error mask the user's exception: the
                # un-flushed tail is discarded (prefixes a mid-block
                # barrier already flushed stay applied)
                rec.stages.clear()
                raise
            finally:
                if self._plan is rec:
                    self._plan = prev
                rec.flush()
        return _ctx()

    def _flush_plan(self) -> None:
        """Execute any pending recorded plan (the barrier hook).  Auto
        recorders (fuse=1) uninstall; an explicit pipeline() recorder
        stays installed and keeps recording after the barrier."""
        rec = self._plan
        if rec is None:
            return
        # the plan barrier is a cancellation barrier too: a cancelled
        # request's pending chain is never dispatched (the request
        # owner then calls discard_plan so the RELEASE path's dataset
        # reads — also flush barriers — cannot dispatch it either)
        from ..obs.context import barrier_check
        barrier_check()
        if rec.auto:
            self._plan = None
        rec.flush()

    def discard_plan(self) -> None:
        """Drop any pending recorded stages WITHOUT executing them —
        the cancellation path (serve/session.py): a cancelled request's
        deferred chain must not dispatch from the cleanup that releases
        its frames (``kv``/``kmv`` reads are flush barriers).  The
        stages' PendingCounts stay unresolved and raise if ever read,
        like any discarded pending value."""
        rec = self._plan
        if rec is None:
            return
        self._plan = None
        rec.stages.clear()

    # ------------------------------------------------------------------
    # internal helpers
    # ------------------------------------------------------------------
    def _ingest_pool(self):
        """ONE ThreadPoolExecutor per MapReduce for mapstyle-2 ingest
        (run_sinks / _run_tasks) instead of a fresh executor per call —
        thread spin-up was per-shard overhead on the pipelined mesh
        ingest.  Sized once at min(cpu, 16).  A weakref finalizer shuts
        the pool down when the MR is collected, so a long-lived process
        churning MapReduce objects never accumulates idle worker
        threads (the executor must not anchor a reference cycle back to
        self)."""
        pool = self._ingest_pool_obj
        if pool is None:
            import os as _os
            import weakref
            from concurrent.futures import ThreadPoolExecutor
            pool = ThreadPoolExecutor(
                max_workers=max(1, min((_os.cpu_count() or 4), 16)),
                thread_name_prefix="mrtpu-ingest")
            self._ingest_pool_obj = pool
            weakref.finalize(self, pool.shutdown, False)
        return pool

    def _new_kv(self, name="kv") -> KeyValue:
        return KeyValue(self.settings, self.error, self.counters, name)

    def _new_kmv(self) -> KeyMultiValue:
        return KeyMultiValue(self.settings, self.error, self.counters)

    def _require_kv(self, op: str) -> KeyValue:
        self._flush_plan()   # barrier: readers need the real dataset
        if self.kv is None or not self.kv.complete_done:
            self.error.all(f"Cannot {op} without completed KeyValue")
        return self.kv

    def _require_kmv(self, op: str) -> KeyMultiValue:
        self._flush_plan()
        if self.kmv is None:
            self.error.all(f"Cannot {op} without KeyMultiValue")
        return self.kmv

    def _start_map(self, addflag: int) -> KeyValue:
        self._flush_plan()   # a new map consumes/replaces the dataset
        if self.kmv is not None:
            self.kmv.free()
            self.kmv = None
        if addflag and self.kv is not None:
            self.kv.append()
        else:
            if self.kv is not None:
                self.kv.free()
            self.kv = self._new_kv()
        return self.kv

    def _finish_kv(self, op: str) -> int:
        if self._open:
            return self.kv.nkv
        n = self.kv.complete()
        n = int(self.backend.allreduce_sum(n))
        self._op_stats(op, nkv=n)
        return n

    def _op_stats(self, op: str, **kw):
        self._last_stats = {"op": op, **kw}
        # ft/: one durable journal record per completed barrier op (and
        # the programmatic auto-checkpoint trigger); a dict-check no-op
        # when MRTPU_JOURNAL is unarmed
        from ..ft.journal import note_op
        note_op(self, op, kw.get("nkv", kw.get("nkmv")))
        if self.settings.verbosity:
            self.kv_stats(self.settings.verbosity, _op=op)
            if self.settings.verbosity >= 2 and self._op_snap is not None:
                c = self.counters
                w0, r0, s0 = self._op_snap
                dw, dr, ds = c.wsize - w0, c.rsize - r0, c.cssize - s0
                if dw or dr or ds:
                    print(f"  {op} I/O: {dw / (1 << 20):.3g} Mb spilled, "
                          f"{dr / (1 << 20):.3g} Mb re-read, "
                          f"{ds / (1 << 20):.3g} Mb shuffled")
        self._op_snap = None

    _op_snap = None

    def _begin_op(self) -> Timer:
        """Per-op start: timer + counter snapshot for verbosity=2 deltas
        (the reference's file_stats/stats per-op reporting,
        src/mapreduce.cpp:3112-3226).  The obs/ span layer snapshots the
        same counters independently (Span.__enter__) — kept separate on
        purpose: the print path must work with tracing disabled, and the
        disabled tracer must cost nothing, so neither can own the other's
        snapshot.

        Also the per-op cancellation barrier: a request cancelled (or
        past its deadline) stops HERE, before the op does any work —
        the dataset is whatever the previous op left, consistent and
        checkpointable (obs/context.barrier_check)."""
        from ..obs.context import barrier_check
        barrier_check()
        c = self.counters
        self._op_snap = (c.wsize, c.rsize, c.cssize)
        return Timer()

    def _shard_counts(self, which: str = "kv"):
        """Per-shard row counts: mesh datasets report real shard counts;
        host datasets report one value per frame (the serial 'procs')."""
        ds = self.kv if which == "kv" else self.kmv
        if ds is None:
            return []
        out = []
        for f in ds._frames:
            counts = getattr(f, "gcounts" if which == "kmv" else "counts",
                             None)
            if counts is not None:
                out.extend(int(x) for x in counts)
            else:
                out.append(f.n if hasattr(f, "n") else len(f))
        return out

    def _tier_note(self, op: str, fr) -> None:
        """verbosity≥2: say which tier an op ran on — a silent fall to the
        host per-pair path is a 1000× slowdown the user should see.  The
        same fact lands on the current span (obs/) for machine readers."""
        from .frame import KMVFrame, KVFrame as _KVF
        host = isinstance(fr, (KMVFrame, _KVF))
        self.tracer.annotate(tier="host" if host else "device",
                             rows=len(fr))
        if self.settings.verbosity >= 2:
            n = len(fr)
            print(f"  {op}: {'host per-row' if host else 'device batch'} "
                  f"tier ({n} rows)")

    # ------------------------------------------------------------------
    # map family (reference src/mapreduce.cpp:1044-1642)
    # ------------------------------------------------------------------
    def _run_tasks(self, kv, tasks, call: Callable) -> int:
        """Dispatch ``call(itask, payload, sink)`` over an iterable of
        task payloads, honouring mapstyle (reference map_tasks
        scheduling, src/mapreduce.cpp:1136-1213).  Returns the task count.

        * 0 chunk / 1 stride — under one controller both reduce to "run
          every task here", in task order;
        * 2 master-slave — the reference hands tasks to ranks on demand
          from a master work queue.  The controller analog is a dynamic
          thread pool: workers PULL the next task when free (good for
          I/O-bound file ingestion, where CPython releases the GIL).
          Each task writes a private buffer; buffers replay into the
          real KV in task order — so the result is bit-identical to
          styles 0/1 (*stronger* than the reference, whose master-slave
          pair order is schedule-dependent) and the KV's normal spill
          budget applies as tasks complete.  A bounded in-flight window
          backpressures both the payload producer (chunk readers) and
          buffered output — peak extra memory is O(window) tasks, never
          O(ntasks)."""
        from ..ft.retry import ingest_task
        onfault = self.settings.onfault
        if self.settings.mapstyle != 2:
            n = 0
            for itask, payload in enumerate(tasks):
                # ft/: per-task fault points + retry/quarantine policy;
                # attempts buffer into a private sink only when the
                # policy is armed (zero-delta fast path otherwise), and
                # a raw OSError wraps as MRError naming file/task
                ingest_task(call, itask, payload, kv, onfault=onfault,
                            private_sink=False)
                n += 1
            return n
        from collections import deque

        from ..obs.context import bind as _ctx_bind
        ingest_task = _ctx_bind(ingest_task)   # pool tasks charge the
        #                                        submitting request
        pool = self._ingest_pool()     # shared per-MR executor
        nworkers = pool._max_workers
        window = 4 * nworkers
        inflight: deque = deque()      # (future, sink) in task order
        n = 0

        def drain_one():
            fut, sink = inflight.popleft()
            fut.result()               # propagate callback exceptions
            sink.replay(kv)

        try:
            for itask, payload in enumerate(tasks):
                if len(inflight) >= window:
                    drain_one()
                sink = _TaskSink()
                inflight.append(
                    (pool.submit(ingest_task, call, itask, payload, sink,
                                 onfault=onfault), sink))
                n += 1
            while inflight:
                drain_one()
        except BaseException:
            for fut, _ in inflight:
                fut.cancel()
            raise
        return n

    @_traced
    def map(self, nmap: int, func: Callable, ptr=None, addflag: int = 0) -> int:
        """Task map: func(itask, kv, ptr) called for nmap tasks
        (reference map(nmap,func,ptr,addflag) → map_tasks,
        src/mapreduce.cpp:1044-1225)."""
        t = self._begin_op()
        kv = self._start_map(addflag)
        self._run_tasks(kv, range(nmap),
                        lambda itask, _task, sink: func(itask, sink, ptr))
        n = self._finish_kv("map")
        self._time("map", t)
        return n


    def _find_inputs(self, files, recurse, readflag) -> List[str]:
        """findfiles under the ft/ discovery policy: a failing path
        surfaces as MRError naming it (never a raw OSError), or —
        under onfault="skip" — quarantines and drops, exactly like the
        same failure noticed one stage later at task-read time."""
        from ..ft.retry import input_unreadable, quarantine_or_raise
        if self.settings.onfault != "skip":
            try:
                return findfiles(files, bool(recurse), bool(readflag))
            except OSError as e:
                raise input_unreadable(e) from e
        names: List[str] = []
        for p in files:
            try:
                names.extend(findfiles([p], bool(recurse),
                                       bool(readflag)))
            except OSError as e:
                quarantine_or_raise(e, p, "skip")
        return names

    @_traced
    def map_files(self, files: Union[str, Sequence[str]], func: Callable,
                  ptr=None, self_flag: int = 0, recurse: int = 0,
                  readflag: int = 0, addflag: int = 0) -> int:
        """File map: func(itask, filename, kv, ptr) per file (reference
        map(nstr,strings,self,recurse,readflag,func,ptr,addflag),
        src/mapreduce.cpp:1060-1092).

        On a mesh backend the ingest is PER-SHARD (parallel/ingest.py):
        each shard's contiguous byte-balanced slice of the file list
        lands on its own device at map time, with byte/object keys
        interned into dest-sharded decode tables — the reference's
        'every rank reads its own files' map stage
        (src/mapreduce.cpp:1102-1225).  ``last_ingest`` records which
        path ran.  Files of fixed-width binary records take a
        ``utils/io.RecordFormat`` as ``func``: the record map."""
        t = self._begin_op()
        if isinstance(files, str):
            files = [files]
        names = self._find_inputs(files, recurse, readflag)
        kv = self._start_map(addflag)
        call = lambda itask, fname, sink: func(itask, fname, sink, ptr)
        if isinstance(func, RecordFormat) and self._mesh_ingest_ok(
                addflag, min_shards=1):
            # fixed-width records: cut into the shards' blocks, on one
            # shard too (the generic map leaves one shard's rows on the
            # host for aggregate to place)
            from ..parallel.ingest import mesh_map_records
            self.last_ingest = mesh_map_records(self, kv, names, func)
        elif self._mesh_ingest_ok(addflag):
            from ..parallel.ingest import mesh_map_files
            self.last_ingest = mesh_map_files(self, kv, names, call)
        else:
            self._run_tasks(kv, names, call)
            self.last_ingest = {"mode": "host"}
        n = self._finish_kv("map_files")
        self._time("map_files", t)
        return n

    def _mesh_ingest_ok(self, addflag: int, min_shards: int = 2) -> bool:
        """Per-shard file ingest preconditions: a multi-shard mesh, a
        fresh KV (addflag appends into an existing — possibly host —
        dataset), and in-core (the out-of-core page/spill budget is the
        host frames' machinery)."""
        from ..parallel.backend import MeshBackend
        return (isinstance(self.backend, MeshBackend)
                and self.backend.nprocs >= min_shards
                and not addflag
                and self.settings.outofcore != 1)

    @_traced
    def map_file_char(self, nmap: int, files, recurse: int, readflag: int,
                      sepchar: Union[str, bytes], delta: int, func: Callable,
                      ptr=None, addflag: int = 0) -> int:
        """Chunk map with single-char separator (reference
        src/mapreduce.cpp:1232-1301,1312-1469): split files into ~nmap chunks
        ending on sepchar; func(itask, chunk_bytes, kv, ptr)."""
        return self._map_chunks(nmap, files, recurse, readflag,
                                _to_bytes(sepchar), delta, func, ptr, addflag)

    @_traced
    def map_file_str(self, nmap: int, files, recurse: int, readflag: int,
                     sepstr: Union[str, bytes], delta: int, func: Callable,
                     ptr=None, addflag: int = 0) -> int:
        """Chunk map with string separator (reference map_chunks sepstr
        variant)."""
        return self._map_chunks(nmap, files, recurse, readflag,
                                _to_bytes(sepstr), delta, func, ptr, addflag)

    def _map_chunks(self, nmap, files, recurse, readflag, sep, delta,
                    func, ptr, addflag) -> int:
        t = self._begin_op()
        if isinstance(files, str):
            files = [files]
        names = self._find_inputs(files, recurse, readflag)
        if not names:
            self.error.all("No files found for chunked map")
        per_file = max(1, nmap // max(1, len(names)))
        kv = self._start_map(addflag)
        call = lambda itask, chunk, sink: func(itask, chunk, sink, ptr)
        if self._mesh_ingest_ok(addflag):
            from ..parallel.ingest import mesh_map_chunks
            self.last_ingest = mesh_map_chunks(self, kv, names, per_file,
                                               sep, delta, call)
        else:
            from ..exec import prefetch_iter
            from ..ft.retry import (ingest_active, ingest_read,
                                    input_unreadable)
            onfault = self.settings.onfault

            def chunk_stream():
                # each file reads under the ft/ ingest.read policy:
                # retry budget, MRError naming the file, quarantine-
                # skip under onfault=skip (None = file skipped).  With
                # the policy disarmed chunks stay LAZY per chunk (the
                # host path's memory property) — a retry needs the
                # whole file's chunks re-readable, so only the armed
                # path materializes per file
                for fname in names:
                    if not ingest_active(onfault):
                        it = file_chunks(fname, per_file, sep, delta)
                        while True:
                            try:
                                chunk = next(it)
                            except StopIteration:
                                break
                            except OSError as e:
                                raise input_unreadable(e, fname) from e
                            yield chunk
                        continue
                    chunks = ingest_read(
                        lambda f=fname: list(file_chunks(f, per_file,
                                                         sep, delta)),
                        file=fname, onfault=onfault)
                    if chunks is not None:
                        yield from chunks
            # the serial chunk reader feeds the window lazily — under
            # mapstyle 2 backpressure holds O(window) chunks, not all.
            # exec/ prefetch overlaps the file read of chunk N+1 with
            # chunk N's callback (MRTPU_PREFETCH extra chunks resident)
            self._run_tasks(kv, prefetch_iter(chunk_stream(),
                                              path="ingest.serial"), call)
            self.last_ingest = {"mode": "host"}
        n = self._finish_kv("map_chunks")
        self._time("map_chunks", t)
        return n

    @_traced
    def map_mr(self, mr: "MapReduce", func: Callable, ptr=None,
               addflag: int = 0, batch: bool = False) -> int:
        """Map over an existing MR's KV pairs (reference map(mr,func,...),
        src/mapreduce.cpp:1560-1642; self-map via snapshot 1584-1601).

        host path: func(itask, key, value, kv, ptr) per pair;
        batch path: func(frame, kv, ptr) per KVFrame (vectorised)."""
        t = self._begin_op()
        src = mr._require_kv("map over")
        src_frames = list(src.frames())  # snapshot supports self-map
        kv = self._start_map(addflag)
        itask = 0
        for fr in src_frames:
            if batch:
                if not isinstance(fr, KVFrame):
                    # the callback may add_frame(fr) into the new KV —
                    # mark sharded frames so donation (exec/) never
                    # deletes arrays the snapshot still references
                    fr._shared = True
                func(fr, kv, ptr)
                itask += 1
            else:
                for k, v in fr.pairs():
                    func(itask, k, v, kv, ptr)
                    itask += 1
        n = self._finish_kv("map_mr")
        self._time("map_mr", t)
        return n

    # ------------------------------------------------------------------
    # shuffle / distribution ops
    # ------------------------------------------------------------------
    @_fusible
    @_traced
    def aggregate(self, hash_fn: Optional[Callable] = None) -> int:
        """THE shuffle: each key to one proc — user hash or
        hashlittle(key)%nprocs (reference src/mapreduce.cpp:385-563;
        call stack SURVEY.md §3.2).  Serial backend: no-op."""
        t = self._begin_op()
        kv = self._require_kv("aggregate")
        self.backend.aggregate(self, hash_fn)
        self._op_stats("aggregate", nkv=kv.nkv)
        self._time("aggregate", t, comm=True)
        return int(self.backend.allreduce_sum(kv.nkv))

    @_traced
    def broadcast(self, root: int = 0) -> int:
        """Replicate root's KV on all procs (reference
        src/mapreduce.cpp:569-623)."""
        kv = self._require_kv("broadcast")
        self.backend.broadcast(self, root)
        return int(self.backend.allreduce_sum(kv.nkv))

    @_traced
    def gather(self, nprocs: int) -> int:
        """Funnel KV onto the first nprocs procs (reference
        src/mapreduce.cpp:893-1036)."""
        kv = self._require_kv("gather")
        if nprocs <= 0:
            self.error.all("Cannot gather to fewer than 1 processor")
        self.backend.gather(self, nprocs)
        return int(self.backend.allreduce_sum(kv.nkv))

    @_traced
    def scrunch(self, nprocs: int, key) -> int:
        """gather + collapse (reference src/mapreduce.cpp:2075-2095)."""
        self.gather(nprocs)
        return self.collapse(key)

    # ------------------------------------------------------------------
    # grouping ops
    # ------------------------------------------------------------------
    def _use_external(self, kv: KeyValue) -> bool:
        """Out-of-core multi-frame host dataset ⇒ stream through the
        external sort/merge instead of consolidating in core."""
        return (self.settings.outofcore == 1 and kv.nframes > 1
                and kv.is_host_dataset())

    def _hbm_budget_bytes(self) -> Optional[int]:
        """Per-shard HBM budget for mesh datasets: maxpage frames ×
        memsize MB — the device-tier reading of the reference's page
        budget (every op runs in 1–7 fixed pages no matter the data,
        doc/Interface_c++.txt:39-59).  None = unlimited (maxpage 0 or
        in-core mode)."""
        s = self.settings
        if s.outofcore != 1 or s.maxpage == 0:
            return None
        return s.memsize * (1 << 20) * s.maxpage

    def _mesh_over_budget(self, kv: KeyValue) -> bool:
        """Whether the mesh-resident per-shard bytes of kv exceed the
        HBM budget (VERDICT r2 #3)."""
        budget = self._hbm_budget_bytes()
        if budget is None or kv.is_host_dataset():
            return False
        from ..parallel.sharded import ShardedKV
        per_shard = sum(f.nbytes() // max(f.nprocs, 1)
                        for f in kv._frames if isinstance(f, ShardedKV))
        return per_shard > budget

    def _demote_mesh_kv(self) -> None:
        """Stream every mesh frame's shard blocks to host frames under
        the page budget (spilling beyond maxpage like any host dataset),
        so convert/sort can run the bounded external path.  One shard
        block is resident at a time; the device dataset frees at the
        end."""
        from .dataset import _split_to_budget
        from ..parallel.sharded import ShardedKV
        kv = self.kv
        newkv = self._new_kv()
        # kv.frames(), not kv._frames: spilled host frames load lazily
        # (a _Spilled record has no to_host) and sharded frames stream
        # per shard block
        for fr in kv.frames():
            if isinstance(fr, ShardedKV):
                for p in range(fr.nprocs):
                    if int(fr.counts[p]):
                        for piece in _split_to_budget(
                                fr.shard_to_host(p), self.settings):
                            newkv._push_frame(piece)
            else:
                for piece in _split_to_budget(
                        fr if isinstance(fr, KVFrame) else fr.to_host(),
                        self.settings):
                    newkv._push_frame(piece)
        kv.free()
        newkv.nkv = sum(newkv._frame_n(f) for f in newkv._frames)
        newkv.complete_done = True
        self.kv = newkv

    @_fusible
    @_traced
    def convert(self) -> int:
        """Local KV→KMV grouping (reference src/mapreduce.cpp:861-886 →
        KeyMultiValue::convert; here sort+segment, SURVEY.md §3.3).  An
        out-of-core multi-frame dataset streams: external sort runs →
        k-way merge → group-boundary frame cuts, in ~one page budget of
        memory (the Spool cascade's job, src/mapreduce.cpp:2359-2633)."""
        t = self._begin_op()
        kv = self._require_kv("convert")
        self.kmv = self._new_kmv()
        if self._mesh_over_budget(kv):
            # a mesh dataset past the per-shard HBM budget demotes to
            # host page frames and groups through the external path
            self._demote_mesh_kv()
            kv = self.kv
        if self._use_external(kv):
            from .external import external_sorted_chunks, group_stream
            chunks = external_sorted_chunks(kv.frames(), "key",
                                            self.settings, self.counters)
            for kmv_frame in group_stream(chunks):
                self.kmv.push(kmv_frame)
        else:
            frame = kv.one_frame()
            if isinstance(frame, KVFrame):
                kmv_frame = group_frame(frame)
                if self.tracer.enabled:
                    from ..obs import names
                    self.tracer.annotate(**{
                        names.ATTR_ROWS: len(frame),
                        names.ATTR_GROUPS: len(kmv_frame),
                        names.ATTR_GROUP_ROWS_MAX: int(
                            kmv_frame.nvalues.max(initial=0))})
            else:  # ShardedKV → per-shard sort+segment under shard_map
                from ..parallel.group import convert_sharded
                kmv_frame = convert_sharded(frame, self.counters)
            self.kmv.push(kmv_frame)
        kv.free()
        self.kv = None
        n = self.kmv.complete()
        self._op_stats("convert", nkmv=n)
        self._time("convert", t)
        return int(self.backend.allreduce_sum(n))

    @_traced
    def collate(self, hash_fn: Optional[Callable] = None) -> int:
        """aggregate + convert (reference src/mapreduce.cpp:710-738)."""
        self.aggregate(hash_fn)
        return self.convert()

    @_traced
    def clone(self) -> int:
        """KV→KMV, each pair its own 1-value group (reference
        src/mapreduce.cpp:631-652).  Sharded input clones per shard on
        device (row i ⇒ group i of size 1)."""
        kv = self._require_kv("clone")
        fr = kv.one_frame()
        if not isinstance(fr, KVFrame):
            from ..parallel.devkernels import clone_sharded
            kmv_frame = clone_sharded(fr)
        else:
            n = len(fr)
            kmv_frame = KMVFrame(fr.key, np.ones(n, np.int64),
                                 np.arange(n + 1, dtype=np.int64), fr.value)
        kv.free()
        self.kv = None
        self.kmv = self._new_kmv()
        self.kmv.push(kmv_frame)
        return int(self.backend.allreduce_sum(self.kmv.complete()))

    @_traced
    def collapse(self, key) -> int:
        """KV→single KMV group per proc: multivalue = [k1,v1,k2,v2,...]
        (reference src/mapreduce.cpp:681-702).  Keys and values must share a
        representable common type (all bytes, or all numeric of one shape) —
        the reference interleaves raw bytes; we interleave typed rows and
        refuse to silently coerce across types."""
        kv = self._require_kv("collapse")
        parts: List[Column] = []
        for fr in kv.frames():      # spilled frames stream one at a time
            fr = fr.to_host()
            if len(fr):
                parts.append(_interleave_frame(fr, self.error))
        values = concat(parts) if parts \
            else DenseColumn(np.zeros(0, np.int64))
        n = len(values)
        kmv_frame = KMVFrame(_rows_to_column([key]), np.asarray([n]),
                             np.asarray([0, n]), values)
        kv.free()
        self.kv = None
        self.kmv = self._new_kmv()
        self.kmv.push(kmv_frame)
        return int(self.backend.allreduce_sum(self.kmv.complete()))

    # ------------------------------------------------------------------
    # reduce family
    # ------------------------------------------------------------------
    @_fusible
    @_traced
    def reduce(self, func: Callable, ptr=None, batch: bool = False,
               block_rows: Optional[int] = None) -> int:
        """Callback per KMV group → new KV (reference
        src/mapreduce.cpp:1769-1867; SURVEY.md §3.4).

        host path: func(key, values_list, kv, ptr) per group;
        batch path: func(kmv_frame, kv, ptr) per KMVFrame — the vectorised
        tier that keeps reduction on device (segment ops).

        ``block_rows``: groups larger than this receive a
        :class:`~.frame.BlockedMultivalue` instead of a list — the
        reference's multi-page "extended" KMV (nvalues==0 signal +
        multivalue_blocks(), src/mapreduce.cpp:1874-1925).  Callbacks use
        ``iter_blocks(mv)`` to handle both uniformly; setting it tiny is
        the ONEMAX stress hook (src/keymultivalue.cpp:43-45)."""
        t = self._begin_op()
        kmv = self._require_kmv("reduce")
        kv = self._new_kv()
        for fr in kmv.frames():
            if batch:
                self._tier_note("reduce(batch)", fr)
                func(fr, kv, ptr)
            elif block_rows is not None:
                self._reduce_blocked(fr, func, kv, ptr, block_rows)
            else:
                self.tracer.annotate(tier="host", groups=len(fr))
                if self.settings.verbosity >= 2:
                    print(f"  reduce: host per-group tier ({len(fr)} groups)")
                for k, vals in fr.groups():
                    func(k, vals, kv, ptr)
        kmv.free()
        self.kmv = None
        self.kv = kv
        return self._finish_kv("reduce")

    @staticmethod
    def _reduce_blocked(fr, func, kv, ptr, block_rows: int):
        if not isinstance(fr, KMVFrame):
            fr = fr.to_host()
        keys = fr.key.tolist()
        for i, k in enumerate(keys):
            if int(fr.nvalues[i]) > block_rows:
                func(k, BlockedMultivalue(fr, i, block_rows), kv, ptr)
            else:
                func(k, fr.group_values(i).tolist(), kv, ptr)

    @_traced
    def compress(self, func: Callable, ptr=None, batch: bool = False,
                 block_rows: Optional[int] = None) -> int:
        """The combiner, KV→KV: every shard's pairs of one key become one
        pair, locally, nothing exchanged (reference
        src/mapreduce.cpp:749-851).  The same KV as :meth:`convert` then
        :meth:`reduce` by ``func``, and as a rule just that; ``block_rows``
        as in :meth:`reduce`.

        Not in one case.  ``func`` one of ``ops/reduces``' registered
        segment reduces (``sum_values``, ``count``, ``min_values``,
        ``max_values``) with ``batch=True``, over a mesh frame of plain
        integer values whose shards each hold at most
        ``parallel/group.COMBINE_GROUPS`` distinct keys, is folded where
        the rows lie by ``parallel/group.combine_sharded`` (program
        ``jit_combine``): the distinct keys by masked minima, one masked
        reduction a key, no sort, gather or scatter of the rows.  Which
        road ran is decided by ``func``, the value's dtype and the
        distinct keys the one count sync finds, never by a setting; a
        traced run's ``compress`` span says ``combined`` 1 or 0."""
        if batch and ptr is None and block_rows is None:
            n = self._combine(func)
            if n is not None:
                return n
        self.convert()
        return self.reduce(func, ptr, batch=batch, block_rows=block_rows)

    def _combine(self, func: Callable) -> Optional[int]:
        """:meth:`compress` by the combiner where it applies: the new
        KV's pairs, or None with the dataset as it was."""
        op = getattr(func, "segment_op", None)
        if op is None or self._plan is not None or self.settings.fuse:
            return None         # a callback; or the planner's to fuse
        from ..parallel.group import combine_sharded, combines
        kv = self._require_kv("compress")
        if kv.nframes != 1 or self._mesh_over_budget(kv):
            return None
        frame = kv.one_frame()
        if not combines(op, frame):
            return None
        self._begin_op()
        out = combine_sharded(frame, op)
        if out is None:
            return None
        kv.free()
        self.kv = self._new_kv()
        self.kv.add_frame(out)
        return self._finish_kv("compress")

    # ------------------------------------------------------------------
    # scan / print (read-only)
    # ------------------------------------------------------------------
    @_traced
    def scan_kv(self, func: Callable, ptr=None, batch: bool = False) -> int:
        """Read-only iteration over KV pairs (reference
        src/mapreduce.cpp:1933-1997)."""
        kv = self._require_kv("scan")
        for fr in kv.frames():
            if batch:
                func(fr, ptr)
            else:
                for k, v in fr.pairs():
                    func(k, v, ptr)
        return int(self.backend.allreduce_sum(kv.nkv))

    @_traced
    def scan_kmv(self, func: Callable, ptr=None, batch: bool = False,
                 block_rows: Optional[int] = None) -> int:
        """Read-only iteration over KMV groups (reference
        src/mapreduce.cpp:2000-2065).  ``block_rows`` as in :meth:`reduce`
        (the reference's scan shares the multi-block machinery)."""
        kmv = self._require_kmv("scan")
        for fr in kmv.frames():
            if batch:
                func(fr, ptr)
            elif block_rows is not None:
                self._reduce_blocked(
                    fr, lambda k, mv, _kv, p: func(k, mv, p), None, ptr,
                    block_rows)
            else:
                for k, vals in fr.groups():
                    func(k, vals, ptr)
        return int(self.backend.allreduce_sum(kmv.nkmv))

    def print(self, nstride: int = 1, kflag: int = -1, vflag: int = -1,
              file=None, fflag: int = 0) -> int:
        """Formatted dump of KV pairs or KMV groups (reference print variants
        src/mapreduce.cpp:1671-1761; type decoders keyvalue.cpp:773-835).
        kflag/vflag are accepted for API parity; columns self-describe, so
        they only force integer/float/string formatting when >=0."""
        self._flush_plan()
        out = sys.stdout if file is None else (open(file, "a") if fflag else open(file, "w"))
        try:
            if self.kv is not None:
                count = 0
                for fr in self.kv.frames():
                    for k, v in fr.pairs():
                        if count % nstride == 0:
                            out.write(f"{_fmt(k, kflag)} {_fmt(v, vflag)}\n")
                        count += 1
                return self.kv.nkv
            if self.kmv is not None:
                for fr in self.kmv.frames():
                    for k, vals in fr.groups():
                        out.write(f"{_fmt(k, kflag)} " +
                                  " ".join(_fmt(v, vflag) for v in vals) + "\n")
                return self.kmv.nkmv
            self.error.all("Cannot print without KeyValue or KeyMultiValue")
        finally:
            if file is not None:
                out.close()

    # ------------------------------------------------------------------
    # sorting (reference src/mapreduce.cpp:2102-2352)
    # ------------------------------------------------------------------
    @_fusible
    @_traced
    def sort_keys(self, flag_or_cmp: Union[int, Callable] = 1) -> int:
        """Per-proc sort of KV by key.  int flag: |flag| selects the
        reference's pre-built comparator family (moot for typed columns),
        sign selects direction (reference flags ±1..6,
        src/mapreduce.cpp:2102-2126,2692-2802).  Callable: compare(a,b)→-1/0/1
        (appcompare)."""
        return self._sort_kv(by="key", flag_or_cmp=flag_or_cmp)

    @_fusible
    @_traced
    def sort_values(self, flag_or_cmp: Union[int, Callable] = 1) -> int:
        """Per-proc sort of KV by value (reference src/mapreduce.cpp:2152)."""
        return self._sort_kv(by="value", flag_or_cmp=flag_or_cmp)

    def _sort_kv(self, by: str, flag_or_cmp) -> int:
        t = self._begin_op()
        kv = self._require_kv(f"sort_{by}s")
        if self._mesh_over_budget(kv):
            self._demote_mesh_kv()   # see convert(): HBM budget
            kv = self.kv
        if not callable(flag_or_cmp) and self._use_external(kv):
            return self._sort_kv_external(kv, by, flag_or_cmp < 0, t)
        fr = kv.one_frame()
        if not isinstance(fr, KVFrame):
            interned = getattr(fr, f"{by}_decode", None) is not None
            budget = self._hbm_budget_bytes()
            if interned and budget is not None and fr.nbytes() > budget:
                # the interned device sort is GLOBAL (GSPMD gathers the
                # whole dataset transiently) — past the budget, demote
                # shard-by-shard into page frames (spilling past
                # maxpage) so the bounded external merge applies; a
                # single to_host() frame never qualified for
                # _use_external and just relocated the blow-up from HBM
                # to controller RAM (ADVICE r3)
                self._demote_mesh_kv()
                kv = self.kv
                if not callable(flag_or_cmp) and self._use_external(kv):
                    return self._sort_kv_external(kv, by,
                                                  flag_or_cmp < 0, t)
                fr = kv.one_frame()
            elif not callable(flag_or_cmp):
                # per-shard device sort; an interned byte/object column
                # sorts by an id→rank surrogate built once from the
                # decode table (u64 ids are hashes, so sorting raw ids
                # would not be lexicographic — reference flag 5/6 string
                # semantics, src/mapreduce.cpp:2763-2802) — the dataset
                # itself stays on device (VERDICT r2 #7)
                from ..parallel.group import (sort_interned_sharded,
                                              sort_sharded)
                out = (sort_interned_sharded if interned
                       else sort_sharded)(fr, by,
                                          descending=flag_or_cmp < 0)
                kv.free()
                kv.add_frame(out)
                n = kv.complete()
                self._op_stats(f"sort_{by}s", nkv=n)
                self._time("sort", t)
                return int(self.backend.allreduce_sum(n))
            # comparator callbacks serialize to host
            fr = fr.to_host()
        col = fr.key if by == "key" else fr.value
        if callable(flag_or_cmp):
            order = argsort_column(col, cmp=flag_or_cmp)
        else:
            order = argsort_column(col, descending=flag_or_cmp < 0)
        fr2 = fr.take(order)
        kv.free()
        kv.add_batch(fr2.key, fr2.value)
        n = kv.complete()
        self._op_stats(f"sort_{by}s", nkv=n)
        self._time("sort", t)
        return int(self.backend.allreduce_sum(n))

    def _sort_kv_external(self, kv: KeyValue, by: str, descending: bool,
                          t: Timer) -> int:
        """Out-of-core sort: external runs + k-way merge into a fresh
        spilling dataset; descending flips each ascending chunk and
        reverses the frame order (global order preserved, memory
        bounded)."""
        from .external import external_sorted_chunks
        newkv = self._new_kv()
        for ch in external_sorted_chunks(kv.frames(), by, self.settings,
                                         self.counters):
            if descending:
                ch = ch.take(np.arange(len(ch) - 1, -1, -1))
            newkv._push_frame(ch)
        if descending:
            newkv._frames.reverse()
        newkv.nkv = sum(newkv._frame_n(f) for f in newkv._frames)
        newkv.complete_done = True
        kv.free()
        self.kv = newkv
        self._op_stats(f"sort_{by}s", nkv=newkv.nkv)
        self._time("sort", t)
        return int(self.backend.allreduce_sum(newkv.nkv))

    @_traced
    def sort_multivalues(self, flag_or_cmp: Union[int, Callable] = 1) -> int:
        """Sort values *within* each multivalue (reference
        src/mapreduce.cpp:2210-2352)."""
        t = self._begin_op()
        kmv = self._require_kmv("sort_multivalues")
        new = self._new_kmv()
        for fr in kmv.frames():
            if not isinstance(fr, KMVFrame):  # ShardedKMV
                if callable(flag_or_cmp) or fr.value_decode is not None:
                    # comparator callbacks serialize; interned byte
                    # values decode first — their ids are hashes, not
                    # lexicographic order
                    fr = fr.to_host()
                else:
                    from ..parallel.group import sort_multivalues_sharded
                    new.push(sort_multivalues_sharded(
                        fr, descending=flag_or_cmp < 0))
                    continue
            values = _sort_groups(fr, flag_or_cmp)
            new.push(KMVFrame(fr.key, fr.nvalues, fr.offsets, values))
        kmv.free()
        self.kmv = new
        self._time("sort", t)
        return int(self.backend.allreduce_sum(new.complete()))

    # ------------------------------------------------------------------
    # whole-object ops
    # ------------------------------------------------------------------
    @_traced
    def add(self, mr: "MapReduce") -> int:
        """Append mr's KV pairs to my KV (reference
        src/mapreduce.cpp:348-374)."""
        self._flush_plan()
        src = mr._require_kv("add from")
        if self.kv is None:
            self.kv = self._new_kv()
        else:
            self.kv.append()
        self.kv.add_kv(src)
        return self._finish_kv("add")

    @_traced
    def join(self, build: "MapReduce") -> int:
        """Inner join of my KV (the probe side) with ``build``'s KV on
        equal keys: afterwards my KV holds, for every pair of mine whose
        key occurs in ``build``, ``key -> (my value's words ++ build's
        value's words)``; pairs without a partner are dropped, pairs of
        one key keep their order, ``build`` is left as it was.  ``build``'s
        keys must be unique (a primary key): one that occurs twice is an
        error raised at the op's one sync, never a silently chosen row.
        Keys and values are fixed-width numbers, the keys of one shape and
        dtype, the values of one dtype.

        MR-MPI's idiom for this is tag / ``add`` / ``collate`` / a reduce
        that reads the tags; here it is an op.  On a mesh both sides are
        first placed by the default hash of the key through the exchange
        (on one shard nothing moves), then joined shard by shard
        (``parallel/group.join_sharded``: the keys' sort, the op's one
        sync, the joined rows' values taken); host frames join in numpy
        (``ops/join.py``).  Never deferred into a plan."""
        t = self._begin_op()
        mine = self._require_kv("join")
        other = build._require_kv("join with")
        pf, bf = mine.one_frame(), other.one_frame()
        if not len(pf) or not len(bf):      # nothing can have a partner
            mine.free()
            self._join_note(len(pf), len(bf), 0)
            return self._finish_kv("join")
        for side, fr in (("probe", pf), ("build", bf)):
            dense = (fr.is_dense() if isinstance(fr, KVFrame) else
                     fr.key_decode is None and fr.value_decode is None)
            if not dense:
                self.error.all(f"join: the {side} side's keys and values "
                               f"must be fixed-width numbers")
        kshape = lambda fr: (_data(fr.key).shape[1:], _data(fr.key).dtype)
        if kshape(pf) != kshape(bf):
            self.error.all(f"join: the two sides' keys differ in shape or "
                           f"type ({kshape(pf)} and {kshape(bf)})")
        if _data(pf.value).dtype != _data(bf.value).dtype:
            self.error.all("join: the two sides' values differ in type")
        nin = pf.nbytes() + bf.nbytes()
        if isinstance(pf, KVFrame) and isinstance(bf, KVFrame) \
                and not hasattr(self.backend, "mesh"):
            from ..ops.join import join_frames
            out, twice = join_frames(pf, bf)
        else:
            out, twice = self._join_mesh(pf, bf)
        if twice:
            self.error.all(f"join: {twice} key(s) of the build side occur "
                           f"more than once; its keys must be unique")
        mine.free()
        mine.add_frame(out)
        n = mine.complete()
        self.counters.add(jisize=nin, josize=out.nbytes())
        self._join_note(len(pf), len(bf), n)
        self._op_stats("join", nkv=n)
        self._time("join", t)
        return int(self.backend.allreduce_sum(n))

    def _join_note(self, probe: int, build: int, matched: int) -> None:
        if self.tracer.enabled:     # on the ``join`` op span
            from ..obs import names
            self.tracer.annotate(**{names.ATTR_PROBE_ROWS: probe,
                                    names.ATTR_BUILD_ROWS: build,
                                    names.ATTR_MATCHED_ROWS: matched})

    def _join_mesh(self, pf, bf):
        """Both sides on the mesh with equal keys on one shard, joined
        there.  The frames handed to the exchange are views marked shared:
        it donates neither side's arrays (``build`` stays as it was, and
        so do I until the join has succeeded)."""
        import dataclasses
        from ..parallel.group import join_sharded
        from ..parallel.sharded import shard_frame
        from ..parallel.shuffle import exchange
        mesh = getattr(self.backend, "mesh", None)
        if mesh is None:        # a serial object handed a mesh frame
            mesh = (bf if isinstance(pf, KVFrame) else pf).mesh

        def placed(fr):
            if isinstance(fr, KVFrame):
                fr = shard_frame(fr.to_host(), mesh)
            if fr.nprocs == 1:
                return fr
            view = dataclasses.replace(fr)
            view._shared = True
            return exchange(view, ("hash", None), counters=self.counters)
        return join_sharded(placed(pf), placed(bf))

    def copy(self) -> "MapReduce":
        """Deep copy: new MR with copied settings and data (reference
        src/mapreduce.cpp:269-342)."""
        self._flush_plan()
        mr = MapReduce()
        mr.backend = self.backend
        mr.settings = _copymod.deepcopy(self.settings)
        if self.kv is not None:
            mr.kv = mr._new_kv()
            mr.kv.add_kv(self.kv)
            mr.kv.complete()
        if self.kmv is not None:
            mr.kmv = mr._new_kmv()
            for fr in self.kmv.frames():
                mr.kmv.push(fr)
            mr.kmv.complete()
        return mr

    def stream(self, sources, dir: str, parser: str = "words",
               reduce: str = "count", **kw):
        """Open a standing query whose resident dataset is THIS object
        (stream/engine.py, doc/streaming.md): tail ``sources``
        (append-only files/dirs), cut micro-batches, run the
        ``parser``/``reduce`` chain on each delta and merge it here —
        after every committed batch ``self`` holds the up-to-date
        aggregate and ``self.kv`` reads it like any batch result.
        ``dir`` is the stream's durable home (journal + checkpoints);
        constructing over a directory with committed batches RESUMES
        from the last committed cursor.  Returns the
        :class:`~..stream.Stream` handle (poll_once/drain/status/
        snapshot/close)."""
        from ..stream import Stream
        comm = getattr(self.backend, "mesh", None)
        return Stream(dir, sources, parser=parser, reduce=reduce,
                      comm=comm, resident=self, **kw)

    def open(self, addflag: int = 0):
        """Begin cross-MR adds: my KV accepts kv.add() from other MRs'
        callbacks until close() (reference src/mapreduce.cpp:1648-1664)."""
        self._start_map(addflag)
        self._open = True
        return self.kv

    def close(self) -> int:
        """End cross-MR adds (reference src/mapreduce.cpp:658-672)."""
        if not self._open:
            self.error.all("Cannot close without open")
        self._open = False
        return self._finish_kv("close")

    # ------------------------------------------------------------------
    # stats (reference src/mapreduce.cpp:2937-3066)
    # ------------------------------------------------------------------
    def kv_stats(self, level: int = 0, _op: str = "") -> tuple:
        """Global pair/byte counts; level ≥ 2 adds the per-shard histogram
        (reference kv_stats verbosity=2, src/mapreduce.cpp:2937-2968 via
        write_histo — how imbalance/corruption is detected)."""
        self._flush_plan()
        kv = self.kv
        if kv is None:
            return (0, 0)
        n = int(self.backend.allreduce_sum(kv.nkv))
        nb = int(self.backend.allreduce_sum(kv.nbytes()))
        if level:
            print(f"{n} pairs, {nb / (1 << 20):.3g} Mb of KV data "
                  f"{('after ' + _op) if _op else ''}".rstrip())
            if level >= 2:
                from .runtime import write_histo
                write_histo("KV pairs", self._shard_counts("kv"))
        return (n, nb)

    def kmv_stats(self, level: int = 0) -> tuple:
        self._flush_plan()
        kmv = self.kmv
        if kmv is None:
            return (0, 0, 0)
        g = int(self.backend.allreduce_sum(kmv.nkmv))
        n = int(self.backend.allreduce_sum(kmv.nvalues))
        nb = int(self.backend.allreduce_sum(kmv.nbytes()))
        if level:
            print(f"{g} pairs, {n} values, {nb / (1 << 20):.3g} Mb of KMV data")
            if level >= 2:
                from .runtime import write_histo
                write_histo("KMV groups", self._shard_counts("kmv"))
        return (g, n, nb)

    # ------------------------------------------------------------------
    # checkpoint / restore (capability improvement over the reference,
    # which persists only via print-to-file text — SURVEY.md §5)
    # ------------------------------------------------------------------
    @_traced
    def save(self, path: str) -> int:
        """Checkpoint the current KV or KMV to a directory; returns the
        number of frames written (core/checkpoint.py).  The save runs
        under the ft/ ``checkpoint.save`` retry policy — the directory
        swap is atomic, so a retried save can never mix generations."""
        self._flush_plan()
        from .checkpoint import save as _save
        from ..ft.retry import retry_call
        return retry_call("checkpoint.save", lambda: _save(self, path),
                          detail=path)

    @_traced
    def load(self, path: str) -> int:
        """Replace the dataset with a checkpoint; returns the global
        pair/group count."""
        self._flush_plan()
        from .checkpoint import load as _load
        return _load(self, path)

    # ------------------------------------------------------------------
    # elastic topology (ROADMAP item 4: reshard live, resume anywhere)
    # ------------------------------------------------------------------
    @_traced
    def reshard(self, comm) -> int:
        """Redistribute the resident dataset onto a new topology and
        swap the backend — the live elasticity op (parallel/reshard.py,
        doc/reliability.md#elastic-recovery).

        ``comm``: a ``jax.sharding.Mesh`` of any width (sharded frames
        move N→M as a collective range exchange, global row/group order
        preserved exactly), or ``None``/an int for the serial backend
        (sharded frames compact to host).  Host-resident frames are
        untouched either way — they shard lazily at the next
        ``aggregate`` under the new backend, like fresh data.  Returns
        the global pair/group count, like every mutating op."""
        self._flush_plan()
        from .runtime import Timer as _T
        t = _T()
        if comm is None or isinstance(comm, int):
            new_backend = SerialBackend()
            mesh = None
        else:
            from ..parallel.backend import MeshBackend
            new_backend = MeshBackend(comm)
            mesh = comm
        from ..parallel.reshard import reshard_kmv, reshard_kv
        from ..parallel.sharded import ShardedKMV, ShardedKV
        from ..parallel.shuffle import free_if_donated
        nfrom = self.backend.nprocs

        def move(ds, fr):
            if not isinstance(fr, (ShardedKV, ShardedKMV)):
                return fr
            if mesh is None:
                return fr.to_host()
            if fr.mesh is mesh:
                return fr
            try:
                if isinstance(fr, ShardedKV):
                    return reshard_kv(fr, mesh, counters=self.counters)
                return reshard_kmv(fr, mesh, counters=self.counters)
            except BaseException:
                # donation may have consumed the frame mid-exchange:
                # leave a clean empty dataset, not deleted buffers
                free_if_donated(ds, fr)
                raise
        n = 0
        for ds in (self._kv_data, self._kmv_data):
            if ds is None:
                continue
            out = []
            for fr in ds._frames:
                new = move(ds, fr)
                if new is not fr:
                    self.counters.mem(new.nbytes() - fr.nbytes())
                out.append(new)
            ds._frames = out
        self.backend = new_backend
        if self._kv_data is not None:
            self._kv_data.nkv = sum(self._kv_data._frame_n(f)
                                    for f in self._kv_data._frames)
            n = self._kv_data.nkv
        if self._kmv_data is not None:
            n = self._kmv_data.complete()
        n = int(self.backend.allreduce_sum(n))
        self.counters.add(commtime=t.elapsed())
        self.last_reshard = {"from": nfrom, "to": self.backend.nprocs,
                             "wall_s": round(t.elapsed(), 6), "n": n}
        self._op_stats("reshard", nkv=n)
        return n

    def stats(self) -> dict:
        """The structured cumulative snapshot that ``cummulative_stats``
        prints: every Counters field by name (msizemax, rsize, wsize,
        cssize, crsize, cspad, commtime, msize, ndispatch, the four
        jit_*), plus — when tracing is enabled (obs/) — an ``"ops"``
        per-op aggregate over the span ring (count / total_s / byte sums
        per op name) and ``"programs"``, per program what JAX lowered,
        compiled and loaded since (obs.programs()), plus a
        ``"plan"`` section with the compile-cache telemetry (plan cache
        + bounded shuffle jit caches: hits/misses/evictions) and the
        cumulative fusion-effectiveness counters (``"fusion"``:
        per-group fused/megafused/pallas program counts and dispatch
        savings vs the eager baseline — doc/plan.md), plus an
        ``"exec"`` section with the async-overlap telemetry (per-path
        overlap ratios + active knobs — doc/perf.md), plus —
        when the metrics registry is armed (obs/metrics.py) — a
        ``"metrics"`` section with the full labeled registry snapshot
        (op latency histograms, exchange byte counters, gauges)."""
        self._flush_plan()   # barrier: counters must include the chain
        out = self.counters.snapshot()
        if self.tracer.enabled:
            out["ops"] = self.tracer.stats()
            from ..obs import programs
            out["programs"] = programs()
        from ..plan.cache import cache_stats
        out["plan"] = cache_stats()
        # overlap telemetry (exec/): per-path busy/wait seconds and the
        # overlap ratio the mrtpu_overlap_ratio gauge exposes
        from ..exec import exec_stats
        out["exec"] = exec_stats()
        # fault-tolerance telemetry (ft/): retry outcomes per site,
        # faults injected, quarantine accounting, journal progress
        from ..ft import ft_stats
        out["ft"] = ft_stats()
        from ..obs import metrics as _metrics
        if _metrics.enabled():
            out["metrics"] = _metrics.snapshot()
        return out

    def cummulative_stats(self, level: int = 1, reset: int = 0):
        # a formatting consumer of the same snapshot stats() returns —
        # the two can never disagree
        s = self.stats()
        if level:
            print(f"Cummulative hi-water mem = {s['msizemax'] / (1 << 20):.3g} Mb")
            print(f"Cummulative spill I/O = {s['rsize'] / (1 << 20):.3g} Mb read, "
                  f"{s['wsize'] / (1 << 20):.3g} Mb written")
            print(f"Cummulative comm = {s['cssize'] / (1 << 20):.3g} Mb sent, "
                  f"{s['crsize'] / (1 << 20):.3g} Mb received, "
                  f"{s['cspad'] / (1 << 20):.3g} Mb padding, "
                  f"{s['commtime']:.3g} secs")
        if reset:
            self.counters.__init__()
        return self.counters

    def _time(self, op: str, t: Timer, comm: bool = False):
        dt = t.elapsed()
        if comm:
            self.counters.add(commtime=dt)
        if self.settings.timer:
            print(f"{op} time (secs) = {dt:.6g}")
            if self.settings.timer >= 2:
                # the controller orchestrates, so per-shard TIME is not
                # observable the way the reference's per-proc barriers are
                # (src/mapreduce.cpp:3112-3128); the per-shard ROW histogram
                # is the imbalance signal that histogram exposed
                from .runtime import write_histo
                which = "kv" if self.kv is not None else "kmv"
                write_histo(f"{op} rows", self._shard_counts(which))


# ---------------------------------------------------------------------------

def _data(col):
    """The array behind a frame's key or value: a host column's ``data``,
    a mesh frame's device array itself."""
    return getattr(col, "data", col)


def _to_bytes(s) -> bytes:
    return s.encode() if isinstance(s, str) else bytes(s)


def _rows_to_column(rows: list) -> Column:
    first = rows[0] if rows else 0
    if isinstance(first, (bytes, str)):
        return BytesColumn([r.encode() if isinstance(r, str) else r
                            for r in rows])
    from .dataset import rows_to_array
    return DenseColumn(rows_to_array(rows))


def _sort_groups(fr: KMVFrame, flag_or_cmp) -> Column:
    """Sort the values inside every group of a host KMVFrame.  Dense
    scalar values sort in ONE stable lexsort over (group, value) — no
    per-group Python; comparator callbacks and non-scalar values keep
    the per-group path."""
    if not callable(flag_or_cmp) and isinstance(fr.values, DenseColumn):
        vals = np.asarray(fr.values.data)
        if vals.ndim == 1:
            seg = np.repeat(np.arange(len(fr), dtype=np.int64),
                            np.asarray(fr.nvalues, dtype=np.int64))
            order = np.lexsort((vals, seg))     # ascending within groups
            if flag_or_cmp < 0:
                # descending: reverse each group's slice of the
                # ascending order (offsets arithmetic, still no loop)
                off = np.asarray(fr.offsets)
                pos = np.arange(len(vals), dtype=np.int64)
                order = order[off[seg] + off[seg + 1] - 1 - pos]
            return DenseColumn(vals[order])
    pieces = []
    for i in range(len(fr)):
        col = fr.group_values(i)
        if callable(flag_or_cmp):
            order = argsort_column(col, cmp=flag_or_cmp)
        else:
            order = argsort_column(col, descending=flag_or_cmp < 0)
        pieces.append(col.take(order))
    return concat(pieces) if pieces else fr.values


def _interleave_frame(fr: KVFrame, error: Error) -> Column:
    """Vectorised collapse() interleave of one frame: [k1,v1,k2,v2,...].
    Dense same-shape columns use a strided write (no per-row Python);
    bytes interleave as lists; anything ragged/object falls back to the
    per-row path."""
    k, v = fr.key, fr.value
    n = len(fr)
    if isinstance(k, BytesColumn) and isinstance(v, BytesColumn):
        out: list = [None] * (2 * n)
        out[0::2] = list(k.data)
        out[1::2] = list(v.data)
        return BytesColumn(out)
    if isinstance(k, DenseColumn) and isinstance(v, DenseColumn):
        ka, va = np.asarray(k.data), np.asarray(v.data)
        # fast path only for IDENTICAL dtypes: numpy "promotes"
        # uint64+int64 to float64, which would silently round u64 hash
        # ids above 2^53 — mixed dtypes take the exact per-row path
        if ka.shape[1:] == va.shape[1:] and ka.dtype == va.dtype:
            arr = np.empty((2 * n,) + ka.shape[1:], ka.dtype)
            arr[0::2] = ka
            arr[1::2] = va
            return DenseColumn(arr)
    rows: list = [None] * (2 * n)
    kl, vl = k.tolist(), v.tolist()
    rows[0::2] = kl
    rows[1::2] = vl
    return _interleave_rows(rows, error)


def _interleave_rows(rows: list, error: Error) -> Column:
    """Build the collapse() multivalue column, refusing mixed types."""
    if not rows:
        return DenseColumn(np.zeros(0, np.int64))
    if all(isinstance(r, (bytes, str)) for r in rows):
        return BytesColumn([r.encode() if isinstance(r, str) else r
                            for r in rows])
    if any(isinstance(r, (bytes, str)) for r in rows):
        error.all("collapse requires keys and values of a common type "
                  "(all bytes or all numeric)")
    from .dataset import rows_to_array
    arr = rows_to_array(rows)
    if arr.dtype == object:
        error.all("collapse requires keys and values of a common shape")
    return DenseColumn(arr)


def _fmt(x, flag: int) -> str:
    if isinstance(x, bytes):
        try:
            return x.decode()
        except UnicodeDecodeError:
            return repr(x)
    if isinstance(x, tuple):
        return " ".join(_fmt(e, flag) for e in x)
    if isinstance(x, float) or flag in (3, 4):
        return f"{x:g}"
    return str(x)
