"""Thread-safe tracer with nested spans.

A span records wall time, the CPU seconds of its own thread beside it
(``cpu_s``; ``off_cpu_s`` is the rest: blocked or descheduled), deltas of
the cumulative ``runtime.Counters`` (bytes shuffled/padded/spilled, HBM
hi-water, programs lowered and loaded), execution tier and arbitrary op
metadata.  Nesting is per thread (a thread-local stack), so
``collate`` naturally parents ``aggregate``/``convert``, which parent
the shuffle's ``exchange`` span, and the ``-partition`` universe's
concurrent interpreter threads each get their own stack.

Events are emitted to sinks already in Chrome trace-event form
(``ph: "X"`` complete events, ``ts``/``dur`` in microseconds), so the
JSONL file a run writes needs only wrapping in ``{"traceEvents": [...]}``
to load in Perfetto (``sinks.chrome_trace``).

Counter deltas are PROCESS-GLOBAL (the counters are shared across
MapReduce objects, like the reference's static stats): when concurrent
``-partition`` worlds overlap, a span may attribute another world's
bytes to itself.  Wall time and nesting stay correct per thread.

JAX reports what its compile path costs (``jax.monitoring``): seconds to
lower a traced program, seconds in the backend (a compile, or a load from
the persistent cache), each with the program's name.  The first
``enable()`` of a process registers one duration listener and one event
listener; while a tracer is on they feed its counters' ``jit_*`` fields
(so every span shows what was built under it) and a per-program table,
:func:`programs`.  With every tracer off they return at once.

Zero-cost when disabled: ``span()`` returns the shared :data:`NULL_SPAN`
singleton — one attribute check, no allocation, no clock read; nothing
is registered with JAX until a tracer is enabled.
"""

from __future__ import annotations

import os
import resource
import threading
import time
import weakref
from typing import Dict, List, Optional

from ..utils.env import env_knob, env_str
from .context import current_trace_id as _ctx_trace_id
from .context import note_span as _ctx_note_span
from . import names

# Counters fields snapshotted at span entry; the exit delta lands in the
# span's args under the mapped name (only when nonzero, to keep traces
# small).  msizemax is a hi-water, not a flow — reported as the absolute
# hi-water at span exit when it moved during the span.
_DELTA_FIELDS = (
    ("cssize", "shuffle_sent_bytes"),
    ("cspad", "shuffle_pad_bytes"),
    ("wsize", "spill_write_bytes"),
    ("rsize", "spill_read_bytes"),
    ("ndispatch", "dispatches"),
    ("jisize", "join_in_bytes"),
    ("josize", "join_out_bytes"),
    ("jit_lowerings", names.ATTR_JIT_LOWERINGS),
    ("jit_lower_s", names.ATTR_JIT_LOWER_S),
    ("jit_backend_s", names.ATTR_JIT_BACKEND_S),
    ("jit_cache_loads", names.ATTR_JIT_CACHE_LOADS),
)
# an ``entry`` span states these even at zero: "nothing was rebuilt under
# this job" is a reading, not a missing one
_JIT_LABELS = tuple(label for _, label in _DELTA_FIELDS
                    if label.startswith("jit_"))


class _NullSpan:
    """Shared no-op stand-in when tracing is disabled (or for the
    ``annotate`` of a thread with no open span)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


NULL_SPAN = _NullSpan()


class Span:
    """One timed region.  Use as a context manager::

        with tracer.span("collate", shards=P) as sp:
            ...
            sp.set(nkv=n)
    """

    __slots__ = ("tracer", "name", "cat", "attrs", "span_id", "parent_id",
                 "t0", "t1", "_cpu0", "_proc0", "_snap", "_mem0",
                 "_jax_ctx", "trace_id")

    def __init__(self, tracer: "Tracer", name: str, cat: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.span_id = 0
        self.parent_id = 0
        self.t0 = self.t1 = 0.0
        self._cpu0 = 0.0
        self._proc0 = None
        self._snap = None
        self._mem0 = 0
        self._jax_ctx = None
        self.trace_id = None

    def set(self, **attrs):
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        tr = self.tracer
        self.span_id = tr._next_id()
        stack = tr._stack()
        self.parent_id = stack[-1].span_id if stack else 0
        stack.append(self)
        # request-scoped trace context (obs/context.py): the id rides
        # the event so one request's spans are filterable out of any
        # sink — including spans emitted from worker threads that
        # re-installed the submitting request's context
        self.trace_id = _ctx_trace_id()
        c = tr.counters
        self._snap = tuple(getattr(c, f) for f, _ in _DELTA_FIELDS)
        self._mem0 = c.msizemax
        # on the profiler's host plane the annotation is what attributes
        # a device idle gap to this span; with no profiler running it
        # costs next to nothing
        try:
            import jax
            self._jax_ctx = jax.profiler.TraceAnnotation(self.name)
            self._jax_ctx.__enter__()
        except Exception:
            self._jax_ctx = None  # no profiler backend: spans still work
        if self.cat == names.ENTRY:
            # one span a job: what costs a system call is read here only
            ru = resource.getrusage(resource.RUSAGE_THREAD)
            self._proc0 = (time.process_time(), ru.ru_stime, ru.ru_nvcsw,
                           ru.ru_nivcsw)
        self._cpu0 = time.thread_time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1 = time.perf_counter()
        dur = self.t1 - self.t0
        # a thread cannot use more CPU than wall: a kernel that charges
        # CPU time by the 10 ms tick (the TPU hosts do) can hand a short
        # span a whole tick
        cpu = min(time.thread_time() - self._cpu0, dur)
        self.attrs[names.ATTR_CPU_S] = round(cpu, 6)
        self.attrs[names.ATTR_OFF_CPU_S] = round(dur - cpu, 6)
        if self._proc0 is not None:
            proc0, sys0, vol0, invol0 = self._proc0
            ru = resource.getrusage(resource.RUSAGE_THREAD)
            self.attrs[names.ATTR_PROC_CPU_S] = round(
                time.process_time() - proc0, 6)
            self.attrs[names.ATTR_SYS_CPU_S] = round(ru.ru_stime - sys0, 6)
            self.attrs[names.ATTR_VOL_SWITCHES] = ru.ru_nvcsw - vol0
            self.attrs[names.ATTR_INVOL_SWITCHES] = ru.ru_nivcsw - invol0
            # stated even at zero; the deltas below overwrite what moved
            self.attrs.update(dict.fromkeys(_JIT_LABELS, 0))
        if self._jax_ctx is not None:
            try:
                self._jax_ctx.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        tr = self.tracer
        stack = tr._stack()
        # pop self even if an inner span leaked (exception unwinding)
        while stack and stack.pop() is not self:
            pass
        c = tr.counters
        for (field, label), before in zip(_DELTA_FIELDS, self._snap):
            d = getattr(c, field) - before
            if d:
                self.attrs[label] = round(d, 6) if isinstance(d, float) else d
        if c.msizemax != self._mem0:
            self.attrs["hbm_hiwater_bytes"] = c.msizemax
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        # per-request stage profile (obs/context.py): the finished
        # span's wall + counter deltas land on the active account too —
        # same numbers, scoped to the request instead of the process
        _ctx_note_span(self.name, self.cat, dur, self.attrs)
        tr._emit(self)
        return False

    def event(self) -> dict:
        """This finished span as a Chrome trace-event dict."""
        tr = self.tracer
        ev = {
            "name": self.name, "cat": self.cat, "ph": "X",
            "ts": round((self.t0 - tr.epoch) * 1e6, 1),
            "dur": round((self.t1 - self.t0) * 1e6, 1),
            "pid": tr.pid, "tid": threading.get_ident() & 0x7FFFFFFF,
            "id": self.span_id, "parent": self.parent_id,
            "wall": round(tr.wall_epoch + self.t0, 6),
            "args": self.attrs,
        }
        if self.trace_id is not None:
            ev["trace"] = self.trace_id
        return ev


# -- JAX's compile path, as it reports itself ---------------------------------
# jaxpr_trace_duration is left out: it nests (a jitted function calling a
# jitted jnp function reports the inner trace inside the outer one's
# seconds), so a sum of its events counts twice.
_JAX_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_JAX_BACKEND = "/jax/core/compile/backend_compile_duration"
_JAX_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_JAX_LOCK = threading.Lock()
_JAX_TLS = threading.local()        # .hit: a cache hit not yet charged
_JAX_REGISTERED = False
_LISTENING: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_PROGRAMS: Dict[str, dict] = {}


def _on_jax_event(event: str, **_kw) -> None:
    if event == _JAX_CACHE_HIT:
        _JAX_TLS.hit = True     # the same thread's backend event follows


def _on_jax_duration(event: str, seconds: float, fun_name: str = "?",
                     **_kw) -> None:
    if event == _JAX_LOWERED:
        deltas = {"jit_lowerings": 1, "jit_lower_s": seconds}
    elif event == _JAX_BACKEND:
        loaded = getattr(_JAX_TLS, "hit", False)
        _JAX_TLS.hit = False
        deltas = {"jit_backend_s": seconds, "jit_cache_loads": int(loaded)}
    else:
        return
    # jax says "jit(convert_sort)"; a device trace, and obs/names.py,
    # say "jit_convert_sort"
    name = fun_name.replace("(", "_").rstrip(")")
    with _JAX_LOCK:
        # the counters of the tracers that are on, each once (private
        # tracers may share the process's)
        live = {id(t.counters): t.counters for t in _LISTENING if t.enabled}
        if not live:
            return
        row = _PROGRAMS.setdefault(name, {"lowerings": 0, "lower_s": 0.0,
                                          "backend_s": 0.0, "cache_loads": 0})
        for field, d in deltas.items():
            row[field[len("jit_"):]] += d
    for counters in live.values():
        counters.add(**deltas)


def _listen(tracer: "Tracer") -> None:
    """Feed ``tracer``'s counters from now on; the first call of a process
    registers the two listeners with JAX (never an import)."""
    global _JAX_REGISTERED
    with _JAX_LOCK:
        _LISTENING.add(tracer)
        if _JAX_REGISTERED:
            return
        _JAX_REGISTERED = True
    import jax.monitoring
    jax.monitoring.register_event_listener(_on_jax_event)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def programs() -> Dict[str, dict]:
    """What was built while a tracer was on, a program: ``{name:
    {lowerings, lower_s, backend_s, cache_loads}}``.  ``lowerings`` counts
    the times the program was traced and lowered (whatever the cache did
    next), ``backend_s`` is compiling or loading from the persistent
    cache, ``cache_loads`` how many of those were loads.  Names are the
    device trace's (``jit_convert_sort``)."""
    with _JAX_LOCK:
        return {name: dict(row) for name, row in _PROGRAMS.items()}


class Tracer:
    """Span factory + sink fan-out.  One per process normally
    (:func:`get_tracer`); tests may build private instances."""

    def __init__(self, counters=None):
        if counters is None:
            from ..core.runtime import global_counters
            counters = global_counters()
        self.enabled = False
        self.counters = counters
        # process-wide attrs merged into EVERY span (parallel/dist.py
        # stamps rank= here so one multi-rank trace merge stays
        # attributable without threading rank through call signatures)
        self.proc_attrs: dict = {}
        self.epoch = time.perf_counter()
        # wall-clock origin of the perf_counter timeline: lets a
        # cross-process merge (trace_view over per-rank shards) rebase
        # each process's private ts epoch onto one shared clock
        self.wall_epoch = time.time() - time.perf_counter()
        self.pid = os.getpid()
        self._sinks: List[object] = []
        self._ring: Optional["RingSink"] = None
        self._jsonl: Dict[str, object] = {}   # path → JsonlSink (dedupe)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._id = 0

    # -- span construction --------------------------------------------------
    def span(self, name: str, cat: str = "op", **attrs):
        """A new child span of this thread's current span — or the no-op
        singleton when disabled (the zero-cost fast path)."""
        if not self.enabled:
            return NULL_SPAN
        if self.proc_attrs:
            attrs = {**self.proc_attrs, **attrs}
        return Span(self, name, cat, attrs)

    def annotate(self, **attrs) -> None:
        """Attach attrs to this thread's innermost open span (no-op when
        disabled or no span is open) — how deep layers report tier/shape
        facts without threading span objects through call signatures."""
        if not self.enabled:
            return
        stack = self._stack()
        if stack:
            stack[-1].attrs.update(attrs)

    def inherited(self, key: str):
        """The attr ``key`` of the nearest open span of this thread that
        carries it, or None — how a span deep in a callback learns the
        ``shard`` its enclosing ingest span runs for."""
        if self.enabled:
            for sp in reversed(self._stack()):
                if key in sp.attrs:
                    return sp.attrs[key]
        return None

    def current(self):
        stack = self._stack() if self.enabled else None
        return stack[-1] if stack else None

    def set_proc_attrs(self, **attrs) -> None:
        """Merge process-wide span attrs (e.g. ``rank=3``) — stamped on
        every span this tracer creates from now on."""
        self.proc_attrs.update(attrs)

    # -- configuration ------------------------------------------------------
    def enable(self, jsonl: Optional[str] = None, ring: Optional[int] = None):
        """Turn tracing on.  ``jsonl``: also stream events to this path
        (idempotent per path).  ``ring``: in-memory buffer capacity (a
        ring is always attached; default from MRTPU_TRACE_RING or 65536).
        Returns self for chaining."""
        from .sinks import JsonlSink, RingSink
        with self._lock:
            if self._ring is None:
                cap = ring or env_knob("MRTPU_TRACE_RING", int, 65536)
                self._ring = RingSink(cap)
                self._sinks.append(self._ring)
            if jsonl and jsonl not in self._jsonl:
                sink = JsonlSink(jsonl)
                self._jsonl[jsonl] = sink
                self._sinks.append(sink)
        _listen(self)
        self.enabled = True
        return self

    def disable(self):
        self.enabled = False
        return self

    def subscribe(self, fn) -> None:
        """Register ``fn(event_dict)`` as a sink and enable tracing —
        the external-consumer hook.  Goes through enable() so the ring
        (and hence events()/stats()/dump_trace) works too."""
        from .sinks import CallbackSink
        self.enable()
        with self._lock:
            self._sinks.append(CallbackSink(fn))

    def subscribe_once(self, fn) -> None:
        """subscribe() unless ``fn`` already is — check and append under
        ONE lock hold, so concurrent enables (two threads constructing
        MapReduce(metrics_port=...)) cannot double-subscribe the metrics
        bridge / flight ring and double-count every span; long-lived
        consumers also re-arm safely after a reset().  Membership is by
        ``==``, not ``is``: a bound method (the flight recorder's
        ``rec.emit``) is a fresh object per access but compares equal."""
        from .sinks import CallbackSink
        self.enable()
        with self._lock:
            if not any(isinstance(s, CallbackSink) and s.fn == fn
                       for s in self._sinks):
                self._sinks.append(CallbackSink(fn))

    def unsubscribe(self, fn) -> None:
        """Detach a callback sink subscribed via subscribe[_once] (by
        ``==``, matching subscribe_once's membership rule).  A consumer
        with a bounded lifetime — the serve/ daemon's per-session event
        feed — must detach on shutdown or every emission keeps paying
        for a dead listener."""
        from .sinks import CallbackSink
        with self._lock:
            self._sinks = [s for s in self._sinks
                           if not (isinstance(s, CallbackSink)
                                   and s.fn == fn)]

    def reset(self) -> None:
        """Drop sinks/events and the per-program table, and disable (test
        isolation).  JAX's listeners stay registered and do nothing until
        a tracer is enabled again."""
        self.enabled = False
        with _JAX_LOCK:
            _PROGRAMS.clear()
        with self._lock:
            for s in self._sinks:
                close = getattr(s, "close", None)
                if close:
                    try:
                        close()
                    except Exception:
                        pass
            self._sinks = []
            self._ring = None
            self._jsonl = {}

    # -- event access -------------------------------------------------------
    def events(self) -> list:
        """Snapshot of the in-memory ring (empty when never enabled)."""
        return self._ring.snapshot() if self._ring is not None else []

    def clear(self) -> None:
        """Drop buffered ring events (sinks stay attached) — e.g. to
        separate a warmup run from the timed run."""
        if self._ring is not None:
            # mrlint: disable=lock-unguarded-mutation — RingSink.clear
            # takes the sink's OWN lock; self._lock only guards the
            # _ring/_jsonl attachment maps, not ring contents
            self._ring.clear()

    def stats(self) -> dict:
        """Per-op aggregate over the ring (see report.aggregate_ops)."""
        from .report import aggregate_ops
        return aggregate_ops(self.events())

    # -- internals ----------------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _next_id(self) -> int:
        with self._lock:
            self._id += 1
            return self._id

    def _emit(self, span: Span) -> None:
        ev = span.event()
        with self._lock:
            sinks = list(self._sinks)
        for s in sinks:
            try:
                s.emit(ev)
            except Exception:
                # a broken sink (full disk, closed file) must never fail
                # the traced op; drop it fully — including its jsonl
                # dedup entry, so a later enable(jsonl=path) can attach
                # a fresh sink instead of silently no-opping
                with self._lock:
                    if s in self._sinks:
                        self._sinks.remove(s)
                    for path, sink in list(self._jsonl.items()):
                        if sink is s:
                            del self._jsonl[path]
                close = getattr(s, "close", None)
                if close:
                    try:
                        close()
                    except Exception:
                        pass


def configure_from_env(tracer: Tracer) -> Tracer:
    """Apply MRTPU_TRACE (JSONL path, or '1' for ring-only) if set."""
    path = env_str("MRTPU_TRACE", None)
    if path:
        tracer.enable(jsonl=None if path == "1" else path)
    return tracer


_GLOBAL: Optional[Tracer] = None
_GLOBAL_LOCK = threading.Lock()


def get_tracer() -> Tracer:
    """The process-global tracer (created on first use; MRTPU_TRACE in
    the environment auto-enables it)."""
    global _GLOBAL
    if _GLOBAL is None:
        with _GLOBAL_LOCK:
            if _GLOBAL is None:
                _GLOBAL = configure_from_env(Tracer())
    return _GLOBAL
