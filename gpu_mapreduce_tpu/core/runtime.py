"""Runtime state: settings, error policy, cumulative statistics.

Mirrors the reference's user-visible settings fields
(``src/mapreduce.h:28-41``, semantics ``doc/settings.txt:12-24``) and the
static cross-instance counters (``src/mapreduce.h:46-57``,
``src/mapreduce.cpp:40-50``) reported by ``cummulative_stats``
(``src/mapreduce.cpp:3007-3066``).

TPU reinterpretations (documented, not silently dropped):

* ``memsize`` (MB) — still the page/frame budget: a dataset frame holds at
  most ``memsize`` MB and datasets exceeding ``maxpage`` frames in HBM spill
  to host DRAM (and to ``fpath`` on disk when ``outofcore=1``).
* ``keyalign``/``valuealign`` — byte alignment is meaningless for columnar
  arrays; accepted and ignored (validated like the reference,
  ``src/mapreduce.cpp:251-261``).
* ``all2all`` — the reference's choice of MPI_Alltoallv over its
  Irecv/Send ring (``src/irregular.cpp:254-363``); accepted and ignored:
  the exchange picks its collective from the mesh
  (``parallel/shuffle._exchange_blocks``).
* ``mapstyle`` — 0 chunk / 1 stride task assignment both reduce to "run
  all tasks here" under one controller; 2 (the reference's master-slave
  MPI work queue, src/mapreduce.cpp:1136-1213) is a dynamic thread-pool
  work queue with deterministic task-order output (MapReduce._run_tasks).
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
import warnings
from dataclasses import dataclass, field
from typing import Optional

from ..utils.env import env_knob, env_str


class MRError(RuntimeError):
    """Raised for fatal conditions (the reference's error->all/one,
    src/error.cpp:33-67 — both abort; in-process we raise instead)."""


class CancelledError(MRError):
    """A request was cancelled (client DELETE, deadline, or the stall
    watchdog) and the cancellation flag tripped at an op barrier
    (obs/context.barrier_check).  Deliberately an :class:`MRError`
    subclass: the ft/ retry engine classifies MRError as FATAL, so a
    cancellation is never retried — it propagates straight up to the
    request owner (the serve/ worker), which records the ``cancelled``
    terminal state."""

    def __init__(self, reason: str = "cancelled"):
        self.reason = reason
        super().__init__(f"request cancelled ({reason})")


class Error:
    def all(self, msg: str):  # collective fatal
        raise MRError(msg)

    def one(self, msg: str):  # single-rank fatal
        raise MRError(msg)

    def warning(self, msg: str):
        warnings.warn(msg, stacklevel=3)


@dataclass
class Settings:
    mapstyle: int = 0       # 0 chunk, 1 stride, 2 master-slave work queue
    all2all: int = 1        # accepted for MR-MPI parity, selects nothing
    verbosity: int = 0      # 0 silent, 1 totals, 2 + per-shard histograms
    timer: int = 0          # 0 off, 1 totals, 2 + per-shard histograms
    # MB per frame (reference default 64, mapreduce.cpp:209); the env
    # vars mirror the reference's compile-time default overrides
    # MRMPI_MEMSIZE / MRMPI_FPATH (mapreduce.cpp:206-229) — explicit
    # settings still win
    memsize: int = field(default_factory=lambda: env_knob(
        "MRTPU_MEMSIZE", int, 64))
    minpage: int = 0
    maxpage: int = 0        # max frames resident in HBM; 0 = unlimited
    freepage: int = 1
    outofcore: int = 0      # 1 = allow disk spill under fpath; -1 = never
    zeropage: int = 0
    keyalign: int = 8       # accepted, ignored (columnar)
    valuealign: int = 8
    fpath: str = field(default_factory=lambda: env_str(
        "MRTPU_FPATH", "."))  # spill-file dir (reference MRMPI_FPATH)
    # 1 = defer op chains into the plan/ recorder and run them fused
    # (no reference analog — the reference is eager by construction);
    # the MRTPU_FUSE env var flips the default like MRTPU_MEMSIZE does
    fuse: int = field(default_factory=lambda: env_knob(
        "MRTPU_FUSE", int, 0))
    # what a failed map input does after the ft/ retry budget is spent
    # (no reference analog — the reference aborts on any read error):
    # "fail" raises MRError, "retry" retries with a default budget even
    # when MRTPU_RETRY is unset, "skip" quarantines the poisoned input
    # and continues (records in mr.stats()["ft"] — doc/reliability.md)
    onfault: str = field(default_factory=lambda: env_str(
        "MRTPU_ONFAULT", "fail"))

    def validate(self, error: Error):
        if self.memsize <= 0:
            error.all("Invalid memsize setting")
        if self.mapstyle not in (0, 1, 2):
            error.all("Invalid mapstyle setting")
        if self.fuse not in (0, 1):
            error.all("Invalid fuse setting")
        if self.onfault not in ("fail", "retry", "skip"):
            error.all("Invalid onfault setting (fail, retry, or skip)")
        for a in (self.keyalign, self.valuealign):
            if a <= 0 or (a & (a - 1)):
                error.all("Alignment setting must be power of 2")


@dataclass
class Counters:
    """Cumulative cross-instance stats (reference mapreduce.h:46-57).

    Updates go through ``add()``/``mem()`` which take a lock — counters
    are shared across MapReduce objects (global_counters) and mutate
    from concurrent -partition world threads and mapstyle-2 workers."""
    msize: int = 0          # current bytes resident (HBM frames)
    msizemax: int = 0       # hi-water
    rsize: int = 0          # bytes read from spill files
    wsize: int = 0          # bytes written to spill files
    cssize: int = 0         # useful bytes sent in shuffles
    crsize: int = 0         # useful bytes received in shuffles
    cspad: int = 0          # PADDING bytes sent (static-shape exchange
    #                         slack: [P,B]-buckets minus real rows —
    #                         the weak-scaling "network volume" diagnosis)
    commtime: float = 0.0   # seconds in collectives
    jisize: int = 0         # bytes of the two sides that went into joins
    josize: int = 0         # bytes of the joined rows that came out
    ndispatch: int = 0      # compiled-program launches (jitted shuffle/
    #                         convert/reduce/sort programs, fused plans,
    #                         AND eager pallas_call kernel launches —
    #                         ops/pallas.note_kernel_launch) — what
    #                         plan/ fusion is meant to shrink; a kernel
    #                         traced inside a jit rides that program's
    #                         count, so megafused pipelines read 1
    # JAX's own compile-path reports (jax.monitoring), fed by the obs/
    # tracer's listener while a tracer is on and 0 otherwise:
    jit_lowerings: int = 0      # programs traced and lowered, whatever
    #                             the compile cache did next
    jit_lower_s: float = 0.0    # their seconds (jaxpr → MLIR module)
    jit_backend_s: float = 0.0  # seconds in the backend: a compile, or
    #                             a load from the persistent cache
    jit_cache_loads: int = 0    # of those, the loads

    def __post_init__(self):
        import threading
        self._lock = threading.Lock()

    def add(self, **deltas):
        """Atomically bump the named counters: add(rsize=n, wsize=m)."""
        with self._lock:
            for name, delta in deltas.items():
                setattr(self, name, getattr(self, name) + delta)
        if "wsize" in deltas or "rsize" in deltas:
            acct = getattr(_ACCOUNT_TLS, "acct", None)
            if acct is not None:
                acct.note_io(deltas.get("wsize", 0),
                             deltas.get("rsize", 0))
        feed = _REQUEST_FEED
        if feed is not None:
            feed("add", deltas)

    def mem(self, delta: int):
        with self._lock:
            self.msize += delta
            if self.msize > self.msizemax:
                self.msizemax = self.msize
        acct = getattr(_ACCOUNT_TLS, "acct", None)
        if acct is not None:
            acct.charge(delta)
        feed = _REQUEST_FEED
        if feed is not None:
            feed("mem", delta)

    def snapshot(self) -> dict:
        """Consistent copy of every counter field — the structured twin
        of the ``cummulative_stats`` print (MapReduce.stats)."""
        with self._lock:
            return {"msize": self.msize, "msizemax": self.msizemax,
                    "rsize": self.rsize, "wsize": self.wsize,
                    "cssize": self.cssize, "crsize": self.crsize,
                    "cspad": self.cspad, "commtime": self.commtime,
                    "ndispatch": self.ndispatch,
                    "jit_lowerings": self.jit_lowerings,
                    "jit_lower_s": self.jit_lower_s,
                    "jit_backend_s": self.jit_backend_s,
                    "jit_cache_loads": self.jit_cache_loads}


class PageAccount:
    """Per-tenant frame-residency accounting (serve/budget.py).

    The enforcement half of a tenant budget is the existing page
    machinery — a session's MRs are created with ``maxpage``/``memsize``
    /``outofcore`` derived from the tenant's allowance, so an
    over-budget dataset spills through ``core/dataset.py`` exactly like
    any memory-constrained run.  This class is the *attribution* half:
    bytes charged through :meth:`Counters.mem` while a tenant scope is
    installed land here, giving the serve/ daemon a live per-tenant
    ``pages in use`` reading (the ``mrtpu_tenant_pages{tenant}`` gauge)
    without a second accounting path in the datasets.

    Attribution is thread-scoped (:func:`page_account_scope`): bytes
    charged from helper threads a session spawns itself (ingest pool
    workers) bill the global counters but not the tenant — frame
    consolidation happens on the session thread, so residency totals
    stay accurate (doc/serve.md)."""

    __slots__ = ("tenant", "page_bytes", "limit_pages", "bytes_in_use",
                 "hi_water", "spilled_bytes", "reread_bytes", "_lock")

    def __init__(self, tenant: str, page_bytes: int,
                 limit_pages: int = 0):
        self.tenant = tenant
        self.page_bytes = max(1, int(page_bytes))
        self.limit_pages = int(limit_pages)      # 0 = unlimited
        self.bytes_in_use = 0
        self.hi_water = 0
        self.spilled_bytes = 0       # budget-enforcement evidence: what
        self.reread_bytes = 0        # THIS tenant paid in disk traffic
        self._lock = threading.Lock()

    def charge(self, delta: int) -> None:
        with self._lock:
            self.bytes_in_use = max(0, self.bytes_in_use + int(delta))
            if self.bytes_in_use > self.hi_water:
                self.hi_water = self.bytes_in_use

    def note_io(self, wsize: int, rsize: int) -> None:
        with self._lock:
            self.spilled_bytes += int(wsize)
            self.reread_bytes += int(rsize)

    def pages_in_use(self) -> float:
        with self._lock:
            return self.bytes_in_use / self.page_bytes

    def snapshot(self) -> dict:
        with self._lock:
            return {"tenant": self.tenant,
                    "bytes_in_use": self.bytes_in_use,
                    "hi_water": self.hi_water,
                    "spilled_bytes": self.spilled_bytes,
                    "reread_bytes": self.reread_bytes,
                    "page_bytes": self.page_bytes,
                    "pages_in_use": round(self.bytes_in_use
                                          / self.page_bytes, 4),
                    "limit_pages": self.limit_pages}


# the request-context attribution hook: obs/context.py installs its
# feed here at import (fn(kind, payload) — "add" with the deltas dict,
# "mem" with the byte delta).  Module-global instead of an import so
# core/ never depends on obs/ and the unarmed cost is one None check.
_REQUEST_FEED = None

_ACCOUNT_TLS = threading.local()


def set_page_account(acct: Optional["PageAccount"]
                     ) -> Optional["PageAccount"]:
    """Install ``acct`` as THIS thread's tenant attribution target;
    returns the previous one (callers restore it)."""
    prev = getattr(_ACCOUNT_TLS, "acct", None)
    _ACCOUNT_TLS.acct = acct
    return prev


def current_page_account() -> Optional["PageAccount"]:
    return getattr(_ACCOUNT_TLS, "acct", None)


@contextlib.contextmanager
def page_account_scope(acct: Optional["PageAccount"]):
    """``with page_account_scope(acct):`` — scoped install/restore."""
    prev = set_page_account(acct)
    try:
        yield acct
    finally:
        set_page_account(prev)


class Timer:
    __slots__ = ("t0",)

    def __init__(self):
        self.t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0


def histogram(values, nbins: int = 10):
    """(min, avg, max, bins) over per-shard values — the reference's
    histogram() (src/mapreduce.cpp:3267-3311): bins count how many shards
    fall in each equal-width slice of [min, max]."""
    import numpy as _np
    v = _np.asarray(values, dtype=_np.float64)
    if v.size == 0:
        return 0.0, 0.0, 0.0, [0] * nbins
    lo, hi = float(v.min()), float(v.max())
    if hi == lo:
        bins = [0] * nbins
        bins[0] = int(v.size)
        return lo, float(v.mean()), hi, bins
    idx = _np.minimum(((v - lo) / (hi - lo) * nbins).astype(int), nbins - 1)
    bins = _np.bincount(idx, minlength=nbins).astype(int).tolist()
    return lo, float(v.mean()), hi, bins


def write_histo(label: str, values, out=None):
    """Reference write_histo (src/mapreduce.cpp:3251-3263): one line of
    min/avg/max across shards plus the shard-count distribution."""
    import sys as _sys
    lo, ave, hi, bins = histogram(values)
    out = out or _sys.stdout
    out.write(f"  {label} (per shard): {ave:.4g} ave {hi:.4g} max "
              f"{lo:.4g} min\n")
    out.write("  histogram: " + " ".join(str(b) for b in bins) + "\n")


_GLOBAL_COUNTERS = Counters()


def global_counters() -> Counters:
    return _GLOBAL_COUNTERS


_DISPATCH_TLS = threading.local()


def bump_dispatch(n: int = 1) -> None:
    """Count one compiled-program launch (the jitted shuffle/convert/
    reduce/sort programs, fused plan programs AND eager pallas_call
    kernel launches — via ops/pallas.note_kernel_launch — all report
    here) — the denominator of the plan/ fusion win.  Also bumps a per-thread counter so a caller can
    meter ITS OWN dispatches (thread_dispatches) without concurrent
    workers contaminating the delta."""
    _GLOBAL_COUNTERS.add(ndispatch=n)
    _DISPATCH_TLS.n = getattr(_DISPATCH_TLS, "n", 0) + n


def thread_dispatches() -> int:
    """Compiled-program launches made by THIS thread (cumulative).
    Delta two reads around a region for an exact per-region count even
    while other threads dispatch — the plan/ fusion telemetry's meter
    (dispatches run synchronously on the calling thread)."""
    return getattr(_DISPATCH_TLS, "n", 0)
