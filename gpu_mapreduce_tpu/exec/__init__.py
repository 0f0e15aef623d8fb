"""Async overlapped execution — the fourth pillar next to eager, fused
(plan/) and observed (obs/) execution.

The reference hides host work behind device work for free: every MPI
rank reads, sorts and spills its own pages while its neighbours compute
(``src/mapreduce.cpp:1102-1225``).  A single-controller JAX port loses
that overlap — ingest reads every chunk before the first device dispatch,
spill writes block the op that triggered them, and nothing ever donates a
dead device buffer.  This package restores the overlap on the three hot
paths, all behind env knobs so any of them can be disabled for a golden
eager run:

* **ingest prefetch** (:mod:`.prefetch`): a bounded double-buffered
  producer thread reads + tokenizes chunk N+1 while chunk N's frames
  assemble/intern (``parallel/ingest.mesh_map_files``/``mesh_map_chunks``
  and the serial ``MapReduce._map_chunks`` path).  Depth knob
  ``MRTPU_PREFETCH`` (default 1 = double buffering, 0 = off);
  backpressure through the queue bounds residency at ~(depth+1) chunks.
* **background spill** (:mod:`.spill`): ``core/external.py`` run writes
  move to a writer thread with a durability barrier at run-handoff (the
  merge's reader blocks on the run's ready-event, so it can never see a
  half-written run; writes land via tmp-file + ``os.replace`` so a crash
  mid-write leaves no torn ``.npy`` under the final name).
  ``MRTPU_SPILL_BG`` (default 1).
* **buffer donation + deferred sync** (helpers here): the shuffle's
  phase-1/phase-2 and the plan/ fused programs donate their dead input
  buffers (``jax.jit(donate_argnums=...)``) so XLA aliases instead of
  re-materialising — ``MRTPU_DONATE`` (default 1); and the per-op
  ``block_until_ready`` timing syncs can be deferred to the natural
  barriers (``MRTPU_DEFER_SYNC=1``, default 0 because the stage
  timers and the benchmark's host spans need exact per-stage
  attribution).

Every overlap reports: ``exec.prefetch`` / ``exec.spill_write`` obs
spans, a ``mrtpu_overlap_ratio{path}`` gauge (obs/metrics.py) and the
``mr.stats()["exec"]`` section (:func:`exec_stats`).  The overlap ratio
of a path is ``hidden / busy``: the fraction of background work time the
foreground never waited for (1.0 = fully hidden, 0.0 = serialized).

See ``doc/perf.md`` for the knob table and donation caveats.
"""

from __future__ import annotations

import threading

from ..utils.env import env_knob


def donated_jit(fn, argnums):
    """THE donation-wrapping rule, one copy (shuffle + fuser builders):
    ``jax.jit`` with the given ``donate_argnums`` (empty = plain jit).
    Callers only pass argnums whose donation is actually ALIASABLE
    (output of the same byte size exists — see the call sites), so
    jax's "Some donated buffers were not usable" warning never fires
    and needs no suppression; an unaliasable buffer simply isn't
    donated, which is the same no-op without the noise."""
    import jax
    argnums = tuple(argnums)
    if not argnums:
        return jax.jit(fn)
    return jax.jit(fn, donate_argnums=argnums)


def prefetch_depth() -> int:
    """Ingest prefetch queue depth (``MRTPU_PREFETCH``): 0 disables,
    1 (default) double-buffers, N keeps up to N chunks in flight."""
    return max(0, env_knob("MRTPU_PREFETCH", int, 1))


def spill_bg_enabled() -> bool:
    """Background spill writer (``MRTPU_SPILL_BG``, default on)."""
    return env_knob("MRTPU_SPILL_BG", int, 1) != 0


def donate_enabled() -> bool:
    """Device-buffer donation in the shuffle/fused programs
    (``MRTPU_DONATE``, default on)."""
    return env_knob("MRTPU_DONATE", int, 1) != 0


def can_donate(frame) -> bool:
    """THE donate-eligibility rule, one copy (shuffle + fuser callers):
    the knob is on, the frame is not shared with another dataset
    (``_shared`` — add_kv/copy/map_mr mark it; deleting a shared
    frame's arrays would corrupt the sibling), and key/value are not
    literally the same array (double donation)."""
    return (donate_enabled()
            and not getattr(frame, "_shared", False)
            and frame.key is not frame.value)


def defer_sync() -> bool:
    """``MRTPU_DEFER_SYNC=1``: skip per-op ``block_until_ready`` timing
    syncs so eager chains only sync at real barriers (count pulls, host
    reads).  Default off — the stage timers and the benchmark's host
    spans need exact per-stage attribution; see doc/perf.md."""
    return env_knob("MRTPU_DEFER_SYNC", int, 0) != 0


def maybe_block(x):
    """``jax.block_until_ready(x)`` unless deferred-sync mode is on.
    Use at per-op sync points that exist only for timing attribution —
    never at correctness barriers (those must call jax directly)."""
    if defer_sync():
        return x
    import jax
    return jax.block_until_ready(x)


# ---------------------------------------------------------------------------
# overlap accounting: per-path cumulative busy/hidden seconds
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
# path → {"busy_s", "wait_s", "items"}; busy = background-thread work,
# wait = foreground time spent blocked on that background work
_OVERLAP: dict = {}


def note_overlap(path: str, busy_s: float = 0.0, wait_s: float = 0.0,
                 items: int = 0) -> None:
    """Accumulate overlap telemetry for one path ("ingest.files",
    "ingest.chunks", "ingest.serial", "spill") and refresh the
    ``mrtpu_overlap_ratio{path}`` gauge.  Crash-proof like every obs
    feed: telemetry must never fail the op it observes."""
    with _LOCK:
        rec = _OVERLAP.setdefault(
            path, {"busy_s": 0.0, "wait_s": 0.0, "items": 0})
        rec["busy_s"] += max(0.0, busy_s)
        rec["wait_s"] += max(0.0, wait_s)
        rec["items"] += items
        ratio = _ratio(rec)
    try:
        from ..obs import metrics as _metrics
        if _metrics.enabled():
            _metrics.get_registry().gauge(
                "mrtpu_overlap_ratio",
                "fraction of background work hidden behind foreground "
                "work, per overlap path (1 = fully overlapped)",
                ("path",)).set(ratio, path=path)
    except Exception:
        pass


def _ratio(rec: dict) -> float:
    busy = rec["busy_s"]
    if busy <= 0.0:
        return 0.0
    return round(max(0.0, min(1.0, (busy - rec["wait_s"]) / busy)), 6)


def exec_stats() -> dict:
    """The ``mr.stats()["exec"]`` section: per-path cumulative overlap
    telemetry plus the active knob values."""
    with _LOCK:
        paths = {p: {**rec, "busy_s": round(rec["busy_s"], 6),
                     "wait_s": round(rec["wait_s"], 6),
                     "overlap_ratio": _ratio(rec)}
                 for p, rec in _OVERLAP.items()}
    return {"overlap": paths,
            "knobs": {"prefetch": prefetch_depth(),
                      "spill_bg": spill_bg_enabled(),
                      "donate": donate_enabled(),
                      "defer_sync": defer_sync()}}


def reset_stats() -> None:
    """Test isolation: drop the cumulative overlap telemetry."""
    with _LOCK:
        _OVERLAP.clear()


from .prefetch import prefetch_iter                        # noqa: E402
from .spill import SpillWriter                             # noqa: E402

__all__ = [
    "prefetch_depth", "spill_bg_enabled", "donate_enabled", "can_donate",
    "defer_sync", "donated_jit",
    "maybe_block", "note_overlap", "exec_stats", "reset_stats",
    "prefetch_iter", "SpillWriter",
]
