"""Bounded caches for compiled artifacts + the plan cache.

Two problems, one mechanism:

* the shuffle/convert jit builders were ``functools.lru_cache(None)`` —
  unbounded, so long soak runs over many meshes/dest functions pin every
  executable forever (ISSUE 2 satellite);
* the plan fuser compiles whole pipelines and must reuse them across
  runs, with visible hit/miss/eviction telemetry (the production
  inference-stack shape: a compiled-plan cache keyed on program
  fingerprint + shapes).

:class:`LRUCache` is the shared policy: thread-safe (``-partition``
worlds record/execute plans from interpreter threads), move-to-back on
hit, evict-front past ``maxsize``, with cumulative hit/miss/eviction
counters that ``MapReduce.stats()`` and the obs spans report.

Key discipline: every knob that changes a compiled program's BYTES must
be in its cache key — the plan cache keys (fingerprint, frame
signature, backend, outofcore, ``MRTPU_WIRE``), and the
shuffle/fused executable caches additionally key the wire codec's full
plan tuple (tier ladder + pack dtypes; ``parallel/wire.py``), so
flipping a knob can never replay a stale executable.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from collections import OrderedDict
from typing import Callable, Optional

from ..utils.env import env_flag, env_knob


class LRUCache:
    """Thread-safe LRU with telemetry.  ``get_or_build(key, build)`` is
    the only way entries appear; ``build()`` runs OUTSIDE the lock (it
    may trace/compile for seconds) — a racing builder for the same key
    wastes one build but never deadlocks or tears the dict."""

    def __init__(self, maxsize: int, name: str = "cache"):
        self.name = name
        self.maxsize = max(1, int(maxsize))
        self._d: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key):
        with self._lock:
            if key in self._d:
                self._d.move_to_end(key)
                self.hits += 1
                hit = self._d[key]
            else:
                self.misses += 1
                hit = None
        # per-request attribution (obs/context.py): the same hit/miss
        # lands on the active request account, so a serve session's
        # "did this recompile?" is ITS delta even with concurrent
        # neighbors warming the same process-global cache
        try:
            from ..obs.context import note_plan
            note_plan(self.name, hit is not None)
        except Exception:
            pass
        return hit

    def put(self, key, value) -> None:
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)
                self.evictions += 1

    def get_or_build(self, key, build: Callable):
        hit = self.get(key)
        if hit is not None:
            return hit
        value = build()
        self.put(key, value)
        return value

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)

    def resize(self, maxsize: int) -> None:
        with self._lock:
            self.maxsize = max(1, int(maxsize))
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)
                self.evictions += 1

    def clear(self) -> None:
        with self._lock:
            self._d.clear()

    def stats(self) -> dict:
        with self._lock:
            return {"size": len(self._d), "maxsize": self.maxsize,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions}


# ---------------------------------------------------------------------------
# the plan cache: (stage-chain fingerprint, frame shapes/dtypes, mesh)
# → executable plan (see fuser.CompiledPlan)
# ---------------------------------------------------------------------------

_PLAN_CACHE: Optional[LRUCache] = None
_PLAN_LOCK = threading.Lock()


def plan_cache() -> LRUCache:
    global _PLAN_CACHE
    if _PLAN_CACHE is None:
        with _PLAN_LOCK:
            if _PLAN_CACHE is None:
                _PLAN_CACHE = LRUCache(
                    env_knob("MRTPU_PLAN_CACHE", int, 32),
                    name="plan")
    return _PLAN_CACHE


def cache_stats() -> dict:
    """Structured snapshot of every bounded compile cache — the plan
    cache plus the shuffle's phase1/phase2 jit caches — and the
    cumulative fusion-effectiveness counters (what
    ``MapReduce.stats()['plan']`` reports).  ``persistent`` is the
    on-disk plan tier (zeros when disarmed) so
    ``mrtpu_plan_cache_hit_ratio{cache="persistent"}`` and the serve
    per-request deltas cover restarts, not just this process."""
    out = {"plan": plan_cache().stats()}
    from ..parallel import shuffle
    out["shuffle_phase1"] = shuffle.PHASE1_CACHE.stats()
    out["shuffle_phase2"] = shuffle.PHASE2_CACHE.stats()
    out["fusion"] = fusion_stats()
    pp = persistent_cache()
    out["persistent"] = pp.stats() if pp is not None else {
        "enabled": 0, "entries": 0, "bytes": 0,
        "hits": 0, "misses": 0, "evictions": 0}
    return out


# ---------------------------------------------------------------------------
# fusion effectiveness: per-group fused program counts + dispatch
# savings (fusion v2, plan/fuser.py) — the "did megafusion actually
# shrink dispatches" half of mr.stats()["plan"], next to the cache
# hit/miss half above.  Also fed per-request into the active
# RequestAccount so GET /v1/jobs/<id>/profile shows it per job.
# ---------------------------------------------------------------------------

_FUSION_LOCK = threading.Lock()
_FUSION = {"groups": 0, "fused_groups": 0, "eager_groups": 0,
           "mega_groups": 0, "dispatches": 0,
           "eager_dispatch_estimate": 0, "dispatches_saved": 0}


def note_fusion(kind: str, mode: str, dispatches: int,
                eager_est: int) -> None:
    """One executed plan group: its fusion kind ("exchange"/"local"/
    "eager"), execution mode ("mega"/"local1" = single-dispatch warm,
    "v1"/"local" = cold or fallback, "eager" = replay), the compiled-
    program launches it actually made, and the eager tier's per-op
    baseline for the same stages."""
    # classify ONCE; the per-request twin (obs/context) receives the
    # derived booleans so the mode-string sets can never drift
    fused = kind != "eager"
    mega = fused and mode in ("mega", "local1")
    saved = max(0, int(eager_est) - int(dispatches)) if fused else 0
    with _FUSION_LOCK:
        _FUSION["groups"] += 1
        if not fused:
            _FUSION["eager_groups"] += 1
        else:
            _FUSION["fused_groups"] += 1
            if mega:
                _FUSION["mega_groups"] += 1
        _FUSION["dispatches"] += int(dispatches)
        _FUSION["eager_dispatch_estimate"] += int(eager_est)
        _FUSION["dispatches_saved"] += saved
    try:
        from ..obs.context import note_fusion as _ctx_note
        _ctx_note(fused, mega, int(dispatches), saved)
    except Exception:
        pass


def fusion_stats() -> dict:
    with _FUSION_LOCK:
        return dict(_FUSION)


def reset_fusion_stats() -> None:
    """Test isolation: zero the cumulative fusion counters."""
    with _FUSION_LOCK:
        for k in _FUSION:
            _FUSION[k] = 0


def stats_delta(before: dict, after: Optional[dict] = None) -> dict:
    """Per-request compile-cache deltas: ``{cache: {hits, misses,
    evictions}}`` between two :func:`cache_stats` snapshots (``after``
    defaults to a fresh snapshot).  The caches are process-global —
    PR 2's LRU is a fleet-wide warm cache under the serve/ daemon — so
    a single request's "did this recompile?" question is only
    answerable as a delta: the serve/ session runner stamps one into
    every result (``misses == 0`` on a warm identical request is the
    no-recompile assertion the acceptance test makes)."""
    after = cache_stats() if after is None else after
    out = {}
    for cname, a in after.items():
        b = before.get(cname, {})
        out[cname] = {k: a.get(k, 0) - b.get(k, 0)
                      for k in ("hits", "misses", "evictions")}
    return out


# ---------------------------------------------------------------------------
# the persistent plan tier (doc/perf.md#the-caching-tier): compiled-plan
# speculation state (exchange caps + megafuse plans) survives process
# restarts under <cas>/plan/, keyed by a STABLE digest of the in-memory
# plan-cache key (function objects render as module.qualname, live mesh
# objects as axis/size/platform signatures).  The actual XLA executables
# persist next door via JAX's compilation cache (<cas>/xla/ —
# enable_executable_cache), so a cold replica's first warm-shaped
# request re-traces against cached speculation state and every compile
# hits the on-disk executable cache: 0 recompiles.
#
# A digest collision (two different lambdas sharing a qualname) is
# SAFE: the payload is speculation state, validated against the fresh
# count matrices on every run (plan_holds / gcap checks) — at worst one
# mega-miss and a v1 re-run, never a wrong result.
# ---------------------------------------------------------------------------


def _mesh_stable(mesh) -> str:
    """Axis names/sizes + device platform: equal meshes on different
    hosts (or across restarts) share plan state; a width change keys
    separately (the caps/plans are per-width shapes)."""
    shape = dict(getattr(mesh, "shape", None) or {})
    kind = ""
    devs = getattr(mesh, "devices", None)
    if devs is not None:
        try:
            first = devs.reshape(-1)[0] if hasattr(devs, "reshape") \
                else list(devs)[0]
            kind = getattr(first, "platform", "") or ""
        except Exception:
            kind = ""
    return f"{sorted(shape.items())}|{kind}"


def _stable_part(x) -> str:
    if isinstance(x, (int, float, str, bytes, bool, type(None))):
        return repr(x)
    if isinstance(x, tuple):
        if len(x) == 2 and x[0] == "fn" and callable(x[1]):
            f = x[1]
            return (f"fn:{getattr(f, '__module__', '?')}."
                    f"{getattr(f, '__qualname__', None) or getattr(f, '__name__', '?')}")
        if len(x) == 2 and x[0] == "mesh" and not isinstance(x[1], str):
            return f"mesh:{_mesh_stable(x[1])}"
        return "(" + ",".join(_stable_part(e) for e in x) + ")"
    raise TypeError(f"no stable rendering for {type(x).__name__}")


def stable_plan_digest(key) -> Optional[str]:
    """Stable cross-process digest of an in-memory plan-cache key, or
    None when some component has no stable rendering (those plans stay
    process-local)."""
    try:
        text = _stable_part(key)
    except TypeError:
        return None
    return hashlib.sha256(text.encode()).hexdigest()


def to_jsonable(x):
    """Plan payloads → JSON-safe (tuples → lists, numpy scalars →
    python); raises TypeError on anything else so an unserializable
    plan skips persistence instead of storing garbage."""
    if isinstance(x, (list, tuple)):
        return [to_jsonable(e) for e in x]
    if isinstance(x, dict):
        return {str(k): to_jsonable(v) for k, v in x.items()}
    if isinstance(x, (str, bool, type(None), int, float)):
        return x
    import numpy as np
    if isinstance(x, np.integer):
        return int(x)
    if isinstance(x, np.floating):
        return float(x)
    if isinstance(x, np.dtype):
        return str(x)
    raise TypeError(f"not plan-serializable: {type(x).__name__}")


def from_jsonable(x):
    """Inverse of :func:`to_jsonable` for plan payloads: lists become
    tuples again (wire plans are compared and used as dict/cache keys,
    so tuple-ness is load-bearing)."""
    if isinstance(x, list):
        return tuple(from_jsonable(e) for e in x)
    if isinstance(x, dict):
        return {k: from_jsonable(v) for k, v in x.items()}
    return x


class PersistentPlanCache:
    """On-disk plan-state entries under ``<cas>/plan/``, one JSON file
    per stable key digest, each stamped (``utils/integrity``) and
    verified on read — a corrupt entry counts an
    ``mrtpu_integrity_failures_total{artifact="cas"}``, is removed, and
    reads as a miss (cold compile, never wrong state).  Bounded by
    ``MRTPU_PLAN_PERSIST_CAP`` entries, oldest-mtime evicted."""

    def __init__(self, root: str):
        self.dir = os.path.join(root, "plan")
        self.cap = max(1, env_knob("MRTPU_PLAN_PERSIST_CAP", int, 512))
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def _path(self, digest: str) -> str:
        return os.path.join(self.dir, digest + ".json")

    def _note(self, hit: bool) -> None:
        with self._lock:
            if hit:
                self.hits += 1
            else:
                self.misses += 1
        try:
            from ..obs.context import note_plan
            note_plan("persistent", hit)
        except Exception:
            pass

    def load(self, digest: str) -> Optional[dict]:
        from ..utils.integrity import (digest_bytes,
                                       record_integrity_failure,
                                       verify_enabled)
        path = self._path(digest)
        try:
            with open(path) as f:
                rec = json.load(f)
            payload = rec["payload"]
            body = json.dumps(payload, sort_keys=True).encode()
            if verify_enabled() and rec.get("c") != digest_bytes(body):
                raise ValueError("stamp mismatch")
        except OSError:
            self._note(False)
            return None
        except (ValueError, KeyError, TypeError):
            # bit-flipped / torn entry: quarantine-by-removal and fall
            # back to a cold compile — corruption degrades, never lies
            record_integrity_failure("cas")
            try:
                os.remove(path)
            except OSError:
                pass
            self._note(False)
            return None
        self._note(True)
        return payload

    def store(self, digest: str, payload: dict) -> bool:
        """Write (or refresh) one entry; no-op when the stored bytes
        already match (steady state costs one small read, no write)."""
        from ..utils.integrity import digest_bytes
        body = json.dumps(payload, sort_keys=True)
        rec = json.dumps({"c": digest_bytes(body.encode()),
                          "payload": payload}, sort_keys=True)
        path = self._path(digest)
        try:
            with open(path) as f:
                if f.read() == rec:
                    return False
        except OSError:
            pass
        try:
            os.makedirs(self.dir, exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
            with open(tmp, "w") as f:
                f.write(rec)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            return False
        self._evict()
        return True

    def _evict(self) -> None:
        try:
            names = [n for n in os.listdir(self.dir)
                     if n.endswith(".json")]
        except OSError:
            return
        excess = len(names) - self.cap
        if excess <= 0:
            return
        aged = []
        for n in names:
            try:
                aged.append((os.path.getmtime(
                    os.path.join(self.dir, n)), n))
            except OSError:
                continue
        for _mt, n in sorted(aged)[:excess]:
            try:
                os.remove(os.path.join(self.dir, n))
                with self._lock:
                    self.evictions += 1
            except OSError:
                pass

    def stats(self) -> dict:
        entries = 0
        nbytes = 0
        try:
            for n in os.listdir(self.dir):
                if not n.endswith(".json"):
                    continue
                try:
                    nbytes += os.path.getsize(os.path.join(self.dir, n))
                except OSError:
                    continue
                entries += 1
        except OSError:
            pass
        with self._lock:
            return {"enabled": 1, "entries": entries, "bytes": nbytes,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions}


_PERSIST: Optional[PersistentPlanCache] = None
_PERSIST_ROOT: Optional[str] = None


def persistent_cache() -> Optional[PersistentPlanCache]:
    """The on-disk tier singleton (re-rooted when the env changes —
    tests); None when no CAS root is armed or ``MRTPU_PLAN_PERSIST=0``."""
    global _PERSIST, _PERSIST_ROOT
    from ..utils.cas import cas_enabled, cas_root
    if not cas_enabled() or not env_flag("MRTPU_PLAN_PERSIST", True):
        return None
    root = cas_root()
    with _PLAN_LOCK:
        if _PERSIST is None or _PERSIST_ROOT != root:
            _PERSIST = PersistentPlanCache(root)
            _PERSIST_ROOT = root
        return _PERSIST


def enable_executable_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at ``<cas>/xla/`` so
    the executables behind every jit/shard_map program survive process
    restarts (the other half of "0 recompiles on a warm-shaped cold
    replica").  Respects an operator's own ``JAX_COMPILATION_CACHE_DIR``
    (never overrides it), is disarmed with the tier
    (``MRTPU_JIT_PERSIST=0`` or no CAS root), and any failure keeps the
    uncached path — pure optimisation."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    from ..utils.cas import cas_enabled, cas_root
    if not cas_enabled() or not env_flag("MRTPU_JIT_PERSIST", True):
        return None
    path = os.path.join(cas_root(), "xla")
    try:
        os.makedirs(path, exist_ok=True)
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
    except Exception:
        return None
    return path


# ---------------------------------------------------------------------------
# plan history: the last few executed plans, described, for dump_plan /
# scripts/plan_dump.py (the trace ring's analog for whole plans)
# ---------------------------------------------------------------------------

_HISTORY: list = []
_HISTORY_LOCK = threading.Lock()
_HISTORY_CAP = 64


def record_history(desc: dict) -> None:
    with _HISTORY_LOCK:
        _HISTORY.append(desc)
        del _HISTORY[:-_HISTORY_CAP]


def plan_history() -> list:
    with _HISTORY_LOCK:
        return list(_HISTORY)


def clear_history() -> None:
    with _HISTORY_LOCK:
        _HISTORY.clear()
