"""The fuser: compile a recorded stage chain into fused device programs.

Walks the plan front-to-back against the LIVE dataset/backend state and
greedily groups maximal fusible runs:

* ``[aggregate, convert, reduce(kernel, batch)]`` on a multi-shard mesh
  → TWO compiled programs: the shuffle's jitted phase 1 (hash + sort by
  dest + counts), then ONE ``jit``/``shard_map`` program that composes
  the phase-2 exchange (``shuffle.phase2_shard_body``), the local
  convert (sort + boundary detection, the ``parallel/group`` bodies)
  and the segment reduce — where the eager path dispatches ~5 programs
  with a host sync between every op.
* ``[aggregate, convert]`` (collate feeding a host-callback reduce)
  → the same two programs, producing a grouped ShardedKMV.
* ``[convert, reduce(kernel, batch)]`` on an already-sharded KV
  → ONE fused local program (no exchange).

**Megafusion** (``MRTPU_MEGAFUSE``, default on — fusion v2,
doc/plan.md): on a *warm* group (the CompiledPlan carries the previous
run's exchange plan and group capacity) the remaining fusion boundary —
the host count/stats sync between phase 1 and the fused program — moves
OFF the dispatch path: ONE jit/``shard_map`` program composes phase-1
dest-sort + wire-encode + exchange + wire-decode + group/segment-reduce
and *additionally emits* the count/stats/meta matrices, which the host
pulls AFTER the single dispatch as a speculation check (``plan_holds``
+ group-capacity coverage).  A failed check
discards the result and re-runs the two-dispatch v1 path on the same
inputs (megafused programs never donate, precisely so this replay and
the chaos retry stay possible).  Steady state: **1 dispatch per plan
group** (``Counters.ndispatch``).

Everything else — host-callback tiers, serial backend, spill/out-of-core
datasets, over-HBM-budget datasets, comparator sorts — **breaks fusion**:
those stages replay through the ordinary eager methods, so every
pipeline still runs, fused or not.

Compiled plans live in the plan cache (``plan.cache``) keyed on
(stage-chain fingerprint, frame shapes/dtypes, mesh); a hit
reuses the previous run's exchange caps (validated against the fresh
count matrix, like the shuffle's speculative-cap cache) so repeated
pipelines reuse compiled programs instead of re-deriving shapes.
Telemetry: ``plan.execute`` / ``plan.group`` obs spans with
``cache_hit``/``fused`` attrs, plan-cache hit/miss/eviction counters in
``MapReduce.stats()["plan"]``, and every program launch counted in
``Counters.ndispatch``.
"""

from __future__ import annotations

from dataclasses import dataclass, field as _field
from typing import Optional

import numpy as np

from ..utils.env import env_flag, env_knob
from .cache import (LRUCache, note_fusion, persistent_cache, plan_cache,
                    record_history, stable_plan_digest)
from .ir import Plan, PlanStage, frame_signature

# bounded builder cache for the fused jitted programs (same policy as
# the shuffle's phase caches)
FUSED_CACHE = LRUCache(env_knob("MRTPU_JIT_CACHE", int, 64),
                       name="plan.fused")


def megafuse_enabled() -> bool:
    """``MRTPU_MEGAFUSE`` (default on): single-dispatch warm groups —
    fusion v2.  ``0`` restores the v1 two-dispatch fuser everywhere
    (the auto-fallback target)."""
    return env_flag("MRTPU_MEGAFUSE", True)


# eager-tier compiled-program launches per op (shuffle phase 1+2,
# convert phase 1+2, one segment-reduce program) — the baseline the
# fusion-savings telemetry in mr.stats()["plan"]["fusion"] compares
# actual group dispatches against (doc/plan.md "reading the counters")
_EAGER_DISPATCHES = {"aggregate": 2, "convert": 2, "reduce": 1}


@dataclass
class CompiledPlan:
    """Cached executable state of one (fingerprint, shapes) plan: the
    group structure last used plus per-group exchange caps for reuse."""
    groups: list = _field(default_factory=list)   # descriptions (history)
    caps: dict = _field(default_factory=dict)     # group idx → (B, R, cap)
    # fusion v2: per-group megafuse speculation state, recorded by a
    # successful v1 run and validated after every single-dispatch run —
    # gidx → ("x", exchange_plan, gcap) | ("l", gcap)
    mega: dict = _field(default_factory=dict)
    runs: int = 0


# ---------------------------------------------------------------------------
# stage classification helpers
# ---------------------------------------------------------------------------

def _kernel_op(fn) -> Optional[str]:
    """Registered kernel reduce → segment-op name (None = host tier)."""
    from ..ops import reduces
    table = {reduces.count: "count", reduces.sum_values: "sum",
             reduces.max_values: "max", reduces.min_values: "min",
             reduces.cull: "first"}
    return table.get(fn)


def _reduce_stage_op(st: PlanStage) -> Optional[str]:
    """Fusible reduce stage → segment-op name, else None."""
    if st.op != "reduce" or not st.args:
        return None
    if not (st.kw.get("batch") or (len(st.args) > 2 and st.args[2])):
        return None
    if st.kw.get("block_rows") is not None:
        return None
    return _kernel_op(st.args[0])


def _agg_hash(st: PlanStage):
    """(ok, hash_fn) for an aggregate stage: host-evaluated hashes break
    fusion (they need per-key python on the controller), and a total
    order runs eagerly, where its splitters are an operand of a cached
    phase 1 and not constants of a program a job."""
    from ..parallel.shuffle import TotalOrder
    fn = st.args[0] if st.args else st.kw.get("hash_fn")
    if fn is not None and (getattr(fn, "host_hash", False)
                           or isinstance(fn, TotalOrder)):
        return False, fn
    return True, fn


def _device_state(mr):
    """The live frame a fused group would consume, or None when the
    current state is not device-fusible (spill, budget, serial, host
    tiers) — the fusion-break rules of doc/plan.md."""
    from ..parallel.backend import MeshBackend
    if not isinstance(mr.backend, MeshBackend):
        return None
    kv = mr.kv
    if kv is None or not kv.complete_done or mr._open:
        return None
    if mr.settings.outofcore == 1:          # spill boundary
        return None
    if not kv.is_host_dataset() and mr._mesh_over_budget(kv):
        return None                          # HBM budget → external path
    frame = kv.one_frame()
    if len(frame) == 0:
        return None                          # eager handles empties
    return frame


def _match_group(mr, stages, i):
    """(n_stages, kind, reduce_op, frame) of the fused group starting at
    stage i against the live state, or (1, None, None, None) → eager
    replay.  The materialized frame rides along so the exec functions
    don't pay ``one_frame()`` (a device concat on multi-frame datasets)
    a second time."""
    from ..core.frame import KVFrame
    from ..parallel.sharded import ShardedKV
    st = stages[i]
    n = len(stages)
    if st.op == "aggregate":
        ok, _fn = _agg_hash(st)
        frame = _device_state(mr) if ok else None
        if (frame is not None and mr.backend.nprocs > 1
                and i + 1 < n and stages[i + 1].op == "convert"
                and (isinstance(frame, ShardedKV)
                     or (isinstance(frame, KVFrame) and frame.is_dense())
                     or _internable(frame))):
            rop = _reduce_stage_op(stages[i + 2]) if i + 2 < n else None
            if rop is not None and not _reduce_value_ok(frame, rop):
                rop = None
            if rop is not None:
                return 3, "exchange", rop, frame
            return 2, "exchange", None, frame
        return 1, None, None, None
    if st.op == "convert":
        frame = _device_state(mr)
        if isinstance(frame, ShardedKV) and i + 1 < n:
            rop = _reduce_stage_op(stages[i + 1])
            if rop is not None and _reduce_value_ok(frame, rop):
                return 2, "local", rop, frame
        return 1, None, None, None
    return 1, None, None, None


def _internable(frame) -> bool:
    from ..core.column import BytesColumn, DenseColumn, ObjectColumn
    return all(isinstance(c, (BytesColumn, DenseColumn, ObjectColumn))
               for c in (frame.key, frame.value))


def _reduce_value_ok(frame, rop: str) -> bool:
    """Arithmetic on interned byte/object VALUE ids is meaningless —
    eager reduce_sharded raises for it; fall back so the same error
    surfaces from the same code path."""
    if rop in ("count", "first"):
        return True
    from ..core.column import BytesColumn, ObjectColumn
    if getattr(frame, "value_decode", None) is not None:
        return False
    value = getattr(frame, "value", None)
    return not isinstance(value, (BytesColumn, ObjectColumn))


# ---------------------------------------------------------------------------
# fused program bodies (composable, shard-local)
# ---------------------------------------------------------------------------
# The convert(+reduce) shard body itself lives with its eager siblings
# in ``parallel/group.fused_group_body``; the builders here only choose
# its static knobs and compose it with the exchange bodies.


def _gcap_for(gcounts, cap_out: int) -> int:
    """The group capacity a warm megafused run compiles at: the eager
    tier's pow2 residency bound (``round_cap`` of the observed max),
    clamped to the exchange output capacity."""
    from ..parallel.sharded import round_cap
    return min(round_cap(max(int(gcounts.max()), 1)), cap_out)


def _donate_argnums(donate: bool, aliasable_dim0: bool, out_kind: str,
                    reduce_op, svalue) -> tuple:
    """Which of (skey, svalue) to donate: only buffers whose donation
    can actually alias an output of the same byte size (anything else
    would be a warned no-op).  The key side always has a same-dtype
    same-trailing-dims output; the value side does too EXCEPT for a
    count reduce, whose output is 1-D int64 regardless of the value's
    shape."""
    if not (donate and aliasable_dim0):
        return ()
    if (out_kind == "kmv" or reduce_op != "count"
            or (svalue.ndim == 1 and svalue.dtype.itemsize == 8)):
        return (0, 1)
    return (0,)


def _fused_exchange_jit(mesh, plan, out_kind: str,
                        reduce_op: Optional[str], donate_argnums=()):
    """``plan`` is the tagged exchange plan (parallel/wire.py): raw
    plans compose the original phase-2 body, wire plans the codec body —
    either way every static knob of the plan keys the executable cache."""
    key = ("exchange", mesh, plan, out_kind, reduce_op,
           tuple(donate_argnums))
    return FUSED_CACHE.get_or_build(
        key, lambda: _fused_exchange_build(mesh, plan, out_kind,
                                           reduce_op, donate_argnums))


def _fused_exchange_build(mesh, plan, out_kind, reduce_op,
                          donate_argnums=()):
    import jax
    from ..exec import donated_jit
    from ..parallel.group import fused_group_body
    from ..parallel.mesh import mesh_axis_size, row_spec
    from ..parallel.shuffle import phase2_shard_body
    from ..parallel.wire import phase2_wire_shard_body, plan_cap_out
    nprocs = mesh_axis_size(mesh)
    spec = row_spec(mesh)
    nouts = 5 if out_kind == "kmv" else 3
    cap_out = plan_cap_out(plan)

    if plan[0] == "wire":
        _tag, tiers, _cap, kpack, vpack = plan

        def run(skey, svalue, counts_local, stats_local):
            def body(k, v, cl, st):
                out_k, out_v, nrecv = phase2_wire_shard_body(
                    nprocs, mesh, tiers, cap_out, kpack, vpack, k, v,
                    cl, st)
                return fused_group_body(out_k, out_v, nrecv, cap_out,
                                        out_kind, reduce_op)
            return jax.shard_map(
                body, mesh=mesh, in_specs=(spec,) * 4,
                out_specs=(spec,) * nouts)(skey, svalue, counts_local,
                                           stats_local)
    else:
        _tag, B, nrounds, _cap = plan

        def run(skey, svalue, counts_local):
            def body(k, v, cl):
                out_k, out_v, nrecv = phase2_shard_body(
                    nprocs, mesh, B, nrounds, cap_out, k, v, cl)
                return fused_group_body(out_k, out_v, nrecv, cap_out,
                                        out_kind, reduce_op)
            return jax.shard_map(
                body, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=(spec,) * nouts)(skey, svalue, counts_local)

    # exec/: the dest-sorted phase-1 intermediates are dead after the
    # fused program — donate the aliasable ones (MRTPU_DONATE)
    return donated_jit(run, donate_argnums)


def _mega_jit(mesh, dest, plan, gcap: int, out_kind: str, reduce_op,
              elig):
    """The fusion-v2 single-dispatch program: phase-1 dest-sort (+wire
    stats) + exchange (+wire encode/decode) + group/segment-reduce in
    ONE jit/shard_map, with the count/stats/meta matrices as extra
    outputs the host pulls AFTER dispatch (the speculation check).
    Every static knob — the exchange plan, the group capacity — keys
    the executable cache."""
    key = ("mega", mesh, dest, plan, gcap, out_kind, reduce_op, elig)
    return FUSED_CACHE.get_or_build(
        key, lambda: _mega_build(mesh, dest, plan, gcap, out_kind,
                                 reduce_op, elig))


def _mega_build(mesh, dest, plan, gcap, out_kind, reduce_op, elig):
    import jax
    from ..parallel.group import fused_group_body
    from ..parallel.mesh import mesh_axis_size, row_spec
    from ..parallel.shuffle import (_dest_fn, phase1_shard_body,
                                    phase2_shard_body)
    from ..parallel.wire import phase2_wire_shard_body, plan_cap_out
    nprocs = mesh_axis_size(mesh)
    spec = row_spec(mesh)
    dest_of = _dest_fn(dest, nprocs, mesh)
    cap_out = plan_cap_out(plan)
    ngout = 5 if out_kind == "kmv" else 3
    nouts = ngout + 1 + (1 if elig is not None else 0)

    def body(k, v, c):
        sk, sv, cl, st = phase1_shard_body(nprocs, dest_of, elig, k, v, c)
        if plan[0] == "wire":
            _tag, tiers, _cap, kpack, vpack = plan
            out_k, out_v, nrecv = phase2_wire_shard_body(
                nprocs, mesh, tiers, cap_out, kpack, vpack, sk, sv, cl,
                st)
        else:
            _tag, B, nrounds, _cap = plan
            out_k, out_v, nrecv = phase2_shard_body(
                nprocs, mesh, B, nrounds, cap_out, sk, sv, cl)
        gouts = fused_group_body(out_k, out_v, nrecv, gcap, out_kind,
                                 reduce_op)
        return (*gouts, cl) if st is None else (*gouts, cl, st)

    def run(key, value, count):
        return jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=(spec,) * nouts)(key, value, count)

    # NEVER donated: a failed speculation check (or a chaos retry)
    # re-runs on the same inputs, which donation would have deleted
    return jax.jit(run)


def _compact_jit(mesh, n: int, narrs: int):
    """Per-shard leading-rows slice: shrink a fused group's [cap_out]
    outputs to the eager tier's round_cap(max groups) residency.  One
    cheap extra dispatch, paid only when it shrinks ≥4× (see
    _maybe_compact) — without it duplicate-heavy keys leave the resident
    dataset (and every downstream compile) sized at row capacity."""
    key = ("compact", mesh, n, narrs)

    def build():
        import jax
        from ..parallel.mesh import row_spec
        spec = row_spec(mesh)

        @jax.jit
        def run(*arrs):
            body = lambda *xs: tuple(x[:n] for x in xs)
            return jax.shard_map(body, mesh=mesh, in_specs=(spec,) * narrs,
                                 out_specs=(spec,) * narrs)(*arrs)
        return run
    return FUSED_CACHE.get_or_build(key, build)


def _maybe_compact(mesh, gcap: int, gcounts, *arrs):
    """Slice group-indexed outputs down to round_cap(max group count)
    when that shrinks ≥4×; otherwise return them unchanged (the extra
    dispatch isn't worth single-digit savings)."""
    from ..core.runtime import bump_dispatch
    from ..parallel.sharded import round_cap
    new_gcap = round_cap(max(int(gcounts.max()), 1))
    if new_gcap * 4 > gcap:
        return arrs
    bump_dispatch()
    return _compact_jit(mesh, new_gcap, len(arrs))(*arrs)


def _fused_local_jit(mesh, out_kind: str, reduce_op: Optional[str],
                     gcap: Optional[int] = None, donate_argnums=()):
    key = ("local", mesh, out_kind, reduce_op, gcap,
           tuple(donate_argnums))
    return FUSED_CACHE.get_or_build(
        key, lambda: _fused_local_build(mesh, out_kind, reduce_op,
                                        gcap, donate_argnums))


def _fused_local_build(mesh, out_kind, reduce_op, gcap=None,
                       donate_argnums=()):
    import jax
    from ..exec import donated_jit
    from ..parallel.group import fused_group_body
    from ..parallel.mesh import row_spec
    spec = row_spec(mesh)
    nouts = 5 if out_kind == "kmv" else 3

    def run(key, value, counts):
        def body(k, v, c):
            # gcap=None → full row capacity (the cold run); a warm run
            # compiles at the cached compact capacity (fusion v2)
            return fused_group_body(k, v, c[0],
                                    k.shape[0] if gcap is None else gcap,
                                    out_kind, reduce_op)
        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=(spec,) * nouts)(key, value, counts)

    # exec/: the consumed KV is replaced by the grouped output right
    # after (_install_kv) — donating lets the group layout reuse its
    # buffers (ukey is same-size as key here: gcap == cap)
    return donated_jit(run, donate_argnums)


# ---------------------------------------------------------------------------
# fused group execution
# ---------------------------------------------------------------------------

def _as_sharded(mr, frame):
    """Host frame → ShardedKV (intern byte/object columns + block-shard),
    exactly the eager aggregate's preparation (shuffle.aggregate_kv)."""
    from ..core.frame import KVFrame
    from ..parallel.sharded import shard_frame
    from ..parallel.shuffle import _intern_frame
    if not isinstance(frame, KVFrame):
        return frame
    frame, ktable, vtable = _intern_frame(frame, mr.backend.nprocs)
    skv = shard_frame(frame, mr.backend.mesh)
    skv.key_decode = ktable
    skv.value_decode = vtable
    return skv


def _install_kv(mr, skv):
    """Replace mr's dataset with a fused group's ShardedKV output."""
    if mr.kmv is not None:
        mr.kmv.free()
        mr.kmv = None
    old = mr.kv
    newkv = mr._new_kv()
    newkv.add_frame(skv)
    newkv.complete()
    if old is not None:
        old.free()
    mr.kv = newkv


def _install_kmv(mr, skmv):
    if mr.kv is not None:
        mr.kv.free()
        mr.kv = None
    mr.kmv = mr._new_kmv()
    mr.kmv.push(skmv)
    mr.kmv.complete()


def _exec_exchange_group(mr, stages, reduce_op, compiled: CompiledPlan,
                         gidx: int, sp, frame) -> str:
    """Run [aggregate, convert(, reduce)] as a fused exchange group.
    Warm + ``MRTPU_MEGAFUSE``: ONE megafused program (see module doc);
    cold or speculation-failed: phase 1 + ONE fused program (v1).
    Under ``MRTPU_WIRE`` both compose the wire-codec bodies
    (parallel/wire.py): the rows cross the interconnect delta-packed
    with tiered caps and decode inside the same program, so the grouped
    output stays byte-identical to the eager tiers.

    Runs under the ft/ ``shuffle.exchange`` fault site + retry policy
    like the eager exchange: the fault point sits before any dispatch,
    and a failure after the v1 path's donated phase-1 dispatch is
    vetoed as non-retryable (the megafused program never donates, so
    its retries are always safe).  Returns the mode for the fusion
    telemetry."""
    from ..ft.inject import fault_point
    from ..ft.retry import retry_call
    from ..parallel.mesh import mesh_axis_size

    skv = _as_sharded(mr, frame)

    def _once():
        fault_point("shuffle.exchange")
        return _exchange_group_impl(mr, stages, reduce_op, compiled,
                                    gidx, sp, skv)

    def _retryable(e):
        try:
            return not skv.key.is_deleted()
        except Exception:
            return False

    return retry_call(
        "shuffle.exchange", _once,
        detail=f"P={mesh_axis_size(mr.backend.mesh)} fused",
        retryable=_retryable)


def _exchange_group_impl(mr, stages, reduce_op, compiled, gidx, sp,
                         skv) -> str:
    import jax
    from ..core.runtime import Timer, bump_dispatch
    from ..parallel import wire as _wire
    from ..parallel.mesh import mesh_axis_size, row_sharding
    from ..parallel.sharded import SyncStats
    from ..parallel.shuffle import _phase1_jit

    mesh = mr.backend.mesh
    nprocs = mesh_axis_size(mesh)
    out_kind = "kv" if reduce_op is not None else "kmv"
    _ok, hash_fn = _agg_hash(stages[0])
    dest = ("hash", hash_fn)

    from ..exec import can_donate
    donate = can_donate(skv)
    wire_on = _wire.wire_enabled()
    elig = _wire.columns_eligible(skv.key, skv.value) if wire_on else None
    counts_dev = jax.device_put(skv.counts.astype(np.int32),
                                row_sharding(mesh))
    t = Timer()

    entry = compiled.mega.get(gidx) if megafuse_enabled() else None
    if entry is not None and entry[0] == "x":
        if _exec_mega_exchange(mr, stages, reduce_op, compiled, gidx,
                               sp, skv, dest, out_kind, entry, wire_on,
                               elig, counts_dev, t):
            return "mega"
        # speculation failed — discard and fall through to v1 on the
        # SAME (never-donated) inputs; the commtime Timer keeps running
        # so the failed attempt's wall is charged honestly

    bump_dispatch()
    stats_local = None
    if wire_on:
        skey, svalue, counts_local, stats_local = _phase1_jit(
            mesh, dest, donate, wire=elig)(skv.key, skv.value, counts_dev)
    else:
        skey, svalue, counts_local = _phase1_jit(mesh, dest, donate)(
            skv.key, skv.value, counts_dev)
    SyncStats.bump()   # the op's ONE round-trip: the count matrix
    counts_mat = np.asarray(counts_local).reshape(nprocs, nprocs)
    stats_mat = (np.asarray(stats_local).reshape(nprocs, nprocs, 4)
                 if stats_local is not None else None)
    # ONE planning step shared with the eager exchange (wire.plan_from_
    # pull): plan choice and telemetry must never diverge between tiers
    plan, kvrange, bmax_raw, nmax_out, _new_counts = _wire.plan_from_pull(
        skv.key, skv.value, counts_mat, stats_mat, wire_on, elig)
    cached = compiled.caps.get(gidx)
    if cached is not None and cached[0] == plan[0] \
            and _wire.plan_holds(cached, bmax_raw, nmax_out, kvrange) \
            and not _wire.plan_oversized(cached, bmax_raw, nmax_out):
        # the cached plan still holds every row exactly and isn't
        # grossly oversized: reuse the compiled program
        plan = cached
    else:
        # too small OR ≥4× too large (skewed first run followed by
        # uniform data would pay the padded transfer forever, like the
        # eager speculative cache's right-sizing): recompile at the
        # fresh plan
        compiled.caps[gidx] = plan
    cap_out = _wire.plan_cap_out(plan)
    bump_dispatch()
    argnums = _donate_argnums(
        donate, cap_out == skey.shape[0] // max(nprocs, 1), out_kind,
        reduce_op, svalue)
    fused = _fused_exchange_jit(mesh, plan, out_kind, reduce_op,
                                donate_argnums=argnums)
    if plan[0] == "wire":
        out = fused(skey, svalue, counts_local, stats_local)
    else:
        out = fused(skey, svalue, counts_local)
    meta = np.asarray(out[-1]).reshape(nprocs, 3)
    gcounts = meta[:, 0].astype(np.int32)
    vcounts = meta[:, 1].astype(np.int32)
    _finish_exchange_group(mr, stages, sp, skv, out_kind, reduce_op,
                           mesh, nprocs, plan, counts_mat, gcounts,
                           vcounts, out, t, compact_from=cap_out)
    # arm the NEXT run's single-dispatch speculation with what this run
    # measured: the plan that ran and the compact group capacity
    if megafuse_enabled():
        compiled.mega[gidx] = ("x", plan, _gcap_for(gcounts, cap_out))
    return "v1"


def _exec_mega_exchange(mr, stages, reduce_op, compiled, gidx, sp, skv,
                        dest, out_kind, entry, wire_on, elig,
                        counts_dev, t):
    """One megafused attempt.  Returns True on success, False when the
    post-dispatch speculation check failed (the caller re-runs v1 on
    the same inputs — nothing was donated)."""
    from ..core.runtime import bump_dispatch
    from ..parallel import wire as _wire
    from ..parallel.mesh import mesh_axis_size
    from ..parallel.sharded import SyncStats

    mesh = mr.backend.mesh
    nprocs = mesh_axis_size(mesh)
    _tag, plan, gcap = entry
    bump_dispatch()   # THE one dispatch of the warm group
    prog = _mega_jit(mesh, dest, plan, gcap, out_kind, reduce_op, elig)
    out = prog(skv.key, skv.value, counts_dev)
    SyncStats.bump()   # still ONE host round-trip — now after dispatch
    ngout = 5 if out_kind == "kmv" else 3
    gouts = out[:ngout]
    counts_mat = np.asarray(out[ngout]).reshape(nprocs, nprocs)
    stats_mat = (np.asarray(out[ngout + 1]).reshape(nprocs, nprocs, 4)
                 if elig is not None else None)
    meta = np.asarray(gouts[-1]).reshape(nprocs, 3)
    gcounts = meta[:, 0].astype(np.int32)
    vcounts = meta[:, 1].astype(np.int32)
    overflow = int(meta[:, 2].sum())
    # the speculation check: would the compiled shapes have dropped any
    # row (exchange plan) or group (gcap)?
    fresh, kvrange, bmax_raw, nmax_out, _nc = _wire.plan_from_pull(
        skv.key, skv.value, counts_mat, stats_mat, wire_on, elig)
    max_g = int(gcounts.max()) if gcounts.size else 0
    if (overflow or max_g > gcap
            or not _wire.plan_holds(plan, bmax_raw, nmax_out, kvrange)):
        compiled.mega.pop(gidx, None)
        sp.set(mega_miss=True)
        return False
    # right-size a grossly oversized or tag-shifted entry for NEXT time
    # (this run's result is exact and kept)
    if (plan[0] != fresh[0]
            or _wire.plan_oversized(plan, bmax_raw, nmax_out)
            or gcap > 4 * _gcap_for(gcounts, _wire.plan_cap_out(plan))):
        compiled.mega[gidx] = (
            "x", fresh, _gcap_for(gcounts, _wire.plan_cap_out(fresh)))
    _finish_exchange_group(mr, stages, sp, skv, out_kind, reduce_op,
                           mesh, nprocs, plan, counts_mat, gcounts,
                           vcounts, gouts, t, compact_from=None,
                           mega=True)
    return True


def _finish_exchange_group(mr, stages, sp, skv, out_kind, reduce_op,
                           mesh, nprocs, plan, counts_mat, gcounts,
                           vcounts, out, t, compact_from=None,
                           mega=False):
    """Shared tail of the v1 and megafused exchange groups: byte/stat
    accounting, span attrs, stage results and dataset installation —
    ONE copy so the two tiers' telemetry can never diverge."""
    from ..parallel import wire as _wire
    from ..parallel.sharded import ShardedKMV, ShardedKV
    from ..parallel.shuffle import ExchangeCallStats

    mr.counters.add(commtime=t.elapsed())
    nrows = int(counts_mat.sum())
    ngroups = int(gcounts.sum())
    cap_out = _wire.plan_cap_out(plan)
    B_eff, nrounds_eff = _wire.plan_rounds(plan)
    stats = ExchangeCallStats(nrounds=nrounds_eff, bucket=B_eff,
                              cap_out=cap_out, rows=nrows,
                              speculative=mega)
    _account_exchange(mr, skv, counts_mat, plan, nprocs, stats)
    mr.last_exchange = stats
    sp.set(bucket=B_eff, nrounds=nrounds_eff, cap_out=cap_out,
           rows=nrows, groups=ngroups, wire_bytes=stats.wire_bytes,
           wire_ratio=stats.wire_ratio, mega=mega)
    stages[0].result = nrows
    stages[1].result = ngroups
    if out_kind == "kv":
        ukey, uval = out[0], out[1]
        if compact_from is not None:
            ukey, uval = _maybe_compact(mesh, compact_from, gcounts,
                                        ukey, uval)
        skv_out = ShardedKV(mesh, ukey, uval, gcounts,
                            key_decode=skv.key_decode)
        if reduce_op == "first":
            skv_out.value_decode = skv.value_decode
        _install_kv(mr, skv_out)
        stages[2].result = ngroups
    else:
        # values/voff stay row-capacity-sized (voff indexes value rows,
        # exactly like the eager ShardedKMV); only group-indexed arrays
        # compact (already compiled compact in the megafused program)
        ukey, sizes, voff, values = out[0], out[1], out[2], out[3]
        if compact_from is not None:
            ukey, sizes, voff = _maybe_compact(mesh, compact_from,
                                               gcounts, ukey, sizes,
                                               voff)
        skmv = ShardedKMV(mesh, ukey, sizes, voff, values, gcounts,
                          vcounts, key_decode=skv.key_decode,
                          value_decode=skv.value_decode)
        _install_kmv(mr, skmv)


def _account_exchange(mr, skv, counts_mat, plan, nprocs, stats):
    from ..obs.metrics import record_exchange
    from ..parallel.shuffle import exchange_volume
    from ..parallel.wire import plan_slots, wire_ratio, wire_volume
    moved, pad, _rowbytes = exchange_volume(skv, counts_mat,
                                            plan_slots(plan), nprocs)
    mr.counters.add(cssize=moved, crsize=moved, cspad=pad)
    stats.sent_bytes, stats.pad_bytes = moved, pad
    if plan[0] == "wire":
        stats.wire_bytes = wire_volume(skv, counts_mat, plan)
        stats.wire_ratio = wire_ratio(moved, pad, stats.wire_bytes)
    # the fused tier's twin of the eager _exchange_impl feed: without it
    # a MRTPU_FUSE=1 run reads "no exchange traffic" on /metrics
    record_exchange(stats)


def _exec_local_group(mr, stages, reduce_op, compiled: CompiledPlan,
                      gidx: int, sp, frame) -> str:
    """Run [convert, reduce(kernel)] on a ShardedKV as ONE program.
    Fusion v2: a warm group compiles at the cached compact group
    capacity (skipping the separate compact dispatch); the
    post-dispatch meta pull validates the capacity and re-runs at full
    capacity when it no longer covers.  Returns the mode for the fusion
    telemetry."""
    import jax
    from ..core.runtime import bump_dispatch
    from ..parallel.mesh import mesh_axis_size, row_sharding
    from ..parallel.sharded import ShardedKV, SyncStats

    skv = frame
    mesh = skv.mesh
    nprocs = mesh_axis_size(mesh)
    from ..exec import can_donate
    donate = can_donate(skv)
    cap = skv.key.shape[0] // nprocs   # before donation deletes the data
    counts_dev = jax.device_put(skv.counts.astype(np.int32),
                                row_sharding(mesh))
    entry = compiled.mega.get(gidx) if megafuse_enabled() else None
    gcap = entry[1] if entry is not None and entry[0] == "l" else None
    mode = "local1" if gcap is not None else "local"
    bump_dispatch()
    # donation only when the group outputs alias the inputs byte for
    # byte — a compact (gcap < cap) warm program's outputs are smaller,
    # and its speculative re-run needs the inputs alive anyway
    argnums = _donate_argnums(donate and gcap is None, True, "kv",
                              reduce_op, skv.value)
    ukey, uval, meta = _fused_local_jit(mesh, "kv", reduce_op,
                                        gcap=gcap,
                                        donate_argnums=argnums)(
        skv.key, skv.value, counts_dev)
    SyncStats.bump()
    m = np.asarray(meta).reshape(nprocs, 3)
    gcounts = m[:, 0].astype(np.int32)
    overflow = int(m[:, 2].sum())
    if gcap is not None and (overflow or int(gcounts.max()) > gcap):
        # the cached capacity no longer covers: discard and re-run at
        # full row capacity (nothing was donated on the compact path)
        compiled.mega.pop(gidx, None)
        sp.set(mega_miss=True)
        bump_dispatch()
        ukey, uval, meta = _fused_local_jit(
            mesh, "kv", reduce_op, donate_argnums=())(
            skv.key, skv.value, counts_dev)
        SyncStats.bump()   # the re-run's meta pull is a second sync
        m = np.asarray(meta).reshape(nprocs, 3)
        gcounts = m[:, 0].astype(np.int32)
        gcap = None
        mode = "local"
    ngroups = int(gcounts.sum())
    if gcap is None:
        ukey, uval = _maybe_compact(mesh, cap, gcounts, ukey, uval)
        if megafuse_enabled():
            compiled.mega[gidx] = ("l", _gcap_for(gcounts, cap))
    skv_out = ShardedKV(mesh, ukey, uval, gcounts,
                        key_decode=skv.key_decode)
    if reduce_op == "first":
        skv_out.value_decode = skv.value_decode
    _install_kv(mr, skv_out)
    sp.set(groups=ngroups, mega=gcap is not None)
    stages[0].result = ngroups
    stages[1].result = ngroups
    return mode


def _replay(mr, stage: PlanStage):
    """Eager fallback: run one recorded stage through the ordinary op
    method (tracing, stats, tier notes all behave as if never deferred),
    under the settings snapshot taken at record time."""
    saved = mr.settings
    if stage.settings is not None:
        mr.settings = stage.settings
    mr._plan_replaying = True
    try:
        stage.result = getattr(mr, stage.op)(*stage.args, **stage.kw)
    finally:
        mr._plan_replaying = False
        mr.settings = saved


# ---------------------------------------------------------------------------
# plan execution
# ---------------------------------------------------------------------------

def execute_plan(mr, plan: Plan) -> None:
    """Fuse + run a recorded plan against mr's current dataset."""
    tracer = mr.tracer
    key = None
    frame = None
    kv = mr.kv
    if kv is not None and kv.complete_done and kv._frames:
        frame = kv._frames[0]
    try:
        # MRTPU_WIRE is part of the key: a cached wire plan's caps are
        # tier/pack tuples a raw run can't validate against (and vice
        # versa), so the two knob states never share an entry
        from ..parallel.wire import wire_enabled
        key = (plan.fingerprint(), frame_signature(frame),
               _backend_signature(mr), mr.settings.outofcore,
               wire_enabled())
        compiled = plan_cache().get(key)
    except TypeError:       # unhashable stage arg: run uncached
        key = None
        compiled = None
    cache_hit = compiled is not None
    # the persistent tier (plan/cache.py): an in-memory miss consults
    # the on-disk plan store before compiling cold — a restarted
    # replica re-enters warm speculation state (caps + megafuse plans)
    # and, with the XLA executable cache armed next door, recompiles
    # nothing
    pkey = stable_plan_digest(key) if key is not None \
        and persistent_cache() is not None else None
    if compiled is None and pkey is not None:
        payload = persistent_cache().load(pkey)
        if payload is not None:
            compiled = _plan_from_payload(payload)
            plan_cache().put(key, compiled)
            cache_hit = True
    if compiled is None:
        compiled = CompiledPlan()
        if key is not None:
            plan_cache().put(key, compiled)
    compiled.runs += 1
    groups_desc = []
    with tracer.span("plan.execute", cat="plan", nstages=len(plan),
                     cache_hit=cache_hit) as psp:
        from ..core.runtime import thread_dispatches
        stages = list(plan.stages)
        i = 0
        gidx = 0
        while i < len(stages):
            n, kind, rop, frame = _match_group(mr, stages, i)
            run = stages[i:i + n]
            desc = {"stages": [s.describe() for s in run],
                    "fused": kind is not None, "kind": kind or "eager",
                    "reduce_op": rop}
            groups_desc.append(desc)
            # per-THREAD meter: concurrent serve workers' dispatches
            # must not contaminate this group's count (review fix)
            d0 = thread_dispatches()
            mode = "eager"
            if kind is None:
                _replay(mr, run[0])
            else:
                with tracer.span("plan.group", cat="plan", kind=kind,
                                 fused=True, nstages=n,
                                 reduce_op=rop or "") as sp:
                    try:
                        if kind == "exchange":
                            mode = _exec_exchange_group(
                                mr, run, rop, compiled, gidx, sp, frame)
                        else:
                            mode = _exec_local_group(
                                mr, run, rop, compiled, gidx, sp, frame)
                    except BaseException:
                        # same contract as the eager exchange callers:
                        # a failure after a donated dispatch must leave
                        # a clean empty dataset (MRError on next op),
                        # never frames holding deleted buffers
                        from ..parallel.shuffle import free_if_donated
                        kv = mr._kv_data
                        if kv is not None:
                            free_if_donated(kv, frame)
                        raise
            # fusion effectiveness telemetry (mr.stats()["plan"]
            # ["fusion"] + the per-request profile): actual dispatches
            # of this group vs the eager tier's known per-op counts
            note_fusion(
                kind or "eager", mode, thread_dispatches() - d0,
                sum(_EAGER_DISPATCHES.get(s.op, 1) for s in run))
            desc["mode"] = mode
            i += n
            gidx += 1
        psp.set(ngroups=gidx,
                nfused=sum(1 for d in groups_desc if d["fused"]))
    compiled.groups = groups_desc
    if pkey is not None:
        # persist what this run learned (no-op when unchanged); an
        # empty speculation state still marks the digest as seen, so a
        # restarted replica re-enters the warm path; an unserializable
        # plan component just stays process-local
        payload = _plan_payload(compiled)
        if payload is not None:
            pp = persistent_cache()
            if pp is not None:
                pp.store(pkey, payload)
    record_history({"stages": plan.describe(), "groups": groups_desc,
                    "cache_hit": cache_hit,
                    "cache_key": _key_brief(key)})


def _plan_payload(compiled: CompiledPlan) -> Optional[dict]:
    """CompiledPlan speculation state → JSON-safe payload (None when a
    component has no stable serialization)."""
    from .cache import to_jsonable
    try:
        # runs is deliberately NOT persisted: it changes every
        # execution, which would defeat the store's unchanged-bytes
        # no-op and rewrite the entry per run
        return {"caps": {str(k): to_jsonable(v)
                         for k, v in compiled.caps.items()},
                "mega": {str(k): to_jsonable(v)
                         for k, v in compiled.mega.items()}}
    except TypeError:
        return None


def _plan_from_payload(payload: dict) -> CompiledPlan:
    """Inverse of :func:`_plan_payload`: group indices back to ints,
    lists back to tuples (wire plans are hashed into FUSED_CACHE keys,
    so tuple-ness matters)."""
    from .cache import from_jsonable
    cp = CompiledPlan()
    try:
        cp.caps = {int(k): from_jsonable(v)
                   for k, v in dict(payload.get("caps") or {}).items()}
        cp.mega = {int(k): from_jsonable(v)
                   for k, v in dict(payload.get("mega") or {}).items()}
        cp.runs = int(payload.get("runs", 0))
    except (TypeError, ValueError):
        return CompiledPlan()
    return cp


def _backend_signature(mr):
    from ..parallel.backend import MeshBackend
    if isinstance(mr.backend, MeshBackend):
        return ("mesh", mr.backend.mesh)
    return ("serial",)


def _key_brief(key) -> Optional[str]:
    if key is None:
        return None
    fp, frame_sig, backend, ooc, wire = key
    ops = "→".join(s[0] for s in fp)
    return (f"ops[{ops}] frame{frame_sig!r} backend={backend[0]} "
            f"outofcore={ooc} wire={int(wire)}")
