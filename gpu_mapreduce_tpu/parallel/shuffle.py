"""The distributed shuffle — aggregate() over ICI collectives.

Re-designs the reference's ``MapReduce::aggregate`` + ``Irregular`` stack
(``src/mapreduce.cpp:385-563``, ``src/irregular.cpp``; call stack SURVEY.md
§3.2) as a two-phase padded all-to-all:

phase 1 (jitted, per shard): hash each valid key to a destination shard
  (user hash or the lookup3 port — same default as
  ``hashlittle(key,bytes,nprocs)%nprocs``, src/mapreduce.cpp:469-472),
  then ONE stable sort keyed by the destination with the key and value
  columns riding as its payloads (``ops/sort.sort_carrying``; a float64
  column or a very wide row goes by the sorted row index and one
  ``take`` instead, chosen from the array itself), so each destination's
  rows become one contiguous run in their original order.  Rows per
  destination, and with the wire codec each destination's key and value
  range, are reductions over the ``P`` destinations.  No scatter and no
  gather over the ``cap`` rows: on the v5e each cost tens of sorts
  (PERF.md §6, PR 33).

host: read the [P,P] count matrix, pick the padded bucket size B and the
  output capacity (rounded to powers of two to bound recompiles).  This
  replaces the reference's INTMAX/fraction flow-control negotiation
  (``irregular.cpp:95-242``) — static shapes instead of retry loops.

phase 2 (jitted, per shard): window the sorted rows into a [P,B] send
  buffer (each destination's rows are one contiguous run), exchange via
  ``lax.all_to_all`` (on a two-axis mesh one all-to-all an axis,
  :func:`_a2a_hier`; :func:`_exchange_blocks` chooses from the mesh),
  then append each source's block to its run of the packed output.  Runs
  move, not rows: no index in phase 2 is a per-row array.

Skew note: padding to the max bucket wastes ICI bandwidth on skewed keys
(RMAT high-degree vertices); the count matrix is already on the host, so a
multi-round fixed-budget variant can slot in here later (SURVEY.md §7).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.frame import KVFrame
from ..core.runtime import bump_dispatch
from ..ops.hash import hash_words32
from ..plan.cache import LRUCache
from .mesh import (flat_axis_index, mesh_axes, mesh_axis_size,
                   row_sharding, row_spec)
from .sharded import (ShardedKV, SyncStats, round_cap, rows_below,
                      shard_frame, slice_rows)

# ---------------------------------------------------------------------------
# hashing of device keys
# ---------------------------------------------------------------------------

def keys_to_words32(keys):
    """Bitcast any fixed-width key array [n(,w)] to uint32 words [n, W] so
    the device hash sees the same little-endian bytes the host hash would
    (reference hashes raw key bytes)."""
    if keys.ndim == 1:
        keys = keys[:, None]
    nbytes = keys.dtype.itemsize
    if nbytes >= 4:
        words = lax.bitcast_convert_type(keys, jnp.uint32)  # [n,w,nb/4]
        return words.reshape(keys.shape[0], -1)
    # sub-4-byte dtypes: widen to u32 (hash equals hashlittle on padded bytes)
    return keys.astype(jnp.uint32).reshape(keys.shape[0], -1)


def default_hash(keys):
    """lookup3 over the key's bytes → uint32 (device twin of
    hashlittle(key,keybytes,nprocs), src/mapreduce.cpp:472)."""
    return hash_words32(keys_to_words32(keys))


# ---------------------------------------------------------------------------
# generic two-phase exchange
# ---------------------------------------------------------------------------

_MAX_ROUNDS = 16     # unrolled in the jitted phase2; bounds trace size

def dest_counts(nprocs: int, dest):
    """Rows per destination, ``[P]`` int32: ``P`` masked sums over the
    destination column in one fused reduction, no scatter (a scatter-add
    of 2^24 rows into five bins cost 1.4 s on the v5e, PERF.md §6,
    PR 33).  Padding rows carry ``dest = nprocs`` and match nothing."""
    hit = dest[:, None] == jnp.arange(nprocs, dtype=dest.dtype)
    return jnp.sum(hit, axis=0, dtype=jnp.int32)


def phase1_shard_body(nprocs: int, dest_of: Callable, wire_elig, k, v, c):
    """Per-shard phase-1 body — the composable twin of
    :func:`phase2_shard_body`: dest-sorted rows + per-dest counts, plus
    (``wire_elig`` set) the wire codec's per-bucket min/max stats
    (``parallel/wire.bucket_stats``).
    Returns ``(skey, svalue, counts_local, stats_or_None)``.  Shared by
    the standalone phase-1 program builder and the plan/ fuser's
    megafused single-dispatch programs, so their row layout can never
    drift.

    One stable sort by destination carries the rows (module docstring);
    padding rows get ``dest = nprocs`` and sort last."""
    from ..ops.sort import sort_carrying
    cap = k.shape[0]
    with jax.named_scope("dest"):
        valid = jnp.arange(cap) < c
        dest = jnp.where(valid, dest_of(k).astype(jnp.int32), nprocs)
    with jax.named_scope("dest_sort"):
        _, (sk, sv) = sort_carrying((dest,), (k, v))
    with jax.named_scope("dest_counts"):
        cl = dest_counts(nprocs, dest)
    if wire_elig is None:
        return sk, sv, cl, None
    from .wire import bucket_stats
    k_elig, v_elig = wire_elig
    with jax.named_scope("wire_stats"):
        return sk, sv, cl, bucket_stats(nprocs, k, v, dest, k_elig, v_elig)


def _run_starts(counts):
    """Exclusive prefix sum of ``[P]`` run lengths, int32."""
    counts = counts.astype(jnp.int32)
    return jnp.cumsum(counts) - counts


def _send_windows(nprocs: int, B: int, start: int, rows, counts_local):
    """The ``P`` windows of one round, unmasked, and the mask of their
    valid slots: ``(wins [P, B, ...], valid [P, B, 1...])``.

    After phase 1 destination ``d``'s rows are ONE contiguous run of the
    dest-sorted shard, ``off[d] .. off[d] + counts_local[d]``, so bucket
    positions ``[start, start + B)`` of ``d`` are the ``B`` rows at
    ``off[d] + start``: one ``dynamic_slice`` a destination, indexed by
    the ``P`` run offsets and by nothing per row (no ``searchsorted``,
    no ``take``, no scatter).  A window running past ``cap`` reads
    zeros (:func:`~.sharded.slice_rows`): ``dynamic_slice`` clamps its
    start, and a clamped window would hold other rows.

    Cost in ``P``: a Python loop, so the trace holds ``P`` slices a
    column a round (the cells' ``P`` is 4, the tests' 8).  They move
    ``P * B`` rows in all — the send block itself — whatever ``P`` is,
    but at ``P = 64+`` and 16 rounds the trace is some thousands of
    operations.  The forms to take there are one batched window (a
    ``gather`` of ``P`` indices with ``[B, ...]`` slices) here and a
    ``fori_loop`` over the sources in :func:`_place_blocks`: for the v5e
    both compile to a ``while`` of ``P`` steps of dynamic-slice +
    dynamic-update-slice (PERF.md §6, PR 31) — copies as well, but a
    loop where the unrolled form is straight-line code, so at the
    cells' ``P`` the unrolled form stands."""
    with jax.named_scope("windows"):
        off = _run_starts(counts_local)
        wins = jnp.stack([slice_rows(rows, off[d] + start, B)
                          for d in range(nprocs)])
        valid = jnp.stack([rows_below(counts_local[d] - start, B, rows.ndim)
                           for d in range(nprocs)])
        return wins, valid


def _build_send_window(nprocs: int, B: int, start: int, rows,
                       counts_local):
    """The ``[P, B, ...]`` send block of one round: row ``d`` holds
    bucket positions ``[start, start + B)`` of destination ``d``, zeros
    elsewhere — the window slice of the flow-controlled exchange
    (uniform rounds use ``start = r * B``; the wire codec's tiered caps
    use the running tier offset).  Built by :func:`_send_windows`."""
    wins, valid = _send_windows(nprocs, B, start, rows, counts_local)
    with jax.named_scope("windows"):
        return jnp.where(valid, wins, jnp.zeros((), rows.dtype))


def _recv_buffer(cap_out: int, pad: int, like):
    """The zeroed output of :func:`_place_blocks`: ``cap_out`` rows plus
    ``pad`` (the largest block of the schedule) so that no window update
    that holds a valid row can run past the end and be shifted
    (``dynamic_update_slice`` clamps its start); the caller trims the
    extension off again."""
    with jax.named_scope("unpack"):
        return jnp.zeros((cap_out + pad,) + like.shape[1:], like.dtype)


def _place_blocks(out, recv, base, counts_from, start: int, rebase=None):
    """Append one round's received ``[P, B, ...]`` blocks to the packed
    output: source ``j``'s rows are ONE run of it, from ``base[j]``, so
    its block of this round goes to ``base[j] + start`` as a window
    update — read the ``B`` rows there, keep them past
    ``counts_from[j] - start`` (they are a later source's, or zeros),
    write the window back.  ``P`` in-place updates of ``B`` rows each,
    indexed by the run offsets alone; the trace grows with ``P`` as
    :func:`_send_windows`' does.  ``rebase`` (the wire codec): ``[P]``
    per-source bases in ``out``'s dtype, added to the block after it is
    widened — the decode, block by block.

    A window with no valid row may be clamped (its update writes back
    what it read); one with a valid row starts below ``cap_out`` and the
    buffer is ``B`` rows longer (:func:`_recv_buffer`)."""
    B = recv.shape[1]
    with jax.named_scope("unpack"):
        for j in range(recv.shape[0]):
            pos = base[j] + start
            block = recv[j]
            if rebase is not None:
                block = block.astype(out.dtype) + rebase[j]
            keep = rows_below(counts_from[j] - start, B, block.ndim)
            here = lax.dynamic_slice_in_dim(out, pos, B)
            out = lax.dynamic_update_slice_in_dim(
                out, jnp.where(keep, block, here), pos, 0)
    return out


def _build_send(nprocs: int, B: int, rows, counts_local, round_idx: int = 0):
    """Uniform-round window: bucket positions [rB, rB+B)."""
    return _build_send_window(nprocs, B, round_idx * B, rows, counts_local)


def _a2a_hier(send, mesh):
    """Hierarchical all-to-all for a (slice, chip) mesh: rows for
    (s', c') first move to the LOCAL chip c' over ICI (axis "c"), then
    one DCN all-to-all between same-chip-index peers (axis "s") delivers
    them — each cross-slice row crosses DCN exactly once, pre-aggregated
    per (c', s') pair.  Output matches the flat all_to_all: recv[p] =
    block from flat proc p."""
    axes = mesh_axes(mesh)
    S = int(mesh.shape[axes[0]])
    C = int(mesh.shape[axes[1]])
    x = send.reshape((S, C) + send.shape[1:])   # [dest_slice, dest_chip,...]
    x = lax.all_to_all(x, axes[1], 1, 1)        # ICI: [dest_slice, src_c,...]
    x = lax.all_to_all(x, axes[0], 0, 0)        # DCN: [src_s, src_c, ...]
    return x.reshape(send.shape)


def _exchange_counts(counts_local, mesh):
    """Exchange per-dest counts: counts_from[j] = rows shard j sends me."""
    with jax.named_scope("exchange"):
        return _exchange_blocks(counts_local[:, None], mesh)[:, 0]


def _exchange_blocks(send, mesh):
    """[P,B,...] send blocks → [P,B,...] recv blocks.  The one place that
    chooses the collective, from the mesh: a (slice, chip) mesh goes
    ICI-then-DCN (:func:`_a2a_hier`: a flat exchange would cross DCN on
    most hops), a one-axis mesh is one ``lax.all_to_all``."""
    axes = mesh_axes(mesh)
    with jax.named_scope("exchange"):
        if len(axes) == 2:
            return _a2a_hier(send, mesh)
        return lax.all_to_all(send, axes[0], 0, 0)


def _dest_fn(dest, nprocs: int, mesh) -> Callable:
    """Destination spec → per-row dest function.  Specs are hashable so
    the jitted phase1 caches across calls (the iterative graph commands
    re-shuffle every round; re-jitting per round was the dominant cost):

    * ("hash", fn_or_None) — fn(keys)%nprocs, default lookup3;
    * ("fixed_mod", n) — every row of shard i to shard i%n: the
      reference gather's EXACT sender→receiver mapping ("lo procs recv
      from set of hi procs with same (ID % numprocs)",
      src/mapreduce.cpp:919-928);
    * ("range", offsets, ends) — topology resharding (reshard.py):
      row r of shard i has GLOBAL index offsets[i]+r; it routes to the
      target shard whose cumulative row range covers that index
      (``searchsorted(ends, g, "right")``).  The redistribution
      schedule (offsets/ends, both hashable tuples) is computed
      host-side from the counts — the data itself moves only through
      the collective, the 2112.01075 recipe;
    * ("order",) — a total order (:class:`TotalOrder`): the number of
      sorted splitter keys a row's key words are not below.  The one
      spec whose function takes an operand, ``fn(keys, splitters)``:
      the splitters are data of the job, so a job over other records
      runs the same program."""
    kind = dest[0]
    if kind == "hash":
        fn = dest[1]
        if fn is None:
            return lambda keys: default_hash(keys) % nprocs
        return lambda keys: fn(keys) % nprocs
    if kind == "fixed_mod":
        n = dest[1]

        def fixed(keys):
            me = flat_axis_index(mesh)
            d = (me % n).astype(jnp.int32)
            return jnp.full(keys.shape[0], d, jnp.int32)
        return fixed
    if kind == "range":
        offsets, ends = dest[1], dest[2]

        def ranged(keys):
            me = flat_axis_index(mesh)
            offs = jnp.asarray(offsets, jnp.int64)
            g = offs[me] + jnp.arange(keys.shape[0], dtype=jnp.int64)
            # dest is monotone in the row index, so phase1's stable
            # dest-sort is the identity and the packed output preserves
            # exact global row order — reshard's byte-identity contract
            # P compares a row, not a binary search: a searchsorted is a
            # gather a round on the chip
            return jnp.searchsorted(jnp.asarray(ends, jnp.int64), g,
                                    side="right", method="compare_all"
                                    ).astype(jnp.int32)
        return ranged
    if kind == "order":
        def ordered(keys, splitters):
            if splitters.shape[0] >= nprocs:
                raise ValueError(
                    f"{splitters.shape[0]} splitters name "
                    f"{splitters.shape[0] + 1} destinations, the mesh "
                    f"has {nprocs}")
            return order_dest(keys, splitters)
        return ordered
    raise ValueError(dest)


def order_dest(keys, splitters):
    """``keys [n, w]`` (or ``[n]``) → the number of ``splitters [s, w]``
    (sorted, the keys' dtype) each key is not below, uint32: destination
    *i* takes the keys in ``[splitter i-1, splitter i)``, a key equal to
    a splitter goes up.  Every key is compared with every splitter (a
    ``searchsorted`` is a gather a round on the chip); a key's words
    compare as numbers, the first the most significant."""
    if keys.ndim == 1:
        keys = keys[:, None]
    k, s = keys[:, None, :], splitters.reshape(1, -1, keys.shape[1])
    w = keys.shape[1]
    ge = k[..., w - 1] >= s[..., w - 1]
    for j in range(w - 2, -1, -1):
        ge = (k[..., j] > s[..., j]) | ((k[..., j] == s[..., j]) & ge)
    return jnp.sum(ge, axis=1, dtype=jnp.uint32)


class TotalOrder:
    """The user hash of a total-order ``aggregate``: ``mr.aggregate(
    TotalOrder(splitters))`` sends every row to the shard that owns its
    key range (:func:`order_dest`), so that shard *i*'s keys all precede
    shard *i+1*'s — Hadoop's ``TotalOrderPartitioner``.  ``splitters
    [s, w]``: sorted keys in the dataset's key dtype, at most one fewer
    than the shards.

    ``aggregate`` recognises it (as it does a ``host_hash``) and runs the
    ``("order",)`` spec with the splitters as an operand of phase 1; a
    caller that only calls it gets the same destinations."""

    def __init__(self, splitters):
        self.splitters = np.ascontiguousarray(splitters)

    def __call__(self, keys):
        return order_dest(keys, jnp.asarray(self.splitters))


# bounded executable caches (ISSUE 2 satellite): the pre-plan caches
# were functools.lru_cache(None) — long soak runs across many meshes /
# dest functions / cap tuples pinned every executable forever.  Same
# LRU policy (and telemetry) as the plan cache; stats land in
# MapReduce.stats()["plan"] via plan.cache.cache_stats().
from ..utils.env import env_knob  # noqa: E402

PHASE1_CACHE = LRUCache(env_knob("MRTPU_JIT_CACHE", int, 64),
                        name="shuffle.phase1")
PHASE2_CACHE = LRUCache(env_knob("MRTPU_JIT_CACHE", int, 64),
                        name="shuffle.phase2")


def _phase1_jit(mesh, dest, donate: bool = False, wire=None):
    """Cache the jitted phase1 only for stable dest specs — a per-call
    user hash lambda would defeat reuse (and one-shot entries would
    churn the LRU), so those build uncached (old behavior).  The
    ``("order",)`` program takes the splitters as a fourth operand.

    ``donate=True`` (exec/: MRTPU_DONATE) donates the key/value inputs —
    the dest-sorted outputs are same-shape/dtype, so XLA aliases the
    input buffers instead of materialising a second copy; the caller's
    arrays are DELETED at dispatch and must be dead (the exchange's
    input dataset is — it is replaced by the exchange output).

    ``wire=(k_elig, v_elig)`` (parallel/wire.py, MRTPU_WIRE): the same
    program ALSO emits per-destination bucket min/max stats — a fourth
    [P, 4] uint64 output the wire codec's host planner reads alongside
    the count matrix.  Part of the cache key: the wire and raw programs
    have different output signatures."""
    if dest[0] == "hash" and dest[1] is not None:
        return _phase1_build(mesh, dest, donate, wire)
    return PHASE1_CACHE.get_or_build(
        (mesh, dest, donate, wire),
        lambda: _phase1_build(mesh, dest, donate, wire))


def _phase1_build(mesh, dest, donate: bool = False, wire=None):
    nprocs = mesh_axis_size(mesh)
    dest_fn = _dest_fn(dest, nprocs, mesh)
    spec = row_spec(mesh)
    nouts = 3 if wire is None else 4
    # the spec's operands (the splitters of a total order) reach every
    # shard whole, behind the three row-sharded arguments
    nargs = 1 if dest[0] == "order" else 0

    def body(k, v, c, *args):
        return phase1_shard_body(
            nprocs, lambda keys: dest_fn(keys, *args), wire, k, v, c)[:nouts]

    def shuffle_phase1(key, value, count, *args):
        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec,) * 3 + (P(),) * nargs,
            out_specs=(spec,) * nouts)(key, value, count, *args)

    # phase 1 is shape-preserving (dest-sorted rows), so donation always
    # aliases — the biggest win, on every aggregate/gather
    from ..exec import donated_jit
    return donated_jit(shuffle_phase1, (0, 1) if donate else ())


def phase2_shard_body(nprocs: int, mesh, B: int, nrounds: int,
                      cap_out: int, k, v, cl):
    """Per-shard phase-2 body — the fusible stage builder the plan/
    fuser composes with convert/reduce inside ONE shard_map program.
    Returns ``(out_k, out_v, nrecv)``: received rows packed to the
    front of a [cap_out, ...] block plus this shard's valid-row count.

    Multi-round bounded exchange: each round moves ≤ B rows per
    (src, dest) bucket, so the padded send buffer is [P, B] regardless
    of skew — the TPU equivalent of the reference's fraction<1.0
    flow-control retry loop (src/mapreduce.cpp:498-513,
    irregular.cpp:95-242), but with statically known round count.
    Received rows go directly to their final packed position
    (base[src] + round*B + slot) as window updates
    (:func:`_place_blocks`), so no per-round compaction pass — and the
    send block is windows of the dest-sorted shard
    (:func:`_send_windows`): both sides move runs, indexed by ``P``
    offsets, not rows indexed one by one."""
    # (the steps are the helpers': ``windows``, ``exchange``, ``unpack``)
    counts_from = _exchange_counts(cl, mesh)
    with jax.named_scope("unpack"):
        base = _run_starts(counts_from)
    out_k = _recv_buffer(cap_out, B, k)
    out_v = _recv_buffer(cap_out, B, v)
    for r in range(nrounds):
        recv_k = _exchange_blocks(_build_send(nprocs, B, k, cl, r), mesh)
        recv_v = _exchange_blocks(_build_send(nprocs, B, v, cl, r), mesh)
        out_k = _place_blocks(out_k, recv_k, base, counts_from, r * B)
        out_v = _place_blocks(out_v, recv_v, base, counts_from, r * B)
    with jax.named_scope("unpack"):
        return out_k[:cap_out], out_v[:cap_out], jnp.sum(counts_from)


def _phase2_jit(mesh, B: int, nrounds: int, cap_out: int,
                donate: bool = False):
    """``donate=True`` donates the dest-sorted skey/svalue (dead after
    the exchange has placed them in the output blocks).  NEVER used for
    the SPECULATIVE phase 2: a failed speculation re-runs phase 2 with
    the same inputs, which donation would have deleted.  Callers only
    pass donate=True when cap_out == cap (the caller checks) — the one
    case the donation is byte-aliasable, so it never degrades to a
    warned no-op."""
    return PHASE2_CACHE.get_or_build(
        (mesh, B, nrounds, cap_out, donate),
        lambda: _phase2_build(mesh, B, nrounds, cap_out, donate))


def _phase2_build(mesh, B: int, nrounds: int, cap_out: int,
                  donate: bool = False):
    nprocs = mesh_axis_size(mesh)
    spec = row_spec(mesh)

    def shuffle_phase2(skey, svalue, counts_local):
        def body(k, v, cl):
            out_k, out_v, _ = phase2_shard_body(
                nprocs, mesh, B, nrounds, cap_out, k, v, cl)
            return out_k, out_v
        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=(spec, spec))(skey, svalue, counts_local)

    from ..exec import donated_jit
    return donated_jit(shuffle_phase2, (0, 1) if donate else ())


def _phase2_wire_jit(mesh, tiers, cap_out: int, kpack, vpack,
                     donate: bool = False):
    """The wire-codec phase 2 (parallel/wire.py): same packed output as
    :func:`_phase2_jit` byte for byte, but rows cross the interconnect
    delta-packed at the planned widths with tiered round caps.  The
    plan's every static knob keys the executable cache — the "wire in
    the jit key" contract of doc/perf.md."""
    return PHASE2_CACHE.get_or_build(
        (mesh, "wire", tiers, cap_out, kpack, vpack, donate),
        lambda: _phase2_wire_build(mesh, tiers, cap_out, kpack, vpack,
                                   donate))


def _phase2_wire_build(mesh, tiers, cap_out: int, kpack, vpack,
                       donate: bool = False):
    from .wire import phase2_wire_shard_body
    nprocs = mesh_axis_size(mesh)
    spec = row_spec(mesh)

    def shuffle_phase2_wire(skey, svalue, counts_local, stats_local):
        def body(k, v, cl, st):
            out_k, out_v, _ = phase2_wire_shard_body(
                nprocs, mesh, tiers, cap_out, kpack, vpack, k, v, cl, st)
            return out_k, out_v
        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec,) * 4,
            out_specs=(spec, spec))(skey, svalue, counts_local,
                                    stats_local)

    from ..exec import donated_jit
    return donated_jit(shuffle_phase2_wire, (0, 1) if donate else ())


# speculative capacity cache (round 4, VERDICT r3 weak #5): composed
# iterative commands pay the exchange's ONE host sync — the count-matrix
# pull that sizes the bucket/round/output shapes — once per op, a full
# device round-trip.  Keyed by (mesh, dest spec,
# operand shapes/dtypes), the caps that worked last time are assumed
# again: phase 2 is ENQUEUED immediately with the cached shapes and the
# count matrix is pulled while it runs.  The pull then verifies the
# speculation — on overflow (a bucket past B*nrounds or an output shard
# past cap_out would have dropped rows) the correctly-sized phase 2
# re-runs; on gross oversizing (>4x) the cache right-sizes for next
# time but the speculative result is kept.  Same sync count either way
# (SyncStats.pulls is still 1/op) — the sync just moves OFF the
# critical path whenever consecutive ops keep a similar distribution,
# which is exactly the composed-loop case.
# Guarded by _SPEC_LOCK: ``-partition`` worlds exchange from interpreter
# THREADS (oink/universe.py), and an unlocked read-modify-write could
# publish a half-observed cap tuple (VERDICT r4 weak #7).  Entries are
# immutable tuples, so lock only the dict accesses, not the exchange.
_SPEC_CACHE: dict = {}
import threading as _threading
_SPEC_LOCK = _threading.Lock()


def _plan_caps(counts_mat: np.ndarray):
    """Bucket/round/output sizing from the pulled count matrix (the
    flow-control policy: pad buckets to ~the mean nonzero bucket, round
    up to _MAX_ROUNDS rounds — see exchange())."""
    Bmax = round_cap(int(counts_mat.max())) if counts_mat.max() else 8
    new_counts = counts_mat.sum(axis=0).astype(np.int32)
    cap_out = round_cap(int(new_counts.max())) if new_counts.max() else 8
    nz = counts_mat[counts_mat > 0]
    B = round_cap(int(np.ceil(nz.mean()))) if len(nz) else 8
    nrounds = -(-Bmax // B)
    if nrounds > _MAX_ROUNDS:
        nrounds = _MAX_ROUNDS
        B = round_cap(-(-Bmax // nrounds))
        nrounds = -(-Bmax // B)
    return B, nrounds, cap_out, Bmax, new_counts


@dataclass
class ExchangeCallStats:
    """Flow-control telemetry of ONE exchange() call: attached to the
    returned ShardedKV (``.exchange_stats``) and surfaced as
    ``MapReduce.last_exchange`` after aggregate(), so two concurrent
    MapReduce objects (mapstyle-2 threads, -partition worlds, fused
    plans running interleaved segments) each read their own.  The same
    numbers land on the obs ``shuffle.exchange`` span."""

    nrounds: int
    bucket: int
    cap_out: int
    rows: int                 # total rows routed (count-matrix sum)
    speculative: bool         # phase 2 ran on cached caps
    sent_bytes: int = 0
    pad_bytes: int = 0
    # wire codec (parallel/wire.py, MRTPU_WIRE): actual interconnect
    # bytes after delta/narrow packing + tiered caps, and the logical/
    # wire compression ratio ((sent+pad)/wire).  0 = codec bypassed or
    # MRTPU_WIRE=0 (the raw path's bytes ARE sent+pad).
    wire_bytes: int = 0
    wire_ratio: float = 0.0


def exchange_volume(skv: ShardedKV, counts_mat, slots: int,
                    nprocs: int) -> tuple:
    """(moved, pad, rowbytes) of one exchange at LOGICAL (unpacked) row
    width — shared by the eager exchange and the plan/ fuser so their
    telemetry can never diverge.  ``slots`` is the per-bucket slot
    budget the flow-control plan exchanges (B*nrounds for the uniform
    schedule, the tier-ladder sum under the wire codec).  Padding
    diagnosis (VERDICT r2 #5): the slack beyond the real rows is pure
    padding volume.  Diagonal (self→self) slots never cross the
    interconnect — excluded on BOTH sides so pad is directly comparable
    to cssize."""
    rowbytes = (skv.key.dtype.itemsize
                * (skv.key.shape[-1] if skv.key.ndim > 1 else 1)
                + skv.value.dtype.itemsize
                * (skv.value.shape[-1] if skv.value.ndim > 1 else 1))
    useful = int(counts_mat.sum() - np.trace(counts_mat))
    moved = useful * rowbytes
    sent_slots = nprocs * (nprocs - 1) * slots
    pad = max(0, sent_slots - useful) * rowbytes
    return moved, pad, rowbytes


def free_if_donated(kv, skv) -> bool:
    """After a FAILED exchange: if donation already consumed ``skv``'s
    buffers and ``skv`` is an installed frame of ``kv``, free the
    dataset — the next op then raises the clean "Cannot … without
    completed KeyValue" MRError instead of a cryptic deleted-array
    RuntimeError deep in XLA.  (Without donation a failed exchange
    leaves the input intact and retryable, as before exec/.)  Returns
    whether it freed."""
    try:
        if (skv is not None and any(f is skv for f in kv._frames)
                and skv.key.is_deleted()):
            kv.free()
            kv.complete_done = False   # _require_kv now raises MRError
            return True
    except Exception:
        pass
    return False


def exchange(skv: ShardedKV, dest, counters=None,
             dest_args: tuple = ()) -> ShardedKV:
    """Full ragged exchange: route every valid row to its dest shard.
    ``dest`` is a hashable spec (see :func:`_dest_fn`), ``dest_args`` the
    arrays its function takes beside the keys (the splitters of
    ``("order",)``).  The intern table of byte-keyed datasets rides along
    (ids move, bytes stay put).

    Emits a ``shuffle.exchange`` child span (obs/) under the calling MR
    op carrying the flow-control telemetry (bucket/rounds/caps, useful
    vs padding bytes, whether the speculative caps held).

    Runs under the ft/ ``shuffle.exchange`` retry policy: a transient
    failure retries the WHOLE two-phase exchange — but only while the
    input buffers still exist (a failure after the donated phase-1
    dispatch consumed them is vetoed as non-retryable and propagates to
    ``free_if_donated`` as before).  The injection fault point sits
    before any dispatch, so injected faults are always retry-safe."""
    from ..ft.inject import fault_point
    from ..ft.retry import retry_call
    from ..obs import NULL_SPAN, get_tracer
    # the shuffle sync is a cancellation barrier (obs/context): a
    # cancelled request stops BEFORE the exchange dispatches — the
    # input frames are untouched, same recovery contract as a fault
    # injected here.  Outside _once so a cancel never burns the ft/
    # retry budget (CancelledError is MRError = fatal anyway).
    from ..obs.context import barrier_check
    barrier_check()

    def _once():
        fault_point("shuffle.exchange")
        tr = get_tracer()
        if not tr.enabled:
            return _exchange_impl(skv, dest, counters, NULL_SPAN,
                                  dest_args)
        with tr.span("shuffle.exchange", cat="shuffle",
                     nprocs=mesh_axis_size(skv.mesh),
                     dest=_dest_kind(dest)) as sp:
            return _exchange_impl(skv, dest, counters, sp, dest_args)

    def _retryable(e):
        try:
            return not skv.key.is_deleted()
        except Exception:
            return False

    return retry_call("shuffle.exchange", _once,
                      detail=f"P={mesh_axis_size(skv.mesh)}",
                      retryable=_retryable)


def _dest_kind(dest) -> str:
    """What the ``shuffle.exchange`` span says of its spec: ``hash`` (the
    lookup3 default), ``user`` (a device callable: phase 1 is built for
    this exchange alone), ``order``, ``fixed_mod`` or ``range``."""
    return "user" if dest[0] == "hash" and dest[1] is not None else dest[0]


def _dispatch_phase2(plan, mesh, donate2, skey, svalue, counts_local,
                     stats_local):
    """Run one exchange plan (the tagged tuple of parallel/wire.py):
    raw plans take the original counts-only program, wire plans the
    codec program (which additionally consumes the phase-1 stats)."""
    if plan[0] == "wire":
        _tag, tiers, cap_out, kpack, vpack = plan
        return _phase2_wire_jit(mesh, tiers, cap_out, kpack, vpack,
                                donate=donate2)(
            skey, svalue, counts_local, stats_local)
    _tag, B, nrounds, cap_out = plan
    return _phase2_jit(mesh, B, nrounds, cap_out, donate=donate2)(
        skey, svalue, counts_local)


def _exchange_impl(skv: ShardedKV, dest, counters, sp,
                   dest_args: tuple = ()) -> ShardedKV:
    from . import wire as _wire
    mesh = skv.mesh
    nprocs = mesh_axis_size(mesh)

    # exec/: donate dead buffers so XLA aliases instead of copying.
    # phase 1's inputs (the pre-exchange dataset, replaced by the
    # exchange output) and the definitive phase 2's inputs (the
    # dest-sorted intermediates) are both dead after their use.  The
    # eligibility rule (knob + not-shared + not-self-aliased) is
    # exec.can_donate — ONE copy, shared with the fuser
    from ..exec import can_donate
    donate = can_donate(skv)
    wire_on = _wire.wire_enabled()
    elig = _wire.columns_eligible(skv.key, skv.value) if wire_on else None

    counts_dev = jax.device_put(skv.counts.astype(np.int32),
                                row_sharding(mesh))
    bump_dispatch()
    phase1 = _phase1_jit(mesh, dest, donate, wire=elig)
    traced = phase1._cache_size()
    skey, svalue, counts_local, *stats = phase1(
        skv.key, skv.value, counts_dev, *dest_args)
    stats_local = stats[0] if stats else None
    # whether this exchange traced and lowered a phase 1 of its own (a new
    # spec or shape, or a user hash) or ran one the process already had
    sp.set(phase1_built=phase1._cache_size() - traced)
    # speculative phase 2: enqueue with last time's plan BEFORE the
    # count-matrix pull, so the pull overlaps device work (async
    # dispatch) instead of gating it
    # dest is part of the key: a gather's fixed-dest exchange and an
    # aggregate's hash exchange over the same shapes have wildly
    # different bucket profiles — sharing one slot would cross-
    # contaminate caps and waste speculative dispatches (r4 review).
    # wire_on too: raw and wire plans are different executables
    spec_key = (mesh, dest, skv.key.shape, skv.key.dtype.str,
                skv.value.shape, skv.value.dtype.str, wire_on)
    with _SPEC_LOCK:
        spec = _SPEC_CACHE.get(spec_key)
    out_spec = None
    if spec is not None:
        bump_dispatch()
        out_spec = _dispatch_phase2(spec, mesh, False, skey, svalue,
                                    counts_local, stats_local)
    SyncStats.bump()   # the op's ONE round-trip: the count matrix
    from ..obs import get_tracer
    with get_tracer().span("shuffle.count_sync", cat="shuffle"):
        # the host pull that sizes the exchange — with a speculative
        # phase 2 in flight this overlaps device work.  The wire stats
        # ride the same sync point (a second small transfer, not a
        # second barrier).  Multi-process runs (parallel/dist.py) route
        # through host_pull (the count matrix spans non-addressable
        # devices there) under the collective watchdog — a dead peer
        # turns this, the op's one mandatory barrier, into a bounded
        # PeerLostError instead of an unbounded stall
        from . import dist as _dist

        def _pull():
            cm = _dist.host_pull(counts_local).reshape(nprocs, nprocs)
            sm = (_dist.host_pull(stats_local).reshape(nprocs, nprocs, 4)
                  if stats_local is not None else None)
            return cm, sm

        counts_mat, stats_mat = _dist.guard_call("count_sync", _pull)
        # straggler attribution (obs/fleetobs): hand the per-dest row
        # totals to the sync observer so the NEXT syncs' cause verdict
        # (data_skew vs host_slow) has the count-matrix evidence
        _dist.note_sync_rows(counts_mat)
    # round budget: pad buckets to ~the mean nonzero bucket, not the max —
    # under key skew (RMAT hubs) the max bucket is far above the mean and
    # single-round padding would inflate the exchanged volume by that
    # ratio.  Up to _MAX_ROUNDS rounds of [P, B] each (uniform data stays
    # one round since mean == max).  The wire planner then tightens the
    # schedule (tier ladder) and picks the pack widths — ONE planning
    # step shared with the fused tier (wire.plan_from_pull)
    plan, kvrange, bmax_raw, nmax_out, new_counts = _wire.plan_from_pull(
        skv.key, skv.value, counts_mat, stats_mat, wire_on, elig)
    if out_spec is not None and _wire.plan_holds(spec, bmax_raw,
                                                 nmax_out, kvrange):
        # speculation holds: no row would have overflowed a bucket
        # window or an output shard, and a cached pack width still
        # round-trips the fresh ranges — keep the already-running result
        out_k, out_v = out_spec
        sp.set(speculative=True)
        # a grossly over-sized speculation right-sizes the cache for
        # next time, and a plan-TAG mismatch migrates the entry (a raw
        # plan cached from a wide first run must not pin compressible
        # repeats to full-width bytes forever); padding/stats below
        # reflect the plan that RAN
        with _SPEC_LOCK:
            _SPEC_CACHE[spec_key] = plan if (
                spec[0] != plan[0]
                or _wire.plan_oversized(spec, bmax_raw, nmax_out)) \
                else spec
        ran = spec
    else:
        sp.set(speculative=False)
        bump_dispatch()
        # definitive phase 2: skey/svalue are dead after — donate them
        # when the donation can actually alias (cap_out == cap; other
        # sizes would be a warned no-op).  The speculative call above
        # never donates: a failed speculation re-runs phase 2 on the
        # same inputs
        donate2 = (donate
                   and _wire.plan_cap_out(plan)
                   == skey.shape[0] // max(nprocs, 1))
        out_k, out_v = _dispatch_phase2(plan, mesh, donate2, skey, svalue,
                                        counts_local, stats_local)
        with _SPEC_LOCK:
            _SPEC_CACHE[spec_key] = plan
        ran = plan

    B_eff, nrounds_eff = _wire.plan_rounds(ran)
    cap_out_eff = _wire.plan_cap_out(ran)
    stats = ExchangeCallStats(nrounds=nrounds_eff, bucket=B_eff,
                              cap_out=cap_out_eff,
                              rows=int(counts_mat.sum()),
                              speculative=out_spec is not None
                              and (out_k is out_spec[0]))
    # rows each shard received, from the count matrix already on the host:
    # max over mean is the skew of the destinations (1.0 = even)
    sp.set(bucket=B_eff, nrounds=nrounds_eff, cap_out=cap_out_eff,
           rows=stats.rows, recv_rows_max=int(new_counts.max()),
           recv_rows_mean=float(new_counts.mean()))
    # which form of phase 1 ran: columns that rode its sort, and columns
    # taken by the sorted row index (ops/sort.riding)
    from ..ops.sort import riding
    rode = sum(riding((skv.key, skv.value)))
    sp.set(cols_rode=rode, cols_by_index=2 - rode)
    # byte accounting ALWAYS lands on the per-call stats (and so the
    # live metrics + request profile), whether or not a Counters object
    # rides along — a direct reshard/gather caller without counters
    # must not read as "no exchange traffic" on /metrics
    moved, pad, rowbytes = exchange_volume(skv, counts_mat,
                                           _wire.plan_slots(ran), nprocs)
    stats.sent_bytes, stats.pad_bytes = moved, pad
    if ran[0] == "wire":
        stats.wire_bytes = _wire.wire_volume(skv, counts_mat, ran)
        stats.wire_ratio = _wire.wire_ratio(moved, pad, stats.wire_bytes)
    sp.set(sent_bytes=moved, pad_bytes=pad, rowbytes=rowbytes,
           wire_bytes=stats.wire_bytes, wire_ratio=stats.wire_ratio)
    if counters is not None:
        counters.add(cssize=moved, crsize=moved, cspad=pad)
    out = ShardedKV(mesh, out_k, out_v, new_counts,
                    key_decode=skv.key_decode,
                    value_decode=skv.value_decode)
    out.exchange_stats = stats   # per-call telemetry rides the result
    # live metrics (obs/metrics.py): the same per-call numbers feed the
    # exchange byte/round counters — a direct feed, not via the span, so
    # the counters are exact even for spans the ring has already evicted
    from ..obs.metrics import record_exchange
    record_exchange(stats)
    return out


# ---------------------------------------------------------------------------
# aggregate()
# ---------------------------------------------------------------------------

def aggregate_kv(backend, mr, hash_fn: Optional[Callable]):
    """MapReduce.aggregate on the mesh backend: shard-if-needed, then
    hash-exchange.  Host byte-string data cannot shard (intern first —
    SURVEY.md §7); it stays controller-resident with a warning."""
    from ..core.runtime import Timer
    kv = mr.kv
    if hash_fn is not None and getattr(hash_fn, "host_hash", False):
        # user hash evaluated per key on the host (the C-ABI apphash and
        # python callbacks over raw key bytes, src/mapreduce.cpp:469-471):
        # partition host-side, then place the blocks on the mesh
        _aggregate_host_hash(backend, mr, hash_fn)
        return
    from ..obs import get_tracer, names
    tr = get_tracer()
    with tr.span(names.AGGREGATE_ONE_FRAME, cat=names.HOST) as sp:
        moved = {}
        frame = kv.one_frame(moved)
        if isinstance(frame, ShardedKV) and mesh_axis_size(backend.mesh) > 1:
            # the exchange below re-homes every row, so the shards over
            # the even level may hand their excess to the short ones
            from .devkernels import level_sharded
            frame = level_sharded(frame)
        # cap: the per-shard capacity every later program of the round is
        # compiled at (0: a host frame, sharded below)
        sp.set(rows=kv.nkv, frames=kv.nframes,
               cap=getattr(frame, "cap", 0), **moved)
    ktable = vtable = None
    if isinstance(frame, KVFrame):
        with tr.span(names.AGGREGATE_INTERN, cat=names.HOST,
                     rows=len(frame)):
            frame, ktable, vtable = _intern_frame(
                frame, mesh_axis_size(backend.mesh))

    def _shard(frame):
        with tr.span(names.AGGREGATE_SHARD, cat=names.HOST,
                     rows=len(frame)) as sp:
            skv = shard_frame(frame, backend.mesh)
            sp.set(bytes=skv.nbytes())
        skv.key_decode = ktable
        skv.value_decode = vtable
        return skv

    if mesh_axis_size(backend.mesh) == 1:
        # reference early-out for nprocs==1 (src/mapreduce.cpp:403-406):
        # no exchange — but a dense host frame still moves onto the device
        # so convert/reduce run the sharded (device) tier, and an already-
        # computed multi-frame concat is kept (one_frame above was not free)
        if isinstance(frame, KVFrame):
            if frame.is_dense():
                _replace_kv_frames(kv, _shard(frame))
        else:
            _replace_kv_frames(kv, frame)
        return
    skv = _shard(frame) if isinstance(frame, KVFrame) else frame
    # a total order is a spec of its own, its splitters an operand: one
    # program for every job; any other device callable is ("hash", fn)
    dest, dest_args = ("hash", hash_fn), ()
    if isinstance(hash_fn, TotalOrder):
        dest, dest_args = ("order",), (hash_fn.splitters,)
    t = Timer()
    try:
        out = exchange(skv, dest, counters=mr.counters,
                       dest_args=dest_args)
    except BaseException:
        free_if_donated(kv, skv)
        raise
    mr.counters.add(commtime=t.elapsed())
    # per-call stats: concurrent MRs each keep their own last_exchange
    mr.last_exchange = getattr(out, "exchange_stats", None)
    _replace_kv_frames(kv, out)


def _key_bytes_rows(col) -> list:
    """Raw per-row key bytes — what the reference's user hash receives."""
    from ..core.column import BytesColumn, ObjectColumn
    if isinstance(col, ObjectColumn):
        return col.pickles()
    if isinstance(col, BytesColumn):
        return [bytes(b) for b in col.data]
    data = np.ascontiguousarray(np.asarray(col.to_host().data))
    return [data[i].tobytes() for i in range(data.shape[0])]


def _aggregate_host_hash(backend, mr, hash_fn):
    kv = mr.kv
    P = mesh_axis_size(backend.mesh)
    frame = kv.one_frame()
    if not isinstance(frame, KVFrame):
        frame = frame.to_host()
    if len(frame) == 0:
        return
    dest = (np.asarray(hash_fn(_key_bytes_rows(frame.key)))
            .astype(np.int64) % P).astype(np.int32)
    frame, ktable, vtable = _intern_frame(frame, P)
    order = np.argsort(dest, kind="stable")
    counts = np.bincount(dest, minlength=P).astype(np.int32)
    from .sharded import shard_frame_with_counts
    skv = shard_frame_with_counts(frame.take(order), backend.mesh, counts)
    skv.key_decode = ktable
    skv.value_decode = vtable
    _replace_kv_frames(kv, skv)


def _intern_frame(frame: KVFrame, P: int = 1):
    """Byte-string or arbitrary-object KEYS and VALUES intern to u64 ids
    for the device shuffle; the id→bytes tables ride on the ShardedKV
    (SURVEY.md §7 'hard parts'; VERDICT r1 #5 for keys, r2 #4 for
    values — the reference shuffles raw bytes on both sides,
    src/mapreduce.cpp:453-473).  With P>1 the tables are DEST-SHARDED
    (ShardTables, VERDICT r4 #5): entry (id, bytes) lives in the table
    of the shard the hash exchange will route the id to, so no
    controller-global dict builds and shard d's post-aggregate output
    decodes from its own table alone."""
    from ..core.column import BytesColumn, ObjectColumn, ShardTables

    def _one(col):
        if not isinstance(col, (BytesColumn, ObjectColumn)):
            return col, None
        if P > 1:
            kind = "object" if isinstance(col, ObjectColumn) else "bytes"
            tables = ShardTables(P, kind=kind)
            return col.intern_sharded(tables), tables
        return col.intern()

    key, ktable = _one(frame.key)
    value, vtable = _one(frame.value)
    if ktable is None and vtable is None:
        return frame, None, None
    return KVFrame(key, value), ktable, vtable


def _replace_kv_frames(kv, sharded_frame):
    kv.free()
    kv._frames = [sharded_frame]
    kv.counters.mem(sharded_frame.nbytes())
    kv.nkv = len(sharded_frame)
    kv.complete_done = True
