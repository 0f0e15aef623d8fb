"""Exact wire codec for the shuffle exchange (ROADMAP item 2).

The two-phase exchange (``shuffle.py``) ships raw u64 keys + full-width
values padded to a global per-bucket cap — the pad tax
``mrtpu_exchange_bytes_total{pad}`` measures on every run.  EQuARX
(PAPERS.md) compresses collectives inside XLA at near-zero cost; here
the compression can stay **byte-exact** because the metadata it needs is
already on the host (the count matrix) or one reduction away (per-
bucket min/max stats, computed by phase 1 in the same program):

* **delta-packed keys** — phase 1 records each per-destination bucket's
  key minimum; phase 2 sends ``key - base[dest]`` cast to the narrowest
  unsigned dtype that holds the largest bucket range shard-wide (the
  static jit parameter), and the receiver adds the sender's base back.
  Integer subtract/add round-trips exactly, so the decode is
  bit-identical to the raw path.  (A per-run *dictionary* would need
  dynamic shapes; base+delta is the static-shape exact equivalent, and
  hash-spread intern ids — the worst case for run deltas — still narrow
  whenever the live id range does.)
* **narrow values** — same mechanism on the value column (base = bucket
  min, signed columns handled via their 64-bit bit patterns).
* **tiered bucket caps** — instead of ``nrounds`` uniform rounds of the
  power-of-two cap ``B`` (overshoot up to 2× of the max bucket), the
  round schedule becomes a descending ladder of power-of-two caps whose
  sum hugs the max bucket to ≲6% (4 significant bits), so one skewed
  bucket no longer inflates every bucket's padding to the next power of
  two.  The ladder is only adopted when it strictly beats the uniform
  schedule's slot count without exploding the round count.

Everything is decided HOST-side from the pulled count/stats matrices —
no extra device sync — and encoded/decoded INSIDE the phase-2
``shard_map`` program, so the host and every downstream consumer
(phase-2 sort/group, plan/ fused programs, reshard range exchanges) see
byte-identical uncompressed rows.  ``MRTPU_WIRE=0`` restores the raw
path; the planner itself falls back (``("raw", ...)`` plan) when no
column narrows and the tier ladder cannot beat the uniform schedule —
the "ratio < 1 auto-bypass" of doc/perf.md.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .sharded import narrowest_uint, round_cap

# wire pack candidates, narrowest first: (dtype name, itemsize)
_PACKS = (("uint8", 1), ("uint16", 2), ("uint32", 4))

_META_COLS = 3             # count, kbase, vbase exchanged per bucket
_MAX_TIERS = 16            # same bound as shuffle._MAX_ROUNDS


def wire_enabled() -> bool:
    """``MRTPU_WIRE`` (default on; ``0`` = raw exchange).  Read at call
    time like the exec/ knobs so tests flip it per
    run without re-importing."""
    from ..utils.env import env_flag
    return env_flag("MRTPU_WIRE", True)


def col_eligible(arr) -> bool:
    """A column the codec can delta-pack: 1-D integer rows wider than a
    byte.  Multi-word keys, floats and 1-byte riders ship raw (the
    tiered caps still apply to them)."""
    return (arr.ndim == 1 and arr.dtype.kind in "iu"
            and arr.dtype.itemsize >= 2)


def columns_eligible(key, value) -> Tuple[bool, bool]:
    return (col_eligible(key), col_eligible(value))


# ---------------------------------------------------------------------------
# phase-1 side: per-destination bucket stats (inside the same program)
# ---------------------------------------------------------------------------

def _widen(col):
    """The column in its 64-bit kind (min/max compare in the SIGNED
    domain for signed columns)."""
    return col.astype(jnp.int64 if col.dtype.kind == "i" else jnp.uint64)


def _bits64(x):
    """64-bit value → its uint64 bit pattern (host decodes signedness
    back via ``.view``)."""
    if x.dtype == jnp.uint64:
        return x
    return lax.bitcast_convert_type(x, jnp.uint64)


def bucket_stats(nprocs: int, key, value, dest, k_elig: bool,
                 v_elig: bool):
    """Per-destination (kmin, kmax, vmin, vmax) of this shard's valid
    rows, [P, 4] uint64 bit patterns.  A min and a max over each
    destination's rows, as one fused reduction of a ``[cap, P]`` select
    a column: no scatter (two of them over 2^24 rows cost 2.8 s on the
    v5e, PERF.md §6, PR 33).  ``dest`` carries ``nprocs`` for padding
    rows, which match no destination; empty buckets keep their
    sentinels and the host masks them via the count matrix."""
    hit = dest[:, None] == jnp.arange(nprocs, dtype=dest.dtype)

    def minmax(col):
        w = _widen(col)[:, None]
        info = jnp.iinfo(w.dtype)
        mn = jnp.min(jnp.where(hit, w, jnp.full((), info.max, w.dtype)),
                     axis=0)
        mx = jnp.max(jnp.where(hit, w, jnp.full((), info.min, w.dtype)),
                     axis=0)
        return _bits64(mn), _bits64(mx)

    zero = jnp.zeros((nprocs,), jnp.uint64)
    kmn, kmx = minmax(key) if k_elig else (zero, zero)
    vmn, vmx = minmax(value) if v_elig else (zero, zero)
    return jnp.stack([kmn, kmx, vmn, vmx], axis=1)


# ---------------------------------------------------------------------------
# host-side planning (from the pulled count + stats matrices)
# ---------------------------------------------------------------------------

def plan_tiers(counts_mat: np.ndarray, B: int, nrounds: int
               ) -> Tuple[int, ...]:
    """The round-cap schedule: a descending power-of-two ladder whose
    sum covers the max bucket with ≲6% overshoot (the max rounded up to
    4 significant bits — bounded compile diversity), each step bounded
    so the send buffer stays ≤ ~P·Bmax/4.  Falls back to the uniform
    ``(B,) * nrounds`` schedule whenever the ladder would not strictly
    reduce slots or would balloon the round count (tiny exchanges)."""
    uniform = (B,) * nrounds
    bmax = int(counts_mat.max()) if counts_mat.size else 0
    if bmax < 64:
        # tiny exchanges are latency-bound: extra collective rounds to
        # shave a few pad slots is a losing trade
        return uniform
    unit = 1 << max(0, bmax.bit_length() - 4)
    # one quantization unit of headroom (~1/8 of the max): a ladder
    # hugging the max exactly would invalidate the speculative-cap
    # cache on every few-percent distribution shift between repeats
    q = -(-bmax // unit) * unit + unit
    bbuf = round_cap(max(-(-q // 4), 8))   # ≤ ~5 rounds, buffer ≤ P·q/2
    tiers = []
    remaining = q
    while remaining > 0 and len(tiers) < _MAX_TIERS - 1:
        step = min(bbuf, round_cap(remaining))
        tiers.append(step)
        remaining -= step
    if remaining > 0:
        tiers.append(round_cap(remaining))
    tiers = tuple(tiers)
    if sum(tiers) >= B * nrounds or len(tiers) > max(nrounds + 2, 6):
        return uniform
    return tiers


def _bucket_ranges(counts_mat: np.ndarray, stats_mat: np.ndarray,
                   lo_col: int, hi_col: int, signed: bool
                   ) -> Optional[int]:
    """Largest (max - min) over nonempty buckets, as a python int (no
    overflow), or None when every bucket is empty."""
    mask = counts_mat > 0
    if not mask.any():
        return None
    view = stats_mat.view(np.int64) if signed else stats_mat
    lo = view[:, :, lo_col][mask]
    hi = view[:, :, hi_col][mask]
    return max(int(h) - int(l) for l, h in zip(lo.tolist(), hi.tolist()))


def _pack_for(rng: Optional[int], itemsize: int) -> Optional[str]:
    """Narrowest unsigned dtype (strictly narrower than the column)
    whose capacity holds ``rng``; None = ship raw."""
    if rng is None:
        return None
    name, width = narrowest_uint(rng)
    return name if width < itemsize else None


def plan_packs(key, value, counts_mat: np.ndarray,
               stats_mat: Optional[np.ndarray],
               elig: Tuple[bool, bool]):
    """(kpack, vpack, kvrange): wire dtypes per column (None = raw) and
    the observed max bucket ranges (speculation-validity evidence)."""
    kpack = vpack = None
    krange = vrange = None
    if stats_mat is not None:
        if elig[0]:
            krange = _bucket_ranges(counts_mat, stats_mat, 0, 1,
                                    key.dtype.kind == "i")
            kpack = _pack_for(krange, key.dtype.itemsize)
        if elig[1]:
            vrange = _bucket_ranges(counts_mat, stats_mat, 2, 3,
                                    value.dtype.kind == "i")
            vpack = _pack_for(vrange, value.dtype.itemsize)
    return kpack, vpack, (krange, vrange)


def make_plan(key, value, counts_mat: np.ndarray,
              stats_mat: Optional[np.ndarray], elig, B: int,
              nrounds: int, cap_out: int):
    """The exchange plan, a hashable tagged tuple (it keys the phase-2
    jit caches, the speculative-cap cache and the fused-plan caps):

    * ``("wire", tiers, cap_out, kpack, vpack)`` — codec engaged;
    * ``("raw", B, nrounds, cap_out)`` — auto-bypass: the codec's TOTAL
      per-pair bytes (tier slots at packed width + the [P, 3] u64
      metadata block) would not undercut the raw program's (uniform
      slots at full width + its int32 counts block), so the original
      program is the cheaper wire format.  Covers both "nothing
      narrows" and the tiny-exchange case where the metadata overhead
      eats the packing savings.

    Returns ``(plan, kvrange)``."""
    tiers = plan_tiers(counts_mat, B, nrounds)
    kpack, vpack, kvrange = plan_packs(key, value, counts_mat,
                                       stats_mat, elig)
    rb_full = _col_rowbytes(key, None) + _col_rowbytes(value, None)
    rb_packed = _col_rowbytes(key, kpack) + _col_rowbytes(value, vpack)
    wire_per_pair = sum(tiers) * rb_packed + _META_COLS * 8
    raw_per_pair = B * nrounds * rb_full + 4      # int32 counts block
    if wire_per_pair >= raw_per_pair:
        return ("raw", B, nrounds, cap_out), kvrange
    return ("wire", tiers, cap_out, kpack, vpack), kvrange


def _pack_capacity(pack: Optional[str]) -> Optional[int]:
    if pack is None:
        return None
    return (1 << (8 * np.dtype(pack).itemsize)) - 1


def _pack_covers(spec_pack: Optional[str], rng: Optional[int]) -> bool:
    """A cached plan's pack still round-trips the fresh data: raw always
    does; a narrow pack needs the fresh range to fit."""
    if spec_pack is None:
        return True
    if rng is None:        # no valid rows — any width is exact
        return True
    return rng <= _pack_capacity(spec_pack)


def plan_slots(plan) -> int:
    """Per-bucket slots the plan exchanges (the pad accounting input)."""
    if plan[0] == "wire":
        return int(sum(plan[1]))
    return int(plan[1] * plan[2])


def plan_rounds(plan) -> Tuple[int, int]:
    """(bucket_cap, nrounds) for telemetry: the largest tier stands in
    for the uniform B under a wire plan."""
    if plan[0] == "wire":
        return int(max(plan[1])), len(plan[1])
    return int(plan[1]), int(plan[2])


def plan_cap_out(plan) -> int:
    return int(plan[2] if plan[0] == "wire" else plan[3])


def plan_holds(plan, Bmax: int, nmax_out: int, kvrange) -> bool:
    """A cached/speculative plan still delivers every row exactly: the
    slot budget covers the max bucket, the output cap covers the max
    shard, and (wire plans) the cached pack widths still hold the fresh
    bucket ranges."""
    if plan_slots(plan) < Bmax or plan_cap_out(plan) < nmax_out:
        return False
    if plan[0] == "wire":
        return (_pack_covers(plan[3], kvrange[0])
                and _pack_covers(plan[4], kvrange[1]))
    return True


def plan_oversized(plan, Bmax: int, nmax_out: int) -> bool:
    """Grossly over-provisioned for the fresh distribution (the
    speculative cache's right-sizing rule, shared with the fused tier)."""
    return (plan_slots(plan) > 4 * max(Bmax, 8)
            or plan_cap_out(plan) > 4 * round_cap(nmax_out))


def plan_from_pull(key, value, counts_mat: np.ndarray,
                   stats_mat: Optional[np.ndarray], wire_on: bool, elig):
    """ONE copy of the host planning step shared by the eager exchange
    and the plan/ fuser (their plan choice and telemetry must never
    diverge): pulled count/stats matrices → ``(plan, kvrange,
    bmax_raw, nmax_out, new_counts)``.  ``bmax_raw`` is the coverage
    bound cached plans validate against (the pow2-rounded Bmax would
    wrongly invalidate tier ladders that hug the real max)."""
    from .shuffle import _plan_caps
    B, nrounds, cap_out, _bmax, new_counts = _plan_caps(counts_mat)
    bmax_raw = int(counts_mat.max())
    nmax_out = max(int(new_counts.max()), 8)
    if wire_on:
        plan, kvrange = make_plan(key, value, counts_mat, stats_mat,
                                  elig, B, nrounds, cap_out)
    else:
        plan, kvrange = ("raw", B, nrounds, cap_out), (None, None)
    return plan, kvrange, bmax_raw, nmax_out, new_counts


def wire_ratio(moved: int, pad: int, wire_bytes: int) -> float:
    """The logical/actual compression ratio (one formula for the eager
    and fused telemetry feeds; 0.0 = the codec did not run)."""
    return round((moved + pad) / wire_bytes, 4) if wire_bytes else 0.0


# ---------------------------------------------------------------------------
# the in-program codec (phase-2 shard body)
# ---------------------------------------------------------------------------

def _base_in(base_bits, dtype):
    """[P] uint64 bit patterns → per-bucket bases in the column dtype
    (exact: the base is a value OF that column)."""
    if np.dtype(dtype).kind == "i":
        return lax.bitcast_convert_type(base_bits, jnp.int64).astype(dtype)
    return base_bits.astype(dtype)


def _encode_windows(wins, valid, base_bits, pack: Optional[str]):
    """One round's ``[P, B]`` send block from its unmasked windows:
    window ``d`` less ``base[d]``, cast to the wire dtype (``pack``
    None: the rows as they are), zero off the valid slots.  Valid rows
    fit the pack width by construction (the planner checked the
    ranges); the rest of a window is other buckets' rows or padding and
    is masked, as the raw send block's is."""
    with jax.named_scope("windows"):
        if pack is not None:
            base = _base_in(base_bits, wins.dtype)
            wins = (wins - base[:, None]).astype(jnp.dtype(pack))
        return jnp.where(valid, wins, jnp.zeros((), wins.dtype))


def phase2_wire_shard_body(nprocs: int, mesh, tiers, cap_out: int,
                           kpack: Optional[str], vpack: Optional[str],
                           k, v, cl, stats):
    """The wire twin of ``shuffle.phase2_shard_body``: same multi-round
    bounded exchange and same packed output layout (row positions are
    identical, so output is byte-identical), but rows cross the
    interconnect delta-packed at the planned widths and the round caps
    follow the tier ladder.  One extra tiny collective replaces the
    counts exchange: ``(count, kbase, vbase)`` per bucket ride together
    as a [P, 3] uint64 block.

    The codec works on blocks, not rows: destination ``d``'s base comes
    off window ``d`` before it is sent, source ``j``'s base goes onto
    block ``j`` before it is placed (``shuffle._place_blocks``), and
    only valid rows are ever written, so rows past the valid prefix
    stay the zeros the raw path leaves there."""
    from .shuffle import (_exchange_blocks, _place_blocks, _recv_buffer,
                          _run_starts, _send_windows)

    # (the steps are the helpers': ``windows``, ``exchange``, ``unpack``)
    with jax.named_scope("exchange"):
        meta_local = jnp.stack([cl.astype(jnp.uint64), stats[:, 0],
                                stats[:, 2]], axis=1)          # [P, 3]
        meta_from = _exchange_blocks(meta_local[:, None, :], mesh)[:, 0, :]
    with jax.named_scope("unpack"):
        counts_from = meta_from[:, 0].astype(jnp.int32)
        kbase = _base_in(meta_from[:, 1], k.dtype) if kpack else None
        vbase = _base_in(meta_from[:, 2], v.dtype) if vpack else None
        base = _run_starts(counts_from)
    out_k = _recv_buffer(cap_out, max(tiers), k)
    out_v = _recv_buffer(cap_out, max(tiers), v)
    start = 0
    for B in tiers:
        send_k = _encode_windows(*_send_windows(nprocs, B, start, k, cl),
                                 stats[:, 0], kpack)
        send_v = _encode_windows(*_send_windows(nprocs, B, start, v, cl),
                                 stats[:, 2], vpack)
        recv_k = _exchange_blocks(send_k, mesh)
        recv_v = _exchange_blocks(send_v, mesh)
        out_k = _place_blocks(out_k, recv_k, base, counts_from, start,
                              rebase=kbase)
        out_v = _place_blocks(out_v, recv_v, base, counts_from, start,
                              rebase=vbase)
        start += B
    with jax.named_scope("unpack"):
        return out_k[:cap_out], out_v[:cap_out], jnp.sum(counts_from)


# ---------------------------------------------------------------------------
# byte accounting
# ---------------------------------------------------------------------------

def _col_rowbytes(arr, pack: Optional[str]) -> int:
    if pack is not None:
        return np.dtype(pack).itemsize
    return arr.dtype.itemsize * (arr.shape[-1] if arr.ndim > 1 else 1)


def wire_volume(skv, counts_mat: np.ndarray, plan) -> int:
    """Actual bytes a ``("wire", ...)`` plan puts on the interconnect:
    every exchanged slot (useful + pad, diagonal excluded on both sides
    like ``exchange_volume``) at the packed row width, plus the [P, 3]
    uint64 per-bucket metadata block the codec ships instead of the raw
    path's [P, 1] int32 counts."""
    nprocs = counts_mat.shape[0]
    _tag, tiers, _cap_out, kpack, vpack = plan
    rowbytes = (_col_rowbytes(skv.key, kpack)
                + _col_rowbytes(skv.value, vpack))
    slots = nprocs * (nprocs - 1) * int(sum(tiers))
    meta = nprocs * (nprocs - 1) * _META_COLS * 8
    return slots * rowbytes + meta
