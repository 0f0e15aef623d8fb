"""Paged Pallas segment-group / segment-reduce kernels (ROADMAP item 3).

The grouping hot path pays a FULL per-shard sort today: convert (and the
plan/ fused group bodies) run ``jnp.lexsort`` over every received row
just to find group boundaries, then segment ops reduce them — O(n log²n)
bitonic work for what is semantically a hash-aggregate.  Ragged Paged
Attention (PAPERS.md) makes the case that purpose-built Pallas kernels
beat generic XLA lowering on exactly this ragged/segmented shape; this
module applies that to grouping:

* **paged segment-group kernel** (:func:`segment_table`): a bucketed
  scatter of interned-u64 (or any ≤8-byte integer) keys into an
  open-addressed accumulation table — one linear pass over the rows in
  page-sized tiles honoring the core page budget (``Settings.memsize``,
  the same budget that sizes dataset frames), each page one
  ``pallas_call`` over VMEM-resident refs.  No row sort ever runs.
* **fused segment-reduce** (the ``with_sum`` variant): the same pass
  accumulates the value column next to the key as two u32 limbs with
  explicit carry, so integer sums are exact mod 2⁶⁴ — byte-identical to
  the eager ``segment_sum`` (which wraps the same way at the value
  dtype's width).  Float sums are order-sensitive and stay on the sort
  path (``group_supported``).

The table epilogue (``ops/segment.table_to_groups``) then orders ONLY
the table slots — O(T) = O(groups), not O(rows) — so the sorted-unique-
key output layout is bit-identical to the sort path's by construction:
eager grouping emits ascending unique keys with zero-fill, and so does
a slot sort.  Overflow (more distinct keys than table slots) and
per-row probe exhaustion are counted into a trash slot the caller
validates host-side — the megafused executor (plan/fuser.py) re-runs
the sort path when the count is nonzero, so a bad capacity guess can
never drop a group.

64-bit values never enter the kernel: keys and sums travel as u32
hi/lo limb pairs (TPU VPUs have no native 64-bit lanes — the same
constraint that shaped ``match.py``'s word-packed kernels).  The
``interpret=True`` path is the only one that has run: on the TPU v5e
(jax 0.9.0, libtpu 0.0.34) Mosaic refuses the scalar probe loop at
lowering with a ``RecursionError`` (CHANGES.md PR 22), so the kernels
are off unless ``MRTPU_PALLAS_GROUP=1`` asks for them — and then a
kernel that does not lower raises; nothing switches back to the sort
path on its own (doc/perf.md).
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...utils.env import env_flag
from . import note_kernel_launch

# multiplicative-hash constants (Fibonacci / murmur3 finalizer mixers)
_GOLD1 = np.uint32(0x9E3779B1)
_GOLD2 = np.uint32(0x85EBCA6B)

# trace-size bound: one program embeds at most this many page calls
MAX_PAGES = 32


def pallas_group_enabled() -> bool:
    """``MRTPU_PALLAS_GROUP``: route supported fused group chains
    through the table kernels instead of the per-shard sort.

    Default off: the sort path groups everywhere.  ``1`` is an explicit
    opt-in — interpret mode on CPU (a correctness/test vehicle that
    trades the sort for a sequential emulated scatter and loses badly
    on wall; what the unit goldens and the soak A/B use), Mosaic on a
    TPU, where a kernel the compiler refuses raises instead of quietly
    taking the sort path.  Read at call time like ``MRTPU_WIRE``; the
    resolved flag is threaded into every builder cache key."""
    return env_flag("MRTPU_PALLAS_GROUP", False)


def group_supported(key, value, out_kind: str, reduce_op) -> tuple:
    """(ok, reason) — which fused group chains the table kernels cover.
    ``reason`` feeds the warn-once fallback (doc/perf.md fallback
    matrix); unsupported chains stay on the sort path, still fused."""
    if out_kind != "kv":
        return False, ("grouped KMV layout needs the full row "
                       "permutation (values stay with their groups)")
    if reduce_op not in ("count", "sum"):
        return False, (f"reduce op {reduce_op!r} is not "
                       f"table-accumulable (only count/sum)")
    if key.ndim != 1 or key.dtype.kind not in "iu" \
            or key.dtype.itemsize > 8:
        return False, "keys are not a 1-D <=8-byte integer column"
    if reduce_op == "sum" and (value.ndim != 1
                               or value.dtype.kind not in "iu"
                               or value.dtype.itemsize > 8):
        return False, ("sum needs a 1-D integer value column — float "
                       "sums are order-sensitive and would drift from "
                       "the sorted segment_sum")
    return True, ""


_WARNED: set = set()


def warn_fallback(reason: str) -> None:
    """One warning per distinct fallback reason per process — the
    'warn once, correct output' contract: the sort path runs instead."""
    if reason in _WARNED:
        return
    _WARNED.add(reason)
    warnings.warn(
        f"MRTPU_PALLAS_GROUP: group kernels falling back to the "
        f"sort path ({reason})", stacklevel=3)


def page_rows_for(cap: int, memsize_mb: int, rowbytes: int = 16) -> int:
    """Rows per kernel page: the largest power of two whose page
    (key+value limbs, ``rowbytes``/row) fits the core ``memsize`` frame
    budget, clamped to [256, 1M] and raised so one program never embeds
    more than :data:`MAX_PAGES` page calls (trace-size bound)."""
    budget = max(1, (int(memsize_mb) << 20) // max(rowbytes, 1))
    page = 1 << max(8, budget.bit_length() - 1)
    page = min(page, 1 << 20)
    min_page = -(-max(cap, 1) // MAX_PAGES)
    while page < min_page:
        page <<= 1
    return page


def table_slots(gcap: int) -> int:
    """Open-addressing table size for an expected group capacity: the
    next power of two at ≤50% load, so probe chains stay short and a
    ~2× group-count miss still fits (overflow is detected, not UB)."""
    g = max(int(gcap), 8)
    t = 1
    while t < g:
        t <<= 1
    return 2 * t


# ---------------------------------------------------------------------------
# 64-bit <-> u32 limb views (the TPU-lane-width contract, see module doc)
# ---------------------------------------------------------------------------

def split_limbs(col):
    """Integer column [n] → (hi, lo) uint32 limb views of its 64-bit
    widening (sign-extended for signed dtypes, so truncating the limbs
    back is exact)."""
    w = col
    if w.dtype.itemsize < 8:
        w = w.astype(jnp.int64 if w.dtype.kind == "i" else jnp.uint64)
    words = lax.bitcast_convert_type(w, jnp.uint32)   # [n, 2] LE
    return words[..., 1], words[..., 0]


def join_limbs(hi, lo, dtype):
    """(hi, lo) u32 limbs → values in ``dtype`` (exact inverse of
    :func:`split_limbs` for values that fit; sums truncate with the
    same mod-2^width wrap the eager ``segment_sum`` has)."""
    u = (hi.astype(jnp.uint64) << np.uint64(32)) | lo.astype(jnp.uint64)
    dt = jnp.dtype(dtype)
    if dt.kind == "u":
        return u.astype(dt)
    return lax.bitcast_convert_type(u, jnp.int64).astype(dt)


# ---------------------------------------------------------------------------
# the table kernel (one page per pallas_call)
# ---------------------------------------------------------------------------

def _seg_table_kernel(T: int, page_rows: int, base: int, with_sum: bool,
                      *refs):
    """Insert one page of rows into the accumulation table.

    Layout: slots [0, T) are the live table, slot T absorbs invalid
    (past-``nvalid``) rows, slot T+1 counts probe-exhausted rows (the
    overflow evidence the host validates).  The table rides page to
    page as plain input→output arrays (copied at page entry; an
    ``input_output_aliases`` zero-copy variant is a TPU follow-up)."""
    if with_sum:
        (kh_ref, kl_ref, vh_ref, vl_ref, nv_ref,
         itkh, itkl, iocc, icnt, ishi, islo,
         tkh, tkl, occ, cnt, shi, slo) = refs
    else:
        (kh_ref, kl_ref, nv_ref, itkh, itkl, iocc, icnt,
         tkh, tkl, occ, cnt) = refs
    tkh[:] = itkh[:]
    tkl[:] = itkl[:]
    occ[:] = iocc[:]
    cnt[:] = icnt[:]
    if with_sum:
        shi[:] = ishi[:]
        slo[:] = islo[:]
    nvalid = nv_ref[0]

    def insert(i, carry):
        valid = (base + i) < nvalid
        kh = kh_ref[i]
        kl = kl_ref[i]
        h = (kl ^ (kh * _GOLD1)) * _GOLD2
        slot0 = (h & np.uint32(T - 1)).astype(jnp.int32)

        def probing(c):
            _s, steps, done = c
            return jnp.logical_and(~done, steps < T)

        def probe(c):
            s, steps, done = c
            o = occ[s]
            hit = (o == 1) & (tkh[s] == kh) & (tkl[s] == kl)
            done2 = hit | (o == 0)
            return (jnp.where(done2, s, (s + 1) & (T - 1)),
                    steps + 1, done2)

        slot, _steps, done = lax.while_loop(
            probing, probe, (slot0, jnp.int32(0), jnp.bool_(False)))
        # found/empty → the slot; probe-exhausted → overflow slot T+1;
        # invalid (padding) rows → trash slot T
        tgt = jnp.where(valid & done, slot,
                        jnp.where(valid, jnp.int32(T + 1), jnp.int32(T)))
        occ[tgt] = jnp.int32(1)
        tkh[tgt] = kh
        tkl[tgt] = kl
        cnt[tgt] = cnt[tgt] + 1
        if with_sum:
            vl = vl_ref[i]
            nlo = slo[tgt] + vl
            slo[tgt] = nlo
            # explicit carry: exact two's-complement 64-bit accumulate
            shi[tgt] = shi[tgt] + vh_ref[i] + (nlo < vl).astype(jnp.uint32)
        return carry

    lax.fori_loop(0, page_rows, insert, 0)


def segment_table(key, value, nvalid, T: int, page_rows: int,
                  with_sum: bool, interpret: bool):
    """Run the paged table kernel over a shard's rows.

    ``key``/``value`` are the shard-local columns ([cap] rows, rows at
    index ≥ ``nvalid`` ignored); returns the table arrays
    ``(tkh, tkl, occ, cnt[, shi, slo])`` of length T+2 (see kernel doc
    for the two trailing trash/overflow slots).  Jit-composable: under
    a trace the page calls ride the enclosing program; called eagerly,
    every page counts one kernel launch in ``Counters.ndispatch``."""
    from jax.experimental import pallas as pl
    cap = key.shape[0]
    kh, kl = split_limbs(key)
    cols = [kh, kl]
    if with_sum:
        vh, vl = split_limbs(value)
        cols += [vh, vl]
    npages = max(1, -(-cap // page_rows))
    pad = npages * page_rows - cap
    if pad:
        cols = [jnp.concatenate([c, jnp.zeros(pad, jnp.uint32)])
                for c in cols]
    nv = jnp.reshape(nvalid, ()).astype(jnp.int32)[None]
    dtypes = (jnp.uint32, jnp.uint32, jnp.int32, jnp.int32) \
        + ((jnp.uint32, jnp.uint32) if with_sum else ())
    table = [jnp.zeros(T + 2, d) for d in dtypes]
    shapes = [jax.ShapeDtypeStruct((T + 2,), d) for d in dtypes]
    for p in range(npages):
        s = slice(p * page_rows, (p + 1) * page_rows)
        page_cols = [c[s] for c in cols]
        note_kernel_launch(*page_cols, *table)
        table = list(pl.pallas_call(
            functools.partial(_seg_table_kernel, T, page_rows,
                              p * page_rows, with_sum),
            out_shape=shapes,
            interpret=interpret,
        )(*page_cols, nv, *table))
    return tuple(table)


def segment_group_reduce(key, value, nrecv, gcap: int, reduce_op: str,
                         cfg: tuple):
    """The kernel-backed fused group(+reduce) shard body: bucketed
    table scatter + slot-ordered extraction → ``(ukey, uval, g,
    overflow)`` with ``ukey``/``uval`` in the exact layout the sort
    path emits (ascending unique keys, zero fill past the shard's
    group count).  ``cfg`` is the hashable ("tbl", T, page_rows,
    interpret) tuple the builder caches key on (plan/fuser)."""
    from ..segment import table_to_groups
    _tag, T, page_rows, interpret = cfg
    if T < gcap:
        raise ValueError(f"table T={T} smaller than group cap {gcap}")
    with_sum = reduce_op == "sum"
    table = segment_table(key, value, nrecv, T, page_rows, with_sum,
                          interpret)
    return table_to_groups(table, T, gcap, reduce_op, key.dtype,
                           value.dtype)
