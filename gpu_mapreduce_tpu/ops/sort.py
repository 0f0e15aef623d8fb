"""Sorting ops — replaces the reference's qsort + 2-way merge cascade.

The reference sorts per-process with an index-array qsort over one page and a
Spool-based merge cascade across pages (``src/mapreduce.cpp:2359-2633``).  On
TPU a whole shard sorts in one ``jax.lax.sort`` call (XLA's bitonic sort runs
on the VPU), so the merge machinery disappears for in-core/device data.
Out-of-core datasets take the streaming path instead: per-frame sorted runs
+ k-way merge in ~one page budget (``core/external.py`` — the Spool
cascade's capability, rebuilt).

Sort "flags" ±1..6 select the pre-built comparators in the reference
(int/uint64/float/double/str/strn, ``src/mapreduce.cpp:2692-2802``).  Columns
already know their dtype, so a flag here only encodes direction: flag > 0
ascending, flag < 0 descending.  A user compare callback is honoured on the
host path (parity with appcompare, slow by design).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.column import BytesColumn, Column, DenseColumn

# ---------------------------------------------------------------------------
# one payload sort (ROADMAP C21)
# ---------------------------------------------------------------------------

# The most 32-bit payload operands one sort carries; what does not fit
# goes by the sorted row index and one ``take``.  Set by the sort's
# COMPILE time, not its run time: on the v5e a u32[4194304, w] column
# riding a sort by an int32 key runs in 0.017 / 0.032 / 0.059 s at
# w = 4 / 8 / 16 where the index and a take run in 0.033 / 0.084 /
# 0.138 s, so riding is 2-2.6 times quicker at every width read; but the
# sort compiles in 42 / 87 / 238 s there and at w = 32 not within 19
# minutes (the take form: 16 s whatever w).  PERF.md §6, PR 33.
RIDE_WORDS = 8


def sort_operands(col) -> int:
    """32-bit operands a row of ``col`` puts into a sort: ``[n, w]`` goes
    in as ``w`` columns, and the chip splits a 64-bit column in two."""
    width = 1 if col.ndim == 1 else col.shape[1]
    return width * max(1, col.dtype.itemsize // 4)


def columns(col) -> list:
    """The 1-D columns of a ``[n]`` or ``[n, w]`` array, in their order:
    what a sort takes as operands, the first the most significant."""
    return [col] if col.ndim == 1 else [col[:, j]
                                        for j in range(col.shape[1])]


def riding(carry) -> list:
    """For each array of ``carry``, whether it reaches its sorted
    position as a payload of the sort (True) or by the sorted row index
    and a ``take`` (False) — decided by what the arrays themselves say,
    dtype and width, in their order: a column rides while the sort's
    payload operands stay within ``RIDE_WORDS``, and a 64-bit float
    never does (the v5e sorts none and refuses its ``bitcast-convert``:
    PERF.md §6, PR 32)."""
    out, used = [], 0
    for c in carry:
        fits = (c.ndim <= 2
                and not (c.dtype.kind in "fc" and c.dtype.itemsize >= 8)
                and used + sort_operands(c) <= RIDE_WORDS)
        used += sort_operands(c) if fits else 0
        out.append(fits)
    return out


def sort_carrying(keys, carry=(), stable: bool = True):
    """Sort rows by the 1-D ``keys`` (lexicographic, the first the most
    significant) and bring every array of ``carry`` (``[n]`` or
    ``[n, w]``) into the same order: ``(sorted keys, sorted carry)``.

    ONE ``lax.sort``.  A column that rides (:func:`riding`) is an
    operand of it;
    ``[n, w]`` is split into its ``w`` columns and stacked again after,
    because columns, not blocks, go into a sort (the chip stores
    u64[n, 2] column-major and a reshape of it is tile-padded 64x:
    PERF.md §6, PR 25).  The others share one more operand, the row
    index, and are taken by it.  On the v5e a payload sort of 16.8 M
    rows is 0.04-0.06 s where a scatter of them is 1.8 s and a gather
    behind a key-only sort twice the sort (PRs 25, 29).

    ``stable=False`` is 5-17 % quicker there (PRs 29, 33); use it where
    tied rows carry nothing that is kept."""
    keys = tuple(keys)
    n = keys[0].shape[0]
    rides = riding(carry)
    operands = list(keys)
    for c, r in zip(carry, rides):
        if r:
            operands += columns(c)
    if not all(rides):
        operands.append(jnp.arange(
            n, dtype=jnp.int32 if n < 2 ** 31 else jnp.int64))
    with jax.named_scope("sort"):
        out = lax.sort(tuple(operands), num_keys=len(keys),
                       is_stable=stable)
    rest = iter(out[len(keys):])
    scarry = []
    for c, r in zip(carry, rides):
        if not r:
            with jax.named_scope("take"):
                scarry.append(jnp.take(c, out[-1], axis=0))  # the row index
        elif c.ndim == 1:
            scarry.append(next(rest))
        else:
            cols = [next(rest) for _ in range(c.shape[1])]
            scarry.append(jnp.stack(cols, axis=1) if cols else c)
    return tuple(out[:len(keys)]), scarry


def front_order(keep):
    """Inside a program: ``(order, count)`` of the rows flagged in
    ``keep``: ``order[j]`` is the index of the j-th flagged row for j
    below ``count``, and past the block's end after.  ONE sort of one
    int32 operand, the row index or a fill: no payload rides it, so it
    compiles in seconds where a sort that carries the rows takes minutes
    (7 s against 176 s at 6 x 10^7 rows of five operands on the v5e's
    compiler, PERF.md §6, PR 43), and no scatter (a prefix sum and two
    scatters with dropped rows cost 0.115 us a row there: PERF.md §6,
    PR 49; ``devkernels._pack`` sorts by this key with the rows riding,
    because a mapper keeps most of its rows).  The rows come by ``order``
    afterwards, as many as are wanted (``parallel/devkernels.skv_scan``,
    ``parallel/group.join_sharded``)."""
    n = keep.shape[0]
    row = jnp.arange(n, dtype=jnp.int32)
    order, = lax.sort((jnp.where(keep, row, row + n),), num_keys=1,
                      is_stable=False)
    return order, jnp.sum(keep, dtype=jnp.int32)


def take_together(at, *blocks):
    """Inside a program: the rows ``at`` of each of ``blocks``.  Blocks
    that are all ``[n, w]`` of one dtype are taken as ONE block of their
    words side by side and cut apart again: on the v5e a gather costs about 20
    ns a row whatever the row holds up to eight words (3.3 x 10^7 rows of
    a 6 x 10^7-row block: 0.66 s for ``[n, 4]``, 0.69 s for ``[n, 8]``,
    1.22 s for two ``[n, 2]``, 3.8 s for four columns apart: PERF.md §6,
    PR 43), so the index is paid once."""
    if (len(blocks) > 1 and all(b.ndim == 2 for b in blocks)
            and len({b.dtype for b in blocks}) == 1):
        rows = jnp.take(jnp.concatenate(blocks, axis=1), at, axis=0)
        cuts = np.cumsum([b.shape[1] for b in blocks])[:-1].tolist()
        return tuple(jnp.split(rows, cuts, axis=1))
    return tuple(jnp.take(b, at, axis=0) for b in blocks)


def argsort_column(col: Column, descending: bool = False,
                   cmp: Optional[Callable] = None) -> np.ndarray:
    """Stable argsort of a column; lexicographic over trailing width dim."""
    n = len(col)
    if cmp is not None:
        rows = col.tolist()
        order = sorted(range(n), key=functools.cmp_to_key(
            lambda i, j: cmp(rows[i], rows[j])))
        return np.asarray(order, dtype=np.int64)
    if isinstance(col, BytesColumn):
        rows = col.tolist()
        order = sorted(range(n), key=lambda i: rows[i], reverse=descending)
        return np.asarray(order, dtype=np.int64)
    from ..core.column import ObjectColumn
    if isinstance(col, ObjectColumn):
        # arbitrary objects order by their pickles (the bytes the
        # reference's C++ comparators would see)
        rows = col.pickles()
        order = sorted(range(n), key=lambda i: rows[i], reverse=descending)
        return np.asarray(order, dtype=np.int64)
    data = col.data
    if isinstance(data, jax.Array):
        if data.ndim == 1:
            idx = jnp.argsort(data, stable=True)
        else:
            # lexicographic: last key = leading column → sort by trailing first
            keys = tuple(data[:, j] for j in range(data.shape[1] - 1, -1, -1))
            idx = jnp.lexsort(keys)
        if descending:
            idx = idx[::-1]
        return idx
    if data.ndim == 1:
        idx = np.argsort(data, kind="stable")
    else:
        idx = np.lexsort(tuple(data[:, j] for j in range(data.shape[1] - 1, -1, -1)))
    if descending:
        idx = idx[::-1]
    return idx


def sorted_dense(data, descending: bool = False):
    """Direct value sort of a dense [n] or [n,w] array (device-friendly)."""
    if data.ndim == 1:
        out = jnp.sort(data) if isinstance(data, jax.Array) else np.sort(data, kind="stable")
        return out[::-1] if descending else out
    idx = argsort_column(DenseColumn(data), descending)
    return data[idx]
