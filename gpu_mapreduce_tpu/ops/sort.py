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

from ..core.column import BytesColumn, Column, DenseColumn


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
