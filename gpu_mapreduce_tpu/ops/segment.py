"""Group-by (convert) and segment reductions — replaces the reference's
in-memory hash of Unique records.

The reference's ``KeyMultiValue::convert`` builds an open-chained hash table
of Unique records in a 2-page arena, recursively splitting partitions that
overflow (``src/keymultivalue.cpp:645-1433``).  On TPU the idiomatic
equivalent is *sort + run-length detection*: sort pairs by key, find group
boundaries, and reduce with segment ops (SURVEY.md §7).  No hash table, no
partition recursion — XLA's sort is the workhorse and skewed keys cost
nothing extra.

Two layers:

* :func:`group_dense` / :func:`group_bytes` — full convert for one frame.
* jittable segment helpers (:func:`segment_ids_from_offsets`,
  :func:`segment_reduce`) used by registered kernel reduces
  (count/sum/max/...) so entire map→collate→reduce pipelines stay on device.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core.column import BytesColumn, Column, DenseColumn
from ..core.frame import KMVFrame, KVFrame
from .sort import argsort_column


def _boundaries_dense(sorted_keys) -> np.ndarray:
    """Boolean host mask: row starts a new group (row 0 always True)."""
    k = np.asarray(sorted_keys)
    if k.ndim == 1:
        new = k[1:] != k[:-1]
    else:
        new = np.any(k[1:] != k[:-1], axis=1)
    return np.concatenate([[True], new]) if len(k) else np.zeros(0, bool)


def group_dense(kv: KVFrame) -> KMVFrame:
    """Convert a dense KVFrame → KMVFrame by sort + boundary detection."""
    if len(kv) == 0:
        return KMVFrame(kv.key, np.zeros(0, np.int64), np.zeros(1, np.int64), kv.value)
    order = argsort_column(kv.key)
    skey = kv.key.take(order)
    svals = kv.value.take(order)
    starts = np.flatnonzero(_boundaries_dense(skey.data))
    offsets = np.concatenate([starts, [len(kv)]]).astype(np.int64)
    nvalues = np.diff(offsets)
    ukeys = skey.take(starts)
    return KMVFrame(ukeys, nvalues, offsets, svals)


def group_bytes(kv: KVFrame) -> KMVFrame:
    """Convert with byte-string keys (host path): dict grouping preserving
    first-seen key order (the reference's hash-insertion order is likewise
    arbitrary but deterministic)."""
    groups = {}
    keys = kv.key.tolist()
    for i, k in enumerate(keys):
        groups.setdefault(k, []).append(i)
    ukeys = list(groups.keys())
    idx = np.asarray([i for ids in groups.values() for i in ids], dtype=np.int64)
    nvalues = np.asarray([len(v) for v in groups.values()], dtype=np.int64)
    offsets = np.concatenate([[0], np.cumsum(nvalues)]).astype(np.int64)
    svals = kv.value.take(idx)
    key_col: Column = BytesColumn(ukeys) if isinstance(kv.key, BytesColumn) \
        else DenseColumn(np.asarray(ukeys))
    return KMVFrame(key_col, nvalues, offsets, svals)


def group_objects(kv: KVFrame) -> KMVFrame:
    """Convert with arbitrary-object keys: group by PICKLE equality (the
    reference's Python wrapper groups by pickled bytes — the C++ core
    only ever sees the pickle, python/mrmpi.py:17-45)."""
    from ..core.column import ObjectColumn
    groups: dict = {}
    firsts: dict = {}
    for i, p in enumerate(kv.key.pickles()):
        groups.setdefault(p, []).append(i)
        firsts.setdefault(p, i)
    idx = np.asarray([i for ids in groups.values() for i in ids],
                     dtype=np.int64)
    nvalues = np.asarray([len(v) for v in groups.values()], dtype=np.int64)
    offsets = np.concatenate([[0], np.cumsum(nvalues)]).astype(np.int64)
    ukeys = ObjectColumn([kv.key.data[firsts[p]] for p in groups])
    return KMVFrame(ukeys, nvalues, offsets, kv.value.take(idx))


def group_frame(kv: KVFrame) -> KMVFrame:
    from ..core.column import ObjectColumn
    if isinstance(kv.key, ObjectColumn):
        return group_objects(kv)
    if kv.is_dense():
        return group_dense(kv)
    return group_bytes(kv)


# ---------------------------------------------------------------------------
# Jittable segment helpers (device pipelines)
# ---------------------------------------------------------------------------

def segment_ids_from_boundary(is_start):
    """[n] bool 'starts new group' mask → [n] int32 segment ids (jittable)."""
    return jnp.cumsum(is_start.astype(jnp.int32)) - 1


def boundary_mask(sorted_keys):
    """Jittable group-start mask for sorted dense keys [n] or [n,w]."""
    k = sorted_keys
    if k.ndim == 1:
        new = k[1:] != k[:-1]
    else:
        new = jnp.any(k[1:] != k[:-1], axis=1)
    first = jnp.ones((1,), dtype=bool)
    return jnp.concatenate([first, new]) if k.shape[0] else jnp.zeros(0, bool)


_REDUCERS = {
    "sum": jax.ops.segment_sum,
    "max": jax.ops.segment_max,
    "min": jax.ops.segment_min,
    "prod": jax.ops.segment_prod,
}


def segment_reduce(values, segment_ids, num_segments: int, op: str = "sum"):
    """Jittable segment reduction; op in {sum,max,min,prod,count}."""
    if op == "count":
        ones = jnp.ones(values.shape[0], dtype=jnp.int64)
        return jax.ops.segment_sum(ones, segment_ids, num_segments=num_segments)
    fn = _REDUCERS[op]
    return fn(values, segment_ids, num_segments=num_segments)


def kmv_segment_ids(kmv: KMVFrame):
    """[n] segment ids for a KMVFrame's flat value column."""
    return np.repeat(np.arange(len(kmv), dtype=np.int64), kmv.nvalues)
