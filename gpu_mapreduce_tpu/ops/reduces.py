"""Registered kernel reduces — the vectorised callback tier.

These are the TPU equivalents of the reference's reusable reduce callbacks
(``oink/reduce_count.cpp:14-20``, ``oink/reduce_cull.cpp:13-20``): batch
functions usable directly as ``mr.reduce(fn, batch=True)`` that dispatch on
the frame kind (local KMVFrame vs mesh ShardedKMV) and stay columnar/on
device throughout."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..core.frame import KMVFrame
from .segment import kmv_segment_ids, segment_reduce


def _is_sharded(frame) -> bool:
    return not isinstance(frame, KMVFrame)


def count(frame, kv, ptr=None):
    """(key, [v...]) → (key, nvalues) — oink reduce_count."""
    if _is_sharded(frame):
        from ..parallel.group import reduce_sharded
        kv.add_frame(reduce_sharded(frame, "count"))
    else:
        kv.add_batch(frame.key, np.asarray(frame.nvalues))


# what ``MapReduce.compress`` reads to know a registered segment reduce
# (the combiner's vocabulary: parallel/group.COMBINE_OPS)
count.segment_op = "count"


def cull(frame, kv, ptr=None):
    """(key, [v...]) → (key, first value) — dedupe, oink reduce_cull."""
    if _is_sharded(frame):
        from ..parallel.group import first_sharded
        kv.add_frame(first_sharded(frame))
    else:
        firsts = frame.offsets[:-1]
        kv.add_batch(frame.key, frame.values.take(firsts))


def _segment_op(op):
    def fn(frame, kv, ptr=None):
        if _is_sharded(frame):
            from ..parallel.group import reduce_sharded
            kv.add_frame(reduce_sharded(frame, op))
        else:
            seg = jnp.asarray(kmv_segment_ids(frame))
            vals = jnp.asarray(np.asarray(frame.values.data))
            out = segment_reduce(vals, seg, len(frame), op)
            kv.add_batch(frame.key, out)
    fn.__name__ = f"reduce_{op}"
    fn.segment_op = op
    fn.__doc__ = f"(key, [v...]) → (key, {op}(values)), columnar."
    return fn


sum_values = _segment_op("sum")
max_values = _segment_op("max")
min_values = _segment_op("min")
