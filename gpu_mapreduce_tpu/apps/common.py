"""Shared app helpers."""

from __future__ import annotations

from typing import List, Tuple


def top_n(mr, ntop: int) -> List[Tuple[object, object]]:
    """Gather to one shard, sort by value descending, take the first ntop
    (key, value) pairs — the reference's top-N tail (gather(1) +
    sort_values + bounded print, examples/wordfreq.cpp:100-116).  Only
    those ntop rows are decoded and become Python pairs: nothing runs
    for the rows after them."""
    from ..core.frame import KVFrame
    mr.gather(1)
    mr.sort_values(-1)
    fr = mr.kv.one_frame()
    if isinstance(fr, KVFrame):
        fr = fr.slice(0, ntop)
    else:                   # a mesh: gather(1) left every row on shard 0
        fr = fr.shard_to_host(0, limit=ntop)
    return list(fr.pairs())
