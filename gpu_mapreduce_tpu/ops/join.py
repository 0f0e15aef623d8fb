"""The keyed join of two host frames — ``MapReduce.join`` on the serial
backend, in plain numpy.

The same semantics as the mesh's ``parallel/group.join_sharded``: an
inner join on equal keys, the build side's keys unique, a joined row's
value the probe value's words followed by the build value's.  Here the
joined rows keep the probe's order."""

from __future__ import annotations

import numpy as np

from ..core.frame import KVFrame


def _rows(col) -> np.ndarray:
    """A dense column as ``[n, w]``."""
    a = np.asarray(col.to_host().data)
    return a[:, None] if a.ndim == 1 else a


def join_frames(probe: KVFrame, build: KVFrame):
    """``(joined frame, build keys that occur more than once)`` of two
    frames that both hold rows."""
    pk, bk = _rows(probe.key), _rows(build.key)
    pv, bv = _rows(probe.value), _rows(build.value)
    # both sides' keys numbered together: equal rows, equal numbers
    _, ids = np.unique(np.concatenate([bk, pk]), axis=0,
                       return_inverse=True)
    ids = ids.reshape(-1)
    bid, pid = ids[:len(bk)], ids[len(bk):]
    at = np.full(int(ids.max()) + 1, -1, np.int64)
    at[bid] = np.arange(len(bk))
    twice = len(bk) - np.count_nonzero(at >= 0)
    partner = at[pid]
    hit = partner >= 0
    key = np.asarray(probe.key.to_host().data)[hit]
    value = np.concatenate([pv[hit], bv[partner[hit]]], axis=1)
    return KVFrame(key, value), int(twice)
