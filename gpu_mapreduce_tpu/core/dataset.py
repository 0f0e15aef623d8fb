"""KeyValue / KeyMultiValue datasets: frame lists with an add/complete
protocol and host-DRAM/disk spill.

This is the TPU re-design of the reference's paged containers:

* ``KeyValue`` (``src/keyvalue.{h,cpp}``) — append-only byte-packed pairs in
  64 MB pages, spilling page-at-a-time to ``fpath/mrmpi.kv.*`` files
  (``src/mapreduce.cpp:3187-3205``).  Here: an append buffer of python rows
  and/or columnar batches that ``complete()`` consolidates into
  :class:`~..core.frame.KVFrame` frames.  Frames beyond the ``maxpage``
  HBM budget live as host numpy; with ``outofcore=1`` they move to ``.npz``
  spill files (same naming scheme), loaded back on demand — the
  ``request_page``/``write_page`` protocol (``src/keyvalue.cpp:277-308,
  688-756``) becomes :meth:`KeyValue.frames` iteration.
* ``KeyMultiValue`` (``src/keymultivalue.{h,cpp}``) — grouped frames.

``add()`` accepts scalars (host path, like kv->add per pair) and
``add_batch()`` accepts whole columns (the vectorised path every kernel op
uses).  ``complete()`` finalises and computes the global pair count, the
analogue of the Allreduce in ``KeyValue::complete`` (src/keyvalue.cpp:216-255).
"""

from __future__ import annotations

import os
import threading
from typing import Iterator, List, Optional, Sequence

import numpy as np

from .column import BytesColumn, Column, DenseColumn, as_column, concat
from .frame import KMVFrame, KVFrame
from .runtime import Counters, Error, Settings

_INSTANCE_COUNTER = [0]
_INSTANCE_LOCK = threading.Lock()


def _next_file_id() -> int:
    # atomic: concurrent -partition worlds (oink/universe.py threads)
    # must never share a spill-file id
    with _INSTANCE_LOCK:
        _INSTANCE_COUNTER[0] += 1
        return _INSTANCE_COUNTER[0]


class _Spilled:
    """A frame parked in an .npz spill file (reference write_page/read_page,
    src/keyvalue.cpp:688-756; naming src/mapreduce.cpp:3187-3205)."""

    __slots__ = ("path", "n", "bytes_")

    def __init__(self, path: str, n: int, bytes_: int):
        self.path = path
        self.n = n
        self.bytes_ = bytes_

    def load(self, counters: Counters) -> KVFrame:
        with np.load(self.path, allow_pickle=True) as z:
            key = _col_from_npz(z, "k")
            value = _col_from_npz(z, "v")
        counters.add(rsize=self.bytes_)
        return KVFrame(key, value)


def _col_to_npz(col: Column, prefix: str, out: dict):
    """Spill one column into npz payload entries.  numpy ≥ 2 refuses to
    save object arrays, so byte strings flatten to buffer+offsets and
    arbitrary objects to one pickle blob (the reference's pages are raw
    bytes on disk too)."""
    from .column import ObjectColumn
    if isinstance(col, ObjectColumn):
        import pickle
        blob = pickle.dumps(col.data.tolist(), protocol=4)
        out[prefix + "_pobj"] = np.frombuffer(blob, np.uint8)
    elif isinstance(col, BytesColumn):
        rows = [bytes(b) for b in col.data]
        out[prefix + "_obj"] = np.frombuffer(b"".join(rows), np.uint8)
        out[prefix + "_obj_off"] = np.concatenate(
            [[0], np.cumsum([len(b) for b in rows])]).astype(np.int64)
    else:
        out[prefix + "_arr"] = np.asarray(col.data)


def _write_spill(settings: Settings, counters: Counters, name: str,
                 fileid: int, seq: int, payload: dict, nbytes: int) -> str:
    """Shared spill writer: fpath dir + mrtpu.<name>.<id>.<seq>.npz naming
    + write-counter accounting (reference file naming
    src/mapreduce.cpp:3187-3205) — one implementation for KV and KMV."""
    os.makedirs(settings.fpath, exist_ok=True)
    path = os.path.join(settings.fpath,
                        f"mrtpu.{name}.{fileid}.{seq}.npz")
    np.savez(path, **payload)
    counters.add(wsize=nbytes)
    return path


def _spill_budget(settings: Settings) -> int:
    return settings.maxpage * settings.memsize * (1 << 20)


def _col_from_npz(z, prefix: str) -> Column:
    if prefix + "_pobj" in z:
        import pickle
        from .column import ObjectColumn
        return ObjectColumn(pickle.loads(z[prefix + "_pobj"].tobytes()))
    if prefix + "_obj" in z:
        buf = z[prefix + "_obj"].tobytes()
        off = z[prefix + "_obj_off"]
        return BytesColumn([buf[off[i]:off[i + 1]]
                            for i in range(len(off) - 1)])
    return DenseColumn(z[prefix + "_arr"])


class KeyValue:
    """Append-only KV dataset (one shard's worth on the serial backend; the
    mesh backend stores per-shard device arrays through the same interface)."""

    def __init__(self, settings: Settings, error: Error, counters: Counters,
                 name: str = "kv"):
        self.settings = settings
        self.error = error
        self.counters = counters
        self.name = name
        self.fileid = _next_file_id()
        self._buf_k: list = []           # scalar append buffer
        self._buf_v: list = []
        self._batches: List[KVFrame] = []  # columnar append buffer
        self._frames: List[object] = []    # KVFrame | _Spilled
        self.nkv = 0
        self.complete_done = False

    # -- add protocol ------------------------------------------------------

    def add(self, key, value):
        """Add one pair (reference kv->add(key,keybytes,value,valuebytes),
        src/keyvalue.cpp:343-392)."""
        self._buf_k.append(key)
        self._buf_v.append(value)
        if len(self._buf_k) >= 1 << 20:
            self._flush_scalars()

    def add_batch(self, keys, values):
        """Add a batch of pairs as columns/arrays (the vectorised fast path —
        replaces the reference's chunked bulk add, src/keyvalue.cpp:526-605)."""
        self._flush_scalars()  # preserve add order when interleaved with add()
        frame = KVFrame(as_column(keys), as_column(values))
        if len(frame):
            self._batches.append(frame)

    def add_kv(self, other: "KeyValue"):
        """Append another KV's pairs (reference MapReduce::add,
        src/mapreduce.cpp:348-374).  Frame OBJECTS are shared, not
        copied — mark them so the exchange's buffer donation (exec/,
        MRTPU_DONATE) never deletes device arrays another dataset still
        reads (an aggregate on one MR must not corrupt its copy())."""
        for fr in other.frames():
            if not isinstance(fr, KVFrame):   # ShardedKV: device arrays
                fr._shared = True             # now alias across datasets
            self._batches.append(fr)

    def add_frame(self, frame):
        """Append a pre-built frame — a KVFrame, or a parallel.ShardedKV
        coming out of a vectorised sharded reduce."""
        self._flush_scalars()
        self._batches.append(frame)

    def _flush_scalars(self):
        if not self._buf_k:
            return
        k = _coerce_rows(self._buf_k)
        v = _coerce_rows(self._buf_v)
        self._batches.append(KVFrame(k, v))
        self._buf_k, self._buf_v = [], []

    # -- completion --------------------------------------------------------

    def complete(self):
        """Finalise: consolidate buffers into budget-sized frames
        (reference KeyValue::complete, src/keyvalue.cpp:216-255)."""
        self._flush_scalars()
        plain = [b for b in self._batches if isinstance(b, KVFrame)]
        opaque = [b for b in self._batches if not isinstance(b, KVFrame)]
        self._batches = []
        if plain:
            merged = _merge_frames(plain)
            for fr in _split_to_budget(merged, self.settings):
                self._push_frame(fr)
        for f in opaque:  # sharded frames bypass the page splitter
            self._frames.append(f)
            self.counters.mem(f.nbytes())
        self.nkv = sum(self._frame_n(f) for f in self._frames)
        self.complete_done = True
        return self.nkv

    def append(self):
        """Re-open a completed KV for more adds (reference KeyValue::append,
        src/keyvalue.cpp:185-209)."""
        self.complete_done = False

    def _frame_n(self, f) -> int:
        return f.n if isinstance(f, _Spilled) else len(f)  # len covers ShardedKV too

    def _push_frame(self, fr: KVFrame):
        budget = _spill_budget(self.settings)
        if (self.settings.outofcore == 1 and budget
                and self._resident_bytes() + fr.nbytes() > budget):
            self._spill(fr)
        else:
            self._frames.append(fr)
            self.counters.mem(fr.nbytes())

    def _resident_bytes(self) -> int:
        return sum(f.nbytes() for f in self._frames if isinstance(f, KVFrame))

    def _spill(self, fr: KVFrame):
        payload: dict = {}
        _col_to_npz(fr.key.to_host(), "k", payload)
        _col_to_npz(fr.value.to_host(), "v", payload)
        nb = fr.nbytes()
        path = _write_spill(self.settings, self.counters, self.name,
                            self.fileid, len(self._frames), payload, nb)
        self._frames.append(_Spilled(path, len(fr), nb))

    # -- read protocol -----------------------------------------------------

    @property
    def nframes(self) -> int:
        return len(self._frames)

    def is_host_dataset(self) -> bool:
        """True when every frame is a host KVFrame or a spill file (the
        external sort/group machinery operates on these)."""
        return all(isinstance(f, (KVFrame, _Spilled)) for f in self._frames)

    def frames(self) -> Iterator[KVFrame]:
        """Stream frames (reference request_info/request_page cursor,
        src/keyvalue.cpp:277-308)."""
        for f in self._frames:
            yield f.load(self.counters) if isinstance(f, _Spilled) else f

    def one_frame(self, moved: Optional[dict] = None):
        """Whole dataset as a single frame (in-core fast path).  Returns the
        ShardedKV directly when that's the sole frame.  A dataset that
        lives on a mesh is assembled THERE: several sharded frames
        concatenate per-shard on device (the add() path of iterative mesh
        commands), and dense host frames added beside them go UP — only
        their rows are placed on the mesh, the short shards first, and
        appended; a sharded frame never comes down to be concatenated.
        Only a host frame of interned byte/object rows (or of another
        row type) still compacts the dataset to the host: its ids are
        not in the sharded frames' space.  ``moved``, when given, is
        told the bytes that crossed: ``to_device_bytes``,
        ``to_host_bytes``."""
        frames = list(self.frames())
        moved = {} if moved is None else moved
        moved.update(to_device_bytes=0, to_host_bytes=0)
        if not frames:
            from .frame import empty_kv
            return empty_kv()
        if len(frames) == 1:
            return frames[0]
        parts = _mesh_parts(frames)
        if parts is None:
            moved["to_host_bytes"] = sum(
                f.nbytes() for f in frames if not isinstance(f, KVFrame))
            return _merge_frames([f if isinstance(f, KVFrame)
                                  else f.to_host() for f in frames])
        import functools as _ft
        from ..parallel.devkernels import concat_sharded
        sharded, host = parts
        out = _ft.reduce(concat_sharded, sharded)
        if host:
            from ..parallel.sharded import (fill_counts,
                                            shard_frame_with_counts)
            new = _merge_frames(host)
            moved["to_device_bytes"] = new.nbytes()
            out = concat_sharded(out, shard_frame_with_counts(
                new, out.mesh, fill_counts(out.counts, len(new))))
        return out

    def shard_rows(self, mesh) -> np.ndarray:
        """Valid rows each shard of ``mesh`` holds in this dataset's
        sharded frames: what a producer that adds rows on the device
        balances its own against (``parallel.sharded.fill_counts``)."""
        from ..parallel.mesh import mesh_axis_size
        from ..parallel.sharded import ShardedKV
        have = np.zeros(mesh_axis_size(mesh), np.int64)
        for f in self._frames:
            if isinstance(f, ShardedKV) and f.mesh == mesh:
                have += f.counts
        return have

    def nbytes(self) -> int:
        return sum(f.bytes_ if isinstance(f, _Spilled) else f.nbytes()
                   for f in self._frames)

    def free(self):
        for f in self._frames:
            if isinstance(f, _Spilled):
                try:
                    os.remove(f.path)
                except OSError:
                    pass
            else:
                self.counters.mem(-f.nbytes())
        self._frames = []
        self._batches = []
        self.nkv = 0


class _SpilledKMV:
    """A KMV frame parked in an .npz spill file (the grouped counterpart
    of _Spilled; the reference's extended-KMV pages also round-trip
    through fpath files, src/keymultivalue.cpp:1219-1350)."""

    __slots__ = ("path", "n", "nvalues_total", "bytes_")

    def __init__(self, path: str, n: int, nvalues_total: int, bytes_: int):
        self.path = path
        self.n = n
        self.nvalues_total = nvalues_total
        self.bytes_ = bytes_

    def load(self, counters: Counters) -> KMVFrame:
        with np.load(self.path, allow_pickle=True) as z:
            key = _col_from_npz(z, "k")
            values = _col_from_npz(z, "v")
            nvalues = z["nv"]
            offsets = z["off"]
        counters.add(rsize=self.bytes_)
        return KMVFrame(key, nvalues, offsets, values)


class KeyMultiValue:
    """Grouped dataset: list of KMVFrames (one per source frame batch),
    spilling to fpath .npz under ``outofcore=1`` like KeyValue."""

    def __init__(self, settings: Settings, error: Error, counters: Counters):
        self.settings = settings
        self.error = error
        self.counters = counters
        self.fileid = _next_file_id()
        self._frames: List[object] = []     # KMVFrame | _SpilledKMV | sharded
        self.nkmv = 0
        self.nvalues = 0

    def push(self, fr):
        budget = _spill_budget(self.settings)
        if (self.settings.outofcore == 1 and budget
                and isinstance(fr, KMVFrame)
                and self._resident_bytes() + fr.nbytes() > budget):
            # split on group boundaries first so each spilled piece fits
            # the budget — reduce()/scan then stream piece-at-a-time in
            # bounded memory instead of reloading one giant frame (the
            # point of the reference's paged KMV, doc/Technical.txt:200-214)
            for piece in _split_kmv_to_budget(fr, self.settings):
                self._spill(piece)
        else:
            self._frames.append(fr)
            self.counters.mem(fr.nbytes())

    def _resident_bytes(self) -> int:
        return sum(f.nbytes() for f in self._frames
                   if isinstance(f, KMVFrame))

    def _spill(self, fr: KMVFrame):
        payload: dict = {"nv": np.asarray(fr.nvalues),
                         "off": np.asarray(fr.offsets)}
        _col_to_npz(fr.key.to_host(), "k", payload)
        _col_to_npz(fr.values.to_host(), "v", payload)
        nb = fr.nbytes()
        path = _write_spill(self.settings, self.counters, "kmv",
                            self.fileid, len(self._frames), payload, nb)
        self._frames.append(_SpilledKMV(path, len(fr), fr.nvalues_total,
                                        nb))

    def complete(self):
        self.nkmv = sum(f.n if isinstance(f, _SpilledKMV) else len(f)
                        for f in self._frames)
        self.nvalues = sum(f.nvalues_total for f in self._frames)
        return self.nkmv

    @property
    def nframes(self) -> int:
        return len(self._frames)

    def frames(self) -> Iterator[KMVFrame]:
        for f in self._frames:
            yield f.load(self.counters) if isinstance(f, _SpilledKMV) else f

    def one_frame(self) -> KMVFrame:
        frames = list(self.frames())
        if len(frames) == 1:
            return frames[0]
        if not frames:
            return KMVFrame(DenseColumn(np.zeros(0, np.uint64)),
                            np.zeros(0, np.int64), np.zeros(1, np.int64),
                            DenseColumn(np.zeros(0, np.uint64)))
        frames = [f if isinstance(f, KMVFrame) else f.to_host()
                  for f in frames]
        key = concat([f.key for f in frames])
        values = concat([f.values for f in frames])
        nvalues = np.concatenate([f.nvalues for f in frames])
        offsets = np.concatenate([[0], np.cumsum(nvalues)]).astype(np.int64)
        return KMVFrame(key, nvalues, offsets, values)

    def nbytes(self) -> int:
        return sum(f.bytes_ if isinstance(f, _SpilledKMV) else f.nbytes()
                   for f in self._frames)

    def free(self):
        for f in self._frames:
            if isinstance(f, _SpilledKMV):
                try:
                    os.remove(f.path)
                except OSError:
                    pass
            else:
                self.counters.mem(-f.nbytes())
        self._frames = []
        self.nkmv = 0
        self.nvalues = 0


# ---------------------------------------------------------------------------

def rows_to_array(rows: list) -> np.ndarray:
    """np.asarray for scalar/tuple rows that REFUSES numpy's silent
    int→float64 fallback: a python-int list straddling 2^63 (u64 hash ids
    next to small counts) coerces to lossy float64 — here it becomes exact
    uint64 instead."""
    arr = np.asarray(rows)

    def _u64able(e):
        return isinstance(e, (int, np.integer)) and 0 <= int(e) < (1 << 64)

    if (arr.dtype == np.float64
            and all(_u64able(r) or
                    (isinstance(r, tuple) and all(_u64able(e) for e in r))
                    for r in rows)):
        arr = np.asarray(rows, dtype=np.uint64)
    return arr


def _coerce_rows(rows: list) -> Column:
    """Turn a python append buffer into a column: bytes→BytesColumn,
    numbers/uniform tuples→DenseColumn, anything else (dicts, mixed
    types, ragged tuples…)→ObjectColumn — the pickle tier matching the
    reference Python wrapper's arbitrary-object KVs
    (python/mrmpi.py:17-45)."""
    from .column import ObjectColumn
    first = rows[0]
    if isinstance(first, (bytes, str, bytearray)):
        if all(isinstance(r, (bytes, str, bytearray, memoryview))
               for r in rows):
            return BytesColumn([r if isinstance(r, bytes) else
                                (r.encode() if isinstance(r, str)
                                 else bytes(r)) for r in rows])
        # mixed with non-string rows (bytes(int) would silently build a
        # NUL run): arbitrary objects, pickle tier
        return ObjectColumn(rows)
    if first is None:
        return DenseColumn(np.zeros(len(rows), dtype=np.uint8))
    try:
        arr = rows_to_array(rows)
    except (ValueError, OverflowError):
        return ObjectColumn(rows)
    if arr.dtype == object or arr.dtype.kind in "USV":
        # numpy stringifies mixed tuples like ('a', 1) — those are
        # arbitrary objects, not data; keep the originals via pickle
        return ObjectColumn(rows)
    return DenseColumn(arr)


def _mesh_parts(frames):
    """``(sharded frames, host frames)`` of a dataset that can be
    assembled on the mesh its sharded frames live on: they share one
    mesh, and every other frame is a dense host KVFrame of the same
    plain row type (no intern tables on either side, which
    ``devkernels._merge_decode`` would refuse to mix).  ``None`` when it
    has to go through the host."""
    from ..parallel.sharded import ShardedKV
    sharded = [f for f in frames if isinstance(f, ShardedKV)]
    host = [f for f in frames if not isinstance(f, ShardedKV)]
    if not sharded or len({f.mesh for f in sharded}) != 1:
        return None
    if not host:
        return sharded, host
    first = sharded[0]

    def same_rows(col, arr):
        data = col.data
        return data.dtype == arr.dtype and data.shape[1:] == arr.shape[1:]

    plain = all(f.key_decode is None and f.value_decode is None
                for f in sharded)
    if plain and all(isinstance(f, KVFrame) and f.is_dense()
                     and same_rows(f.key, first.key)
                     and same_rows(f.value, first.value) for f in host):
        return sharded, host
    return None


def _merge_frames(frames: Sequence[KVFrame]) -> KVFrame:
    if len(frames) == 1:
        return frames[0]
    return KVFrame(concat([f.key for f in frames]),
                   concat([f.value for f in frames]))


def _split_kmv_to_budget(fr: KMVFrame, settings: Settings) -> List[KMVFrame]:
    """Split a KMV frame into ≤ memsize pieces on group boundaries.  A
    single group larger than the budget stays one piece — that is the
    multi-block case BlockedMultivalue streams (reference "extended" KMV,
    src/keymultivalue.cpp:974-999)."""
    limit = settings.memsize * (1 << 20)
    if len(fr) == 0 or fr.nbytes() <= limit:
        return [fr]
    row_bytes = fr.nbytes() / max(1, fr.nvalues_total)
    rows_per = max(1, int(limit / row_bytes))
    offsets = np.asarray(fr.offsets)
    pieces: List[KMVFrame] = []
    g = 0
    while g < len(fr):
        start_row = int(offsets[g])
        # furthest group whose end stays within rows_per of start_row
        h = int(np.searchsorted(offsets, start_row + rows_per,
                                side="right")) - 1
        h = max(h, g + 1)          # always advance ≥ 1 group
        h = min(h, len(fr))
        sub_off = (offsets[g:h + 1] - start_row).astype(np.int64)
        pieces.append(KMVFrame(
            fr.key.slice(g, h), np.asarray(fr.nvalues[g:h]), sub_off,
            fr.values.slice(start_row, int(offsets[h]))))
        g = h
    return pieces


def _split_to_budget(fr: KVFrame, settings: Settings) -> List[KVFrame]:
    """Split a frame to the memsize budget (a reference page boundary)."""
    limit = settings.memsize * (1 << 20)
    n = len(fr)
    if n == 0 or fr.nbytes() <= limit:
        return [fr]
    rows_per = max(1, int(n * limit / fr.nbytes()))
    return [fr.slice(s, min(s + rows_per, n)) for s in range(0, n, rows_per)]
