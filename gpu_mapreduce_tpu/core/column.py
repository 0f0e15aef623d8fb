"""Column types backing KV/KMV datasets.

The reference packs every key/value into byte-aligned pages
(``src/keyvalue.cpp:343-392``: ``[keybytes][valuebytes][key pad][value pad]``).
A TPU wants fixed-width lanes, so we go columnar instead (SURVEY.md §7):

* :class:`DenseColumn` — fixed-width numeric data, shape ``[n]`` or
  ``[n, w]``; lives as a ``numpy`` or ``jax`` array and moves between the two
  lazily.  This is the fast path: every oink graph workload uses fixed-width
  struct keys/values (``oink/typedefs.h:22-40`` VERTEX=uint64, EDGE={vi,vj},
  WEIGHT=double).
* :class:`BytesColumn` — arbitrary per-row byte strings (object ndarray),
  host-only; the analogue of the reference's variable-length byte path.  It
  can be *interned* to a u64 DenseColumn plus a host-side id→bytes dictionary
  so shuffles/group-bys run on device (SURVEY.md §7 "hard parts").
* fixed-width byte rows need neither: ``[n, w]`` bytes are a DenseColumn of
  u32 words (:func:`fixed_key_words` when the rows must ORDER on the device
  as their bytes do, :func:`fixed_value_words` when they only travel), and
  come back as the same bytes with no table.

Both support the minimal op set the runtime needs: ``take`` (gather by row
index), ``concat``, ``slice``, and conversion to/from host.
"""

from __future__ import annotations

import collections.abc
import contextlib
import functools
import re
import threading
from typing import Dict, List, Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp

from ..ops.hash import hash_bytes64_batch

ArrayLike = Union[np.ndarray, jax.Array]


def _is_device(arr) -> bool:
    return isinstance(arr, jax.Array)


class Column:
    """Abstract base: a sequence of n fixed-arity rows."""

    def __len__(self) -> int:
        raise NotImplementedError

    def take(self, idx) -> "Column":
        raise NotImplementedError

    def slice(self, start: int, stop: int) -> "Column":
        raise NotImplementedError

    def to_host(self) -> "Column":
        raise NotImplementedError

    def nbytes(self) -> int:
        raise NotImplementedError

    def tolist(self) -> list:
        """Rows as python scalars/tuples/bytes (for host callbacks/printing)."""
        raise NotImplementedError


class DenseColumn(Column):
    __slots__ = ("data",)

    def __init__(self, data: ArrayLike):
        if not (_is_device(data) or isinstance(data, np.ndarray)):
            data = np.asarray(data)
        if data.ndim == 0:
            data = data.reshape(1)
        assert data.ndim in (1, 2), f"column rank must be 1 or 2, got {data.ndim}"
        self.data = data

    def __len__(self) -> int:
        return int(self.data.shape[0])

    @property
    def width(self) -> int:
        return 1 if self.data.ndim == 1 else int(self.data.shape[1])

    @property
    def dtype(self):
        return self.data.dtype

    def device(self) -> "DenseColumn":
        return self if _is_device(self.data) else DenseColumn(jnp.asarray(self.data))

    def to_host(self) -> "DenseColumn":
        return DenseColumn(np.asarray(self.data)) if _is_device(self.data) else self

    def take(self, idx) -> "DenseColumn":
        xp = jnp if _is_device(self.data) or _is_device(idx) else np
        return DenseColumn(xp.asarray(self.data)[xp.asarray(idx)])

    def slice(self, start: int, stop: int) -> "DenseColumn":
        return DenseColumn(self.data[start:stop])

    def nbytes(self) -> int:
        return int(self.data.size) * self.data.dtype.itemsize

    def tolist(self) -> list:
        host = np.asarray(self.data)
        if host.ndim == 1:
            return host.tolist()
        return [tuple(row) for row in host.tolist()]

    def __repr__(self):
        where = "dev" if _is_device(self.data) else "host"
        return f"DenseColumn<{self.data.dtype}{list(self.data.shape)}@{where}>"


def _padded_rows(raw: np.ndarray, out=None) -> np.ndarray:
    """``[n, w]`` bytes as ``[n, 4 * ceil(w / 4)]``, zeros after each row
    (into ``out``'s bytes when given: ``[n, ceil(w / 4)]`` u32)."""
    n, w = raw.shape
    words = -(-w // 4)
    if out is None:
        out = np.empty((n, words), np.uint32)
    rows = out.view(np.uint8).reshape(n, 4 * words)
    rows[:, :w] = raw
    rows[:, w:] = 0
    return rows


def fixed_key_words(raw: np.ndarray, out=None) -> np.ndarray:
    """Fixed-width byte keys ``[n, w]`` (u8) as dense words ``[n,
    ceil(w/4)]`` (u32) whose unsigned lexicographic order, the first word
    the most significant, IS the ``memcmp`` order of the bytes: each word
    is four key bytes big-endian, the last one zero-filled below (every
    row has the same width, so the fill decides nothing).  No table:
    :func:`fixed_key_bytes` gives the bytes back.  ``out``: the ``[n,
    words]`` u32 array to fill (a slice of a shard's block)."""
    rows = _padded_rows(raw, out)
    words = rows.view(np.uint32).reshape(rows.shape[0], rows.shape[1] // 4)
    if np.little_endian:
        words.byteswap(inplace=True)
    return words


def fixed_key_bytes(words: np.ndarray, width: int) -> np.ndarray:
    """The ``[n, width]`` key bytes of :func:`fixed_key_words`' words."""
    be = np.ascontiguousarray(words, np.uint32).astype(">u4")
    return be.view(np.uint8).reshape(len(be), 4 * be.shape[1])[:, :width]


def fixed_value_words(raw: np.ndarray, out=None) -> np.ndarray:
    """Fixed-width byte values ``[n, w]`` as ``[n, ceil(w/4)]`` u32, the
    bytes in place (host byte order) and zeros after them: a payload
    that travels with its key and is never compared."""
    rows = _padded_rows(raw, out)
    return rows.view(np.uint32).reshape(rows.shape[0], rows.shape[1] // 4)


def fixed_value_bytes(words: np.ndarray, width: int) -> np.ndarray:
    """The ``[n, width]`` value bytes of :func:`fixed_value_words`' words."""
    rows = np.ascontiguousarray(words, np.uint32)
    return rows.view(np.uint8).reshape(len(rows), 4 * rows.shape[1])[:, :width]


def fixed_record_words(key: ArrayLike, value: ArrayLike,
                       key_bytes: int) -> ArrayLike:
    """The records ``[n, (key_bytes + value bytes) / 4]`` (u32) whose
    bytes, on a little-endian host, are the ``key_bytes`` bytes of
    :func:`fixed_key_words`' ``key`` and then the value bytes of
    :func:`fixed_value_words`' ``value``: what ``fixed_key_bytes`` and
    ``fixed_value_bytes`` give side by side, by word arithmetic alone
    (numpy arrays, or jax arrays inside a program).  The key words are
    byte-swapped; the whole ones are the record's first words.  With
    ``s = key_bytes % 4`` bytes of key left over, the word in which the key
    ends takes its low ``8 s`` bits from the key and the rest from the
    first value word, and word ``j`` after it is ``v[j-1] >> (32 - 8 s) |
    v[j] << 8 s``; at ``s = 0`` the value words follow as they are.  The
    record is a whole number of words (``(key_bytes + value bytes) % 4 ==
    0``): then the last value word's zero fill falls off the end."""
    xp = jnp if _is_device(key) or _is_device(value) else np
    whole, s = divmod(key_bytes, 4)
    le = ((key >> 24) | ((key >> 8) & 0xFF00) | ((key << 8) & 0xFF0000)
          | (key << 24))
    if s == 0:
        return xp.concatenate([le, value], axis=1)
    low = 8 * s
    carried = xp.concatenate([le[:, whole:] & ((1 << low) - 1),
                              value[:, :-1] >> (32 - low)], axis=1)
    return xp.concatenate([le[:, :whole], carried | (value << low)], axis=1)


class BytesColumn(Column):
    """Host column of arbitrary byte strings (reference's byte-packed path).

    The rows are held in one of two ways.  As an object ndarray of
    ``bytes`` (``data``): what ``add`` and the per-pair callbacks build.
    Or as RANGES of one byte buffer (:meth:`from_ranges`: ``buf`` u8[m],
    ``starts`` and ``lens`` i64[n]): what a tokenizer hands over for a
    whole file, with no Python object per row.  ``data`` builds the
    objects on first use, so every consumer that wants ``bytes`` still
    gets them; ``take``/``slice``/``concat`` and the interns work on the
    ranges themselves."""

    __slots__ = ("_data", "ranges")

    def __init__(self, data: Sequence[bytes]):
        self.ranges = None
        if isinstance(data, np.ndarray) and data.dtype == object:
            self._data = data
        else:
            arr = np.empty(len(data), dtype=object)
            for i, x in enumerate(data):
                arr[i] = x if isinstance(x, bytes) else bytes(x)
            self._data = arr

    @classmethod
    def from_ranges(cls, buf, starts, lens) -> "BytesColumn":
        """Row i is ``buf[starts[i]:starts[i] + lens[i]]``; ``buf`` is
        ``bytes`` or a uint8 array and is not copied."""
        self = cls.__new__(cls)
        self._data = None
        if not isinstance(buf, np.ndarray):
            buf = np.frombuffer(buf, np.uint8)
        self.ranges = (buf, np.ascontiguousarray(starts, np.int64),
                       np.ascontiguousarray(lens, np.int64))
        return self

    @property
    def data(self) -> np.ndarray:
        if self._data is None:
            self._data = np.empty(len(self), dtype=object)
            for i, row in enumerate(_range_rows(*self.ranges)):
                self._data[i] = row
        return self._data

    def __len__(self) -> int:
        if self.ranges is not None:
            return int(self.ranges[1].shape[0])
        return int(self._data.shape[0])

    def to_host(self) -> "BytesColumn":
        return self

    def take(self, idx) -> "BytesColumn":
        idx = np.asarray(idx)
        if self.ranges is not None:
            buf, starts, lens = self.ranges
            return BytesColumn.from_ranges(buf, starts[idx], lens[idx])
        return BytesColumn(self._data[idx])

    def slice(self, start: int, stop: int) -> "BytesColumn":
        if self.ranges is not None:
            buf, starts, lens = self.ranges
            return BytesColumn.from_ranges(buf, starts[start:stop],
                                           lens[start:stop])
        return BytesColumn(self._data[start:stop])

    def nbytes(self) -> int:
        if self.ranges is not None:
            return int(self.ranges[2].sum())
        return int(sum(len(x) for x in self._data))

    def tolist(self) -> list:
        return self.data.tolist()

    def _packed(self) -> tuple:
        """``(buf, starts, lens)``: the ranges the column has, or its row
        objects packed end to end, once."""
        if self.ranges is not None:
            return self.ranges
        return _pack_rows([bytes(s) for s in self._data])

    def _intern(self):
        """(ids, unique ids, the ranges of their first rows): no row
        becomes an object here, whichever way the column holds them."""
        buf, starts, lens = self._packed()
        ids, uniq, first = _intern_ranges(buf, starts, lens)
        return ids, uniq, (buf, starts[first], lens[first])

    def intern(self) -> tuple:
        """Map byte strings to u64 ids for device-side shuffling/grouping.

        Returns ``(DenseColumn[uint64], {id: bytes})``.  All-vectorised:
        native batch hash of every row, numeric unique for the table,
        and — only when duplicate ids exist — an independent second hash
        family detects collisions (one id, two alts), the same standard
        the device tier uses (apps/invertedindex).  The former per-row
        Python dict loop was the aggregate hot spot on heavy-repetition
        columns (wordfreq tokens)."""
        ids, uniq, first = self._intern()
        return DenseColumn(ids), InternTable(
            zip(uniq.tolist(), _range_rows(*first)), kind="bytes")

    def intern_sharded(self, tables: "ShardTables",
                       turn=None) -> "DenseColumn":
        """Intern into dest-sharded decode tables — no controller-global
        dict ever builds (VERDICT r4 #5); cross-batch collisions surface
        in ShardTables.absorb_parts.  The distinct words go from the
        column's buffer into the tables as bytes of arrays: hash, dedupe,
        split by destination and gather touch no table and release the
        GIL, so shards run them side by side; only the absorb is entered
        through ``turn`` (a context manager: parallel/ingest's shard
        order) and the tables' lock."""
        from ..obs import get_tracer
        ids, uniq, first = self._intern()
        parts = tables.split_ranges(uniq, *first)
        with turn or contextlib.nullcontext():
            added, checked = tables.absorb_parts(parts)
        tracer = get_tracer()
        if tracer.enabled:      # onto ingest.intern / aggregate.intern
            tracer.annotate(unique=len(uniq), added=added, checked=checked,
                            table_bytes=int(first[2].sum()))
        return DenseColumn(ids)

    def __repr__(self):
        how = "ranges" if self.ranges is not None else "objects"
        return f"BytesColumn<n={len(self)},{how}>"


def _offsets(lens: np.ndarray) -> np.ndarray:
    """i64[n + 1]: where ranges of these lengths start when they lie end
    to end, and after the last, their total."""
    offs = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(lens, out=offs[1:])
    return offs


def _pack_rows(rows) -> tuple:
    """Byte strings end to end as ranges: ``(buf, starts, lens)``."""
    lens = np.fromiter(map(len, rows), np.int64, len(rows))
    return (np.frombuffer(b"".join(rows), np.uint8), _offsets(lens)[:-1],
            lens)


def _gather_ranges(buf: np.ndarray, starts: np.ndarray, lens: np.ndarray):
    """The ranges' bytes end to end: ``(blob u8[m], offsets i64[n+1])``,
    range i at ``blob[offsets[i]:offsets[i + 1]]``."""
    from .. import native
    offs = _offsets(lens)
    total = int(offs[-1])
    if native.available():
        return native.gather_ranges(buf, starts, lens, total), offs
    at = np.repeat(starts - offs[:-1], lens) + np.arange(total)
    return buf[at], offs


FORMAT_BLOCK = 65536    # rows a block: format_rows' callers', its Python form's


@functools.lru_cache(maxsize=None)
def row_fields(template: str) -> tuple:
    """A row template's fields: ``%d``, ``%g`` or ``%.Ng``, single
    spaces between (``"%d %.8g"``).  One entry a field: -1 for ``%d``,
    else the ``g`` precision (6 for a bare ``%g``)."""
    fields = []
    for f in template.split(" "):
        m = re.fullmatch(r"%(?:(d)|(?:\.(\d{1,2}))?g)", f)
        if m is None:
            raise ValueError(f"row template {template!r}: field {f!r} is "
                             f"not %d, %g or %.Ng")
        fields.append(-1 if m.group(1) else int(m.group(2) or 6))
    return tuple(fields)


def row_columns(template: str, arrays) -> Optional[list]:
    """One contiguous column a field of ``template`` out of ``arrays``
    (each ``[n]``, or ``[n, w]`` for ``w`` fields in a row), or None
    where they are not what the template names: as many columns as
    fields, integers (as u64 / i64) under ``%d``, floats (as f64, a
    float32 widened as ``float()`` widens it) under ``%g``.  What
    :func:`format_rows` takes."""
    spread = [a if a.ndim == 1 else a[:, j] for a in arrays
              for j in range(1 if a.ndim == 1 else a.shape[1])]
    fields = row_fields(template)
    if len(spread) != len(fields):
        return None
    cols = []
    for a, prec in zip(spread, fields):
        kind = a.dtype.kind
        if prec < 0 and kind in "ui":
            dtype = np.uint64 if kind == "u" else np.int64
        elif prec >= 0 and kind == "f" and a.dtype.itemsize <= 8:
            dtype = np.float64
        else:
            return None
        cols.append(np.ascontiguousarray(a, dtype))
    return cols


def format_rows(template: str, cols: list, start: int = 0,
                stop: Optional[int] = None) -> np.ndarray:
    """Rows ``[start, stop)`` of :func:`row_columns`' columns as the u8
    bytes of their text lines, one line a row: byte for byte
    ``"".join((template + "\\n") % row for row in zip(*cols))``, with no
    Python object made for a row.  Natively where the library has the
    formatter (``native.has_format_rows``; the call drops the GIL, so
    blocks run side by side on a thread pool), else ``%`` over a
    template repeated ``FORMAT_BLOCK`` rows at a time."""
    from .. import native
    stop = len(cols[0]) if stop is None else stop
    if native.has_format_rows():
        return native.format_rows(row_fields(template), cols, start, stop)
    nf, line = len(cols), template + "\n"
    out = []
    for s in range(start, stop, FORMAT_BLOCK):
        e = min(s + FORMAT_BLOCK, stop)
        flat = [None] * ((e - s) * nf)
        for f, c in enumerate(cols):
            flat[f::nf] = c[s:e].tolist()
        out.append((line * (e - s) % tuple(flat)).encode("ascii"))
    return np.frombuffer(b"".join(out), np.uint8)


def write_rows(fp, template: str, cols: list, pool=None) -> None:
    """The lines of :func:`row_columns`' columns onto the binary file
    ``fp``: blocks of ``FORMAT_BLOCK`` rows formatted on ``pool`` (in the
    calling thread without one) and written in order, at most 64 blocks
    formatted ahead of the write."""
    n = len(cols[0])
    run = pool.map if pool is not None and n > FORMAT_BLOCK else map
    wave = 64 * FORMAT_BLOCK    # blocks in flight: what memory may hold
    for lo in range(0, n, wave):
        for block in run(lambda s: format_rows(template, cols, s,
                                               min(s + FORMAT_BLOCK, n)),
                         range(lo, min(lo + wave, n), FORMAT_BLOCK)):
            fp.write(block)


def _differ_ranges(a: np.ndarray, astarts: np.ndarray, b: np.ndarray,
                   bstarts: np.ndarray, lens: np.ndarray) -> int:
    """Index of the first pair ``a[astarts[i]:+lens[i]]``,
    ``b[bstarts[i]:+lens[i]]`` that differs in a byte, or -1."""
    from .. import native
    if native.available():
        return native.differ_ranges(a, astarts, b, bstarts, lens)
    offs = _offsets(lens)
    within = np.arange(offs[-1]) - np.repeat(offs[:-1], lens)
    bad = np.flatnonzero(a[np.repeat(astarts, lens) + within]
                         != b[np.repeat(bstarts, lens) + within])
    if not len(bad):
        return -1
    return int(np.searchsorted(offs, bad[0], side="right")) - 1


def _same_words(a, astarts, alens, b, bstarts, blens) -> None:
    """Pairs of words that share an id, pair i at ``a[astarts[i]:
    +alens[i]]`` and ``b[bstarts[i]:+blens[i]]``: every pair compared
    byte for byte, and the first that differs is a 64-bit intern
    collision."""
    short = np.flatnonzero(alens != blens)
    i = (int(short[0]) if len(short)
         else _differ_ranges(a, astarts, b, bstarts, alens))
    if i >= 0:
        raise ValueError("64-bit intern collision: %r vs %r" % (
            a[astarts[i]:astarts[i] + alens[i]].tobytes(),
            b[bstarts[i]:bstarts[i] + blens[i]].tobytes()))


def _range_rows(buf: np.ndarray, starts: np.ndarray, lens: np.ndarray) -> list:
    """The ranges as ``bytes`` objects (one slice each)."""
    blob, offs = _gather_ranges(buf, starts, lens)
    raw, at = blob.tobytes(), offs.tolist()
    return [raw[s:e] for s, e in zip(at, at[1:])]


def _intern_ids(strings, rows, kind: str):
    """Shared vectorised intern core: hash ``strings`` (the per-row
    bytes), build the id→``rows[i]`` table from the first occurrence of
    each unique id, and — when duplicate ids exist — verify them with
    an independent second hash family (same id + different alt = a real
    collision; both families agreeing on distinct inputs is ~2^-128,
    the device tier's standard, apps/invertedindex).  The byte buffer
    packs ONCE for both families.  Returns (ids uint64[n], InternTable);
    the former per-row Python dict loop was the aggregate hot spot."""
    ids, uniq, first = _intern_core(strings)
    table = InternTable(((int(h), rows[int(i)]) for h, i in
                         zip(uniq, first)), kind=kind)
    return ids, table


_ALT_SEEDS = (0x9E3779B9, 0x85EBCA6B)    # the independent check family


def _intern_core(strings):
    """Hash + collision-check core shared by the global and the
    dest-sharded intern: returns (ids uint64[n], unique ids uint64[u],
    first-occurrence row index int64[u])."""
    from .. import native
    if not len(strings):
        z = np.zeros(0, np.uint64)
        return z, z, np.zeros(0, np.int64)
    if native.available():
        return _intern_ranges(*_pack_rows(strings))
    ids = hash_bytes64_batch(strings)
    return _unique_first(ids, lambda: hash_bytes64_batch(strings,
                                                         *_ALT_SEEDS),
                         lambda i: strings[i])


def _intern_ranges(buf: np.ndarray, starts: np.ndarray, lens: np.ndarray):
    """:func:`_intern_core` over ranges of one buffer: with the native
    library no row becomes a Python object; without it the rows are
    sliced out and hashed in Python, to the same ids."""
    from .. import native
    if not native.available():
        return _intern_core(_range_rows(buf, starts, lens))
    ids = native.intern_ranges(buf, starts, lens)
    first = native.unique_ranges(buf, starts, lens, ids)
    if isinstance(first, tuple):    # one id, two byte strings
        raise ValueError("64-bit intern collision between %r and %r" % tuple(
            bytes(buf[starts[i]:starts[i] + lens[i]]) for i in first))
    order = np.argsort(ids[first])  # by id, as _unique_first gives them
    return ids, ids[first][order], first[order]


def _unique_first(ids: np.ndarray, alt, row):
    """(ids, unique ids, first-occurrence rows) with the collision check:
    ``alt()`` hashes the same rows in the independent family, ``row(i)``
    is row i's bytes for the error."""
    # ONE stable sort yields unique ids, first-occurrence rows AND the
    # adjacency layout the collision check needs (np.unique would be a
    # second full sort on this hot path)
    order = np.argsort(ids, kind="stable")
    si = ids[order]
    head = np.ones(len(si), bool)
    head[1:] = si[1:] != si[:-1]
    if not head.all():
        sa = alt()[order]
        # no collision ⇒ every row of an id shares one alt; a collision
        # puts ≥2 alt values in some id run ⇒ some adjacent pair differs
        bad = ~head[1:] & (sa[1:] != sa[:-1])
        if bad.any():
            i = int(np.nonzero(bad)[0][0])
            raise ValueError(
                "64-bit intern collision between %r and %r"
                % (row(int(order[i])), row(int(order[i + 1]))))
    return ids, si[head], order[head]


def dest_of_ids(ids: np.ndarray, P: int) -> np.ndarray:
    """Aggregate destination shard of each u64 id — the HOST twin of the
    device shuffle's ``default_hash(keys) % P`` (lookup3 over the key's
    little-endian bytes, parallel/shuffle.py).  hash_words32 runs the
    same word-path lookup3 on numpy input, so the routing is bit-
    identical to what the exchange will do on device."""
    from ..ops.hash import hash_words32
    words = np.ascontiguousarray(ids.astype("<u8")).view("<u4")
    return (hash_words32(words.reshape(len(ids), 2)).astype(np.int64)
            % P).astype(np.int32)


class InternTable(dict):
    """id→key table from Column.intern(); ``kind`` records whether the
    decoded keys are raw bytes or arbitrary objects so the decode side
    rebuilds the right column type (no first-row guessing)."""

    def __init__(self, *a, kind: str = "bytes", **kw):
        super().__init__(*a, **kw)
        self.kind = kind

    def decode_batch(self, ids) -> list:
        return [self[int(h)] for h in ids]


class _ByteTable(collections.abc.Mapping):
    """One destination's id→bytes entries of a byte-kind
    :class:`ShardTables`, held as arrays: ``ids`` u64[n] in the order
    the entries came, entry i's word at ``blob[offs[i]:offs[i + 1]]``,
    and an index over them (``_sorted``: the ids ascending, ``_pos``:
    each one's entry).  A read-only mapping for every reader: a lookup is
    a binary search, iteration goes in insertion order, and a ``bytes``
    object is made only for a row somebody reads.  It grows by
    :meth:`absorb` alone (``t[h] = word`` is an absorb of one)."""

    __slots__ = ("ids", "offs", "blob", "_sorted", "_pos")
    kind = "bytes"

    def __init__(self):
        self.ids = self._sorted = np.zeros(0, np.uint64)
        self.offs = np.zeros(1, np.int64)
        self.blob = np.zeros(0, np.uint8)
        self._pos = np.zeros(0, np.int64)

    def __len__(self) -> int:
        return len(self.ids)

    def __iter__(self):
        return iter(self.ids.tolist())

    def _entry(self, h) -> int:
        """The entry that holds id ``h``, or -1."""
        if not isinstance(h, (int, np.integer)) or not 0 <= h < 1 << 64:
            return -1           # no u64: never an id of this table
        key = np.uint64(h)
        at = int(self._sorted.searchsorted(key))
        if at < len(self._sorted) and self._sorted[at] == key:
            return int(self._pos[at])
        return -1

    def __contains__(self, h) -> bool:
        return self._entry(h) >= 0

    def __getitem__(self, h) -> bytes:
        i = self._entry(h)
        if i < 0:
            raise KeyError(h)
        return self.blob[self.offs[i]:self.offs[i + 1]].tobytes()

    def __setitem__(self, h, word) -> None:
        word = bytes(word)
        self.absorb(np.array([h], np.uint64),
                    np.array([0, len(word)], np.int64),
                    np.frombuffer(word, np.uint8))

    def values(self):
        return _range_rows(self.blob, self.offs[:-1], np.diff(self.offs))

    def items(self):
        return zip(self.ids.tolist(), self.values())

    def entries_of(self, ids: np.ndarray):
        """(entry of each id, whether the table holds it), vectorised."""
        if not len(self.ids):
            return np.zeros(len(ids), np.int64), np.zeros(len(ids), bool)
        at = np.minimum(self._sorted.searchsorted(ids), len(self.ids) - 1)
        return self._pos[at], self._sorted[at] == ids

    def decode_batch(self, ids) -> list:
        ids = np.asarray(ids, np.uint64)
        entry, found = self.entries_of(ids)
        if not found.all():
            raise KeyError(int(ids[np.flatnonzero(~found)[0]]))
        return _range_rows(self.blob, self.offs[entry],
                           self.offs[entry + 1] - self.offs[entry])

    def absorb(self, ids: np.ndarray, offs: np.ndarray,
               blob: np.ndarray) -> tuple:
        """Take a batch of entries, id i's word at ``blob[offs[i]:
        offs[i + 1]]``.  An id the table holds (or one the batch repeats)
        must come with the same word, compared byte for byte: a
        difference is a 64-bit intern collision.  The others are appended
        in the batch's order.  Returns ``(added, checked)``; the table is
        whole again when this returns."""
        lens = np.diff(offs)
        entry, found = self.entries_of(ids)
        old = np.flatnonzero(found)
        mine = entry[old]
        _same_words(self.blob, self.offs[mine],
                    self.offs[mine + 1] - self.offs[mine],
                    blob, offs[old], lens[old])
        new = np.flatnonzero(~found)            # appended in this order
        fresh, by_id = ids[new], None
        if (fresh[1:] <= fresh[:-1]).any():     # not ascending: index them
            by_id = np.argsort(fresh, kind="stable")
            again = np.flatnonzero(
                fresh[by_id][1:] == fresh[by_id][:-1]) + 1
            if len(again):                      # the batch repeats an id
                a, b = new[by_id[again - 1]], new[by_id[again]]
                _same_words(blob, offs[a], lens[a], blob, offs[b], lens[b])
                new = np.delete(new, by_id[again])
                fresh = ids[new]
                by_id = np.argsort(fresh, kind="stable")
        if len(new):
            if len(new) == len(ids):            # the whole batch, as it is
                words, ends = blob[offs[0]:offs[-1]], offs - offs[0]
            else:
                words, ends = _gather_ranges(blob, offs[new], lens[new])
            entry = len(self.ids) + np.arange(len(new))
            if by_id is not None:
                fresh, entry = fresh[by_id], entry[by_id]
            at = self._sorted.searchsorted(fresh)
            self._sorted = np.insert(self._sorted, at, fresh)
            self._pos = np.insert(self._pos, at, entry)
            self.ids = np.concatenate([self.ids, ids[new]])
            self.offs = np.concatenate([self.offs,
                                        self.offs[-1] + ends[1:]])
            self.blob = np.concatenate([self.blob, words])
        return len(new), len(ids) - len(new)

    def __repr__(self):
        return f"_ByteTable<n={len(self)}, bytes={len(self.blob)}>"


class ShardTables:
    """Dest-sharded id→row decode tables (VERDICT r4 #5).

    The reference shuffles raw key bytes fully distributed
    (``src/mapreduce.cpp:453-473``); our exchange moves u64 ids and keeps
    the bytes host-side.  Instead of ONE controller-global dict, every
    (id, bytes) entry lives in the table of the shard the DEFAULT hash
    routes that id to (``dest_of_ids`` — the same lookup3 % P the device
    exchange applies).  Lookups always re-route by the same id hash, so
    decode is correct on every path.  The LOCALITY guarantee — shard d's
    rows decode from ``tables[d]`` alone after an exchange — holds for
    KEY tables under the default aggregate hash (the per-shard output
    case, and the entries a multi-host mesh would keep host-local).  A
    custom hash_fn or the value-side tables still get the size bound
    (~1/P of the id space per table) but place rows independently of
    their table, so cross-table decode_batch routing is the contract
    there, not per-table locality.

    What a table is made of follows from ``kind``: ``"bytes"`` rows are
    kept as arrays (:class:`_ByteTable`: a shard's distinct words arrive
    as ranges and stay bytes of one blob, checked and filed by numpy and
    native code); ``"object"`` rows are arbitrary Python objects compared
    by their pickles and live in :class:`InternTable` dicts.  Every
    absorb runs under one lock, so threads may share a ``ShardTables``.

    Quacks like the InternTable dict for every existing consumer
    (``__getitem__``/``get``/``decode_batch``/``kind``)."""

    # _rank_cache: sort_interned_sharded memoises its id→rank permutation
    # on the table object (same contract as InternTable's dynamic attr)
    __slots__ = ("tables", "P", "kind", "_probes", "_rank_cache", "_lock")

    def __init__(self, P: int, kind: str = "bytes"):
        self.P = P
        self.kind = kind
        self.tables = [_ByteTable() if kind == "bytes"
                       else InternTable(kind=kind) for _ in range(P)]
        # per-DEST id→pickle side tables for object rows — sharded like
        # the row tables, so no flat controller-global dict rebuilds
        # what the class exists to avoid (r5 review)
        self._probes: Optional[list] = None
        self._rank_cache = None
        self._lock = threading.Lock()

    def __getstate__(self) -> dict:     # a lock does not pickle
        return {k: getattr(self, k) for k in self.__slots__ if k != "_lock"}

    def __setstate__(self, state: dict) -> None:
        for k, v in state.items():
            setattr(self, k, v)
        self._lock = threading.Lock()

    def merge(self, other) -> "ShardTables":
        """Union with another decode table (ShardTables or plain dict) —
        the concat_sharded / MapReduce.add path.  Everything funnels
        through absorb so overlapping ids get the same cross-batch
        collision check as ingest (and object rows compare by pickle,
        never by __eq__ — r5 review).

        CONTRACT: both tables' ids must live in ONE hash domain.  A
        bytes-kind table hashes raw bytes, an object-kind table hashes
        pickles — merging across kinds would give the same logical key
        two distinct ids (they'd never group).  concat_sharded aligns
        domains first (devkernels._align_domains re-interns the
        bytes-kind side through the pickle domain, ADVICE r5); direct
        callers mixing kinds must do the same."""
        kind = ("object" if "object" in (self.kind,
                                         getattr(other, "kind", "bytes"))
                else "bytes")
        out = ShardTables(self.P, kind=kind)
        for src in (self, other):
            if kind == "bytes" and isinstance(src, ShardTables):
                for t in src.tables:    # arrays to arrays, no row objects
                    out.absorb_parts(out.split_ranges(
                        t.ids, t.blob, t.offs[:-1], np.diff(t.offs)))
                continue
            ids = np.fromiter(src.keys(), np.uint64, len(src))
            rows = (src.decode_batch(ids) if hasattr(src, "decode_batch")
                    else [src[int(h)] for h in ids])
            # reuse stored probes (the bytes that were HASHED) instead
            # of re-pickling live rows — cheaper, and immune to objects
            # mutated after ingest (r5 review)
            probes = (src.probes_for(ids)
                      if isinstance(src, ShardTables) else None)
            out.absorb(ids, rows, probes=probes)
        return out

    def probes_for(self, ids: np.ndarray):
        """Stored pickle probes for these ids, or None when this table
        never needed probes (bytes rows compare directly)."""
        if self._probes is None:
            return None
        dests = dest_of_ids(np.asarray(ids, np.uint64), self.P)
        return [self._probes[d][int(h)]
                for h, d in zip(ids.tolist(), dests.tolist())]

    def split_ranges(self, uniq_ids: np.ndarray, buf: np.ndarray,
                     starts: np.ndarray, lens: np.ndarray) -> list:
        """A batch of unique (id, word) pairs, word i at ``buf[starts[i]:
        starts[i] + lens[i]]``, by destination: ``(ids, offsets, blob)``
        a table, the words gathered end to end in the batch's order.
        Reads no table, so a shard's thread runs it while another's
        absorbs."""
        uniq_ids = np.asarray(uniq_ids, np.uint64)
        dests = dest_of_ids(uniq_ids, self.P)
        parts = []
        for d in range(self.P):
            at = np.flatnonzero(dests == d)
            blob, offs = _gather_ranges(buf, starts[at], lens[at])
            parts.append((uniq_ids[at], offs, blob))
        return parts

    def absorb_parts(self, parts: list) -> tuple:
        """File what :meth:`split_ranges` made, table by table: every id
        a table already holds is checked byte for byte against the word
        it came with (a difference is a 64-bit intern collision and
        raises), the others are appended.  Returns ``(added, checked)``."""
        if self.kind != "bytes":
            raise TypeError("byte ranges into an object-kind ShardTables: "
                            "its ids are hashes of pickles")
        added = checked = 0
        with self._lock:
            for table, part in zip(self.tables, parts):
                a, c = table.absorb(*part)
                added, checked = added + a, checked + c
        return added, checked

    def absorb(self, uniq_ids: np.ndarray, rows: list,
               probes: Optional[list] = None) -> tuple:
        """Route unique (id, row) pairs into the per-dest tables; a
        pre-existing id with DIFFERENT bytes is a real u64 intern
        collision (cross-batch — within-batch collisions are caught by
        the intern core's alt-family check).  Byte rows pack once and go
        the array way.  ``probes``: comparison bytes when rows are
        arbitrary objects (object __eq__ is not a reliable identity; the
        pickle is — it IS what was hashed).  Returns ``(added,
        checked)``."""
        if not len(uniq_ids):
            return 0, 0
        if self.kind == "bytes":
            return self.absorb_parts(
                self.split_ranges(uniq_ids, *_pack_rows(rows)))
        if probes is None:
            # object rows always compare by pickle — normalise here so
            # a probe-less batch (e.g. bytes rows promoted into an
            # object-kind table) can never compare a pickle to a row
            import pickle
            probes = [pickle.dumps(r, protocol=4) for r in rows]
        dests = dest_of_ids(np.asarray(uniq_ids, np.uint64), self.P)
        added = 0
        with self._lock:
            if self._probes is None:
                self._probes = [{} for _ in range(self.P)]
            for i, (h, d) in enumerate(zip(uniq_ids.tolist(),
                                           dests.tolist())):
                seen = self._probes[d]
                if h not in seen:
                    self.tables[d][h] = rows[i]
                    seen[h] = probes[i]
                    added += 1
                elif seen[h] != probes[i]:
                    raise ValueError("64-bit intern collision: "
                                     f"{seen[h]!r} vs {probes[i]!r}")
        return added, len(uniq_ids) - added

    def shard(self, d: int):
        return self.tables[d]

    def __getitem__(self, h):
        return self.tables[int(dest_of_ids(np.array([h], np.uint64),
                                           self.P)[0])][h]

    def get(self, h, default=None):
        try:
            return self[h]
        except KeyError:
            return default

    def __contains__(self, h) -> bool:
        # not via get(): an ObjectColumn row may legitimately BE None
        try:
            self[h]
            return True
        except KeyError:
            return False

    def __len__(self) -> int:
        return sum(len(t) for t in self.tables)

    def decode_batch(self, ids) -> list:
        """Vectorised decode: one dest computation for the whole id
        array, then each table decodes its own ids at once (the scalar
        __getitem__ would pay a hash dispatch per row)."""
        ids = np.asarray(ids, np.uint64)
        dests = dest_of_ids(ids, self.P)
        out = [None] * len(ids)
        for d in range(self.P):
            at = np.flatnonzero(dests == d)
            for i, row in zip(at.tolist(),
                              self.tables[d].decode_batch(ids[at])):
                out[i] = row
        return out

    def items(self):
        for t in self.tables:
            yield from t.items()

    def keys(self):
        for t in self.tables:
            yield from t.keys()

    def __repr__(self):
        sizes = [len(t) for t in self.tables]
        return f"ShardTables(P={self.P}, kind={self.kind}, sizes={sizes})"


class ObjectColumn(Column):
    """Host column of ARBITRARY pickled python objects — the tier behind
    the reference's Python wrapper, which cPickles any key/value into the
    byte-packed KV (``python/mrmpi.py:17-45``, ``doc/Technical.txt:375-418``).

    Rows compare/group/sort by their pickled bytes (exactly the
    reference's semantics: the C++ core sees only the pickle), so keys
    need not be hashable or orderable themselves."""

    __slots__ = ("data", "_pickles")

    def __init__(self, data: Sequence):
        if isinstance(data, np.ndarray) and data.dtype == object:
            self.data = data
        else:
            arr = np.empty(len(data), dtype=object)
            for i, x in enumerate(data):
                arr[i] = x
            self.data = arr
        self._pickles: Optional[List[bytes]] = None

    def __len__(self) -> int:
        return int(self.data.shape[0])

    def to_host(self) -> "ObjectColumn":
        return self

    def take(self, idx) -> "ObjectColumn":
        return ObjectColumn(self.data[np.asarray(idx)])

    def slice(self, start: int, stop: int) -> "ObjectColumn":
        return ObjectColumn(self.data[start:stop])

    def pickles(self) -> List[bytes]:
        """Per-row pickles, computed once — nbytes/sort/intern all consume
        these and a budget check per push must not re-pickle the world."""
        if self._pickles is None:
            import pickle
            self._pickles = [pickle.dumps(x, protocol=4) for x in self.data]
        return self._pickles

    def nbytes(self) -> int:
        return int(sum(len(p) for p in self.pickles()))

    def tolist(self) -> list:
        return self.data.tolist()

    def intern(self) -> tuple:
        """Objects → u64 ids via their pickles (see BytesColumn.intern);
        the id→object table stays controller-side."""
        ids, table = _intern_ids(self.pickles(), self.data.tolist(),
                                 "object")
        return DenseColumn(ids), table

    def intern_sharded(self, tables: "ShardTables",
                       turn=None) -> "DenseColumn":
        """See BytesColumn.intern_sharded; rows are the live objects,
        compared across batches by their pickles."""
        rows = self.data.tolist()
        pk = self.pickles()
        ids, uniq, first = _intern_core(pk)
        with turn or contextlib.nullcontext():
            tables.absorb(uniq, [rows[int(i)] for i in first],
                          probes=[pk[int(i)] for i in first])
        return DenseColumn(ids)

    def __repr__(self):
        return f"ObjectColumn<n={len(self)}>"


def concat(cols: List[Column]) -> Column:
    cols = [c for c in cols if len(c) > 0] or cols[:1]
    if len(cols) == 1:
        return cols[0]
    if any(isinstance(c, ObjectColumn) for c in cols):
        # bytes are picklable objects: a mix of Bytes/Object frames (from
        # separate add-buffer flushes) promotes to the object tier
        if not all(isinstance(c, (ObjectColumn, BytesColumn))
                   for c in cols):
            raise TypeError("cannot concat object rows with numeric rows")
        return ObjectColumn(np.concatenate([c.data for c in cols]))
    first = cols[0]
    if isinstance(first, BytesColumn):
        if not all(isinstance(c, BytesColumn) for c in cols):
            raise TypeError("cannot concat byte rows with numeric rows")
        if all(c.ranges is not None for c in cols):
            # ranges stay ranges: the buffers end to end, starts shifted
            bufs = [c.ranges[0] for c in cols]
            base = np.cumsum([0] + [len(b) for b in bufs[:-1]])
            return BytesColumn.from_ranges(
                np.concatenate(bufs),
                np.concatenate([c.ranges[1] + o for c, o in zip(cols, base)]),
                np.concatenate([c.ranges[2] for c in cols]))
        return BytesColumn(np.concatenate([c.data for c in cols]))
    assert all(isinstance(c, DenseColumn) for c in cols)
    if any(_is_device(c.data) for c in cols):
        return DenseColumn(jnp.concatenate([jnp.asarray(c.data) for c in cols], axis=0))
    return DenseColumn(np.concatenate([c.data for c in cols], axis=0))


def as_column(x) -> Column:
    """Coerce user-supplied data to a Column.

    bytes/str sequences → BytesColumn; numeric arrays/sequences → DenseColumn.
    """
    if isinstance(x, Column):
        return x
    if isinstance(x, (bytes, str)):
        return BytesColumn([x if isinstance(x, bytes) else x.encode()])
    if isinstance(x, np.ndarray) and x.dtype == object:
        return BytesColumn(x)
    if _is_device(x) or isinstance(x, np.ndarray):
        return DenseColumn(x)
    if isinstance(x, (list, tuple)) and len(x) > 0 and isinstance(x[0], (bytes, str)):
        return BytesColumn([s if isinstance(s, bytes) else s.encode() for s in x])
    return DenseColumn(np.asarray(x))


def empty_like(col: Column) -> Column:
    if isinstance(col, BytesColumn):
        return BytesColumn([])
    data = col.data
    shape = (0,) if data.ndim == 1 else (0, data.shape[1])
    if _is_device(data):
        return DenseColumn(jnp.zeros(shape, dtype=data.dtype))
    return DenseColumn(np.zeros(shape, dtype=data.dtype))
