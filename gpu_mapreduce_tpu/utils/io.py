"""File discovery and chunked ingestion.

Re-implements the reference's map-over-files machinery
(``src/mapreduce.cpp:2812-2931``): recursive directory expansion
(``findfiles``), file-of-filenames mode (``readflag=1``), and the chunked
reader that splits files on a separator char/string with a ``delta``
lookahead so chunk boundaries land on separators
(``map_chunks``/``map_file_wrapper``, ``src/mapreduce.cpp:1312-1552``).

All of this is host-side I/O (it was in the reference too — user callbacks
did fopen); no MPI bcast of the file list is needed since ingestion is
driven from the single controller process and data is *sharded later* by
``aggregate()``.
"""

from __future__ import annotations

import glob
import os
import sys
from typing import Iterator, List, Optional, Sequence, Tuple


def findfiles(paths: Sequence[str], recurse: bool = False,
              readflag: bool = False) -> List[str]:
    """Expand paths → flat file list (reference findfiles,
    src/mapreduce.cpp:2812-2848; readflag file-of-filenames 2857-2906)."""
    out: List[str] = []
    for p in paths:
        if any(c in p for c in "*?[") and not os.path.exists(p):
            hits = sorted(glob.glob(p))
            if not hits:
                raise FileNotFoundError(p)
            out.extend(findfiles(hits, recurse, readflag))
            continue
        if os.path.isdir(p):
            for entry in sorted(os.listdir(p)):
                full = os.path.join(p, entry)
                if os.path.isdir(full):
                    if recurse:
                        out.extend(findfiles([full], recurse, readflag))
                elif os.path.isfile(full):
                    out.append(full)
        elif os.path.isfile(p):
            if readflag:
                with open(p) as f:
                    names = [ln.strip() for ln in f if ln.strip()]
                out.extend(names)
            else:
                out.append(p)
        else:
            raise FileNotFoundError(p)
    return out


def file_chunks(filename: str, nchunks: int, sep: bytes = b"\n",
                delta: int = 80) -> Iterator[bytes]:
    """Split one file into ~nchunks pieces ending on `sep`.

    Mirrors map_file_wrapper (src/mapreduce.cpp:1486-1552): each task reads
    its slice plus a `delta` lookahead, then trims so every chunk ends just
    past a separator and no byte is lost or duplicated.  `sep` may be a
    single char or a multi-byte string (sepchar vs sepstr variants).
    """
    size = os.path.getsize(filename)
    if size == 0 or nchunks <= 0:
        return
    chunksize = max(1, (size + nchunks - 1) // nchunks)
    with open(filename, "rb") as f:
        start = 0
        while start < size:
            f.seek(start)
            want = min(chunksize, size - start)
            buf = f.read(want + delta * 64)
            if start + len(buf) >= size:  # last chunk: take it all
                yield buf[: size - start]
                break
            # find separator at/after the nominal boundary
            cut = buf.find(sep, want - 1)
            if cut < 0:
                # separator beyond lookahead: extend search to EOF
                rest = f.read()
                buf += rest
                cut = buf.find(sep, want - 1)
                if cut < 0:
                    yield buf
                    break
            cut += len(sep)
            yield buf[:cut]
            start += cut


WHITESPACE = b" \t\n\r\f\v"      # what bytes.split() and the native tokenizer
#                                  split at: ASCII whitespace


def read_words(chunk: bytes, whitespace: bytes = WHITESPACE) -> List[bytes]:
    """Whitespace tokenizer (the oink read_words map callback,
    oink/map_read_words.cpp)."""
    table = bytes.maketrans(whitespace, b" " * len(whitespace))
    return chunk.translate(table).split()


def word_ranges(raw: bytes):
    """The words of ``raw`` as a ``BytesColumn`` by ranges: one buffer
    with the start and length of every word, no Python object per word
    (``core/column.BytesColumn.from_ranges``).  Splits exactly as
    ``bytes.split()`` and :func:`read_words` do.  The native tokenizer
    when built, else a numpy pass over the same whitespace set: the same
    ranges either way.  THE word map of ``map_files`` callbacks
    (``oink/kernels.read_words``, ``apps/wordfreq``)."""
    import numpy as np

    from .. import native
    from ..core.column import BytesColumn
    from ..obs import get_tracer, names
    tracer = get_tracer()
    with tracer.span(names.INGEST_TOKENIZE, cat=names.HOST,
                     bytes=len(raw)) as sp:
        shard = tracer.inherited("shard")
        if shard is not None:
            sp.set(shard=shard)
        buf = np.frombuffer(raw, np.uint8)
        if native.available():
            starts, lens = native.tokenize(buf)
        else:
            space = np.zeros(256, bool)
            space[list(WHITESPACE)] = True
            edge = np.diff(np.concatenate(
                [[True], space[buf], [True]]).astype(np.int8))
            starts = np.flatnonzero(edge == -1)
            lens = np.flatnonzero(edge == 1) - starts
        sp.set(words=len(starts))
        return BytesColumn.from_ranges(buf, starts, lens)


class RecordFormat:
    """Files of fixed-width binary records: ``record_bytes`` a record,
    the first ``key_bytes`` of it the key, the rest the value; a file is
    a whole number of records and nothing else (TeraSort's 100 / 10).

    An instance IS the ``map_files`` callback of such files — THE record
    map: no tokenizer and no intern.  A file's keys become
    ``core/column.fixed_key_words`` (dense u32 words whose order is the
    bytes' ``memcmp`` order) and its values ``fixed_value_words``; on a
    mesh ``map_files`` hands the instance to
    ``parallel/ingest.mesh_map_records``, which cuts every file straight
    into its shard's block.  ``join`` is the way back, for a writer,
    and ``join_words`` the same inside a device program."""

    def __init__(self, record_bytes: int, key_bytes: int):
        if not 0 < key_bytes < record_bytes:
            raise ValueError(f"a {record_bytes}-byte record with a "
                             f"{key_bytes}-byte key")
        self.record_bytes = int(record_bytes)
        self.key_bytes = int(key_bytes)
        self.value_bytes = self.record_bytes - self.key_bytes
        self.key_words = -(-self.key_bytes // 4)
        self.value_words = -(-self.value_bytes // 4)

    def rows(self, fname: str, nbytes: int) -> int:
        """Records in a file of ``nbytes`` bytes; a ragged tail is an
        error, never cut or padded."""
        from ..core.runtime import MRError
        if nbytes % self.record_bytes:
            raise MRError(f"{fname}: {nbytes} bytes is no whole number of "
                          f"{self.record_bytes}-byte records")
        return nbytes // self.record_bytes

    def read_into(self, fname: str, key, value) -> None:
        """Cut ``fname``'s records into ``key`` ``[n, key_words]`` and
        ``value`` ``[n, value_words]`` (u32, rows of a shard's block)."""
        import numpy as np

        from ..core.column import fixed_key_words, fixed_value_words
        from ..core.runtime import MRError
        raw = np.fromfile(fname, np.uint8)
        if raw.size != len(key) * self.record_bytes:
            raise MRError(f"{fname}: {raw.size} bytes read where "
                          f"{len(key)} records were expected")
        raw = raw.reshape(-1, self.record_bytes)
        fixed_key_words(raw[:, :self.key_bytes], key)
        fixed_value_words(raw[:, self.key_bytes:], value)

    def __call__(self, itask, fname, kv, ptr=None) -> None:
        import numpy as np
        n = self.rows(fname, os.path.getsize(fname))
        key = np.empty((n, self.key_words), np.uint32)
        value = np.empty((n, self.value_words), np.uint32)
        self.read_into(fname, key, value)
        kv.add_batch(key, value)

    def join(self, key, value):
        """``[n, record_bytes]`` bytes of the records whose key and value
        words these are: what ``read_into`` cut, put together again."""
        import numpy as np

        from ..core.column import fixed_key_bytes, fixed_value_bytes
        out = np.empty((len(key), self.record_bytes), np.uint8)
        out[:, :self.key_bytes] = fixed_key_bytes(key, self.key_bytes)
        out[:, self.key_bytes:] = fixed_value_bytes(value, self.value_bytes)
        return out

    def join_words(self, key, value):
        """``join``'s twin for a device program: ``u32[n, record_bytes /
        4]`` whose bytes in the host's memory are ``join``'s, from the
        same key and value words by shifts, masks and ORs
        (``core/column.fixed_record_words``).  It exists where a record
        is a whole number of u32 words and a word's first byte in memory
        is its lowest; elsewhere it raises."""
        from ..core.runtime import MRError
        if self.record_bytes % 4 or sys.byteorder != "little":
            raise MRError(
                f"no word form of a {self.record_bytes}-byte record on a "
                f"{sys.byteorder}-endian host: join_words needs whole u32 "
                f"words, the lowest byte first")
        from ..core.column import fixed_record_words
        return fixed_record_words(key, value, self.key_bytes)
