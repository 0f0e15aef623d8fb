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
