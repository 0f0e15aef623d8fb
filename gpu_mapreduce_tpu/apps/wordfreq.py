"""Word frequency — the reference's hello-world pipeline
(``examples/wordfreq.cpp:64-121``): map files → collate → reduce(sum) →
sort by count → top-N.

Two paths through the same MapReduce algebra:

* :func:`wordfreq` — host-callback path, byte-string words as keys
  (exactly the reference's flow: fileread callback emitting one KV per word,
  sum reduce, descending value sort, gather to 1, print top N).
* :func:`wordfreq_interned` — device path: words interned to u64 ids
  (``BytesColumn.intern_sharded``, by ranges) so collate/reduce run
  columnar; the id→word tables decode the top-N at the end.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..core.mapreduce import MapReduce
from ..utils.io import read_words, word_ranges
from .common import top_n


def _fileread(itask, filename, kv, ptr):
    """Emit (word, 1) per word of the file (reference fileread,
    examples/wordfreq.cpp:125-151)."""
    with open(filename, "rb") as f:
        for w in read_words(f.read()):
            kv.add(w, 1)


def _sum(key, values, kv, ptr):
    """(word, [1,1,...]) → (word, count) (reference sum,
    examples/wordfreq.cpp:158-162)."""
    kv.add(key, sum(values))


def wordfreq(files: Sequence[str], ntop: int = 10, comm=None,
             quiet: bool = True) -> Tuple[int, int, List[Tuple[bytes, int]]]:
    """Returns (nwords_total, nunique, top list of (word, count))."""
    mr = MapReduce(comm)
    nwords = mr.map_files(list(files), _fileread)
    mr.collate()
    nunique = mr.reduce(_sum)
    top = [(k, int(v)) for k, v in top_n(mr, ntop)]
    if not quiet:
        print(f"{nwords} total words, {nunique} unique words")
        for w, c in top:
            print(f"{c} {w.decode(errors='replace')}")
    return nwords, nunique, top


def wordfreq_interned(files: Sequence[str], ntop: int = 10, comm=None
                      ) -> Tuple[int, int, List[Tuple[bytes, int]]]:
    """Device-path wordfreq: u64-interned words, columnar count reduce.
    The words come from the shared word map (``utils/io.word_ranges``:
    ranges of the file's buffer, no object per word) and intern into one
    ``ShardTables``, whose absorb is the collision guard across files
    (under the tables' lock: with mapstyle 2 the callbacks run on pool
    threads) and whose tables decode the top-N at the end."""
    from ..core.column import ShardTables
    from ..ops.reduces import count

    mr = MapReduce(comm)
    tables = ShardTables(mr.backend.nprocs)

    def fileread_ids(itask, filename, kv, ptr):
        with open(filename, "rb") as f:
            ids = word_ranges(f.read()).intern_sharded(tables)
        kv.add_batch(ids, np.ones(len(ids), np.int64))

    nwords = mr.map_files(list(files), fileread_ids)
    mr.collate()
    nunique = mr.reduce(count, batch=True)
    top = top_n(mr, ntop)
    words = tables.decode_batch(np.array([k for k, _ in top], np.uint64))
    return nwords, nunique, [(w, int(v)) for w, (_, v) in zip(words, top)]
