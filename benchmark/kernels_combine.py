"""Bytes a combiner must move, from its counts.

``jit_combine`` (``gpu_mapreduce_tpu/parallel/group.py``) adds, compares and
takes minima of integers: it multiplies nothing, so the bound named for it
is HBM bandwidth, as for every kernel of this system (``kernels.py``)."""


def combine_bytes(rows: int, key_bytes: int, value_bytes: int,
                  groups: int) -> int:
    """HBM bytes a local ``compress`` by a sum, count, minimum or maximum
    cannot avoid, whatever implements it (a sort and a segment reduce, a
    hash table, masked reductions): every row's key and value words read
    once, and every group's row (key, value) written once.  NOT counted, so
    the share this gives is of the useful minimum and reads low: the
    passes that find the distinct keys (one read of the key columns a key
    found and one more); a second, third ... read of the rows where the
    fold takes one pass a key; the columns of the SOURCE table a deferred
    scan reads to make a row (Query 1 reads 10 of lineitem's 17 words to
    make 14); the rows of a block past its count and the rows a predicate
    refuses, which are read and masked; and the lanes that pad each column
    in HBM."""
    return int(rows * (key_bytes + value_bytes)
               + groups * (key_bytes + value_bytes))
