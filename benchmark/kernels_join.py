"""Bytes a keyed join must move, from its counts.

``jit_join_rows`` (``gpu_mapreduce_tpu/parallel/group.py``) multiplies
nothing: it orders both sides' rows together and copies the partner's
value, so the bound named for it is HBM bandwidth, as for every kernel of
this system (``kernels.py``)."""


def join_bytes(probe_rows: int, build_rows: int, matched_rows: int,
               key_bytes: int, probe_value_bytes: int,
               build_value_bytes: int) -> int:
    """HBM bytes an inner join cannot avoid, whatever implements it: every
    probe row and every build row read once and written once by whatever
    brings equal keys together (a sort, a hash table), and every joined
    row (key, probe value, build value) written once.  NOT counted, so the
    share this gives is of the useful minimum and reads low: the sort
    network's own sweeps over the key words, the flags and the payloads
    (about log2(n)^2 / 2 passes), the second sort that brings the joined
    rows to the front, the rows of both blocks past their counts, and the
    lanes that pad each column in HBM."""
    probe = probe_rows * (key_bytes + probe_value_bytes)
    build = build_rows * (key_bytes + build_value_bytes)
    joined = matched_rows * (key_bytes + probe_value_bytes
                             + build_value_bytes)
    return int(2 * (probe + build) + joined)
