"""Bytes the wedge walk of ``tri_find`` must move, from its counts.

``tri_wedges`` (``gpu_mapreduce_tpu/models/tri.py``) multiplies nothing:
it makes wedge keys, joins them with the resident edge keys and keeps the
hits, so the bound named for it is HBM bandwidth, as for every kernel of
this system (``kernels.py``)."""

KEY_BYTES = 8           # a packed (u, w) pair, and a packed edge
ROW_BYTES = 8 + 4       # a hit: its packed pair and its centre


def wedge_bytes(wedges: int, batches: int, edges: int, triangles: int) -> int:
    """HBM bytes the wedge program cannot avoid per job: every wedge key
    written once (by the expansion) and read once (by the join), the edge
    keys read once a batch (the join is against all of them), every
    triangle written once.  NOT counted, so the share this gives is of the
    useful minimum and reads low: the four sorts' own passes over a batch
    (each a bitonic network of about log2(n)^2 / 2 sweeps over 25 M rows of
    12 to 16 bytes: the program's real traffic); the merge of the owners'
    offsets with the wedge indices that stands in for a per-wedge binary
    search; the gather of each wedge's partner neighbour; the prefix
    scans; the rows of the last batch past the wedge count and the edge
    rows past the edge count (padding to the static caps)."""
    return int(2 * KEY_BYTES * wedges + KEY_BYTES * edges * batches
               + ROW_BYTES * triangles)
