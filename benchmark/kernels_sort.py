"""Bytes a sort of fixed-width records must move, from its counts.

``jit_sort_rows`` (``gpu_mapreduce_tpu/parallel/group.py``) multiplies
nothing: it orders rows, so the bound named for it is HBM bandwidth, as
for every kernel of this system (``kernels.py``)."""


def sort_bytes(records: int, record_bytes: int) -> int:
    """HBM bytes a sort of ``records`` records cannot avoid per job: every
    record read once and written once, ``2 * record_bytes`` a record.
    NOT counted, so the share this gives is of the useful minimum and
    reads low: the sort network's own sweeps over the key words and the
    row index (a bitonic network of about log2(n)^2 / 2 passes over 16 to
    20 bytes a row: most of the program's real traffic); the row index
    written by the sort and read by the gather; the two bytes a row that
    pad the value to whole words and the lanes that pad each column in
    HBM (``hbm_row_bytes`` on the sort's span says what a row occupies);
    the rows of the block past the record count."""
    return int(2 * records * record_bytes)
