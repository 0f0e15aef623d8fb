"""Bytes a kernel must move, from shapes; and the table of peaks.

Kernels in this system move bytes and multiply nothing, so the bound named
for each is HBM bandwidth."""

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}: add it with its source")
    return table[device_kind]


def extract_bytes(corpus_bytes: int) -> int:
    """HBM bytes the InvertedIndex extract program cannot avoid: one read of
    every corpus byte (the mark pass).  NOT counted, so the share this
    gives is of the useful minimum and reads low: the padding of each
    shard's corpus to whole pages; the match mask the mark kernel writes
    and the compaction reads back; the gathers of one 64- or 256-byte
    window per hit; the hash, pack and sort tail over ``cap`` rows."""
    return int(corpus_bytes)


def hbm_share(nbytes: float, seconds: float, device_kind: str) -> float:
    """Percent of the HBM roofline: the least time the chip could take for
    ``nbytes`` over the time it took."""
    if seconds <= 0:
        raise ValueError("a kernel that took no time")
    return 100.0 * (nbytes / peaks(device_kind)["hbm_bytes_per_s"]) / seconds
