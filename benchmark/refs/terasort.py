"""Plain reference for TeraSort, and valsort's checks of part files.

The Sort Benchmark's records: ``RECORD`` = 100 bytes, the first ``KEY`` =
10 of them the key, ordered as ``memcmp`` orders them.  ``sort_records``
is the straightforward sort: the records seen as ``(n, 100)`` bytes, a
stable ``argsort`` of the key column seen as 10-byte strings.
``validate`` holds part files to what ``summary`` says of the input, as
``valsort`` does: the record count; keys non-decreasing inside each part
and from the last key of a part to the first of the next; an
order-independent checksum of WHOLE records (so a value that left its key
is caught, which the keys alone would not show); and the SHA-256 of the
output's key column, equal to the reference's.  numpy and hashlib only:
nothing of the program is used.
"""

import hashlib

import numpy as np

from benchmark import check

RECORD = 100
KEY = 10
BLOCK = 1 << 20         # records mixed or hashed at a time


def records(buf) -> np.ndarray:
    """``(n, RECORD)`` bytes of a buffer, an array or a file."""
    if isinstance(buf, str):
        buf = np.fromfile(buf, np.uint8)
    flat = np.frombuffer(buf, np.uint8) if isinstance(
        buf, (bytes, bytearray, memoryview)) else np.asarray(buf, np.uint8)
    check(flat.size % RECORD == 0,
          f"{flat.size} bytes is no whole number of {RECORD}-byte records")
    return flat.reshape(-1, RECORD)


def keys(recs: np.ndarray) -> np.ndarray:
    """The key column as ``n`` byte strings of ``KEY`` bytes: numpy orders
    and compares them as ``memcmp`` does."""
    return np.ascontiguousarray(recs[:, :KEY]).view(f"S{KEY}").ravel()


def sort_records(recs: np.ndarray) -> np.ndarray:
    """The records in key order, ties in input order."""
    return recs[np.argsort(keys(recs), kind="stable")]


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser: a hash of each u64."""
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def record_mix(recs: np.ndarray) -> np.ndarray:
    """A 64-bit mix of each record's 100 bytes: its twelve u64 words and
    its last four bytes chained through ``_mix``, so that a byte that
    moves inside a record, or from one record to another, changes it."""
    words = np.ascontiguousarray(recs[:, :96]).view(np.uint64)
    tail = np.ascontiguousarray(recs[:, 96:]).view(np.uint32).ravel()
    h = _mix(tail.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15))
    for j in range(words.shape[1]):
        h = _mix((h ^ words[:, j]) + np.uint64(0x9E3779B97F4A7C15))
    return h


def checksum(recs: np.ndarray) -> int:
    """Sum mod 2^64 of ``record_mix``: the same records in any order
    give the same sum."""
    total = 0
    for lo in range(0, len(recs), BLOCK):
        total += int(np.sum(record_mix(recs[lo:lo + BLOCK]), dtype=np.uint64))
    return total % (1 << 64)


def key_sha256(parts) -> str:
    """SHA-256 of the key columns of ``parts`` (arrays of records), in
    their order."""
    h = hashlib.sha256()
    for recs in parts:
        for lo in range(0, len(recs), BLOCK):
            h.update(np.ascontiguousarray(recs[lo:lo + BLOCK, :KEY]).data)
    return h.hexdigest()


def summary(inputs) -> dict:
    """What ``validate`` holds the output to, from the input files' (or
    arrays') records: their number, their checksum, the SHA-256 of the
    sorted key column, and whether two records share a key (where none
    do, the sorted output is one sequence of bytes and no other)."""
    recs = np.concatenate([records(x) for x in inputs]) if len(
        inputs) else np.zeros((0, RECORD), np.uint8)
    ordered = np.sort(keys(recs), kind="stable")
    return {"records": int(len(recs)), "checksum": checksum(recs),
            "key_sha256": hashlib.sha256(ordered.data).hexdigest(),
            "duplicate_keys": bool(len(ordered) > 1 and np.any(
                ordered[1:] == ordered[:-1]))}


def validate(parts, want: dict) -> dict:
    """``parts`` (part files or arrays of records, in part order) against
    ``want`` (``summary`` of the input).  Raises ``CheckFailure`` at the
    first thing that does not hold."""
    parts = [records(p) for p in parts]
    n = sum(len(p) for p in parts)
    check(n == want["records"],
          f"{n} records in the part files, {want['records']} in the input")
    last = None         # arrays of one key: a numpy scalar drops NUL bytes
    for i, recs in enumerate(parts):
        k = keys(recs)
        bad = np.flatnonzero(k[1:] < k[:-1])
        check(not len(bad), f"part {i}: the key of record "
              f"{int(bad[0]) + 1 if len(bad) else 0} is below the one "
              f"before it")
        if len(k):
            check(last is None or bool(k[:1] >= last),
                  f"part {i} starts with a key below the last key of the "
                  f"part before it")
            last = k[-1:]
    total = sum(checksum(p) for p in parts) % (1 << 64)
    check(total == want["checksum"],
          f"whole-record checksum {total:#x} of the part files, "
          f"{want['checksum']:#x} of the input: a record was changed, "
          f"lost or written twice")
    sha = key_sha256(parts)
    check(sha == want["key_sha256"],
          "the part files' key column differs from the reference's sorted "
          "key column")
    return {"records": n, "parts": len(parts),
            "rows_per_part": [len(p) for p in parts]}
