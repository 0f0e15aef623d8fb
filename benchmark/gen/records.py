"""Seeded Sort Benchmark records: the input of the TeraSort deployment.

What ``gensort`` makes in its default, binary mode (``-a`` would make
ASCII records; ``-b`` sets the first record's number), from memory of its
documentation (``configs/sortbench-terasort-1chip.json`` lists it under
``assumed``): a record is 100 bytes, a 10-byte key uniformly random over
all 2^80 values and a 90-byte value laid out as

    00 11 | the record's number, 32 hex characters | 88 99 AA BB |
    48 filler bytes | CC DD EE FF

The filler is drawn from the seed (gensort repeats a character of the
record number there; a drawn filler makes every value byte matter to the
checksum).  One departure, ``TWIN_RATE``: one record in 10^4 copies the
first 8 key bytes of the record before it in its file and draws only
bytes 8-9 anew.  Random 80-bit keys share a 64-bit prefix with
probability about 3e-6 at 10^7 records, so a sort that orders by a u64
prefix and drops bytes 8-9 would pass every check at cell size; with a
thousand planted prefix twins it is caught.

Everything is numpy in bulk, a file at a time: the benchmark makes the
records anew for every seed, and that time is set-up.
"""

import concurrent.futures
import os

import numpy as np

RECORD = 100
KEY = 10
TWIN_RATE = 1e-4
_HEX = np.frombuffer(b"0123456789ABCDEF", np.uint8)
_FILLER = 48


def make_file(first: int, n: int, seed: int, index: int,
              twin_rate: float = TWIN_RATE) -> np.ndarray:
    """``(n, RECORD)`` bytes: the records numbered ``first`` to ``first +
    n - 1`` of file ``index``."""
    rng = np.random.default_rng([int(seed), int(index)])
    out = np.empty((n, RECORD), np.uint8)
    drawn = np.frombuffer(rng.bytes(n * (KEY + _FILLER)), np.uint8).reshape(
        n, KEY + _FILLER)
    out[:, :KEY] = drawn[:, :KEY]
    twins = np.flatnonzero(rng.random(n) < twin_rate)
    twins = twins[twins > 0]
    out[twins, :8] = out[twins - 1, :8]     # bytes 8-9 stay as drawn
    number = np.arange(first, first + n, dtype=">u8").view(np.uint8)
    number = number.reshape(n, 8)           # big-endian: high byte first
    out[:, KEY:KEY + 2] = (0x00, 0x11)
    out[:, KEY + 2:KEY + 18] = _HEX[0]      # the number's high 64 bits
    out[:, KEY + 18:KEY + 34:2] = _HEX[number >> 4]
    out[:, KEY + 19:KEY + 34:2] = _HEX[number & 15]
    out[:, KEY + 34:KEY + 38] = (0x88, 0x99, 0xAA, 0xBB)
    out[:, KEY + 38:KEY + 38 + _FILLER] = drawn[:, KEY:]
    out[:, KEY + 38 + _FILLER:] = (0xCC, 0xDD, 0xEE, 0xFF)
    return out


def make_records(dirpath: str, nfiles: int, file_records: int, seed: int,
                 twin_rate: float = TWIN_RATE) -> list:
    """``nfiles`` files of ``file_records`` records each under ``dirpath``
    (``part-00000.dat`` ...); returns their paths."""
    os.makedirs(dirpath, exist_ok=True)

    def one(i: int) -> str:
        path = os.path.join(dirpath, f"part-{i:05d}.dat")
        make_file(i * file_records, file_records, seed, i,
                  twin_rate).tofile(path)
        return path
    # a file is its own stream of the seed, so the order they are made
    # in decides nothing; numpy's copies run beside one another
    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        return list(pool.map(one, range(nfiles)))
