"""Native C++ host runtime — ctypes loader and numpy-facing wrappers.

The reference's host hot paths are C++ (hashing ``src/hash.cpp``, file
parsing ``oink/map_read_*.cpp``, the InvertedIndex FSM
``cpu/InvertedIndex.cpp``); ours live in ``mrnative.cpp`` next to this
file, compiled lazily with the baked-in ``g++`` the first time the
package is imported (no pybind11 in the image — plain ``extern "C"`` +
ctypes, see environment notes).  Every wrapper has a pure-Python/numpy
fallback, so the framework works identically when no compiler exists —
``available()`` tells which path is live, and callers (ops/hash.py,
oink/kernels.py, apps/invertedindex.py) branch on it.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
from typing import List, Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "mrnative.cpp")
_TAG = sys.implementation.cache_tag

_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _so_path() -> Optional[str]:
    """``mrnative-<sha256 of mrnative.cpp, 16 hex>-<python tag>.so`` —
    the artifact is keyed by the source it was built from, so what loads
    is what git holds (an mtime says nothing after a copy or a
    checkout).  None when there is no source."""
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None
    return os.path.join(_DIR, f"mrnative-{digest}-{_TAG}.so")


def _build(so: str) -> Optional[str]:
    """Compile mrnative.cpp → ``so`` (via a per-pid temp name, renamed
    into place: two processes racing the first import never load a
    half-written file); returns an error string or None."""
    cxx = os.environ.get("CXX", "g++")
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = [cxx, "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
        err = None if proc.returncode == 0 \
            else proc.stderr.strip() or f"{cxx} failed"
    except (OSError, subprocess.TimeoutExpired) as e:
        err = f"{cxx}: {e}"
    if err is None:
        os.replace(tmp, so)
    elif os.path.exists(tmp):
        os.unlink(tmp)
    return err


def _load() -> Optional[ctypes.CDLL]:
    global _build_error
    so = _so_path()
    if so is None:              # nothing to build from, nothing to trust
        _build_error = f"{_SRC} missing"
        return None
    for name in os.listdir(_DIR):   # stale siblings: another source hash
        if name.startswith("mrnative") and name.endswith(f"-{_TAG}.so") \
                and name != os.path.basename(so):
            try:
                os.unlink(os.path.join(_DIR, name))
            except OSError:
                pass
    if not os.path.exists(so):
        _build_error = _build(so)
        if _build_error is not None:
            return None
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:  # pragma: no cover
        _build_error = str(e)
        return None
    i64, u32, u64 = ctypes.c_int64, ctypes.c_uint32, ctypes.c_uint64
    p = ctypes.POINTER
    u8p = p(ctypes.c_uint8)
    lib.mr_hashlittle.restype = u32
    lib.mr_hashlittle.argtypes = [u8p, i64, u32]
    lib.mr_hashlittle_batch.restype = None
    lib.mr_hashlittle_batch.argtypes = [u8p, p(i64), i64, u32, p(u32)]
    lib.mr_intern64_batch.restype = None
    lib.mr_intern64_batch.argtypes = [u8p, p(i64), i64, p(u64)]
    lib.mr_intern_ranges.argtypes = [u8p, p(i64), p(i64), i64, u32, u32,
                                     p(u64)]
    lib.mr_intern_ranges.restype = None
    lib.mr_intern_ranges2.argtypes = [u8p, p(i64), p(i64), i64, u32, u32,
                                      u32, u32, p(u64), p(u64)]
    lib.mr_intern_ranges2.restype = None
    lib.mr_unique_ranges.restype = i64
    lib.mr_unique_ranges.argtypes = [u8p, p(i64), p(i64), p(u64), i64,
                                     p(i64), p(i64)]
    lib.mr_gather_ranges.restype = None
    lib.mr_gather_ranges.argtypes = [u8p, p(i64), p(i64), i64, u8p]
    lib.mr_differ_ranges.restype = i64
    lib.mr_differ_ranges.argtypes = [u8p, p(i64), u8p, p(i64), p(i64), i64]
    lib.mr_parse_table.restype = i64
    lib.mr_parse_table.argtypes = [u8p, i64, i64, p(ctypes.c_int32),
                                   p(ctypes.c_void_p), i64]
    lib.mr_find_hrefs.restype = i64
    lib.mr_find_hrefs.argtypes = [u8p, i64, p(i64), p(i64), i64]
    lib.mr_tokenize.restype = i64
    lib.mr_tokenize.argtypes = [u8p, i64, p(i64), p(i64), i64]
    if hasattr(lib, "mr_format_rows"):  # g++ 11 or newer: see the source
        i32 = ctypes.c_int32
        lib.mr_format_rows.restype = i64
        lib.mr_format_rows.argtypes = [i32, p(i32), p(i32),
                                       p(ctypes.c_void_p), i64, u8p, i64]
    return lib


def available() -> bool:
    return _lib is not None


def build_error() -> Optional[str]:
    return _build_error


def _u8(buf: bytes):
    return ctypes.cast(ctypes.c_char_p(buf), ctypes.POINTER(ctypes.c_uint8))


def _arr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


# ---------------------------------------------------------------------------
# wrappers (callers must check available() first)
# ---------------------------------------------------------------------------

def hashlittle(data: bytes, initval: int = 0) -> int:
    return int(_lib.mr_hashlittle(_u8(data), len(data), initval))


def hashlittle_batch(buf: bytes, offsets: np.ndarray,
                     initval: int = 0) -> np.ndarray:
    """Hash n packed byte strings; offsets is int64[n+1]."""
    n = len(offsets) - 1
    offsets = np.ascontiguousarray(offsets, np.int64)
    out = np.empty(n, np.uint32)
    _lib.mr_hashlittle_batch(_u8(buf), _arr(offsets, ctypes.c_int64), n,
                             initval, _arr(out, ctypes.c_uint32))
    return out


def intern_ranges(buf: np.ndarray, starts: np.ndarray, lens: np.ndarray,
                  seed_hi: int = 0, seed_lo: int = 0xDEADBEEF) -> np.ndarray:
    """u64 ids over (start, len) ranges of ``buf`` — zero-copy interning
    straight out of a file buffer (default seeds = the intern family of
    hash_bytes64; alternate seeds = an independent check family)."""
    n = len(starts)
    starts = np.ascontiguousarray(starts, np.int64)
    lens = np.ascontiguousarray(lens, np.int64)
    out = np.empty(n, np.uint64)
    if isinstance(buf, np.ndarray):
        ptr = _arr(np.ascontiguousarray(buf, np.uint8), ctypes.c_uint8)
    else:
        ptr = _u8(buf)
    _lib.mr_intern_ranges(ptr, _arr(starts, ctypes.c_int64),
                          _arr(lens, ctypes.c_int64), n, seed_hi, seed_lo,
                          _arr(out, ctypes.c_uint64))
    return out


def intern_ranges2(buf: np.ndarray, starts: np.ndarray, lens: np.ndarray,
                   alt_hi: int, alt_lo: int) -> Tuple[np.ndarray, np.ndarray]:
    """Both u64 id families over (start, len) ranges in one pass over
    ``buf``: (intern ids, alt-family check ids).  Equivalent to two
    :func:`intern_ranges` calls but reads each URL byte once."""
    n = len(starts)
    starts = np.ascontiguousarray(starts, np.int64)
    lens = np.ascontiguousarray(lens, np.int64)
    out0 = np.empty(n, np.uint64)
    out1 = np.empty(n, np.uint64)
    if isinstance(buf, np.ndarray):
        ptr = _arr(np.ascontiguousarray(buf, np.uint8), ctypes.c_uint8)
    else:
        ptr = _u8(buf)
    _lib.mr_intern_ranges2(ptr, _arr(starts, ctypes.c_int64),
                           _arr(lens, ctypes.c_int64), n, 0, 0xDEADBEEF,
                           alt_hi, alt_lo, _arr(out0, ctypes.c_uint64),
                           _arr(out1, ctypes.c_uint64))
    return out0, out1


def unique_ranges(buf: np.ndarray, starts: np.ndarray, lens: np.ndarray,
                  ids: np.ndarray):
    """Row index of the first occurrence of every distinct id, in order
    of appearance (int64[u]) — or ``(row, row)``, a tuple, when two rows
    share an id and differ in their bytes (a 64-bit intern collision)."""
    n = len(ids)
    starts = np.ascontiguousarray(starts, np.int64)
    lens = np.ascontiguousarray(lens, np.int64)
    ids = np.ascontiguousarray(ids, np.uint64)
    first = np.empty(n, np.int64)
    clash = np.zeros(2, np.int64)
    u = _lib.mr_unique_ranges(
        _arr(np.ascontiguousarray(buf, np.uint8), ctypes.c_uint8),
        _arr(starts, ctypes.c_int64), _arr(lens, ctypes.c_int64),
        _arr(ids, ctypes.c_uint64), n, _arr(first, ctypes.c_int64),
        _arr(clash, ctypes.c_int64))
    if u == -1:
        return int(clash[0]), int(clash[1])
    if u < 0:
        raise MemoryError("mr_unique_ranges: no memory for its table")
    return first[:u].copy()


def _ranges_args(buf: np.ndarray, starts: np.ndarray, lens: np.ndarray):
    """Contiguous (buf, starts, lens) whose every range lies inside
    ``buf`` — checked here because the callee copies or compares by
    pointer."""
    buf = np.ascontiguousarray(buf, np.uint8)
    starts = np.ascontiguousarray(starts, np.int64)
    lens = np.ascontiguousarray(lens, np.int64)
    if len(starts) != len(lens) or (len(starts) and (
            starts.min() < 0 or lens.min() < 0
            or (starts + lens).max() > len(buf))):
        raise ValueError("ranges outside their buffer")
    return buf, starts, lens


def gather_ranges(buf: np.ndarray, starts: np.ndarray, lens: np.ndarray,
                  total: int) -> np.ndarray:
    """The ranges' bytes end to end, u8[total]; ``total`` is
    ``lens.sum()``, which the caller has from its offsets."""
    buf, starts, lens = _ranges_args(buf, starts, lens)
    out = np.empty(total, np.uint8)
    _lib.mr_gather_ranges(_arr(buf, ctypes.c_uint8),
                          _arr(starts, ctypes.c_int64),
                          _arr(lens, ctypes.c_int64), len(starts),
                          _arr(out, ctypes.c_uint8))
    return out


def differ_ranges(a: np.ndarray, astarts: np.ndarray, b: np.ndarray,
                  bstarts: np.ndarray, lens: np.ndarray) -> int:
    """Index of the first pair of ranges ``a[astarts[i]:+lens[i]]``,
    ``b[bstarts[i]:+lens[i]]`` that differ in a byte, or -1."""
    a, astarts, lens = _ranges_args(a, astarts, lens)
    b, bstarts, lens = _ranges_args(b, bstarts, lens)
    return int(_lib.mr_differ_ranges(
        _arr(a, ctypes.c_uint8), _arr(astarts, ctypes.c_int64),
        _arr(b, ctypes.c_uint8), _arr(bstarts, ctypes.c_int64),
        _arr(lens, ctypes.c_int64), len(lens)))


def has_format_rows() -> bool:
    """Whether :func:`format_rows` is there: the library built, and with
    a ``<charconv>`` that writes doubles (``std::to_chars`` for floating
    types: g++ 11 or newer)."""
    return _lib is not None and hasattr(_lib, "mr_format_rows")


_ROW_KINDS = {np.dtype(np.uint64): 0, np.dtype(np.int64): 1,
              np.dtype(np.float64): 2}


def format_rows(precisions, cols, start: int, stop: int) -> np.ndarray:
    """The text lines of rows ``[start, stop)`` as u8: field f of a row
    is ``cols[f][row]`` (contiguous u64 / i64 written as ``%d``, f64 as
    ``%.<precisions[f]>g``), single spaces between, a newline after.
    ctypes drops the GIL for the call, so blocks format side by side on
    a thread pool.  Callers check :func:`has_format_rows` first; a
    buffer under the library's own bound raises."""
    nf, n = len(cols), stop - start
    kinds = np.array([_ROW_KINDS[c.dtype] for c in cols], np.int32)
    precs = np.asarray(precisions, np.int32)
    ptrs = (ctypes.c_void_p * nf)(
        *[c.ctypes.data + start * 8 for c in cols])
    args = (nf, _arr(kinds, ctypes.c_int32), _arr(precs, ctypes.c_int32),
            ptrs, n)
    cap = _lib.mr_format_rows(*args, None, 0)   # the most n rows can take
    out = np.empty(cap, np.uint8)
    nbytes = _lib.mr_format_rows(*args, _arr(out, ctypes.c_uint8), cap)
    if nbytes < 0:
        raise RuntimeError(f"mr_format_rows: {cap} bytes are under its "
                           f"bound for {n} rows")
    return out[:nbytes]


def intern64_batch(buf: bytes, offsets: np.ndarray) -> np.ndarray:
    """String → u64 intern ids (ops/hash.py hash_bytes64 semantics)."""
    n = len(offsets) - 1
    offsets = np.ascontiguousarray(offsets, np.int64)
    out = np.empty(n, np.uint64)
    _lib.mr_intern64_batch(_u8(buf), _arr(offsets, ctypes.c_int64), n,
                           _arr(out, ctypes.c_uint64))
    return out


def parse_table(buf: bytes, dtypes) -> List[np.ndarray]:
    """Parse a whitespace table of len(dtypes) columns; dtype entries are
    np.uint64 or np.float64.  Returns one array per column; raises
    ValueError on malformed input (same contract as kernels._parse_cols)."""
    ncols = len(dtypes)
    spec = np.array([0 if dt == np.uint64 else 1 for dt in dtypes],
                    np.int32)
    cap = max(16, len(buf) // (2 * ncols))
    while True:
        cols = [np.empty(cap, dt) for dt in dtypes]
        ptrs = (ctypes.c_void_p * ncols)(
            *[c.ctypes.data_as(ctypes.c_void_p) for c in cols])
        n = _lib.mr_parse_table(_u8(buf), len(buf), ncols,
                                _arr(spec, ctypes.c_int32), ptrs, cap)
        if n == -1:
            raise ValueError("malformed numeric table")
        if n >= 0:
            return [c[:n] for c in cols]
        cap = -n


def find_hrefs(buf) -> Tuple[np.ndarray, np.ndarray]:
    """URL (starts, lens) of every `<a href="..."` match — the host
    equivalent of the Pallas mark/extract pipeline.  ``buf``: bytes or a
    uint8 ndarray (passed zero-copy)."""
    if isinstance(buf, np.ndarray):
        ptr = _arr(np.ascontiguousarray(buf, np.uint8), ctypes.c_uint8)
    else:
        ptr = _u8(buf)
    cap = max(16, len(buf) // 64)
    while True:
        starts = np.empty(cap, np.int64)
        lens = np.empty(cap, np.int64)
        n = _lib.mr_find_hrefs(ptr, len(buf),
                               _arr(starts, ctypes.c_int64),
                               _arr(lens, ctypes.c_int64), cap)
        if n >= 0:
            return starts[:n], lens[:n]
        cap = -n


def tokenize(buf) -> Tuple[np.ndarray, np.ndarray]:
    """(starts, lens) of every whitespace-separated token — the host
    tokenizer behind wordfreq/read_words ingestion (pairs with
    intern_ranges for zero-per-token-Python word ids)."""
    if isinstance(buf, np.ndarray):
        ptr = _arr(np.ascontiguousarray(buf, np.uint8), ctypes.c_uint8)
    else:
        ptr = _u8(buf)
    cap = max(16, len(buf) // 4)
    while True:
        starts = np.empty(cap, np.int64)
        lens = np.empty(cap, np.int64)
        n = _lib.mr_tokenize(ptr, len(buf),
                             _arr(starts, ctypes.c_int64),
                             _arr(lens, ctypes.c_int64), cap)
        if n >= 0:
            return starts[:n], lens[:n]
        cap = -n


_lib = _load()
