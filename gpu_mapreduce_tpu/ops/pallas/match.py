"""Pallas substring matching — the TPU twin of the CUDA ``mark`` kernel.

The reference marks every occurrence of ``<a href="`` in an HTML buffer with
a 0/1 segmask via a 9-char stencil compare on the GPU
(``cuda/InvertedIndex.cu:79-107``), then compacts the mask with Thrust
(``:321-362``) and scans each hit forward to the closing quote
(``compute_url_length``, ``:109-135``).

TPU re-design: the byte buffer is laid out ``[rows, 128]`` (one byte per
lane, widened to int32 in VMEM — the VPU has no sub-word lanes).  For each
pattern offset j the shifted view ``x[i+j]`` is assembled from two
``pltpu.roll``s (same-row lane roll + next-row carry), and the stencil
compare ANDs across offsets.  One kernel pass over the buffer produces the
match mask; compaction and length-scan stay in XLA (`jnp.nonzero` /
windowed gather), where fusion already does the right thing.

``mark_xla`` is the compiler-twin used for CPU tests and as a fallback —
bit-identical output by construction.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
BLOCK_ROWS = 256  # 32 KB of bytes per grid step

# shipped compaction default — ONE constant so the env fallback, the
# builder parameter defaults, and the proof script cannot drift apart
# (r5 review).  'blocked' since r5: ~3x 'scatter' on the CPU backend,
# avoids the full-length major-axis cumsum and the m-element scatter.
DEFAULT_COMPACT = "blocked"


def _i32(x: int):
    """Index-map constants must stay i32: under jax_enable_x64 a bare python
    int traces as i64, which Mosaic refuses to return from an index map."""
    return np.int32(x)


def _pad_to(buf: jnp.ndarray, mult: int) -> jnp.ndarray:
    n = buf.shape[0]
    pad = (-n) % mult
    if pad:
        buf = jnp.concatenate([buf, jnp.zeros(pad, buf.dtype)])
    return buf


def mark_xla(buf, pattern: bytes):
    """Reference implementation: mask[i]=1 iff pattern starts at byte i.
    Nine shifted compares; XLA fuses them into one elementwise pass."""
    n = buf.shape[0]
    acc = jnp.ones(n, dtype=bool)
    for j, p in enumerate(pattern):
        shifted = jnp.concatenate(
            [buf[j:], jnp.zeros(j, buf.dtype)]) if j else buf
        acc = acc & (shifted == np.uint8(p))
    return acc


def _mark_kernel(pattern: bytes, buf_ref, nxt_ref, mask_ref):
    x = buf_ref[:].astype(jnp.int32)                  # [BR, 128]
    nxt = nxt_ref[0:1].astype(jnp.int32)              # next block's first row
    # next-row view of x (row r+1; last row fed by the next block's head)
    from jax.experimental.pallas import tpu as pltpu
    # pltpu.roll requires non-negative shifts: roll by (size - j) ≡ roll by -j
    # (shifts as np.int32 — x64 mode would make a weak i64 that mosaic rejects)
    xr = pltpu.roll(x, np.int32(x.shape[0] - 1), axis=0)
    xr = jnp.where(jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
                   == x.shape[0] - 1, nxt, xr)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    acc = jnp.ones(x.shape, dtype=jnp.bool_)
    for j, p in enumerate(pattern):
        if j == 0:
            shifted = x
        else:
            a = pltpu.roll(x, np.int32(LANES - j), axis=1)   # x[r, c+j mod 128]
            b = pltpu.roll(xr, np.int32(LANES - j), axis=1)  # x[r+1, c+j mod 128]
            shifted = jnp.where(lane < LANES - j, a, b)
        acc = acc & (shifted == p)
    mask_ref[:] = acc.astype(jnp.int8)


def mark_pallas(buf, pattern: bytes, interpret: bool = False):
    """Pallas mark kernel over a uint8 buffer [n] → int8 mask [n]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from . import note_kernel_launch

    note_kernel_launch(buf)   # eager launches count as dispatches
    n = buf.shape[0]
    blk = BLOCK_ROWS * LANES
    buf_p = _pad_to(buf, blk)
    rows = buf_p.shape[0] // LANES
    grid = rows // BLOCK_ROWS
    # one extra zero block so the "next block head" index map stays in range
    buf_2d = jnp.concatenate(
        [buf_p.reshape(rows, LANES),
         jnp.zeros((BLOCK_ROWS, LANES), buf_p.dtype)])
    out = pl.pallas_call(
        functools.partial(_mark_kernel, pattern),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, _i32(0)),
                         memory_space=pltpu.VMEM),
            # 8-row block (TPU min sublane tile); kernel uses its first row
            pl.BlockSpec((8, LANES),
                         lambda i: ((i + _i32(1)) * _i32(BLOCK_ROWS // 8),
                                    _i32(0)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, _i32(0)),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
        name="mark_bytes",
    )(buf_2d, buf_2d)
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# word-packed mark kernel — 4 bytes/lane
# ---------------------------------------------------------------------------
#
# The byte-per-lane kernel above widens every byte to an i32 lane: 9 pattern
# offsets × (2 rolls + select + compare + and) ≈ 45 VPU ops *per byte*, and
# it writes a byte-sized mask — most of the kernel's time is arithmetic on
# 75%-empty lanes.  The word-packed variant bitcasts the buffer to u32
# words (4 bytes/lane) and checks the pattern at each of the 4 byte
# alignments with masked word compares: ``(w & m) == v`` over the
# ceil((L+3)/4) words the pattern can touch.  Output is ONE int8 per word
# encoding which alignment matched (0 = none, a+1 = byte 4*i+a) — valid
# whenever the pattern cannot match at two alignments of the same word,
# i.e. its minimal period is ≥ 4 (checked; ``<a href="`` has period 9).
# Net: ~4× fewer VPU ops and a 4× smaller mask for downstream compaction.

WORD_BLOCK_ROWS = 512   # 256 KB of buffer per grid step (u32 lanes)


def _min_period(pattern: bytes) -> int:
    for d in range(1, len(pattern)):
        if pattern[d:] == pattern[:-d]:
            return d
    return len(pattern)


def _alignment_tables(pattern: bytes):
    """Per-alignment masked-compare constants: for byte alignment a in 0..3,
    (masks[a], vals[a]) are u32 words with 0xFF at the byte positions the
    pattern occupies in the little-endian word window starting at the
    match word."""
    L = len(pattern)
    nw = (L + 3 + 3) // 4  # pattern shifted by ≤3 bytes spans ≤ this many words
    masks = np.zeros((4, nw), np.uint32)
    vals = np.zeros((4, nw), np.uint32)
    for a in range(4):
        mb = bytearray(4 * nw)
        vb = bytearray(4 * nw)
        for i, p in enumerate(pattern):
            mb[a + i] = 0xFF
            vb[a + i] = p
        masks[a] = np.frombuffer(bytes(mb), "<u4")
        vals[a] = np.frombuffer(bytes(vb), "<u4")
    return masks, vals


def _u32_as_i32(v: int) -> np.int32:
    return np.int32(v - (1 << 32) if v >= (1 << 31) else v)


def _mark_words_kernel(masks, vals, w_ref, nxt_ref, out_ref):
    from jax.experimental.pallas import tpu as pltpu
    x = w_ref[:]                                   # [BR, 128] i32 words
    nxt = nxt_ref[0:1]                             # next block's first row
    br = x.shape[0]
    xr = pltpu.roll(x, np.int32(br - 1), axis=0)   # next-row view
    xr = jnp.where(jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
                   == br - 1, nxt, xr)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    nw = masks.shape[1]
    views = [x]
    for j in range(1, nw):                         # word at linear index i+j
        a = pltpu.roll(x, np.int32(LANES - j), axis=1)
        b = pltpu.roll(xr, np.int32(LANES - j), axis=1)
        views.append(jnp.where(lane < LANES - j, a, b))
    out = jnp.zeros(x.shape, jnp.int32)
    for a in range(3, -1, -1):                     # lowest alignment wins
        hit = None
        for j in range(nw):
            if not masks[a, j]:
                continue
            m = _u32_as_i32(int(masks[a, j]))
            v = _u32_as_i32(int(vals[a, j] & masks[a, j]))
            eq = (views[j] & m) == v
            hit = eq if hit is None else (hit & eq)
        out = jnp.where(hit, np.int32(a + 1), out)
    out_ref[:] = out.astype(jnp.int8)


def mark_words_xla(words, pattern: bytes):
    """Compiler-twin of the word-packed kernel over a u32/i32 word buffer
    [m] — same masked-compare math in plain jnp (the 'xla' engine path and
    the CPU oracle; XLA fuses the compares into one elementwise pass)."""
    if _min_period(pattern) < 4:
        raise ValueError("pattern period < 4 needs the byte kernel")
    masks, vals = _alignment_tables(pattern)
    m = words.shape[0]
    wu = words.astype(jnp.uint32)
    nw = masks.shape[1]
    views = [wu]
    for j in range(1, nw):
        views.append(jnp.concatenate([wu[j:], jnp.zeros(j, jnp.uint32)]))
    out = jnp.zeros(m, jnp.int8)
    for a in range(3, -1, -1):
        hit = None
        for j in range(nw):
            if not masks[a, j]:
                continue
            eq = (views[j] & np.uint32(masks[a, j])) \
                == np.uint32(vals[a, j] & masks[a, j])
            hit = eq if hit is None else (hit & eq)
        out = jnp.where(hit, np.int8(a + 1), out)
    return out


def bytes_view_u32(data: np.ndarray) -> np.ndarray:
    """HOST helper: u8 [n] → little-endian u32 words [ceil(n/4)] (zero-pad
    tail).  The device buffer travels and lives as u32 — a [m,4] u8 view
    on TPU would tile to (8,128) per 4-wide row and blow up 32× in HBM."""
    n = data.shape[0]
    pad = (-n) % 4
    if pad:
        data = np.concatenate([data, np.zeros(pad, np.uint8)])
    return np.ascontiguousarray(data).view(np.dtype("<u4"))


# Fixed page size for the paged mark (words; 4 MW = 16 MB of corpus per
# Pallas dispatch).  Paging keeps every on-chip dispatch at one shape
# class — one Mosaic kernel regardless of corpus size — and bounds what
# any per-dispatch scale limit can see; the enclosing program unrolls one
# pallas_call per page.  Exact by construction: mask word i depends only
# on words i..i+nw-1 (nw = ceil((len(pattern)+3+3)/4)), so pages overlap
# by nw-1 words.  Override with MR_MARK_PAGE_WORDS (tests use tiny pages
# to cross page seams).
MARK_PAGE_WORDS = 1 << 22


def mark_words_pallas(words, pattern: bytes, interpret: bool = False,
                      page_words: int | None = None):
    """Word-packed Pallas mark over a u32/i32 word buffer [m] → int8 word
    mask [m]: 0 = no match, a+1 = pattern starts at byte 4*i+a.  Buffers
    larger than ``page_words`` are marked page-by-page (same compiled
    kernel per page; see MARK_PAGE_WORDS)."""
    if _min_period(pattern) < 4:
        raise ValueError(
            f"pattern period {_min_period(pattern)} < 4: two alignments of "
            f"one word could match; use the byte kernel (mark_pallas)")
    masks, vals = _alignment_tables(pattern)
    m = words.shape[0]
    if words.dtype != jnp.int32:
        words = jax.lax.bitcast_convert_type(words, jnp.int32)
    if page_words is None:
        # mrlint: disable=cache-key-missing-knob,purity-host-call —
        # documented eager-fallback: cached/jitted callers pass
        # page_words explicitly (threaded through _env_knobs keys)
        page_words = int(os.environ.get("MR_MARK_PAGE_WORDS",
                                        MARK_PAGE_WORDS))
    if m > page_words:
        ov = masks.shape[1] - 1
        npages = -(-m // page_words)
        pad = npages * page_words + ov - m
        padded = jnp.concatenate([words, jnp.zeros(pad, jnp.int32)])
        outs = [
            _mark_words_call(padded[p * page_words:
                                    p * page_words + page_words + ov],
                             masks, vals, interpret)[:page_words]
            for p in range(npages)]
        return jnp.concatenate(outs)[:m]
    return _mark_words_call(words, masks, vals, interpret)


def _mark_words_call(words, masks, vals, interpret: bool):
    """One Pallas dispatch over an i32 word buffer [m] (the pre-r4 whole-
    buffer path; pages funnel through here at a fixed shape)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from . import note_kernel_launch

    note_kernel_launch(words)   # eager launches count as dispatches
    m = words.shape[0]
    blk = WORD_BLOCK_ROWS * LANES
    # one concatenate: round up to a block multiple AND append the zero
    # sentinel block the next-block-head index map reads past the end
    pad = (-m) % blk + blk
    words = jnp.concatenate([words, jnp.zeros(pad, jnp.int32)])
    rows = words.shape[0] // LANES               # incl. the sentinel block
    grid = rows // WORD_BLOCK_ROWS - 1
    out_rows = grid * WORD_BLOCK_ROWS            # mask excludes the sentinel
    words_2d = words.reshape(rows, LANES)
    out = pl.pallas_call(
        functools.partial(_mark_words_kernel, masks, vals),
        out_shape=jax.ShapeDtypeStruct((out_rows, LANES), jnp.int8),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((WORD_BLOCK_ROWS, LANES), lambda i: (i, _i32(0)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, LANES),
                         lambda i: ((i + _i32(1)) * _i32(WORD_BLOCK_ROWS // 8),
                                    _i32(0)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((WORD_BLOCK_ROWS, LANES),
                               lambda i: (i, _i32(0)),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
        name="mark_words",
    )(words_2d, words_2d)
    return out.reshape(-1)[:m]


def compact_word_matches(wmask, nbytes: int, max_hits: int,
                         mode: str | None = None):
    """Word mask → sorted byte start offsets [max_hits] (fill = nbytes,
    i.e. positively out of range) + match count.

    Stream compaction as cumsum + scatter — the Thrust copy_if stage
    (cuda/InvertedIndex.cu:321-362) in XLA terms.  NOT jnp.nonzero: its
    TPU lowering runs ~20× slower than this two-op form at 16M words
    (measured on v5e; nonzero sorts where a prefix-sum + scatter-with-drop
    suffices, since scatter positions here are unique by construction).

    mode (or MR_COMPACT when mode is None) selects among three
    bit-identical variants for on-chip A/B: 'scatter' (this path),
    'searchsorted' (each OUTPUT slot binary-searches the hit-count
    prefix sum — max_hits·log m gathered lanes instead of an m-element
    scatter), and 'blocked' (_compact_blocked: two-level scan, no
    full-length major-axis cumsum at all).  NOTE: the env fallback reads
    at TRACE time — callers inside cached/jitted builders must pass
    mode explicitly (apps/invertedindex.py threads it through
    _env_knobs into every builder cache key)."""
    if mode is None:
        # mrlint: disable=cache-key-missing-knob,purity-host-call —
        # the trace-time read documented above: cached/jitted callers
        # must pass mode explicitly (and do, via _env_knobs keys)
        mode = os.environ.get("MR_COMPACT", DEFAULT_COMPACT)
    if mode not in ("scatter", "searchsorted", "blocked"):
        # a typo'd A/B label must error, not silently measure scatter
        raise ValueError(f"MR_COMPACT/mode {mode!r}: expected "
                         f"'scatter', 'searchsorted' or 'blocked'")
    if mode == "searchsorted":
        return _compact_searchsorted(wmask, nbytes, max_hits)
    if mode == "blocked":
        return _compact_blocked(wmask, nbytes, max_hits)
    m = wmask.shape[0]
    hit = wmask > 0
    pos = jnp.cumsum(hit.astype(jnp.int32)) - 1
    tgt = jnp.where(hit & (pos < max_hits), pos, max_hits)
    idx = jax.lax.broadcasted_iota(jnp.int32, (m,), 0)
    start_of_word = 4 * idx + wmask.astype(jnp.int32) - 1
    starts = jnp.full(max_hits, nbytes, jnp.int32).at[tgt].set(
        start_of_word, mode="drop")
    return starts, jnp.sum(hit.astype(jnp.int32))


_BLOCK_C = 512   # lanes per row in the blocked compaction's 2-level scan


def _compact_blocked(wmask, nbytes: int, max_hits: int):
    """Hierarchical compaction: NO scan or scatter ever runs over the full
    m words along the major axis.  The mask reshapes to [R, 512]; the
    per-row prefix sum is a minor-axis cumsum (lane-parallel on the VPU),
    the row totals scan is R = m/512 elements, and each output slot then
    finds its hit with a two-level binary search (log R gathered lanes to
    pick the row, log 512 within it).  The right trade when XLA's
    full-length major-axis cumsum lowering dominates the map stage —
    bit-identical to the scatter path (oracle test runs all three)."""
    m = wmask.shape[0]
    C = _BLOCK_C
    pad = (-m) % C
    hit = (wmask > 0).astype(jnp.int32)
    if pad:
        hit = jnp.concatenate([hit, jnp.zeros(pad, jnp.int32)])
    R = hit.shape[0] // C
    intra = jnp.cumsum(hit.reshape(R, C), axis=1)        # [R, C] minor axis
    row_tot = intra[:, C - 1]
    row_off = jnp.cumsum(row_tot)                        # [R] inclusive
    total = row_off[R - 1]
    j = jnp.arange(1, max_hits + 1, dtype=jnp.int32)
    row = jnp.searchsorted(row_off, j, side="left").astype(jnp.int32)
    rsafe = jnp.minimum(row, R - 1)
    prev = jnp.where(row > 0,
                     jnp.take(row_off, jnp.maximum(rsafe - 1, 0)),
                     jnp.int32(0))
    r = j - prev                                         # rank within row
    flat = intra.reshape(-1)
    lo = jnp.zeros(max_hits, jnp.int32)
    hi = jnp.full(max_hits, C, jnp.int32)
    # lower_bound over a size-C range converges in bit_length(C) guarded
    # steps (the last resolves the final length-1 interval; converged
    # lanes are no-ops under the lo<hi guard)
    for _ in range(C.bit_length()):
        upd = lo < hi
        mid = (lo + hi) // 2
        v = jnp.take(flat, jnp.minimum(rsafe * C + mid, R * C - 1))
        ge = v >= r
        hi = jnp.where(upd & ge, mid, hi)
        lo = jnp.where(upd & ~ge, mid + 1, lo)
    word = rsafe * C + lo
    wsafe = jnp.minimum(word, m - 1)
    starts = 4 * word + jnp.take(wmask, wsafe).astype(jnp.int32) - 1
    starts = jnp.where(j <= total, starts, jnp.int32(nbytes))
    return starts, total


def _compact_searchsorted(wmask, nbytes: int, max_hits: int):
    """Gather-side compaction: slot j finds the (j+1)-th hit via binary
    search over the hit-count prefix sum.  Replaces the 64M-element
    scatter with max_hits·ceil(log2 m) random 4-byte reads — the right
    trade when XLA's TPU scatter lowering dominates the map stage."""
    m = wmask.shape[0]
    hit = wmask > 0
    c = jnp.cumsum(hit.astype(jnp.int32))
    total = c[m - 1]
    j = jnp.arange(1, max_hits + 1, dtype=jnp.int32)
    idx = jnp.searchsorted(c, j, side="left").astype(jnp.int32)
    safe = jnp.minimum(idx, m - 1)
    starts = 4 * idx + jnp.take(wmask, safe).astype(jnp.int32) - 1
    starts = jnp.where(j <= total, starts, jnp.int32(nbytes))
    return starts, total


# ---------------------------------------------------------------------------
# unaligned word windows — the u32-resident replacement for byte gathers
# ---------------------------------------------------------------------------

def unaligned_words(words, starts, nwords: int):
    """Gather unaligned little-endian u32 windows from a u32 buffer [m]:
    row i holds ``nwords`` words whose bytes start at BYTE offset
    ``starts[i]``.  Rebuilt from two aligned loads + shifts — the TPU never
    sees a byte-typed array (a [m,4] u8 view would tile 32× larger in HBM).
    Out-of-range bytes read as zero."""
    m = words.shape[0]
    wu = words.astype(jnp.uint32) if words.dtype != jnp.uint32 else words
    k = (starts // 4).astype(jnp.int32)
    r = (starts % 4).astype(jnp.uint32)
    idx = k[:, None] + jnp.arange(nwords + 1, dtype=jnp.int32)[None, :]
    g = jnp.take(wu, jnp.clip(idx, 0, m - 1), axis=0)
    g = jnp.where((idx >= 0) & (idx < m), g, np.uint32(0))
    sh = (np.uint32(8) * r)[:, None]
    lo = g[:, :-1] >> sh
    hi_sh = (np.uint32(32) - sh) % np.uint32(32)   # avoid shift-by-32 UB
    hi = jnp.where(sh > 0, g[:, 1:] << hi_sh, np.uint32(0))
    return lo | hi


def first_byte_pos(wu, byte: int):
    """Per row of a u32 window array [n, W]: byte offset of the first
    occurrence of ``byte``, or -1 (the compute_url_length scan,
    cuda/InvertedIndex.cu:109-135, on word lanes)."""
    n, W = wu.shape
    big = np.int32(4 * W)
    best = jnp.full(n, big, jnp.int32)
    for j in range(4):
        hit = ((wu >> np.uint32(8 * j)) & np.uint32(0xFF)) == np.uint32(byte)
        p = jnp.argmax(hit, axis=1).astype(jnp.int32)
        cand = jnp.where(jnp.any(hit, axis=1), 4 * p + j, big)
        best = jnp.minimum(best, cand)
    return jnp.where(best < big, best, np.int32(-1))


def mask_words_to_length(wu, lengths):
    """Zero every byte at offset >= lengths[i] in row i of a u32 window
    array — produces the zero-padded words the masked hash requires."""
    W = wu.shape[1]
    nb = jnp.clip(lengths[:, None]
                  - np.int32(4) * jnp.arange(W, dtype=jnp.int32)[None, :],
                  0, 4)
    lut = jnp.asarray(
        np.array([0, 0xFF, 0xFFFF, 0xFFFFFF, 0xFFFFFFFF], np.uint32))
    return wu & jnp.take(lut, nb)


def compact_matches(mask, max_hits: int):
    """Mask → sorted start offsets [max_hits] (fill = len(mask)) + count.
    The Thrust sequence/count/copy_if stage (cuda/InvertedIndex.cu:321-362)
    collapses to one jnp.nonzero."""
    n = mask.shape[0]
    idx = jnp.nonzero(mask.astype(bool), size=max_hits, fill_value=n)[0]
    return idx, jnp.sum(mask.astype(jnp.int32))


def url_lengths(buf, starts, terminator: int, max_len: int):
    """For each start offset, distance to the terminator byte (the
    compute_url_length kernel, cuda/InvertedIndex.cu:109-135).

    Returns lengths [k] (-1 if no terminator within max_len — the reference
    would run off the buffer; we flag and let the caller drop) and the
    gathered windows [k, max_len].  A length of 0 is a real empty URL
    (``href=""``), distinct from the no-terminator case."""
    n = buf.shape[0]
    pos = starts[:, None] + jnp.arange(max_len)[None, :]
    windows = jnp.take(buf, jnp.minimum(pos, n - 1), axis=0)
    windows = jnp.where(pos < n, windows, 0)
    hit = windows == np.uint8(terminator)
    any_hit = jnp.any(hit, axis=1)
    length = jnp.where(any_hit, jnp.argmax(hit, axis=1), -1)
    return length.astype(jnp.int32), windows


