"""``ops/sort.sort_carrying``: one payload sort, written once (ROADMAP
C21).  Phase 1 of the shuffle (``tests/test_shuffle_phase1.py``), the
per-shard ``sort_keys`` / ``sort_values`` program (``tests/test_terasort.py``)
and ``convert``'s ``_local_sort`` (``tests/test_convert_sort.py``) call it,
and since PR 49 the device mappers' ``_pack`` (``tests/test_pack.py``);
``rank_graph`` can take it up as a call-site change, so the helper is held
to numpy here, alone."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpu_mapreduce_tpu.ops import sort as sortops
from gpu_mapreduce_tpu.ops.sort import riding, sort_carrying, sort_operands

N = 96
WIDE = sortops.RIDE_WORDS + 1


def _col(rng, dtype, width=None, n=N):
    shape = (n,) if width is None else (n, width)
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return rng.uniform(-5, 5, shape).astype(dt)
    return rng.integers(0, 250, shape).astype(dt)


# carried columns as (dtype, width)
CARRIES = {
    "none": [],
    "one_column": [("u8", None)],
    "block_n2": [("u8", 2)],
    "block_n1": [("u4", 1)],
    "block_n0": [("u4", 0)],
    "mixed": [("u8", 2), ("u1", None), ("i8", None), ("f4", 3)],
    "float64_by_index": [("f8", None), ("u8", None)],
    "wide_by_index": [("u4", WIDE), ("u2", None)],
    "all_by_index": [("f8", 2), ("u4", WIDE)],
}


@pytest.mark.parametrize("stable", [True, False], ids=["stable", "unstable"])
@pytest.mark.parametrize("nkeys", [1, 2])
@pytest.mark.parametrize("carry", CARRIES, ids=list(CARRIES))
def test_sort_carrying_equals_numpy(carry, nkeys, stable):
    rng = np.random.default_rng(21)
    # few distinct first keys, so ties abound; an unstable sort is given
    # a last key that breaks every tie, as its callers must
    keys = [rng.integers(0, 5, N).astype(np.int32)]
    if nkeys == 2:
        keys.append(rng.integers(0, 3, N).astype(np.uint64))
    if not stable:
        keys.append(rng.permutation(N).astype(np.int32))
    cols = [_col(rng, dt, w) for dt, w in CARRIES[carry]]
    skeys, scols = jax.jit(
        lambda ks, cs: sort_carrying(ks, cs, stable=stable))(keys, cols)
    order = np.lexsort(tuple(reversed(keys)))       # stable
    assert len(skeys) == len(keys) and len(scols) == len(cols)
    for got, k in zip(skeys, keys):
        assert got.dtype == k.dtype
        assert np.array_equal(np.asarray(got), k[order])
    for got, c in zip(scols, cols):
        assert got.dtype == c.dtype and got.shape == c.shape
        assert np.array_equal(np.asarray(got), c[order])


@pytest.mark.parametrize("stable", [True, False], ids=["stable", "unstable"])
def test_three_key_words_carry_a_wide_row_by_index(stable):
    """TeraSort's row (ISSUE 36): three u32 key words ahead of 23 payload
    words, which is past ``RIDE_WORDS`` and so comes by the row index; a
    narrow column after it still rides."""
    rng = np.random.default_rng(36)
    keys = [rng.integers(0, 3, N).astype(np.uint32) for _ in range(3)]
    if not stable:
        keys.append(rng.permutation(N).astype(np.int32))
    wide = rng.integers(0, 1 << 32, (N, 23), dtype=np.uint64).astype(np.uint32)
    narrow = rng.integers(0, 250, N).astype(np.uint16)
    assert riding([wide, narrow]) == [False, True]
    skeys, (swide, snarrow) = jax.jit(
        lambda ks, cs: sort_carrying(ks, cs, stable=stable))(
            keys, [wide, narrow])
    order = np.lexsort(tuple(reversed(keys)))
    for got, k in zip(skeys, keys):
        assert np.array_equal(np.asarray(got), k[order])
    assert swide.dtype == wide.dtype and swide.shape == wide.shape
    assert np.array_equal(np.asarray(swide), wide[order])
    assert np.array_equal(np.asarray(snarrow), narrow[order])


def _sds(dt, w, n=N):
    return jax.ShapeDtypeStruct((n,) if w is None else (n, w), np.dtype(dt))


@pytest.mark.parametrize("col,words,ride", [
    (("u1", None), 1, True), (("u4", None), 1, True),
    (("u8", None), 2, True), (("u8", 2), 4, True), (("u4", 3), 3, True),
    (("i8", 4), 8, True), (("f4", 8), 8, True),
    (("f8", None), 2, False), (("c16", None), 4, False),
    (("u4", WIDE), WIDE, False), (("u8", 5), 10, False),
    (("u1", WIDE), WIDE, False)],
    ids=lambda c: "%s_%s" % c if isinstance(c, tuple) else None)
def test_what_rides_is_read_off_the_array(col, words, ride):
    """Dtype and width alone decide: no 64-bit float (the v5e sorts
    none), no more than ``RIDE_WORDS`` payload operands."""
    x = _sds(*col)
    assert sort_operands(x) == words
    assert riding([x]) == [ride]


@pytest.mark.parametrize("cols,want", [
    ([("u8", 2), ("u1", None)], [True, True]),            # graph build
    ([("u8", 2), ("u8", 2)], [True, True]),               # 4 + 4
    ([("u8", 3), ("u8", 2)], [True, False]),              # 6 + 4 > 8
    ([("u4", WIDE), ("u1", None)], [False, True]),        # the next may fit
    ([("u8", 3), ("f8", None), ("u2", None), ("u4", 2)],
     [True, False, True, False])],
    ids=["5", "8", "10", "wide_then_narrow", "mixed"])
def test_the_operands_of_one_sort_are_counted_together(cols, want):
    """The budget is the sort's, not a column's: columns ride in their
    order while the payload operands stay within ``RIDE_WORDS``."""
    assert riding([_sds(*c) for c in cols]) == want


def _ops(text):
    return re.findall(r'stablehlo\.(scatter|gather|while|sort)"?\(', text)


def test_columns_not_blocks_go_into_the_sort():
    """``[n, 2]`` is two operands of ONE sort and is stacked again
    after; nothing is gathered.  What cannot ride shares one more
    operand, the row index, and costs one ``take`` a column."""
    d = jax.ShapeDtypeStruct((N,), jnp.int32)
    blk = jax.ShapeDtypeStruct((N, 2), jnp.uint64)
    u8 = jax.ShapeDtypeStruct((N,), jnp.uint8)
    f64 = jax.ShapeDtypeStruct((N, 2), jnp.float64)
    wide = jax.ShapeDtypeStruct((N, WIDE), jnp.uint32)

    def lowered(*cols):
        return jax.jit(lambda k, cs: sort_carrying((k,), cs)).lower(
            d, list(cols)).as_text()

    def operands(text):
        sort_line = re.search(r'"stablehlo\.sort"\((.*?)\)', text).group(1)
        return len(sort_line.split(","))

    text = lowered(blk, u8)
    assert _ops(text) == ["sort"] and operands(text) == 4
    text = lowered(blk, f64, wide)
    assert sorted(_ops(text)) == ["gather", "gather", "sort"]
    assert operands(text) == 4          # key, two columns, the row index
    text = lowered()
    assert _ops(text) == ["sort"] and operands(text) == 1
