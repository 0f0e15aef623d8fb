"""``parallel.group._local_sort``: convert's per-shard sort carries its
rows (ISSUE 38).  ONE stable payload sort through
``ops/sort.sort_carrying`` where a ``lexsort`` order and two ``take``
gathers stood: held to a numpy oracle (``np.lexsort`` + fancy index)
over the cells' and the library's column shapes, and the program it is
jitted into (``jit_convert_sort``) held to one ``sort`` and as many
``gather`` as ``ops/sort.riding`` refuses columns."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpu_mapreduce_tpu.obs import names
from gpu_mapreduce_tpu.ops import sort as sortops
from gpu_mapreduce_tpu.parallel import group
from gpu_mapreduce_tpu.parallel.mesh import make_mesh, row_spec

CAP = 64
WIDE = sortops.RIDE_WORDS + 4           # u32[n, 12]: past RIDE_WORDS

# (dtype, width): the cells' columns and the library's
KEYS = {"u64": ("u8", None), "u64x2": ("u8", 2), "i32": ("i4", None),
        "u32x3": ("u4", 3)}
VALUES = {"u8_null": ("u1", None), "u64": ("u8", None), "i64": ("i8", None),
          "f64": ("f8", None), "u32x3": ("u4", 3),
          "u32x12_by_index": ("u4", WIDE)}
COUNTS = {"none": 0, "one": 1, "partial": 41, "full": CAP}


def _column(rng, spec, n, few):
    """``few`` distinct values a column where ties are wanted (a key),
    else every row its own (a value: its arrival order is readable)."""
    dtype, width = spec
    shape = (n,) if width is None else (n, width)
    dt = np.dtype(dtype)
    if few:
        return rng.integers(0, 3, shape).astype(dt)
    rows = np.arange(n) * 3 + 1
    if dt.kind == "f":
        rows = rows + 0.5
    rows = rows.astype(dt)      # u8 wraps past 255: n is below that
    return rows if width is None else np.stack(
        [rows + j for j in range(width)], axis=1).astype(dt)


def _garbage(rng, spec, n):
    """What a shard holds past its count: zeros, the dtype's extremes and
    junk, so a row past the count would sort before and among the rest."""
    dtype, width = spec
    dt = np.dtype(dtype)
    shape = (n,) if width is None else (n, width)
    if dt.kind == "f":
        return rng.choice(np.array([0.0, -1e300, 1e300, 7.0], dt), shape)
    info = np.iinfo(dt)
    return rng.choice(np.array([info.min, info.max, 0, 2], dt), shape)


def _oracle(key, value, count):
    """Rows below ``count`` in key order (column 0 the most significant,
    equal keys in arrival order), the rows past it last in theirs."""
    cols = [key] if key.ndim == 1 else [key[:, j]
                                        for j in range(key.shape[1])]
    past = np.arange(key.shape[0]) >= count
    order = np.lexsort(tuple(reversed(cols)) + (past,))     # stable
    return key[order], value[order], ~past


@pytest.mark.parametrize("count", COUNTS, ids=list(COUNTS))
@pytest.mark.parametrize("vkind", VALUES, ids=list(VALUES))
@pytest.mark.parametrize("kkind", KEYS, ids=list(KEYS))
def test_local_sort_equals_numpy(kkind, vkind, count):
    rng = np.random.default_rng(38)
    n = COUNTS[count]
    key = np.concatenate([_column(rng, KEYS[kkind], n, few=True),
                          _garbage(rng, KEYS[kkind], CAP - n)])
    value = np.concatenate([_column(rng, VALUES[vkind], n, few=False),
                            _garbage(rng, VALUES[vkind], CAP - n)])
    skey, svalue, valid = jax.jit(group._local_sort)(
        key, value, jnp.int32(n))
    wkey, wvalue, wvalid = _oracle(key, value, n)
    assert skey.dtype == key.dtype and svalue.dtype == value.dtype
    assert skey.shape == key.shape and svalue.shape == value.shape
    np.testing.assert_array_equal(np.asarray(valid), wvalid)
    # the whole shard, bit for bit: valid rows in key order with equal
    # keys in arrival order (the values are distinct, so a swapped pair
    # shows), the rows past the count behind them whatever they hold
    np.testing.assert_array_equal(np.asarray(skey), wkey)
    np.testing.assert_array_equal(np.asarray(svalue), wvalue)
    if n > 1:
        assert len(np.unique(key[:n], axis=0)) < n      # ties were there


def test_the_count_may_be_a_shards_slice():
    """``convert_sort`` hands ``_local_sort`` its shard of the counts,
    shape ``[1]``; the fused tiers hand it a scalar."""
    rng = np.random.default_rng(3)
    key = rng.integers(0, 4, CAP).astype(np.uint64)
    value = np.arange(CAP, dtype=np.uint64)
    for count in (jnp.int32(20), jnp.full(1, 20, jnp.int32)):
        got = jax.jit(group._local_sort)(key, value, count)
        for g, w in zip(got, _oracle(key, value, 20)):
            assert g.shape == w.shape
            np.testing.assert_array_equal(np.asarray(g), w)


# -- the program ---------------------------------------------------------------

def _ops(text):
    return re.findall(r'stablehlo\.(scatter|gather|while|sort)"?\(', text)


def _sds(P, spec):
    dtype, width = spec
    shape = (P * CAP,) if width is None else (P * CAP, width)
    return jax.ShapeDtypeStruct(shape, dtype)


# the cells' columns (wordfreq-zipf-4chip, both graph-build cells, both
# InvertedIndex cells), `collapse`'s and `reduce(count)`'s u64 values, and
# what `riding` refuses: a float64 (`add_weight`), a row past RIDE_WORDS
PROGRAM_COLUMNS = [
    ("word", ("u8", None), ("u1", None), 0),
    ("graph", ("u8", 2), ("u1", None), 0),
    ("invindex", ("u8", None), ("u4", None), 0),
    ("counts", ("u8", None), ("u8", None), 0),
    ("edge_values", ("u8", None), ("u8", 2), 0),
    ("f64_weight", ("u8", 2), ("f8", None), 1),
    ("wide_row", ("u4", 3), ("u4", WIDE), 1),
]


@pytest.mark.parametrize("P", [1, 4], ids=["mesh1", "mesh4"])
@pytest.mark.parametrize("cols", PROGRAM_COLUMNS,
                         ids=[c[0] for c in PROGRAM_COLUMNS])
def test_convert_sort_lowers_to_one_sort(cols, P):
    """The chip's rule a sixth time (PERF.md §6, PRs 25-36): no gather
    behind a key-only sort.  The key never costs one; the value costs
    one where it cannot ride."""
    mesh = make_mesh(P)
    _, kspec, vspec, taken = cols
    k, v = _sds(P, kspec), _sds(P, vspec)
    assert sortops.riding([v]) == [not taken]
    text = group._convert_phase1_jit(mesh).lower(
        k, v, jax.ShapeDtypeStruct((P,), jnp.int32)).as_text()
    assert re.search(r"module @(\w+)", text).group(1) == names.CONVERT_SORT
    assert sorted(_ops(text)) == ["gather"] * taken + ["sort"]


def test_the_lexsort_form_would_be_caught():
    """The check can fail: the body this replaced holds two gathers."""
    mesh = make_mesh(4)
    spec = row_spec(mesh)

    def old_body(key, value, count):
        valid = jnp.arange(key.shape[0]) < count
        cols = [key[:, j] for j in range(key.shape[1] - 1, -1, -1)]
        order = jnp.lexsort(tuple(cols) + (~valid,))
        return jnp.take(key, order, axis=0), jnp.take(value, order, axis=0)

    old = jax.jit(jax.shard_map(old_body, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=(spec,) * 2))
    ops = _ops(old.lower(_sds(4, ("u8", 2)), _sds(4, ("u1", None)),
                         jax.ShapeDtypeStruct((4,), jnp.int32)).as_text())
    assert sorted(ops) == ["gather", "gather", "sort"]
