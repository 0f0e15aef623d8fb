"""`parallel.group.grouped_layout` against a plain numpy reference.

The layout's whole contract, fills included: for a shard of sorted rows
with a boundary flag on the first row of each group it returns the
group keys, sizes and first-row offsets packed to the front of
``gcap``-long arrays, the running segment id of every row and the group
count.  The reference is ``np.unique`` over the valid rows; no case
depends on how the function brings the flagged rows to the front.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpu_mapreduce_tpu.parallel.group import grouped_layout

KEY_KINDS = {
    "u64": (np.uint64, ()),
    "u64x2": (np.uint64, (2,)),
    "u32x3": (np.uint32, (3,)),
}

# name -> (cap, nrows, gcap, how the valid keys are drawn)
CASES = {
    "no_rows": (16, 0, 8, "dups"),
    "one_row": (16, 1, 8, "dups"),
    "full_shard": (64, 64, 64, "dups"),
    "under_cap": (64, 41, 48, "dups"),
    "all_equal": (32, 20, 8, "equal"),
    "all_distinct_gcap_is_g": (32, 24, 24, "distinct"),
    "all_distinct_full_shard": (16, 16, 16, "distinct"),
    "gcap_over_cap": (4, 3, 8, "dups"),
    "zero_and_max_keys": (32, 20, 24, "extremes"),
}


def _sorted_rows(dtype, tail, cap, nrows, draw, rng):
    """`cap` rows: the first `nrows` sorted as `_local_sort` leaves them
    (column 0 primary), the rest padding that repeats the last valid key,
    the extreme values and junk, as rows sorted past the count may."""
    top = np.iinfo(dtype).max
    shape = (nrows,) + tail
    if draw == "equal":
        rows = np.full(shape, 7, dtype)
    elif draw == "distinct":
        rows = rng.permutation(nrows).astype(dtype).reshape(nrows, *[1] * len(tail))
        rows = np.broadcast_to(rows, shape).copy()
    elif draw == "extremes":
        rows = rng.choice(np.array([0, 1, top - 1, top], dtype), size=shape)
    else:
        rows = rng.integers(0, 6, size=shape).astype(dtype)
    if tail:
        rows = rows[np.lexsort(rows.T[::-1])]
    else:
        rows = np.sort(rows)
    pad = rng.choice(np.array([0, 3, top], dtype), size=(cap - nrows,) + tail)
    if nrows and cap > nrows:
        pad[0] = rows[-1]
    return np.concatenate([rows, pad]).astype(dtype)


def _reference(sk, nrows, gcap):
    cap = sk.shape[0]
    valid = sk[:nrows]
    mask = np.zeros(cap, bool)
    if nrows:
        differs = valid[1:] != valid[:-1]
        if differs.ndim > 1:
            differs = differs.any(axis=1)
        mask[:nrows] = np.concatenate([[True], differs])
    axis = 0 if sk.ndim > 1 else None
    uniq, first, counts = np.unique(valid, axis=axis, return_index=True,
                                    return_counts=True)
    order = np.argsort(first)
    g = len(first)
    ukey = np.zeros((gcap,) + sk.shape[1:], sk.dtype)
    sizes = np.zeros(gcap, np.int32)
    voff = np.full(gcap, cap, np.int32)
    ukey[:g], sizes[:g], voff[:g] = uniq[order], counts[order], first[order]
    seg = np.cumsum(mask).astype(np.int32) - 1
    return mask, (ukey, sizes, voff, seg, np.int64(g))


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind", KEY_KINDS)
def test_grouped_layout_equals_numpy_unique(kind, case, rng):
    dtype, tail = KEY_KINDS[kind]
    cap, nrows, gcap, draw = CASES[case]
    sk = _sorted_rows(dtype, tail, cap, nrows, draw, rng)
    mask, want = _reference(sk, nrows, gcap)
    if draw == "distinct":
        assert want[4] == gcap
    if draw == "extremes":
        assert sk[:nrows].min() == 0
        assert sk[:nrows].max() == np.iinfo(dtype).max

    got = jax.jit(grouped_layout, static_argnums=3)(
        jnp.asarray(sk), jnp.asarray(mask), jnp.int32(nrows), gcap)

    for name, g, w in zip(("ukey", "sizes", "voff", "seg", "g"), got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype, (name, g.dtype, w.dtype)
        np.testing.assert_array_equal(g, w, err_msg=name)
