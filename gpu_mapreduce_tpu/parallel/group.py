"""Sharded convert / sort / segment-reduce — the local half of collate.

The reference's convert is purely local per rank (SURVEY.md §3.3: "No MPI at
all — the parallelism came from aggregate").  Same here: each shard sorts its
own block and finds group boundaries under ``shard_map``; no collectives.

Both halves are sorts whose rows ride them: `_local_sort` orders a shard
by key with the value as payload, and the grouped layout
(`grouped_layout`) packs each group's first row to the front by a sort
with the key columns as payloads, because a scatter is what the chip
does worst and a gather behind a key-only sort next to worst (the
readings are in the two functions' docstrings).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax.sharding import NamedSharding

from ..core.runtime import bump_dispatch
from ..obs import get_tracer, names
from ..ops.sort import (columns, front_order, riding, sort_carrying,
                        sort_operands, take_together)
from .mesh import mesh_axes, mesh_axis_size, row_sharding, row_spec
from .sharded import (ShardedKMV, ShardedKV, SyncStats, _decode_col,
                      front_cap, round_cap, rows_below)


def _local_sort(key, value, count):
    """A shard's rows in key order, the rows past ``count`` last whatever
    they hold: ``(sorted key, sorted value, valid)``.  ONE stable sort
    (ops/sort.sort_carrying), as `sort_rows` below: the key's columns are
    its keys behind the flag of the rows past the count, so they come out
    of the sort itself, and the value rides or comes by the sorted row
    index, as its dtype and width say.  Stable, so a group's values keep
    their arrival order, which `reduce` callbacks and `first_sharded`
    see.  A key-only sort and two gathers behind it was 2.33 s a job of
    `graph-build-1chip` where the sort was 0.42 s (PERF.md §6, PR 38)."""
    valid = jnp.arange(key.shape[0], dtype=jnp.int32) < count
    past = (~valid).astype(jnp.uint8)
    (_, *scols), (svalue,) = sort_carrying((past, *columns(key)), (value,))
    skey = scols[0] if key.ndim == 1 else jnp.stack(scols, axis=1)
    return skey, svalue, valid


def _sort_words(col, other) -> dict:
    """What a traced op span says of a sort by ``col`` that carries
    ``other``: the key's 32-bit operands, and the carried words that rode
    as payloads or were taken by the sorted row index (ops/sort.riding).
    ``taken_words`` 0 is a program without a gather."""
    rode = sort_operands(other) if riding([other])[0] else 0
    return {names.ATTR_KEY_WORDS: sort_operands(col),
            names.ATTR_RODE_WORDS: rode,
            names.ATTR_TAKEN_WORDS: sort_operands(other) - rode}


def _boundary(skey, valid):
    if skey.ndim == 1:
        diff = skey[1:] != skey[:-1]
    else:
        diff = jnp.any(skey[1:] != skey[:-1], axis=1)
    first = jnp.ones(1, bool)
    return valid & jnp.concatenate([first, diff])


@functools.lru_cache(maxsize=None)
def _convert_phase1_jit(mesh):
    spec = row_spec(mesh)

    @jax.jit
    def convert_sort(key, value, count):
        def body(k, v, c):
            with jax.named_scope("sort"):
                sk, sv, valid = _local_sort(k, v, c)
            with jax.named_scope("boundary"):
                mask = _boundary(sk, valid)
                return sk, sv, mask, jnp.sum(mask).astype(jnp.int32)[None]
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(spec, spec, spec),
                             out_specs=(spec, spec, spec, spec))(key, value, count)

    return convert_sort


def grouped_layout(sk, mask, nrows, gcap: int):
    """Shard-local group layout of SORTED rows → (ukey, sizes, voff,
    seg, g).  THE one copy of the convert phase-2 math — shared by the
    eager `_convert_phase2_jit` and the plan/ fuser's fused programs, so
    fused output can never drift from eager.

    The first row of every group is flagged in ``mask``; the layout is
    those rows brought to the front in their order.  That is ONE sort,
    keyed by the row index (flagged) or ``cap`` (not), with the key
    columns riding as payloads: the sorted key column is ``voff`` with
    its ``cap`` fill, the payloads are ``ukey``, and the sizes are the
    differences of consecutive offsets.  The key is the row index, so
    no key value is a sentinel.  A sort and not scatter-drops, because
    on the v5e a scatter of 16.8 M u64 elements with dropped rows costs
    1.79 s and a sort of them with a payload 0.056 s (PERF.md §6,
    PR 25); here, at u64[8388608, 2], 1.05 s against 0.038 s (PR 29)."""
    cap = sk.shape[0]
    with jax.named_scope("segment_ids"):
        flags = mask.astype(jnp.int32)
        seg = jnp.cumsum(flags) - 1
        g = jnp.sum(flags)
    # columns, not [cap, w] blocks, go into the sort: the chip stores
    # u64[cap, 2] column-major and a reshape of it is tile-padded 64x
    cols = (sk,) if sk.ndim == 1 else tuple(
        sk[:, j] for j in range(sk.shape[1]))
    # unstable: only unflagged rows tie (at ``cap``), and what they
    # carry is replaced by the fills below
    with jax.named_scope("flagged_rows_first"):
        row = jnp.arange(cap, dtype=jnp.int32)
        first, *ucols = jax.lax.sort(
            (jnp.where(mask, row, cap),) + cols, num_keys=1,
            is_stable=False)
    # the last group ends at nrows: padding rows sorted past the valid
    # count are not counted; slots past the g-th group hold the fills
    with jax.named_scope("group_sizes"):
        nxt = jnp.concatenate([first[1:], jnp.full(1, cap, jnp.int32)])
        sizes = jnp.where(
            row < g, jnp.minimum(nxt, nrows.astype(jnp.int32)) - first, 0)
    with jax.named_scope("unique_keys"):
        ucols = [_fit(jnp.where(row < g, c, 0), gcap, 0) for c in ucols]
        ukey = ucols[0] if sk.ndim == 1 else jnp.stack(ucols, axis=1)
    with jax.named_scope("group_offsets"):
        voff = _fit(first, gcap, cap)
    return ukey, _fit(sizes, gcap, 0), voff, seg, g


def _fit(x, n: int, fill):
    """The first ``n`` entries of 1-D ``x``, padded with ``fill`` where
    ``n`` exceeds its length (`round_cap` has a floor, so a tiny shard's
    ``gcap`` can exceed its ``cap``)."""
    if n <= x.shape[0]:
        return x[:n]
    return jnp.concatenate([x, jnp.full(n - x.shape[0], fill, x.dtype)])


def segment_reduce_rows(x, seg, valid, gcap: int, op: str):
    """One output row per segment (sum/max/min with the kernel tier's
    fill values) — shared by `_reduce_build` and the fuser."""
    ids = jnp.where(valid, seg, gcap)
    vmask = _bmask(valid, x)
    if op == "sum":
        return jax.ops.segment_sum(jnp.where(vmask, x, 0), ids,
                                   num_segments=gcap + 1)[:gcap]
    if op == "max":
        return jax.ops.segment_max(jnp.where(vmask, x, _tiny(x.dtype)),
                                   ids, num_segments=gcap + 1)[:gcap]
    if op == "min":
        return jax.ops.segment_min(jnp.where(vmask, x, _huge(x.dtype)),
                                   ids, num_segments=gcap + 1)[:gcap]
    raise ValueError(op)


@functools.lru_cache(maxsize=None)
def _convert_phase2_jit(mesh, gcap: int):
    spec = row_spec(mesh)

    @jax.jit
    def convert_layout(skey, mask, count):
        def body(sk, m, c):
            with jax.named_scope("layout"):
                ukey, sizes, voff, _seg, _g = grouped_layout(sk, m, c[0],
                                                             gcap)
            # the largest group's rows: read only by a traced run
            with jax.named_scope("largest_group"):
                return ukey, sizes, voff, jnp.max(sizes)[None]
        return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=(spec,) * 4)(skey, mask, count)

    return convert_layout


def convert_sharded(skv: ShardedKV, counters=None) -> ShardedKMV:
    """Per-shard sort + boundary detection → grouped frame.  The jitted
    phases are cached per (mesh, gcap) — iterative commands convert every
    round and must not re-trace (see shuffle._phase1_jit).  Exactly ONE
    controller round-trip (the ucounts pull that sizes gcap and becomes
    the host gcounts metadata) — per-op parity with the reference's one
    MPI_Allreduce."""
    mesh = skv.mesh
    counts_dev = jax.device_put(skv.counts.astype(np.int32), row_sharding(mesh))
    bump_dispatch()
    skey, svalue, mask, ucounts = _convert_phase1_jit(mesh)(
        skv.key, skv.value, counts_dev)
    SyncStats.bump()
    with get_tracer().span(names.CONVERT_COUNT_SYNC, cat=names.HOST) as sp:
        gcounts = np.asarray(ucounts).astype(np.int32)
        sp.set(groups=int(gcounts.sum()))
    gcap = round_cap(int(gcounts.max())) if gcounts.max() else 8

    bump_dispatch()
    ukey, nvalues, voffsets, gmax = _convert_phase2_jit(mesh, gcap)(
        skey, mask, counts_dev)
    tracer = get_tracer()
    if tracer.enabled:          # on the ``convert`` op span; one more pull
        tracer.annotate(**{
            names.ATTR_ROWS: int(skv.counts.sum()),
            names.ATTR_GROUPS: int(gcounts.sum()),
            names.ATTR_GROUP_ROWS_MAX: int(np.asarray(gmax).max()),
            **_sort_words(skv.key, skv.value)})
    return ShardedKMV(skv.mesh, ukey, nvalues, voffsets, svalue,
                      gcounts, skv.counts.copy(), key_decode=skv.key_decode,
                      value_decode=skv.value_decode)


def fused_group_body(k, v, nrecv, gcap: int, out_kind: str, reduce_op):
    """THE fused convert(+reduce) shard-local body — composed by the
    plan/ fuser's exchange/local/megafused programs over packed valid
    rows: sort by key, boundary-detect, then either the grouped layout
    (``out_kind='kmv'``) or a segment reduce to one pair per group
    (``out_kind='kv'``) — the SAME shard-local bodies the eager tier
    jits (`_local_sort`/`_boundary`/`grouped_layout`/
    `segment_reduce_rows`).

    Returns ``(..., meta)`` where meta = [groups, nrecv, overflow];
    ``overflow`` is always 0 (a sort drops no group) and keeps the
    position the fused executors' host-side validation reads."""
    sk, sv, valid = _local_sort(k, v, nrecv)
    mask = _boundary(sk, valid)
    ukey, sizes, voff, seg, g = grouped_layout(sk, mask, nrecv, gcap)
    meta = jnp.stack([g, nrecv.astype(jnp.int32),
                      jnp.zeros((), jnp.int32)])
    if out_kind == "kmv":
        return ukey, sizes, voff, sv, meta
    if reduce_op == "count":
        return ukey, sizes.astype(jnp.int64), meta
    if reduce_op == "first":
        uval = jnp.zeros((gcap,) + sv.shape[1:], sv.dtype).at[
            jnp.where(mask, seg, gcap)].set(sv, mode="drop")
        return ukey, uval, meta
    return ukey, segment_reduce_rows(sv, seg, valid, gcap, reduce_op), \
        meta


# ---------------------------------------------------------------------------
# segment reductions over a ShardedKMV (the registered-kernel reduce tier)
# ---------------------------------------------------------------------------

def _local_segment_ids(voff, nval, vcap: int):
    """Per-shard value-row → group-id mapping (jittable, shard-local)."""
    starts = jnp.zeros(vcap + 1, jnp.int32).at[voff].add(
        jnp.where(nval > 0, 1, 0).astype(jnp.int32), mode="drop")
    return jnp.cumsum(starts[:vcap]) - 1


def _reduce_jit(mesh, gcap: int, op: str, values_transform):
    """Cache only transform-free reduces (see shuffle._phase1_jit)."""
    if values_transform is not None:
        return _reduce_build(mesh, gcap, op, values_transform)
    return _reduce_cached(mesh, gcap, op, None)


@functools.lru_cache(maxsize=None)
def _reduce_cached(mesh, gcap, op, values_transform):
    return _reduce_build(mesh, gcap, op, values_transform)


def _reduce_build(mesh, gcap: int, op: str, values_transform):
    spec = row_spec(mesh)

    @jax.jit
    def reduce_segments(ukey, nval, voff, values, vcount):
        def body(uk, nv, vo, vals, vc):
            if op == "count":
                with jax.named_scope("reduce"):
                    return uk, nv.astype(jnp.int64)
            vcap = vals.shape[0]
            with jax.named_scope("segment_ids"):
                seg = _local_segment_ids(vo, nv, vcap)
                valid = jnp.arange(vcap) < vc
            with jax.named_scope("reduce"):
                x = (vals if values_transform is None
                     else values_transform(vals))
                return uk, segment_reduce_rows(x, seg, valid, gcap, op)
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(spec, spec, spec, spec, spec),
                             out_specs=(spec, spec))(ukey, nval, voff, values,
                                                     vcount)

    return reduce_segments


def reduce_sharded(kmv: ShardedKMV, op: str = "sum",
                   values_transform: Callable = None) -> ShardedKV:
    """Vectorised reduce: one output pair per group, computed with XLA
    segment ops per shard (count/sum/max/min).  Cached per (mesh, gcap,
    op, transform identity)."""
    if kmv.value_decode is not None and op != "count":
        raise ValueError(
            f"reduce_sharded({op!r}): values are interned byte/object "
            f"ids — arithmetic on them is meaningless; decode to host "
            f"first (only 'count' is value-agnostic)")
    run = _reduce_jit(kmv.mesh, kmv.gcap, op, values_transform)
    vcounts_dev = jax.device_put(kmv.vcounts.astype(np.int32),
                                 row_sharding(kmv.mesh))
    bump_dispatch()
    ukey, out = run(kmv.ukey, kmv.nvalues, kmv.voffsets, kmv.values, vcounts_dev)
    return ShardedKV(kmv.mesh, ukey, out, kmv.gcounts.copy(),
                     key_decode=kmv.key_decode)


# ---------------------------------------------------------------------------
# the combiner: compress of a frame with few distinct keys, without a sort
# ---------------------------------------------------------------------------

# The most distinct keys a shard may hold for `combine_sharded` to fold it;
# past it `compress` is `convert` + `reduce`.  Arithmetic on rows, groups
# and words: the combiner reads the key columns once a key column a key
# found and once more (groups + 1 rounds of masked minima), then key and
# value once a key found (one masked reduction each), so it moves about
#     rows x 4 B x (groups x (key_words + 1) x key_words
#                   + groups x (key_words + value_words)),
# where the sort road orders the rows (`jit_convert_sort`), lays the
# groups out (`jit_convert_layout`) and scatter-adds every row
# (`jit_reduce_segments`) at a cost that does not fall with the groups.
# On the v5e, 2^23 rows a frame (PERF.md §6, PR 50): a u64[n, 2] key with
# a NULL value counted, 0.0039 / 0.0043 / 0.0065 s at 1 / 4 / 16 keys
# where the sort road takes 0.0818 s at any number of them (19 times at
# four keys; the combiner stays ahead to about 450); two u32 key words
# with six int64 summed, 0.0107 / 0.0202 s at 4 / 16 keys against 1.037 s
# (97 times).  So the edge is not where the fold stops winning but where
# a FAILED probe stops being cheap: a frame of many keys pays the rounds
# of minima and one more count sync before it sorts, 0.0062 s for 17
# rounds there, 7.6 % of the sort it then runs; every round more is
# 0.0004 s more.  (`COMBINE_SAMPLE` below takes most of that away for a
# frame whose first rows already show it.)
COMBINE_GROUPS = 16
COMBINE_OPS = ("sum", "count", "min", "max")
# rows a fold reads at a time: what a deferred scan's body makes of a tile
# (and a 64-bit column's two halves, which the chip keeps apart) is a
# tile's worth of temporaries, not the block's
COMBINE_TILE = 1 << 22
# rows at the head of a block that are asked first: a frame of many keys
# shows more than COMBINE_GROUPS of them there, and the probe of the whole
# block (COMBINE_GROUPS + 1 rounds over every row) is not paid
COMBINE_SAMPLE = 1 << 16


def combines(op, frame) -> bool:
    """Whether ``compress`` by the registered reduce ``op`` may take the
    combiner for ``frame``, by what the frame itself says: a mesh frame of
    plain integer values (an interned or float value, a callback, ``cull``
    go through ``convert``).  The distinct-key count decides the rest, in
    :func:`combine_sharded`."""
    if op not in COMBINE_OPS or not isinstance(frame, ShardedKV) \
            or frame.value_decode is not None:
        return False
    key, value = frame.row_types
    return value.dtype.kind in "iu" and value.ndim <= 2 and key.ndim <= 2


def _at(x, j):
    """Row ``j`` (traced) of a small array: a dynamic slice, no gather."""
    return jax.lax.dynamic_index_in_dim(x, j, 0, keepdims=False)


def _put(x, row, j):
    """``x`` with row ``j`` (traced) replaced: a dynamic update slice, no
    scatter."""
    return jax.lax.dynamic_update_index_in_dim(x, row, j, 0)


def _lex_gt(cols, last):
    """Rows whose key (the columns, the first the most significant) is
    above ``last``."""
    gt = jnp.zeros(cols[0].shape, bool)
    for c, l in zip(reversed(cols), reversed(last)):
        gt = (c > l) | ((c == l) & gt)
    return gt


def _distinct_keys(cols, valid, gmax: int, vary):
    """A shard's distinct keys in ascending order, as far as ``gmax + 1``
    of them: ``(columns each [gmax + 1], found)``.  One masked minimum a
    key column a key: the least key above the last one found, until none
    is left or ``gmax + 1`` are found (``found`` = ``gmax + 1`` says "more
    than ``gmax``").  Nothing is sorted, nothing written but the keys.
    ``vary`` makes a loop's first carry a per-shard value."""
    def cond(s):
        return (s[0] <= gmax) & ~s[1]

    def step(s):
        i, _, last, ukeys = s
        cand = valid & ((i == 0) | _lex_gt(cols, last))
        found = jnp.any(cand)
        key = []
        for c in cols:
            m = jnp.min(jnp.where(cand, c, _huge(c.dtype)))
            cand = cand & (c == m)
            key.append(m)
        ukeys = tuple(_put(u, jnp.where(found, k, 0), i)
                      for u, k in zip(ukeys, key))
        return i + found.astype(jnp.int32), ~found, tuple(key), ukeys

    zero = tuple(jnp.zeros((), c.dtype) for c in cols)
    i, _, _, ukeys = jax.lax.while_loop(
        cond, step, vary((jnp.int32(0), jnp.bool_(False), zero,
                          tuple(jnp.zeros(gmax + 1, c.dtype) for c in cols))))
    return ukeys, i


_FOLD = {   # op -> (a group's rows folded, two folds merged, the identity)
    "sum": (lambda x, m: jnp.sum(jnp.where(m, x, 0), axis=0, dtype=x.dtype),
            jnp.add, lambda dt: jnp.zeros((), dt)),
    "max": (lambda x, m: jnp.max(jnp.where(m, x, _tiny(x.dtype)), axis=0),
            jnp.maximum, lambda dt: _tiny(dt)),
    "min": (lambda x, m: jnp.min(jnp.where(m, x, _huge(x.dtype)), axis=0),
            jnp.minimum, lambda dt: _huge(dt)),
}


def _fold(tile_rows, ntiles: int, ukeys, found, cap: int, op: str, vary):
    """``([cap, ...] one row a key found, [cap] its rows)``: a key's rows
    are the valid rows whose columns equal it, folded by one masked
    reduction, a key after another, a tile of rows after another
    (``tile_rows(t) -> (key columns, value, valid)``).  The value rows are
    read where they lie: no order, no gather, no scatter; integers stay
    what they are."""
    count = op == "count"
    like = tile_rows(jnp.int32(0))[1]       # the value's dtype and shape
    dtype = jnp.int64 if count else like.dtype
    shape = (cap,) if count else (cap,) + like.shape[1:]

    def tile(t, acc):
        cols, value, valid = tile_rows(t)

        def one(j, acc):
            out, sizes = acc
            m = valid
            for c, u in zip(cols, ukeys):
                m = m & (c == _at(u, j))
            n = jnp.sum(m, dtype=jnp.int32)
            if count:
                r = _at(out, j) + n.astype(jnp.int64)
            else:
                fold, merge, _ = _FOLD[op]
                r = merge(_at(out, j), fold(value, _bmask(m, value)))
            return _put(out, r, j), _put(sizes, _at(sizes, j) + n, j)
        return jax.lax.fori_loop(0, found, one, acc)

    first = jnp.zeros((), dtype) if count else _FOLD[op][2](dtype)
    out, sizes = jax.lax.fori_loop(
        0, ntiles, tile, vary((jnp.full(shape, first, dtype),
                               jnp.zeros(cap, jnp.int32))))
    return jnp.where(rows_below(found, cap, out.ndim), out, 0), sizes


@functools.lru_cache(maxsize=None)
def _combine_jit(mesh, op: str, fn=None, static=(), nextra: int = 0):
    """The combiner's program over a plain frame (``fn`` None: program
    ``jit_combine``) or over a deferred scan's source (``fn`` the scan's
    body, with its static operands and the number of its traced ones:
    ``jit_combine_<body>``, the body applied inside, to all rows for their
    keys and a tile at a time for their values)."""
    spec = row_spec(mesh)
    gmax = COMBINE_GROUPS
    cap = round_cap(gmax)
    axes = mesh_axes(mesh)
    vary = lambda tree: jax.tree.map(
        lambda x: jax.lax.pcast(x, axes, to="varying"), tree)

    def rows_from(k, v, c, ex, start):
        """``(key, value, valid)`` of the block's rows from ``start`` on, as
        long as ``k`` is: the frame's own, or what the body makes."""
        if fn is None:
            return k, v, start + jnp.arange(k.shape[0], dtype=jnp.int32) < c
        with jax.named_scope("kernel"):
            return fn(k, v, c - start, *ex, *static)

    def combine(key, value, count, *extra):
        def body(k, v, c, *ex):
            n = k.shape[0]
            with jax.named_scope("distinct_keys"):
                allk, _, valid = rows_from(k, v, c[0], ex, 0)
                head = min(n, COMBINE_SAMPLE)
                _, few = _distinct_keys(
                    [x[:head] for x in columns(allk)], valid[:head], gmax,
                    vary)
                ukeys, found = _distinct_keys(
                    columns(allk), valid & (few <= gmax), gmax, vary)
                found = jnp.maximum(found, few)
            step = min(n, COMBINE_TILE)

            def tile_rows(t):
                # the last tile ends at the block's end and starts inside
                # the one before it, whose rows it leaves out
                start = jnp.minimum(t * step, n - step).astype(jnp.int32)
                kt, vt, ok = rows_from(
                    jax.lax.dynamic_slice_in_dim(k, start, step, 0),
                    jax.lax.dynamic_slice_in_dim(v, start, step, 0),
                    c[0], ex, start)
                new = start + jnp.arange(step, dtype=jnp.int32) >= t * step
                return columns(kt), vt, ok & new

            with jax.named_scope("fold"):
                # more keys than the rule folds: nothing is folded, the
                # caller sorts
                out, sizes = _fold(tile_rows, -(-n // step), ukeys,
                                   jnp.where(found > gmax, 0, found), cap,
                                   op, vary)
            with jax.named_scope("largest_group"):
                ucols = [_fit(u[:gmax], cap, 0) for u in ukeys]
                ukey = ucols[0] if allk.ndim == 1 else jnp.stack(ucols, 1)
                return ukey, out, found[None], jnp.max(sizes)[None]
        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec,) * 3 + (P(),) * nextra,
            out_specs=(spec,) * 4)(key, value, count, *extra)

    if fn is not None:
        from .devkernels import _body_name
        combine.__name__ = "combine_" + _body_name(fn)
    return jax.jit(combine)


def combine_sharded(skv: ShardedKV, op: str):
    """``compress`` by a registered segment reduce without ordering the
    rows: each shard's distinct keys found by masked minima, then one
    masked reduction a key (program ``jit_combine``).  Returns the KV
    ``convert_sharded`` + ``reduce_sharded`` would (a row a distinct key a
    shard, keys ascending, the same values bit for bit, the same counts),
    or None when some shard holds more than `COMBINE_GROUPS` distinct keys:
    the one count sync this op pays says so, and the caller takes the sort
    road.  Local to a shard, as the reference's compress is
    (src/mapreduce.cpp:749-851): nothing is exchanged.  A deferred scan
    (`devkernels.ScannedKV`) is folded from its source's rows, where they
    lie (``jit_combine_<body>``), and never made, ordered or packed."""
    mesh = skv.mesh
    if skv.scan is None:
        run, rows, extra = _combine_jit(mesh, op), skv, ()
    else:
        fn, static, extra = skv.scan
        run = _combine_jit(mesh, op, fn, static, len(extra))
        rows = skv.source
    counts_dev = jax.device_put(rows.counts.astype(np.int32),
                                row_sharding(mesh))
    bump_dispatch()
    ukey, out, found, most = run(rows.key, rows.value, counts_dev, *extra)
    SyncStats.bump()
    tracer = get_tracer()
    with tracer.span(names.COMBINE_COUNT_SYNC, cat=names.HOST) as sp:
        gcounts = np.asarray(found).astype(np.int32)
        sp.set(groups=int(gcounts.sum()))
    combined = int(gcounts.max(initial=0)) <= COMBINE_GROUPS
    if tracer.enabled:          # on the ``compress`` op span
        key, value = skv.row_types
        tracer.annotate(**{
            names.ATTR_ROWS: int(skv.counts.sum()),
            names.ATTR_KEY_WORDS: sort_operands(key),
            names.ATTR_VALUE_WORDS: sort_operands(value),
            names.ATTR_COMBINED: int(combined)})
        if combined:
            tracer.annotate(**{
                names.ATTR_GROUPS: int(gcounts.sum()),
                names.ATTR_GROUP_ROWS_MAX: int(np.asarray(most).max(
                    initial=0))})
    if not combined:
        return None
    return ShardedKV(mesh, ukey, out, gcounts, key_decode=skv.key_decode)


def _bmask(valid, x):
    return valid if x.ndim == 1 else valid[:, None]


def _tiny(dtype):
    v = (jnp.finfo(dtype).min if jnp.issubdtype(dtype, jnp.floating)
         else jnp.iinfo(dtype).min)
    return jnp.array(v, dtype=dtype)  # typed scalar: u64 max overflows weak int


def _huge(dtype):
    v = (jnp.finfo(dtype).max if jnp.issubdtype(dtype, jnp.floating)
         else jnp.iinfo(dtype).max)
    return jnp.array(v, dtype=dtype)


@functools.lru_cache(maxsize=None)
def _first_jit(mesh):
    spec = row_spec(mesh)

    @jax.jit
    def group_first(ukey, voff, values):
        def body(uk, vo, vals):
            with jax.named_scope("gather"):
                idx = jnp.minimum(vo, vals.shape[0] - 1)
                return uk, jnp.take(vals, idx, axis=0)
        return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=(spec, spec))(ukey, voff, values)

    return group_first


def first_sharded(kmv: ShardedKMV) -> ShardedKV:
    """One output pair per group with the group's FIRST value (dedupe/cull)."""
    bump_dispatch()
    uk, v = _first_jit(kmv.mesh)(kmv.ukey, kmv.voffsets, kmv.values)
    return ShardedKV(kmv.mesh, uk, v, kmv.gcounts.copy(),
                     key_decode=kmv.key_decode,
                     value_decode=kmv.value_decode)


@functools.lru_cache(maxsize=None)
def _sortmv_jit(mesh, descending: bool):
    spec = row_spec(mesh)

    @jax.jit
    def sort_multivalues(voff, nval, values, vcount):
        def body(vo, nv, vals, vc):
            vcap = vals.shape[0]
            with jax.named_scope("segment_ids"):
                seg = _local_segment_ids(vo, nv, vcap)
                valid = jnp.arange(vcap) < vc
            with jax.named_scope("sort"):
                v = vals if vals.ndim == 1 else vals[:, 0]
                keyv = _desc_key(v) if descending else v
                order = jnp.lexsort((keyv, seg, ~valid))
            with jax.named_scope("take"):
                return jnp.take(vals, order, axis=0)
        return jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 4,
                             out_specs=spec)(voff, nval, values, vcount)

    return sort_multivalues


def sort_multivalues_sharded(kmv: ShardedKMV,
                             descending: bool = False) -> ShardedKMV:
    """Sort values within each group, per shard (reference
    src/mapreduce.cpp:2210-2352).  Stable lexsort by (validity, group,
    value) keeps every group in its original [voffset, voffset+nvalue)
    region, so offsets/sizes are unchanged."""
    vcounts_dev = jax.device_put(kmv.vcounts.astype(np.int32),
                                 row_sharding(kmv.mesh))
    bump_dispatch()
    values = _sortmv_jit(kmv.mesh, descending)(
        kmv.voffsets, kmv.nvalues, kmv.values, vcounts_dev)
    return ShardedKMV(kmv.mesh, kmv.ukey, kmv.nvalues, kmv.voffsets, values,
                      kmv.gcounts.copy(), kmv.vcounts.copy(),
                      key_decode=kmv.key_decode,
                      value_decode=kmv.value_decode)


def _desc_key(v):
    """A column whose ascending order is ``v``'s descending order, and
    the way back (applied twice it is ``v``): the bitwise complement of
    an integer of either sign (no value overflows, as ``-v`` does at
    the least int), the negative of a float."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        return -v
    return ~v


# ---------------------------------------------------------------------------
# per-shard sort (reference sort_keys/sort_values are rank-local)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sort_jit(mesh, by: str, descending: bool):
    spec = row_spec(mesh)

    @jax.jit
    def sort_rows(key, value, count):
        def body(k, v, c):
            # ONE sort (ops/sort.sort_carrying): the sorted-by column's
            # words are its keys, behind a flag that puts the rows past
            # the count last whatever they hold; the other column rides
            # or comes by the row index, as its width says.  Descending
            # is the same sort on complemented words: a reversal by
            # scatter is what the chip does worst (PERF.md §6, PR 25)
            # (the sort and the take by the row index are steps of their
            # own inside sort_carrying; ``keys`` is the words around them)
            col, other = (k, v) if by == "key" else (v, k)
            with jax.named_scope("keys"):
                past = (jnp.arange(col.shape[0], dtype=jnp.int32)
                        >= c[0]).astype(jnp.uint8)
                cols = columns(col)
                if descending:
                    cols = [_desc_key(x) for x in cols]
                (_, *scols), (sother,) = sort_carrying((past, *cols),
                                                       (other,))
                if descending:
                    scols = [_desc_key(x) for x in scols]
                scol = (scols[0] if col.ndim == 1
                        else jnp.stack(scols, axis=1))
            return (scol, sother) if by == "key" else (sother, scol)
        return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=(spec, spec))(key, value, count)

    return sort_rows


def sort_sharded(skv: ShardedKV, by: str = "key",
                 descending: bool = False) -> ShardedKV:
    counts_dev = jax.device_put(skv.counts.astype(np.int32),
                                row_sharding(skv.mesh))
    tracer = get_tracer()
    if tracer.enabled:          # on the sort_keys / sort_values op span
        col, other = ((skv.key, skv.value) if by == "key"
                      else (skv.value, skv.key))
        tracer.annotate(**{
            names.ATTR_RECORDS: int(skv.counts.sum()),
            **_sort_words(col, other),
            names.ATTR_HBM_ROW_BYTES: round(
                (skv.key.on_device_size_in_bytes()
                 + skv.value.on_device_size_in_bytes())
                / max(1, skv.key.shape[0]), 3)})
    bump_dispatch()
    # the op's one sync is on completion, nothing is pulled (as
    # sharded.place_rows): the op span runs from dispatch to ready, and
    # the first reader of the result (a pull, a top-N) waits for no sort
    k, v = jax.block_until_ready(
        _sort_jit(skv.mesh, by, descending)(skv.key, skv.value, counts_dev))
    SyncStats.bump()
    return ShardedKV(skv.mesh, k, v, skv.counts.copy(),
                     key_decode=skv.key_decode,
                     value_decode=skv.value_decode)


# ---------------------------------------------------------------------------
# device sort of INTERNED byte/object columns by rank surrogate
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sort_interned_jit(mesh, nrows: int, by: str, descending: bool):
    shard = NamedSharding(mesh, row_spec(mesh))
    nprocs = mesh_axis_size(mesh)
    cap = nrows // nprocs

    @functools.partial(jax.jit, out_shardings=(shard, shard))
    def sort_interned(key, value, counts, ids_by_id, rank_of):
        col = key if by == "key" else value
        with jax.named_scope("rank"):
            idx = jnp.arange(nrows)
            valid = (idx % cap) < counts[idx // cap]
            pos = jnp.clip(jnp.searchsorted(ids_by_id, col), 0,
                           ids_by_id.shape[0] - 1)
            rank = jnp.take(rank_of, pos)
        with jax.named_scope("sort"):
            order = jnp.lexsort((rank, ~valid))   # valid first, GLOBAL order
            if descending:
                total = jnp.sum(counts)
                r = jnp.arange(nrows)
                ppos = jnp.where(r < total, total - 1 - r, r)
                inv = jnp.zeros(nrows, order.dtype).at[ppos].set(
                    r, mode="drop")
                order = jnp.take(order, inv)
        with jax.named_scope("take"):
            return (jnp.take(key, order, axis=0),
                    jnp.take(value, order, axis=0))

    return sort_interned


def sort_interned_sharded(skv: ShardedKV, by: str = "key",
                          descending: bool = False) -> ShardedKV:
    """GLOBAL sort of an INTERNED byte/object column without pulling the
    dataset to host (VERDICT r2 #7): the id→rank permutation builds once
    from the (small, controller-side) decode table — ranked by the
    decoded bytes / pickles, the host tiers' comparison order — and one
    jitted lexsort orders the whole mesh dataset by the rank surrogate
    (GSPMD inserts the collectives).  Matches the host path's global
    lexicographic output; valid rows pack to the front shards."""
    table = skv.key_decode if by == "key" else skv.value_decode
    cached = getattr(table, "_rank_cache", None)
    if cached is not None and cached[0] == len(table):
        _, ids_by_id, rank_of = cached
    else:
        from ..ops.sort import argsort_column
        ids = np.fromiter(table.keys(), np.uint64, len(table))
        by_bytes = argsort_column(_decode_col(table, ids))
        rank = np.empty(len(ids), np.int64)
        rank[by_bytes] = np.arange(len(ids))
        by_id = np.argsort(ids)
        # pad the replicated lookup to a pow2 so recompiles stay bounded
        m = len(ids)
        mcap = round_cap(m)
        ids_by_id = np.full(mcap, np.uint64(0xFFFFFFFFFFFFFFFF),
                            np.uint64)
        rank_of = np.full(mcap, m, np.int64)
        ids_by_id[:m] = ids[by_id]
        rank_of[:m] = rank[by_id]
        # memoised on the table itself (rebuilt only if it grows —
        # iterative sorts over an unchanged dictionary pay once)
        table._rank_cache = (len(table), ids_by_id, rank_of)
    rep = NamedSharding(skv.mesh, P())
    nrows = skv.key.shape[0]
    bump_dispatch()
    k, v = _sort_interned_jit(skv.mesh, nrows, by, descending)(
        skv.key, skv.value, jnp.asarray(skv.counts.astype(np.int32)),
        jax.device_put(ids_by_id, rep), jax.device_put(rank_of, rep))
    # valid rows are globally packed to the front: first shards full
    total = int(skv.counts.sum())
    cap = nrows // mesh_axis_size(skv.mesh)
    new_counts = np.clip(total - np.arange(len(skv.counts)) * cap,
                         0, cap).astype(np.int32)
    return ShardedKV(skv.mesh, k, v, new_counts,
                     key_decode=skv.key_decode,
                     value_decode=skv.value_decode)


# ---------------------------------------------------------------------------
# keyed join of two datasets (MapReduce.join)
# ---------------------------------------------------------------------------

def value_width(v) -> int:
    """Words a value row holds: ``[n]`` is one."""
    return 1 if v.ndim == 1 else v.shape[1]


def join_rows_body(pk, pc, bk, bc):
    """A shard's inner join, up to the rows' values: ``(order, where,
    [joined rows, duplicate build keys])`` over the ``bcap + pcap`` rows
    of both sides in key order (``where``: `join_take_body`).

    ONE sort orders both sides' KEYS together, and nothing rides it: its
    operands are the key's columns and, as the last key, a row's tag,
    which is its own index in (build block ++ probe block), or that plus
    the blocks' length for a row past its count.  Tags are unique, so the
    sort need not be stable; in a run of equal keys the build row (the
    lowest tag) comes first, the probe rows follow in their order, and
    rows past a count come last in their run and are nobody's partner.
    Two int32 prefix maxima over the sorted position say where a row's
    run starts and where the last build row at or before it lies: a probe
    row has a partner when the second is not before the first.  A build
    key that occurs twice is a build row whose predecessor is a build row
    of the same key: counted, never chosen.  ``order`` brings the joined
    probe rows' positions to the front (ops/sort.front_order: a second
    sort, of one operand).  Values are taken afterwards, for the joined
    rows alone (`join_take_body`): a sort that carried them compiled in
    486 s at 3.6 x 10^7 rows where this one takes about 100 (PERF.md §6,
    PR 43), and its gather of the partners' values ran over every row."""
    pcap, bcap = pk.shape[0], bk.shape[0]
    n = bcap + pcap
    with jax.named_scope("sort_sides"):
        row = jnp.arange(n, dtype=jnp.int32)
        valid = jnp.where(row >= bcap, row - bcap < pc, row < bc)
        (*scols, tag), _ = sort_carrying(
            (*columns(jnp.concatenate([bk, pk])),
             jnp.where(valid, row, row + n)), stable=False)
    with jax.named_scope("partners"):
        isb = tag < bcap
        isp = (tag >= bcap) & (tag < n)
        same = jnp.ones(n - 1, bool)
        for c in scols:
            same = same & (c[1:] == c[:-1])
        same = jnp.concatenate([jnp.zeros(1, bool), same])
        run = jax.lax.cummax(jnp.where(same, 0, row))
        lastb = jax.lax.cummax(jnp.where(isb, row, -1))
        matched = isp & (lastb >= run)
        twice = isb & same & jnp.concatenate([jnp.zeros(1, bool), isb[:-1]])
    with jax.named_scope("joined_rows_first"):
        order, joined = front_order(matched)
        return order, jnp.stack([tag, lastb], axis=1), jnp.stack(
            [joined, jnp.sum(twice, dtype=jnp.int32)])


def join_take_body(cap: int, order, where, pk, pv, bv):
    """The first ``cap`` joined rows of a shard: ``(key, probe value ++
    build value)``.  A joined row's sorted position (``order``) gives, in
    ``where`` (a row's tag and the position of the last build row at or
    before it, side by side), its probe row and its partner's position,
    and that position the partner's build row; key and values are taken
    from the blocks they came in, the probe's key and value together
    (ops/sort.take_together).  Four gathers of ``cap`` rows, whatever the
    blocks hold."""
    pcap, bcap = pk.shape[0], bv.shape[0]
    words = lambda v: v[:, None] if v.ndim == 1 else v
    with jax.named_scope("positions"):
        here = jnp.take(where, jnp.minimum(order[:cap], where.shape[0] - 1),
                        axis=0)
        src = jnp.clip(here[:, 0] - bcap, 0, pcap - 1)
        partner = jnp.clip(
            jnp.take(where[:, 0], jnp.maximum(here[:, 1], 0)), 0, bcap - 1)
    with jax.named_scope("take"):
        key, pvalue = take_together(src, pk, words(pv))
        return key, jnp.concatenate(
            [pvalue, jnp.take(words(bv), partner, axis=0)], axis=1)


@functools.lru_cache(maxsize=None)
def _join_jit(mesh):
    spec = row_spec(mesh)

    def join_rows(pkey, pcount, bkey, bcount):
        def body(pk, pc, bk, bc):
            return join_rows_body(pk, pc[0], bk, bc[0])
        return jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 4,
                             out_specs=(spec,) * 3)(
            pkey, pcount, bkey, bcount)

    return jax.jit(join_rows)


@functools.lru_cache(maxsize=None)
def _join_take_jit(mesh, cap: int):
    spec = row_spec(mesh)

    def join_take(order, where, pkey, pvalue, bvalue):
        return jax.shard_map(functools.partial(join_take_body, cap),
                             mesh=mesh, in_specs=(spec,) * 5,
                             out_specs=(spec, spec))(
            order, where, pkey, pvalue, bvalue)

    rows = row_sharding(mesh)
    return jax.jit(join_take, out_shardings=(rows, rows))


def join_sharded(probe: ShardedKV, build: ShardedKV):
    """Inner join of two mesh frames whose equal keys share a shard:
    ``(frame of the joined rows, duplicate build keys)``; no frame when
    there is a duplicate.  ``jit_join_rows`` a shard, then ONE controller
    round-trip, the pull of every shard's two counts, then
    ``jit_join_take`` at the capacity the joined rows need."""
    mesh = probe.mesh
    put = lambda c: jax.device_put(c.astype(np.int32), row_sharding(mesh))
    tracer = get_tracer()
    if tracer.enabled:          # on the ``join`` op span
        taken = value_width(probe.value) + value_width(build.value)
        tracer.annotate(**{
            names.ATTR_KEY_WORDS: sort_operands(probe.key) + 1,
            names.ATTR_RODE_WORDS: 0, names.ATTR_TAKEN_WORDS: taken,
            names.ATTR_HBM_ROW_BYTES: round(
                (probe.key.on_device_size_in_bytes()
                 + probe.value.on_device_size_in_bytes())
                / max(1, probe.key.shape[0]), 3)})
    bump_dispatch()
    order, where, meta = _join_jit(mesh)(
        probe.key, put(probe.counts), build.key, put(build.counts))
    SyncStats.bump()
    meta = np.asarray(meta).reshape(-1, 2)
    counts, twice = meta[:, 0].astype(np.int32), int(meta[:, 1].sum())
    if twice:
        return None, twice
    bump_dispatch()
    key, value = _join_take_jit(mesh, front_cap(counts, probe.cap))(
        order, where, probe.key, probe.value, build.value)
    return ShardedKV(mesh, key, value, counts), 0
