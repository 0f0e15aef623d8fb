"""Device twins of the OINK graph kernels — shard-resident iteration.

Round 1 ran every graph-command callback by pulling ShardedKV/ShardedKMV to
host numpy each round (``oink/kernels.py`` ``host_kv``/``host_kmv``) — the
mesh shuffled on device but computed on the controller, which caps scaling
at the controller's memory and PCIe (VERDICT r1 #4).  This module gives the
iterative commands (cc_find, luby_find, sssp, tri_find, degree …) a
*device tier*: each batch kernel has a per-shard jittable body running
under ``shard_map``, so a whole iteration is shuffle → segment ops →
emit, all in HBM; the only host traffic is the per-op row counts — the
same scalars the reference Allreduces after every op
(``src/mapreduce.cpp:557-558``).

Kernel bodies follow one convention: they receive the shard's padded
blocks and return ``(key_rows, value_rows, valid_mask)`` of one static
shape; the wrapper brings the valid rows to the front by one payload sort
keyed by the row index (:func:`_pack`: emission order within a shard is
kept, zero rows follow), counts them, and wraps a new :class:`ShardedKV`.
Row counts per shard are data-dependent — the pack + count IS the TPU
version of the reference's "emit into the open KV page".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .group import _local_segment_ids
from .mesh import mesh_axes, mesh_axis_size, row_sharding, row_spec
from ..ops.sort import front_order, sort_carrying, take_together
from .sharded import (ShardedKMV, ShardedKV, SyncStats, fill_counts,
                      front_cap, round_cap, rows_below, window_rows)

U64MAX = np.uint64(0xFFFFFFFFFFFFFFFF)


def is_sharded_kv(fr) -> bool:
    return isinstance(fr, ShardedKV)


def is_sharded_kmv(fr) -> bool:
    return isinstance(fr, ShardedKMV)


def _body_name(fn) -> str:
    """A kernel body's name as the device trace should show it:
    ``_edge_upper_dev`` → ``edge_upper``."""
    name = getattr(fn, "__name__", type(fn).__name__)
    return name.strip("_").removesuffix("_dev")


def _pack(ok, ov, valid):
    """The rows flagged in ``valid`` first, in their emission order, zero
    rows after them, and their count as ``int32[1]``.

    ONE unstable ``lax.sort`` keyed by the row index (kept) or the block's
    length (dropped), the rows riding it as payloads
    (:func:`~..ops.sort.sort_carrying`: a column rides within
    ``RIDE_WORDS``; a float64 value or a wider row comes by the sorted row
    index and one ``take``).  The kept rows' keys are distinct, so the
    order is the stable one; the dropped rows tie, and what they carried
    comes back as zeros.  No prefix sum and no scatter: on the v5e
    ``edge_upper``'s 8.4 M rows of 17 bytes take 0.0327 s this way and
    took 0.969 s by ``cumsum`` and two ``.at[].set(mode="drop")`` (PERF.md
    §6, PR 49; the CPU backend says the opposite, ~20x).  Inputs that are
    already front-packed never need it: :func:`_append`."""
    n = valid.shape[0]
    row = jnp.arange(n, dtype=jnp.int32)
    count = jnp.sum(valid, dtype=jnp.int32)
    _, rows = sort_carrying((jnp.where(valid, row, n),), (ok, ov),
                            stable=False)
    okey, oval = (jnp.where(rows_below(count, n, x.ndim), x,
                            jnp.zeros((), x.dtype)) for x in rows)
    return okey, oval, count[None]


@functools.lru_cache(maxsize=None)
def _skv_map_jit(mesh, fn, static, nextra):
    spec = row_spec(mesh)

    def run(key, value, count, *extra):
        def body(k, v, c, *ex):
            with jax.named_scope("kernel"):
                out = fn(k, v, c[0], *ex, *static)
            with jax.named_scope("pack"):
                return _pack(*out)
        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec) + (P(),) * nextra,
            out_specs=(spec, spec, spec))(key, value, count, *extra)

    # one program per kernel body, named for it (obs/names.KV_MAP_PREFIX)
    run.__name__ = "kv_map_" + _body_name(fn)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _skv_rows_jit(mesh, fn, static, nextra, scan: bool):
    """The program of :func:`skv_each` (``scan`` False: the body's rows as
    they lie) or :func:`skv_scan` (True: beside them the order of the rows
    the body keeps, and their count)."""
    spec = row_spec(mesh)

    def run(key, value, count, *extra):
        def body(k, v, c, *ex):
            with jax.named_scope("kernel"):
                out = fn(k, v, c[0], *ex, *static)
            if not scan:
                return out
            with jax.named_scope("pack"):
                order, kept = front_order(out[2])
                return out[0], out[1], order, kept[None]
        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec) + (P(),) * nextra,
            out_specs=(spec,) * (4 if scan else 2))(key, value, count, *extra)

    # one program per kernel body (obs/names.KV_MAP_PREFIX, KV_SCAN_PREFIX)
    run.__name__ = ("kv_scan_" if scan else "kv_map_") + _body_name(fn)
    return jax.jit(run)


def skv_each(skv: ShardedKV, fn, static=(), extra=()) -> ShardedKV:
    """:func:`skv_map` for a body that keeps every row, ``fn(key, value,
    count, *extra, *static) -> (okey, ovalue)``: a re-keying, a
    projection.  The rows stay where they are and the counts are the
    source's, so nothing is packed and nothing is pulled.  Plain numeric
    frames only."""
    _check_decodes(skv, False, "skv_each")
    counts = jax.device_put(skv.counts.astype(np.int32),
                            row_sharding(skv.mesh))
    k, v = _skv_rows_jit(skv.mesh, fn, tuple(static), len(extra), False)(
        skv.key, skv.value, counts, *extra)
    return ShardedKV(skv.mesh, k, v, skv.counts.copy())


@functools.lru_cache(maxsize=None)
def _take_rows_jit(mesh, cap: int):
    spec = row_spec(mesh)

    def take_rows(key, value, order):
        def body(k, v, o):
            with jax.named_scope("take"):
                at = jnp.minimum(o[:cap], k.shape[0] - 1)
                return take_together(at, k, v)
        return jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=(spec, spec))(key, value, order)

    rows = row_sharding(mesh)
    return jax.jit(take_rows, out_shardings=(rows, rows))


def _check_decodes(fr, preserve_decodes: bool, what: str):
    """Interned byte/object ids look like plain numbers inside a kernel
    body; silently doing arithmetic on them is the bug reduce_sharded
    already guards against (ADVICE r3: the kernel-map path did not).
    ``preserve_decodes=True`` is the caller's assertion that the kernel
    treats ids as opaque and keeps them in the same id space, so the
    tables stay valid on the output frame."""
    if preserve_decodes:
        return fr.key_decode, fr.value_decode
    if fr.key_decode is not None or fr.value_decode is not None:
        which = [n for n, t in (("key", fr.key_decode),
                                ("value", fr.value_decode)) if t is not None]
        raise ValueError(
            f"{what}: {'/'.join(which)} entries are interned byte/object "
            f"ids — a numeric kernel over them is meaningless; decode to "
            f"host first, or pass preserve_decodes=True if the kernel "
            f"treats them as opaque ids")
    return None, None


def skv_map(skv: ShardedKV, fn, static=(), extra=(),
            preserve_decodes: bool = False) -> ShardedKV:
    """Run a per-shard KV kernel body ``fn(key, value, count, *extra,
    *static) → (okey, ovalue, valid)`` and pack the result into a new
    ShardedKV.  ``static`` values are jit constants (shapes, caps);
    ``extra`` values are TRACED replicated operands (seeds, thresholds) —
    varying them re-uses the compiled kernel.  Frames carrying decode
    tables are rejected unless ``preserve_decodes`` (see
    :func:`_check_decodes`)."""
    kd, vd = _check_decodes(skv, preserve_decodes, "skv_map")
    counts = jax.device_put(skv.counts.astype(np.int32),
                            row_sharding(skv.mesh))
    k, v, c = _skv_map_jit(skv.mesh, fn, tuple(static), len(extra))(
        skv.key, skv.value, counts, *extra)
    SyncStats.bump()
    return ShardedKV(skv.mesh, k, v, np.asarray(c).astype(np.int32),
                     key_decode=kd, value_decode=vd)


def skv_scan(skv: ShardedKV, fn, static=(), extra=()) -> ShardedKV:
    """:func:`skv_map` for a filter over a large resident frame: the same
    kernel-body convention (``fn -> (okey, ovalue, keep)``), but the rows
    that pass are not packed inside the body's program.  That program
    (``jit_kv_scan_<body>``) writes the body's rows where they lie and
    the order of the kept ones (:func:`front_order`); behind the sync that
    brings their count, ``jit_take_rows`` takes just them, into a frame of
    the capacity they need.  So a scan that keeps a hundredth of a table
    hands the next op a hundredth of its block, and one that keeps half
    gathers half.  The source frame is read, never donated.  Plain numeric
    frames only."""
    _check_decodes(skv, False, "skv_scan")
    counts = jax.device_put(skv.counts.astype(np.int32),
                            row_sharding(skv.mesh))
    k, v, order, c = _skv_rows_jit(
        skv.mesh, fn, tuple(static), len(extra), True)(
        skv.key, skv.value, counts, *extra)
    SyncStats.bump()
    kept = np.asarray(c).astype(np.int32)
    k, v = _take_rows_jit(skv.mesh, front_cap(kept, skv.cap))(k, v, order)
    return ShardedKV(skv.mesh, k, v, kept)


@functools.lru_cache(maxsize=None)
def _skv_keep_jit(mesh, fn, static, nextra):
    """The program of :func:`skv_keep`: the count of the rows the body
    keeps.  Nothing else of the body is an output, so nothing else of it
    is computed."""
    spec = row_spec(mesh)

    def run(key, value, count, *extra):
        def body(k, v, c, *ex):
            with jax.named_scope("kernel"):
                keep = fn(k, v, c[0], *ex, *static)[2]
            with jax.named_scope("pack"):   # what is left of it: the count
                return jnp.sum(keep, dtype=jnp.int32)[None]
        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec) + (P(),) * nextra,
            out_specs=spec)(key, value, count, *extra)

    run.__name__ = "kv_scan_" + _body_name(fn)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _scan_types(fn, static, key, value, *extra):
    """The key and value a kernel body makes of blocks of these types:
    traced abstractly once a body and shape, not once a job."""
    k, v, _ = jax.eval_shape(
        lambda k, v, c, *ex: fn(k, v, c, *ex, *static), key, value,
        jax.ShapeDtypeStruct((), jnp.int32), *extra)
    return k, v


class ScannedKV(ShardedKV):
    """What :func:`skv_keep` returns: a scan not yet run.  It holds the
    ``source`` frame, the kernel body and its operands (``scan``), and the
    rows the body keeps a shard (``counts``), so it counts as the frame
    the scan would make.  ``MapReduce.compress``'s combiner folds it as it
    is: its program applies the body to the source's rows where they lie,
    a tile at a time (`parallel/group.combine_sharded`), and the mapped
    rows never exist as a block.  To every other reader it is a plain
    :class:`ShardedKV`: the first read of ``key`` or ``value`` runs
    :func:`skv_scan` (the kept rows ordered and taken), once, and the frame
    is a plain one from then on."""

    def __init__(self, source, fn, static, extra, counts):
        self.mesh, self.counts, self.source = source.mesh, counts, source
        self.scan = (fn, tuple(static), tuple(extra))
        self.key_decode = self.value_decode = None
        self._made = None

    @property
    def row_types(self):
        if self._made is not None:
            return self._made
        fn, static, extra = self.scan
        sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        return _scan_types(fn, static, sds(self.source.key),
                           sds(self.source.value), *map(sds, extra))

    def _rows(self):
        if self._made is None:
            fn, static, extra = self.scan
            made = skv_scan(self.source, fn, static, extra)
            self._made, self.scan, self.source = (made.key, made.value), \
                None, None
        return self._made

    key = property(lambda self: self._rows()[0])
    value = property(lambda self: self._rows()[1])

    @property
    def cap(self) -> int:
        rows = self.source.key if self._made is None else self._made[0]
        return rows.shape[0] // self.nprocs

    def nbytes(self) -> int:
        return 0 if self._made is None else sum(
            x.nbytes for x in self._made)


def skv_keep(skv: ShardedKV, fn, static=(), extra=()) -> ScannedKV:
    """:func:`skv_scan` for a filter whose kept rows are folded next
    (``compress``), not read row by row: the same kernel-body convention
    (``fn -> (okey, ovalue, keep)``), but only the kept rows are COUNTED
    now (program ``jit_kv_scan_<body>``, its one output the count, its one
    sync that pull); the scan itself is deferred in a :class:`ScannedKV`.
    A scan that keeps nearly all of a wide table then costs ``compress``
    one more read of the table's columns, where :func:`skv_scan` would
    write the mapped rows, order the kept ones' indices and gather a copy
    of them (about 20 ns a row kept: PERF.md §6, PR 43).  ``fn`` must be
    row-wise (row i of its result from row i of the block and the count):
    the combiner applies it to runs of the block's rows.  Plain numeric
    frames only; the source must outlive the frame unread (a resident
    table does)."""
    _check_decodes(skv, False, "skv_keep")
    counts = jax.device_put(skv.counts.astype(np.int32),
                            row_sharding(skv.mesh))
    c = _skv_keep_jit(skv.mesh, fn, tuple(static), len(extra))(
        skv.key, skv.value, counts, *extra)
    SyncStats.bump()
    return ScannedKV(skv, fn, static, extra,
                     np.asarray(c).astype(np.int32))


@functools.lru_cache(maxsize=None)
def _skmv_map_jit(mesh, fn, static, nextra):
    spec = row_spec(mesh)

    def run(ukey, nval, voff, values, gcount, vcount, *extra):
        def body(uk, nv, vo, vals, gc, vc, *ex):
            with jax.named_scope("kernel"):
                out = fn(uk, nv, vo, vals, gc[0], vc[0], *ex, *static)
            with jax.named_scope("pack"):
                return _pack(*out)
        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec,) * 6 + (P(),) * nextra,
            out_specs=(spec, spec, spec))(ukey, nval, voff, values,
                                          gcount, vcount, *extra)

    run.__name__ = "kmv_map_" + _body_name(fn)
    return jax.jit(run)


def skmv_map(kmv: ShardedKMV, fn, static=(), extra=(),
             preserve_decodes: bool = False) -> ShardedKV:
    """Run a per-shard KMV kernel body ``fn(ukey, nvalues, voffsets,
    values, gcount, vcount, *extra, *static) → (okey, ovalue, valid)`` (a
    vectorised appreduce) and pack into a new ShardedKV.  ``extra`` and
    the decode-table guard as in :func:`skv_map`."""
    kd, vd = _check_decodes(kmv, preserve_decodes, "skmv_map")
    put = lambda x: jax.device_put(x.astype(np.int32), row_sharding(kmv.mesh))
    k, v, c = _skmv_map_jit(kmv.mesh, fn, tuple(static), len(extra))(
        kmv.ukey, kmv.nvalues, kmv.voffsets, kmv.values,
        put(kmv.gcounts), put(kmv.vcounts), *extra)
    SyncStats.bump()
    return ShardedKV(kmv.mesh, k, v, np.asarray(c).astype(np.int32),
                     key_decode=kd, value_decode=vd)


# ---------------------------------------------------------------------------
# shard-resident concat (MapReduce.add of two mesh datasets)
# ---------------------------------------------------------------------------

def _append(a, b, ca, cb, cap: int):
    """``[cap, ...]`` rows: ``a``'s first ``ca``, then ``b``'s first
    ``cb``, zero rows after.  Both inputs are front-packed, so row i of
    the result is ``a[i]`` below ``ca`` and ``b[i - ca]`` from there: a
    select between two :func:`window_rows`, of ``a`` and of ``b`` shifted
    down by ``ca`` rows (behind ``cap`` zero rows) — a copy, no scatter,
    no sort."""
    lead = jnp.zeros((cap,) + b.shape[1:], b.dtype)
    shifted = window_rows(jnp.concatenate([lead, b]), cap - ca, ca + cb,
                          cap)
    return jnp.where(rows_below(ca, cap, a.ndim),
                     window_rows(a, 0, ca, cap), shifted)


@functools.lru_cache(maxsize=None)
def _concat_jit(mesh, cap: int):
    spec = row_spec(mesh)

    def concat_rows(k1, v1, c1, k2, v2, c2):
        def body(ka, va, ca, kb, vb, cb):
            with jax.named_scope("append"):
                return (_append(ka, kb, ca[0], cb[0], cap),
                        _append(va, vb, ca[0], cb[0], cap))
        return jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 6,
                             out_specs=(spec, spec))(k1, v1, c1,
                                                     k2, v2, c2)

    rows = row_sharding(mesh)
    return jax.jit(concat_rows, out_shardings=(rows, rows))


def _merge_decode(ta, tb, what: str):
    """Union two id→key/value intern tables (None means plain ids; mixing
    plain with interned would merge two incompatible spaces).  Tables of
    DIFFERENT kinds (bytes vs object) must be domain-aligned first —
    concat_sharded re-interns the bytes-kind side through the pickle
    domain before calling here (:func:`_align_domains`)."""
    if (ta is None) != (tb is None):
        raise ValueError(
            f"cannot add an interned byte/object-{what}ed mesh dataset "
            f"to a plain one: the merge would span two {what} spaces")
    if not tb:
        return ta
    from ..core.column import InternTable, ShardTables
    if isinstance(ta, ShardTables):
        return ta.merge(tb)
    if isinstance(tb, ShardTables):
        return tb.merge(ta)
    kind = ("object" if "object" in (getattr(ta, "kind", "bytes"),
                                     getattr(tb, "kind", "bytes"))
            else "bytes")
    return InternTable({**ta, **tb}, kind=kind)


@functools.lru_cache(maxsize=None)
def _remap_ids_jit(mesh, m: int):
    """old-id → new-id elementwise remap against a replicated sorted
    lookup of length m (pow2-padded); ids absent from the lookup pass
    through unchanged (padding rows beyond counts)."""
    from jax.sharding import NamedSharding

    @functools.partial(jax.jit,
                       out_shardings=NamedSharding(mesh, row_spec(mesh)))
    def remap_ids(col, old_sorted, new_by_old):
        with jax.named_scope("remap"):
            pos = jnp.clip(jnp.searchsorted(old_sorted, col), 0, m - 1)
            hit = old_sorted[pos] == col
            return jnp.where(hit, new_by_old[pos], col)

    return remap_ids


def _reintern_pickle_domain(col, table, mesh):
    """Re-intern a bytes-kind decode table + its device id column through
    the PICKLE id domain (the object tier's): every stored bytes row
    re-hashes over its pickle — exactly what _SideInterns'
    BytesColumn→ObjectColumn promotion does at ingest
    (parallel/ingest.py) — and the id column remaps old→new in one
    jitted lookup.  Returns (new column, new object-kind table)."""
    import pickle
    from jax.sharding import NamedSharding
    from ..core.column import InternTable, ShardTables, _intern_core
    from .sharded import round_cap
    old_ids = np.fromiter(table.keys(), np.uint64, len(table))
    if not len(old_ids):
        empty = (ShardTables(table.P, kind="object")
                 if isinstance(table, ShardTables)
                 else InternTable(kind="object"))
        return col, empty
    rows = (table.decode_batch(old_ids) if hasattr(table, "decode_batch")
            else [table[int(h)] for h in old_ids])
    probes = [pickle.dumps(r, protocol=4) for r in rows]
    new_ids, uniq, first = _intern_core(probes)
    if isinstance(table, ShardTables):
        newt = ShardTables(table.P, kind="object")
        newt.absorb(uniq, [rows[int(i)] for i in first],
                    probes=[probes[int(i)] for i in first])
    else:
        newt = InternTable(((int(new_ids[int(i)]), rows[int(i)])
                            for i in first), kind="object")
    order = np.argsort(old_ids)
    # pow2-padded replicated lookup (sentinel never matches a real id)
    # so recompiles stay bounded, like sort_interned_sharded's surrogate
    m = len(old_ids)
    mcap = round_cap(m)
    old_sorted = np.full(mcap, U64MAX, np.uint64)
    new_by_old = np.full(mcap, U64MAX, np.uint64)
    old_sorted[:m] = old_ids[order]
    new_by_old[:m] = new_ids[order]
    rep = NamedSharding(mesh, P())
    out = _remap_ids_jit(mesh, mcap)(col,
                                     jax.device_put(old_sorted, rep),
                                     jax.device_put(new_by_old, rep))
    return out, newt


def _align_domains(a: ShardedKV, b: ShardedKV, which: str):
    """Cross-domain id alignment before a concat (ADVICE r5): a
    bytes-kind table's ids hash RAW BYTES while an object-kind table's
    hash PICKLES, so the same logical key concatenated from a bytes-keyed
    and an object-keyed dataset would carry two distinct u64 ids and
    never group.  When the kinds differ, the bytes-kind side re-interns
    through the pickle domain so both datasets share one id space."""
    ta = a.key_decode if which == "key" else a.value_decode
    tb = b.key_decode if which == "key" else b.value_decode
    ca = a.key if which == "key" else a.value
    cb = b.key if which == "key" else b.value
    if ta is None or tb is None or \
            getattr(ta, "kind", "bytes") == getattr(tb, "kind", "bytes"):
        return ca, cb, ta, tb
    if getattr(ta, "kind", "bytes") == "bytes":
        ca, ta = _reintern_pickle_domain(ca, ta, a.mesh)
    else:
        cb, tb = _reintern_pickle_domain(cb, tb, b.mesh)
    return ca, cb, ta, tb


def concat_sharded(a: ShardedKV, b: ShardedKV) -> ShardedKV:
    """Per-shard concatenation of two mesh KV datasets (the device path of
    ``MapReduce::add``, src/mapreduce.cpp:348-374).  Differing intern
    domains (bytes-kind vs object-kind tables) align through the pickle
    domain first, so equal logical keys from the two datasets group after
    the concat (:func:`_align_domains`, ADVICE r5)."""
    assert a.mesh is b.mesh or a.mesh == b.mesh
    ak, bk, kta, ktb = _align_domains(a, b, "key")
    av, bv, vta, vtb = _align_domains(a, b, "value")
    put = lambda s: jax.device_put(s.counts.astype(np.int32),
                                   row_sharding(a.mesh))
    # valid rows are a prefix of every shard and the counts are on the
    # host, so the result's counts and capacity are known before the
    # program runs: the fullest shard's rows, not cap_a + cap_b
    counts = (a.counts + b.counts).astype(np.int32)
    # the op's one sync is on completion, nothing is pulled: a program's
    # buffers are allocated when it is dispatched, so the caller's next
    # program (a sort) would otherwise be allocated beside both inputs
    # and everything that made them (PERF.md §6, PR 27)
    k, v = jax.block_until_ready(
        _concat_jit(a.mesh, round_cap(int(counts.max())))(
            ak, av, put(a), bk, bv, put(b)))
    SyncStats.bump()
    return ShardedKV(a.mesh, k, v, counts,
                     key_decode=_merge_decode(kta, ktb, "key"),
                     value_decode=_merge_decode(vta, vtb, "value"))


# ---------------------------------------------------------------------------
# levelling before an exchange
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _level_jit(mesh, cap: int, xcap: int, ycap: int):
    """Each shard cuts its rows over the level into an ``[xcap]`` block,
    all blocks are gathered (they are small), and each short shard takes
    its run of the gathered overflow behind its own rows: copies only."""
    spec = row_spec(mesh)
    nshards = mesh_axis_size(mesh)
    axes = mesh_axes(mesh)
    ax = axes[0] if len(axes) == 1 else axes

    def level_rows(key, value, keep, over, start, take):
        def body(k, v, kp, ov, st, tk):
            st, tk = st[0], tk[0]               # this shard's row: [P]

            def levelled(x):
                spill = lax.all_gather(
                    window_rows(x, kp[0], ov[0], xcap), ax)   # [P, xcap, ...]
                inc = jnp.zeros((ycap,) + x.shape[1:], x.dtype)
                got = jnp.int32(0)
                for s in range(nshards):
                    inc = _append(inc, window_rows(spill[s], st[s], tk[s],
                                                   xcap), got, tk[s], ycap)
                    got = got + tk[s]
                return _append(x, inc, kp[0], got, cap)

            with jax.named_scope("level"):
                return levelled(k), levelled(v)
        return jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 6,
                             out_specs=(spec, spec))(key, value, keep, over,
                                                     start, take)

    rows = row_sharding(mesh)
    return jax.jit(level_rows, out_shardings=(rows, rows))


def level_sharded(skv: ShardedKV) -> ShardedKV:
    """Even out a frame that an exchange is about to re-home anyway: where
    a row waits for the exchange is free, and the programs of the round
    are compiled at the fullest shard's capacity, rounded up to a power of
    two.  When moving the rows over the even level to the short shards
    lowers that capacity, do it (on the device: the few rows over the
    level are gathered, nothing else moves); otherwise the frame is
    returned as it is.  Never for a frame whose rows must stay where
    they are (``convert`` after ``add`` counts on equal keys sharing a
    shard)."""
    counts = skv.counts.astype(np.int64)
    nshards = skv.nprocs
    level = -(-int(counts.sum()) // nshards)
    if nshards == 1 or round_cap(level) >= round_cap(int(counts.max())):
        return skv
    keep = np.minimum(counts, level)
    over = counts - keep
    give = fill_counts(keep, int(over.sum())).astype(np.int64)
    # shard d takes the run [dst[d], dst[d] + give[d]) of the gathered
    # overflow, in which shard s's rows are [src[s], src[s] + over[s])
    src = np.cumsum(over) - over
    dst = np.cumsum(give) - give
    lo = np.maximum(src[None, :], dst[:, None])
    hi = np.minimum((src + over)[None, :], (dst + give)[:, None])
    take = np.maximum(hi - lo, 0).astype(np.int32)           # [dest, src]
    start = np.where(take > 0, lo - src[None, :], 0).astype(np.int32)
    put = lambda a: jax.device_put(np.asarray(a, np.int32),
                                   row_sharding(skv.mesh))
    new = (keep + give).astype(np.int32)
    k, v = _level_jit(skv.mesh, round_cap(int(new.max())),
                      round_cap(int(over.max())), round_cap(int(give.max())))(
        skv.key, skv.value, put(keep), put(over), put(start), put(take))
    return ShardedKV(skv.mesh, k, v, new, key_decode=skv.key_decode,
                     value_decode=skv.value_decode)


def clone_sharded(skv: ShardedKV) -> ShardedKMV:
    """KV→KMV with every row its own single-value group, per shard
    (the device path of ``MapReduce::clone``, src/mapreduce.cpp:631-652)."""
    P, cap = skv.nprocs, skv.cap
    nv = (np.arange(cap)[None, :] < skv.counts[:, None]).astype(np.int32)
    vo = np.tile(np.arange(cap, dtype=np.int32), (P, 1))
    sharding = row_sharding(skv.mesh)
    from .mesh import device_put_chunked
    return ShardedKMV(skv.mesh, skv.key,
                      device_put_chunked(nv.reshape(-1), sharding),
                      device_put_chunked(vo.reshape(-1), sharding),
                      skv.value, skv.counts.copy(), skv.counts.copy(),
                      key_decode=skv.key_decode,
                      value_decode=skv.value_decode)


# ---------------------------------------------------------------------------
# segment helpers shared by the KMV kernel bodies
# ---------------------------------------------------------------------------

def kmv_row_state(nv, vo, vals, gc, vc):
    """Common prologue: (segment ids [vcap], row-valid [vcap],
    group-valid [gcap])."""
    vcap = vals.shape[0]
    seg = _local_segment_ids(vo, nv, vcap)
    rows_valid = (jnp.arange(vcap) < vc) & (seg >= 0)
    groups_valid = jnp.arange(nv.shape[0]) < gc
    return seg, rows_valid, groups_valid


def seg_min_u64(x, seg, valid, gcap):
    v = jnp.where(valid, x, U64MAX)
    return jax.ops.segment_min(v, jnp.where(valid, seg, gcap),
                               num_segments=gcap + 1)[:gcap]


def seg_max_u64(x, seg, valid, gcap):
    v = jnp.where(valid, x, jnp.uint64(0))
    return jax.ops.segment_max(v, jnp.where(valid, seg, gcap),
                               num_segments=gcap + 1)[:gcap]


def seg_min_with(x, seg, valid, gcap, identity):
    """Segment min with an explicit identity (f64 paths use +inf)."""
    v = jnp.where(valid, x, identity)
    return jax.ops.segment_min(v, jnp.where(valid, seg, gcap),
                               num_segments=gcap + 1)[:gcap]


def seg_lex_min2(a, b, seg, valid, gcap, ident_a, ident_b):
    """Per-segment lexicographic min of (a, b) rows: returns (amin, bmin)
    where amin = min a and bmin = min b among rows attaining amin —
    the shared 'best (dist, pred) per vertex' idiom (sssp)."""
    amin = seg_min_with(a, seg, valid, gcap, ident_a)
    att = valid & (a == jnp.take(amin, jnp.maximum(seg, 0)))
    bmin = seg_min_with(b, seg, att, gcap, ident_b)
    return amin, bmin


# ---------------------------------------------------------------------------
# generic edge/vertex kernel bodies (device twins of oink/kernels.py maps)
# ---------------------------------------------------------------------------

def _null_like(k):
    return jnp.zeros(k.shape[0], jnp.uint8)


def edge_to_vertices_dev(k, v, c):
    valid = jnp.arange(k.shape[0]) < c
    okey = jnp.concatenate([k[:, 0], k[:, 1]])
    vv = jnp.concatenate([valid, valid])
    return okey, _null_like(okey), vv


def edge_to_vertex_dev(k, v, c):
    valid = jnp.arange(k.shape[0]) < c
    return k[:, 0], _null_like(k), valid


def edge_to_vertex_pair_dev(k, v, c):
    valid = jnp.arange(k.shape[0]) < c
    return k[:, 0], k[:, 1], valid


def edge_both_directions_dev(k, v, c):
    valid = jnp.arange(k.shape[0]) < c
    okey = jnp.concatenate([k[:, 0], k[:, 1]])
    oval = jnp.concatenate([k[:, 1], k[:, 0]])
    return okey, oval, jnp.concatenate([valid, valid])


def edge_upper_dev(k, v, c):
    valid = (jnp.arange(k.shape[0]) < c) & (k[:, 0] != k[:, 1])
    lo = jnp.minimum(k[:, 0], k[:, 1])
    hi = jnp.maximum(k[:, 0], k[:, 1])
    return jnp.stack([lo, hi], 1), _null_like(k), valid


def invert_dev(k, v, c):
    valid = jnp.arange(k.shape[0]) < c
    return v, k, valid


def add_weight_dev(k, v, c):
    valid = jnp.arange(k.shape[0]) < c
    return k, jnp.ones(k.shape[0], jnp.float64), valid
