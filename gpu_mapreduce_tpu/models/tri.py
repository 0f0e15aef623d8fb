"""Triangle enumeration — the degree-ordered wedge walk as device programs.

The reference's tri_find is Cohen's MapReduce algorithm
(``oink/tri_find.cpp:43-81``): augment edges with degrees, have the
low-degree endpoint of each edge emit its "angles" (neighbour pairs),
and match angles against the edge list — 6 shuffled MR stages.  The
composed twin lives in oink/commands/tri.py.

This model keeps Cohen's core insight (orient edges from the
lexicographically smaller (degree, id) endpoint, so every vertex's
out-neighbourhood is O(√m) and the total wedge count is Σ k_v(k_v-1)/2
≤ O(m^1.5)) and runs it as four jitted programs over the ranked edge
arrays ``parallel/staging.stage_graph`` leaves on the device.  They are
built from what the chip is good at (PERF.md §6: a scatter costs thirty
sorts, a ``searchsorted`` a gather per round): sorts that carry
payloads, prefix scans, copies, and one gather.

* ``tri_orient`` (once a job): canonical edge keys by a sort (duplicates
  and self loops out), degrees as run lengths of the sorted endpoints
  carried back by a second sort, the (degree, id) orientation, the
  out-neighbour lists by a third sort, and the prefix sum of the wedges
  each list position owns (position p pairs with every later position
  of its vertex).
* ``tri_wedges`` (once a batch of ``_BATCH`` wedge indices, a static
  cap): the owners' offsets are merged with the batch's indices by one
  sort and their (position, neighbour) pairs filled forward by a prefix
  max, a second sort brings the wedges back to index order, the partner
  neighbour is gathered, the wedge keys are merged with the resident
  edge keys by a third sort (a wedge closes when the key before it in
  that order is its own edge), and a fourth sort brings the hits to the
  front.
* ``tri_append`` copies a batch's hits behind those the buffer holds;
  ``tri_rows`` turns the buffer into (centre, u, w) rows of vertex ids.
  Ids below 2^31 name the vertices from ``tri_orient`` on, so that this
  is a copy; wider ids are gathered from the vertex table here.

Each triangle is found exactly once: the wedge (u, w) at centre v exists
only in v's out-neighbourhood, and the edge (u, w) closes it.  The host
reads one scalar after ``tri_orient`` (the wedge count) and one a batch
(its hits, to keep room in the buffer); no edge, wedge or triangle row
crosses to the host.  Vertices are int32 through the walk (``stage_graph``'s
ranks, or ids that fit), so a packed pair of them leaves the low bit of a
u64 free for the merge's tag."""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..parallel.mesh import mesh_axis_size, row_sharding
from ..parallel.sharded import round_cap

_BATCH = 1 << 24        # wedges per tri_wedges execution (bounds peak memory)
_ROWS_STEP = 1 << 20    # a large result's capacity is a multiple of this

_DEAD = np.int32(np.iinfo(np.int32).max)        # rank of a dropped edge row
_SENT = np.uint64(0xFFFFFFFFFFFFFFFF)           # its key: odd, sorts last


def _pack(lo, hi):
    """Two int32 ranks as one u64 that sorts like the pair, low bit 0."""
    return (lo.astype(jnp.uint64) << 33) | (hi.astype(jnp.uint64) << 1)


def _runs(s):
    """First and last index of the run of equal values each element of the
    sorted ``s`` lies in."""
    m = s.shape[0]
    idx = lax.iota(jnp.int32, m)
    edge = s[1:] != s[:-1]
    first = jnp.concatenate([jnp.ones(1, bool), edge])
    last = jnp.concatenate([edge, jnp.ones(1, bool)])
    start = lax.cummax(jnp.where(first, idx, 0))
    end = lax.cummin(jnp.where(last, idx, m - 1), reverse=True)
    return start, end


def tri_orient(src, dst, valid, verts, canonical: bool, by_id: bool):
    """Ranked edge rows → (sorted canonical edge keys, centre and
    out-neighbour of every oriented edge sorted by centre, the exclusive
    prefix sum of the wedges each such position owns, the wedge count, the
    edge count, the largest out-degree).  ``by_id``: the vertices are
    named by their ids from here on (two gathers of the edge rows, once),
    which orders them as their ranks do."""
    ne = src.shape[0]
    if by_id:
        last = verts.shape[0] - 1
        src, dst = (jnp.take(verts, jnp.minimum(x, last)).astype(jnp.int32)
                    for x in (src, dst))
    a, b = jnp.minimum(src, dst), jnp.maximum(src, dst)
    with jax.named_scope("edge_keys"):
        ekey = jnp.sort(jnp.where(valid & (a != b), _pack(a, b), _SENT))
        if not canonical:       # duplicates to the back, by one more sort
            dup = jnp.concatenate([jnp.zeros(1, bool), ekey[1:] == ekey[:-1]])
            ekey = jnp.sort(jnp.where(dup, _SENT, ekey))
    live = ekey != _SENT
    a = jnp.where(live, (ekey >> 33).astype(jnp.int32), _DEAD)
    b = jnp.where(live, ((ekey >> 1) & 0xFFFFFFFF).astype(jnp.int32), _DEAD)
    with jax.named_scope("degrees"):
        # a vertex's degree is the length of its run among the sorted
        # endpoints; each endpoint's position rides the sort and brings the
        # length back to edge order (parallel/staging.py's rule)
        s, origin = lax.sort((jnp.concatenate([a, b]),
                              lax.iota(jnp.int32, 2 * ne)),
                             num_keys=1, is_stable=False)
        start, end = _runs(s)
        _, deg = lax.sort((origin, end - start + 1), num_keys=1,
                          is_stable=False)
        dega, degb = deg[:ne], deg[ne:]
    with jax.named_scope("orient"):
        swap = (dega > degb) | ((dega == degb) & (a > b))
        grp, nbr = lax.sort((jnp.where(swap, b, a), jnp.where(swap, a, b)),
                            num_keys=2, is_stable=False)
    with jax.named_scope("wedge_offsets"):
        start, end = _runs(grp)
        alive = grp != _DEAD
        pos = lax.iota(jnp.int32, ne)
        owns = jnp.where(alive, end - pos, 0).astype(jnp.int64)
        off = jnp.cumsum(owns) - owns
        maxk = jnp.max(jnp.where(alive, end - start + 1, 0))
    return (ekey, grp, nbr, off, jnp.sum(owns), jnp.sum(live.astype(jnp.int32)),
            maxk)


def tri_wedges(ekey, grp, nbr, off, t0, nwedges, batch: int):
    """The wedges with indices ``t0 … t0 + batch`` joined against the edge
    keys: (keys of those that close, their centres, their number), the
    hits at the front of ``[batch]`` arrays."""
    ne = ekey.shape[0]
    with jax.named_scope("expand"):
        # merge the owners' offsets with the batch's wedge indices: an
        # owner sorts before the wedge index equal to its offset, every
        # owner at or before t0 at the very front, those past the batch at
        # the back.  The prefix max of (position, neighbour) then gives a
        # wedge its owner: positions grow with offsets.
        rel = off - t0
        okey = jnp.where(rel <= 0, 0, jnp.where(rel >= batch, 2 * batch,
                                                2 * rel)).astype(jnp.int32)
        tl = lax.iota(jnp.int32, batch)
        pos = lax.iota(jnp.uint64, ne)
        k, pu, c = lax.sort(
            (jnp.concatenate([okey, 2 * tl + 1]),
             jnp.concatenate([(pos << 32) | nbr.astype(jnp.uint64),
                              jnp.zeros(batch, jnp.uint64)]),
             jnp.concatenate([grp, jnp.zeros(batch, jnp.int32)])),
            num_keys=1, is_stable=False)
        pu, c = lax.cummax(pu), lax.cummax(c)
        # back to wedge order: the batch's entries are the odd keys
        _, pu, c = lax.sort((jnp.where(k & 1 == 1, k, 2 * batch + 1), pu, c),
                            num_keys=1, is_stable=False)
        pu, c = pu[:batch], c[:batch]
        p = (pu >> 32).astype(jnp.int32)
        u = (pu & 0xFFFFFFFF).astype(jnp.int32)
    with jax.named_scope("partner"):
        # a wedge's place among its owner's: the distance to the first
        # wedge of the same owner, which for the batch's first owner lies
        # off[p] - t0 before the batch (one element read)
        new = jnp.concatenate([jnp.zeros(1, bool), p[1:] != p[:-1]])
        seg = lax.cummax(jnp.where(new, tl, 0))
        lead = (t0 - off[p[0]]).astype(jnp.int32)
        j = tl - seg + jnp.where(seg == 0, lead, 0)
        # the one gather over a batch: the partner's neighbour
        w = jnp.take(nbr, jnp.minimum(p + 1 + j, ne - 1))
    with jax.named_scope("join"):
        inside = (t0 + tl.astype(jnp.int64)) < nwedges
        wkey = jnp.where(inside,
                         _pack(jnp.minimum(u, w), jnp.maximum(u, w)) | 1,
                         _SENT)
        key, c = lax.sort((jnp.concatenate([ekey, wkey]),
                           jnp.concatenate([jnp.zeros(ne, jnp.int32), c])),
                          num_keys=1, is_stable=False)
        # the last edge key at or before each entry; a wedge's own edge
        # sorts directly before it (same pair, low bit 0)
        edge = lax.cummax(jnp.where(key & 1 == 0, key, 0))
        hit = (key & 1 == 1) & (key != _SENT) & (edge == key - 1)
    with jax.named_scope("compact"):
        _, key, c = lax.sort((1 - hit.astype(jnp.int32), key, c),
                             num_keys=1, is_stable=False)
    return key[:batch], c[:batch], jnp.sum(hit.astype(jnp.int32))


def tri_append(kbuf, cbuf, key, c, count):
    """A batch's hits behind the ``count`` the buffer holds: two copies.
    The caller keeps ``count + len(key) <= len(kbuf)``."""
    return (lax.dynamic_update_slice_in_dim(kbuf, key, count, 0),
            lax.dynamic_update_slice_in_dim(cbuf, c, count, 0))


def tri_grow(kbuf, cbuf):
    """The buffer at twice its capacity."""
    return (jnp.concatenate([kbuf, jnp.zeros_like(kbuf)]),
            jnp.concatenate([cbuf, jnp.zeros_like(cbuf)]))


def tri_rows(kbuf, cbuf, verts, rows: int, by_id: bool):
    """The buffer's first ``rows`` entries as (centre, u, w) rows of vertex
    ids with their NULL values; u < w.  Where the walk named the vertices
    by rank, the ids are gathered from the vertex table a block of rows at
    a time, so that the three id columns of a hundred million triangles
    never stand beside the result."""
    null = jnp.zeros(rows, jnp.uint8)
    if by_id:
        k = kbuf[:rows]
        return jnp.stack([cbuf[:rows].astype(jnp.uint64), k >> 33,
                          (k >> 1) & 0xFFFFFFFF], 1), null
    block = min(rows, _ROWS_STEP)
    last = verts.shape[0] - 1

    def ids(ranks):
        return jnp.take(verts, jnp.minimum(ranks.astype(jnp.int32), last))

    def body(i, key):
        k = lax.dynamic_slice_in_dim(kbuf, i * block, block)
        c = lax.dynamic_slice_in_dim(cbuf, i * block, block)
        part = jnp.stack([ids(c), ids(k >> 33), ids((k >> 1) & 0xFFFFFFFF)],
                         1)
        return lax.dynamic_update_slice_in_dim(key, part, i * block, 0)

    return lax.fori_loop(0, rows // block, body,
                         jnp.zeros((rows, 3), jnp.uint64)), null


class _Programs(NamedTuple):
    orient: object
    wedges: object
    append: object
    grow: object
    rows: object


@functools.lru_cache(maxsize=None)
def _programs(mesh: Optional[Mesh]) -> _Programs:
    """The five programs.  On a mesh everything between the sharded edge
    rows and the result rows is replicated: every device walks the same
    wedges (the walk is one chip's work until it is sharded), and only a
    one-device mesh keeps the rows as a frame of its own."""
    rep = rows = None
    if mesh is not None:
        rep = NamedSharding(mesh, PartitionSpec())
        rows = row_sharding(mesh) if mesh_axis_size(mesh) == 1 else rep
    return _Programs(
        jax.jit(tri_orient, static_argnames=("canonical", "by_id"),
                out_shardings=rep),
        jax.jit(tri_wedges, static_argnames="batch", out_shardings=rep),
        jax.jit(tri_append, donate_argnums=(0, 1), out_shardings=rep),
        jax.jit(tri_grow, out_shardings=rep),
        jax.jit(tri_rows, static_argnames=("rows", "by_id"),
                out_shardings=rows))


def result_cap(ntri: int) -> int:
    """Capacity of a result of ``ntri`` rows: a power of two while small
    (the sharded tier's rule), a multiple of 2^20 rows beyond."""
    if ntri <= _ROWS_STEP:
        return round_cap(ntri)
    return -(-ntri // _ROWS_STEP) * _ROWS_STEP


class Walk(NamedTuple):
    """What :func:`walk` leaves on the device and what it counted."""
    kbuf: Optional[jax.Array]   # packed (u, w) pairs of the triangles
    cbuf: Optional[jax.Array]   # their centres
    verts: Optional[jax.Array]  # the rank → id table, on the device
    by_id: bool                 # the buffers name vertices by id, not rank
    ntri: int
    wedges: int
    batches: int
    edges: int
    max_out_degree: int


NO_WALK = Walk(None, None, None, False, 0, 0, 0, 0, 0)


def walk(src, dst, valid, verts: np.ndarray, mesh: Optional[Mesh] = None,
         canonical: bool = False) -> Walk:
    """Every triangle of the ranked edge rows (device arrays), each once,
    as packed vertex pairs and centres in device buffers.  ``verts`` is
    the sorted rank → id table (host).  Ids that fit 31 bits name the
    vertices through the walk (they order as the ranks do, and the result
    needs no translation: 3 × 10^8 gathers at RMAT-20); wider ids stay
    ranks until :func:`rows`."""
    prog = _programs(mesh)
    by_id = int(verts[-1]) < int(_DEAD)
    table = np.full(round_cap(len(verts)), verts[-1], np.uint64)
    table[:len(verts)] = verts
    table = jnp.asarray(table)
    ekey, grp, nbr, off, nw, ne, maxk = prog.orient(
        src, dst, valid, table, canonical=canonical, by_id=by_id)
    nw = int(nw)                        # the one read before the loop
    batch = min(_BATCH, round_cap(nw))  # a small graph's walk is one small batch
    nbatch = -(-nw // batch)
    kbuf = cbuf = None
    count = 0
    ahead = None
    for i in range(nbatch + 1):
        # one batch is dispatched before the last one's count is read, so
        # the device does not wait for the host
        nxt = (prog.wedges(ekey, grp, nbr, off, jnp.int64(i * batch),
                           jnp.int64(nw), batch=batch)
               if i < nbatch else None)
        if ahead is not None:
            key, c, nhit = ahead
            if kbuf is None:
                # the first batch's hits are the buffer, at twice their room
                kbuf, cbuf = prog.grow(key, c)
            else:
                while count + batch > kbuf.shape[0]:
                    kbuf, cbuf = prog.grow(kbuf, cbuf)
                kbuf, cbuf = prog.append(kbuf, cbuf, key, c,
                                         jnp.int32(count))
            count += int(nhit)
        ahead = nxt
    return Walk(kbuf, cbuf, table, by_id, count, nw, nbatch, int(ne),
                int(maxk))


def rows(w: Walk, mesh: Optional[Mesh] = None):
    """(key [cap, 3] u64, value [cap] u8) of a walk's triangles, the first
    ``w.ntri`` rows real."""
    return _programs(mesh).rows(w.kbuf, w.cbuf, w.verts,
                                rows=result_cap(w.ntri), by_id=w.by_id)


def triangles(edges: np.ndarray) -> np.ndarray:
    """All triangles of an undirected edge list, each exactly once.
    Returns [t, 3] uint64 rows (centre, u, w)."""
    e = np.asarray(edges, np.uint64).reshape(-1, 2)
    verts, inv = np.unique(e.reshape(-1), return_inverse=True)
    inv = inv.reshape(-1, 2)
    return triangles_ranked(inv[:, 0], inv[:, 1], len(verts), verts)


def triangles_ranked(a: np.ndarray, b: np.ndarray, n: int,
                     verts: np.ndarray,
                     canonical: bool = False) -> np.ndarray:
    """Triangles from pre-ranked endpoints (0..n-1) plus the rank→id
    table ``verts``, on the default device.  ``canonical=True`` promises
    unique a<b rows and skips the sort that merges duplicates."""
    if n == 0 or len(a) == 0:
        return np.zeros((0, 3), np.uint64)
    assert n < 2**31, f"triangles(): {n} vertices overflow int32 ranks"
    w = walk(jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32),
             jnp.ones(len(a), bool), np.asarray(verts, np.uint64),
             canonical=canonical)
    if w.ntri == 0:
        return np.zeros((0, 3), np.uint64)
    return np.asarray(rows(w)[0])[:w.ntri]
