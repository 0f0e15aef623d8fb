"""Triangle enumeration — the degree-ordered wedge walk as device programs.

The reference's tri_find is Cohen's MapReduce algorithm
(``oink/tri_find.cpp:43-81``): augment edges with degrees, have the
low-degree endpoint of each edge emit its "angles" (neighbour pairs),
and match angles against the edge list — 6 shuffled MR stages.  The
composed twin lives in oink/commands/tri.py.

This model keeps Cohen's core insight (orient edges from the
lexicographically smaller (degree, id) endpoint, so every vertex's
out-neighbourhood is O(√m) and the total wedge count is Σ k_v(k_v-1)/2
≤ O(m^1.5)) and runs it as five jitted programs over the ranked edge
arrays ``parallel/staging.stage_graph`` leaves on the device.  They are
built from what the chip is good at (PERF.md §6: a scatter costs thirty
sorts, a ``searchsorted`` a gather per round, a gathered element as much
as two sorted rows): sorts that carry payloads, prefix scans, copies, and
as few gathered elements as the enumeration allows.

A wedge is a pair of positions of one out-list, and the lists lie sorted
by centre in one array, so there are two ways to enumerate them, chosen
list by list from the list's length:

* **by tiles**, where a list has ``_TILED`` (K0) out-neighbours or more.
  The list is cut into blocks of ``_BLOCK`` (B) positions, block a
  starting at ``start + a·B`` (no row moves), and a *tile* is block a
  against block b ≥ a of the same list: B² wedges from 2·B neighbour
  reads, by broadcast.  A tile on the diagonal (a = b) keeps its pairs
  i < j; a row that names a position past the list's end is masked.
  Masked rows are padding, not wedges dropped.
* **by index**, where it is shorter: wedge t belongs to the position p
  with ``off[p] ≤ t < off[p+1]`` and pairs p with position p + 1 + (t −
  off[p]).  A tile of a 3-element list would be 61 masked rows for 3
  wedges (the median list of an R-MAT is 4 long), so short lists are
  walked wedge by wedge; they hold 1.2 % of RMAT-20's wedges.

The programs:

* ``tri_orient`` (once a job): canonical edge keys by a sort (duplicates
  and self loops out), degrees as run lengths of the sorted endpoints
  carried back by a second sort, the (degree, id) orientation, the
  out-neighbour lists by a third sort, and two prefix sums over the list
  positions: the index wedges a position of a short list owns (it pairs
  with every later position of its vertex), and the tiles the first
  position of a long list's block owns (block a of kb pairs with blocks
  a … kb−1).
* ``tri_tiles`` (once a job; once a table of ``_TILES`` where a graph has
  more): the tile space expanded into a table of (first block's start,
  second block's start, the list's end, the centre) by ``_owners``: the
  owners' offsets merged with the tiles' indices by one sort, their
  positions, ends and centres filled forward by int32 prefix maxima (all
  three grow along the array; one u64 prefix max of packed pairs would
  cost 0.04 s a run and 200 s to compile), a second sort back to index
  order.
* ``tri_wedges`` (once a batch of ``_BATCH`` wedge rows, a static cap), of
  either kind.  A tile batch slices ``_BATCH / B²`` tiles off the table,
  gathers both blocks of each (2·B elements a tile) and broadcasts them
  into ``[B, B, tiles]`` keys, the tile on the minor axis.  An index
  batch runs ``_owners`` over the wedge offsets and gathers each wedge's
  two neighbours.  Both then merge the wedge keys with the resident edge keys by
  a sort (a wedge closes when the key before it in that order is its own
  edge) and bring the hits to the front by another.
* ``tri_append`` copies a batch's hits behind those the buffer holds;
  ``tri_rows`` turns the buffer into (centre, u, w) rows of vertex ids.
  Ids below 2^31 name the vertices from ``tri_orient`` on, so that this
  is a copy; wider ids are gathered from the vertex table here.

``_BLOCK`` = 8 and ``_TILED`` = 16 were chosen on the chip over RMAT-20
(359 M wedges; PERF.md §6, PR 41, has the readings).  A batch costs what
its 2^24 rows cost to join and compact whatever they hold (0.235 s), so
the blocks' gather is all B can move: B = 8 generates 1.158 rows a wedge
of the tiled lists, 25 tile batches of 0.2649 s (the gather 0.030 s of
each); B = 16 generates 1.356, 29 batches of 0.2499 s (the gather
0.015 s): 6.88 s of ``tri_wedges`` a job against 7.50.  K0 = 2·B is where
a list's tiles stop being mostly padding: counted over the cell's graph,
K0 = 12 … 20 give 24 or 25 tile batches and one index batch of 2^22 or
2^23 (0.249 s at 2^22), and from K0 = 24 on the short lists fill an
index batch of 2^24 (0.665 s) or two for one tile batch saved.

Each triangle is found exactly once: the wedge (u, w) at centre v exists
only in v's out-neighbourhood, and the edge (u, w) closes it.  The host
reads five scalars in one array after ``tri_orient`` (wedges, index
wedges, tiles, edges, the largest out-degree) and one a batch (its hits,
to keep room in the buffer); no edge, wedge or triangle row crosses to
the host.  Vertices are int32 through the walk (``stage_graph``'s ranks, or
ids that fit), so a packed pair of them leaves the low bit of a u64 free
for the merge's tag."""

from __future__ import annotations

import functools
import itertools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..parallel.mesh import mesh_axis_size, row_sharding
from ..parallel.sharded import round_cap

_BATCH = 1 << 24        # wedge rows per tri_wedges execution (bounds peak memory)
_BLOCK = 8              # B: a tile pairs B positions of a list with B of the same
_TILED = 16             # K0: a list of this many out-neighbours or more is tiled
_TILES = 1 << 23        # tiles per tri_tiles execution (the table's static cap)
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


def tri_orient(src, dst, valid, verts, canonical: bool, by_id: bool,
               block: int, tiled: int):
    """Ranked edge rows → (sorted canonical edge keys, centre and
    out-neighbour of every oriented edge sorted by centre, the exclusive
    prefix sums of what each such position owns — index wedges, where its
    list is shorter than ``tiled``, and tiles of ``block`` positions a side,
    where it is not and the position starts a block — and five counts in
    one array: wedges, index wedges, tiles, edges, the largest out-degree).
    ``by_id``: the vertices are named by their ids from here on (two
    gathers of the edge rows, once), which orders them as their ranks do."""
    ne = src.shape[0]
    with jax.named_scope("ids"):
        if by_id:
            last = verts.shape[0] - 1
            src, dst = (
                jnp.take(verts, jnp.minimum(x, last)).astype(jnp.int32)
                for x in (src, dst))
        a, b = jnp.minimum(src, dst), jnp.maximum(src, dst)
    with jax.named_scope("edge_keys"):
        ekey = jnp.sort(jnp.where(valid & (a != b), _pack(a, b), _SENT))
        if not canonical:       # duplicates to the back, by one more sort
            dup = jnp.concatenate([jnp.zeros(1, bool), ekey[1:] == ekey[:-1]])
            ekey = jnp.sort(jnp.where(dup, _SENT, ekey))
        live = ekey != _SENT
        a = jnp.where(live, (ekey >> 33).astype(jnp.int32), _DEAD)
        b = jnp.where(live, ((ekey >> 1) & 0xFFFFFFFF).astype(jnp.int32),
                      _DEAD)
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
        k = end - start + 1
        pairs = jnp.where(alive, end - pos, 0).astype(jnp.int64)
        short = k < tiled
        off, nindex = _offsets(jnp.where(short, pairs, 0))
    with jax.named_scope("tile_offsets"):
        # block a of a list of kb blocks is paired with blocks a … kb-1; a
        # last block of one position has no pair of its own
        a, r = jnp.divmod(pos - start, block)
        owns = jnp.where(alive & ~short & (r == 0) & (pos < end),
                         -(-k // block) - a, 0)
        toff, ntiles = _offsets(owns.astype(jnp.int64))
    with jax.named_scope("counts"):
        counts = jnp.stack([
            jnp.sum(pairs), nindex, ntiles, jnp.sum(live.astype(jnp.int64)),
            jnp.max(jnp.where(alive, k, 0)).astype(jnp.int64)])
    return ekey, grp, nbr, off, toff, counts


def _offsets(owns):
    """(exclusive prefix sum, total) of what each position owns."""
    total = jnp.cumsum(owns)
    return total - owns, total[-1]


def _owners(off, vals, t0, n: int):
    """Entries ``t0 … t0 + n`` of the space ``off`` lays out (position p
    owns the entries from ``off[p]`` up to the next position's offset): of
    each entry (its owner's position, each of ``vals`` there, its place
    among the owner's entries).  ``vals`` are int32 arrays, none negative,
    that do not fall along the positions (a list's centre, its end).  Two
    sorts and no search."""
    ne = off.shape[0]
    # merge the owners' offsets with the entries' indices: an owner sorts
    # before the index equal to its offset, every owner at or before t0 at
    # the very front, those past t0 + n at the back.  A prefix max then
    # gives an entry its owner: positions grow with offsets, and of the
    # positions at one offset the last is the owner.
    rel = off - t0
    okey = jnp.where(rel <= 0, 0, jnp.where(rel >= n, 2 * n,
                                            2 * rel)).astype(jnp.int32)
    tl = lax.iota(jnp.int32, n)
    none = jnp.zeros(n, jnp.int32)
    k, *pv = lax.sort(
        (jnp.concatenate([okey, 2 * tl + 1]),
         *(jnp.concatenate([x, none])
           for x in (lax.iota(jnp.int32, ne), *vals))),
        num_keys=1, is_stable=False)
    # back to index order: the entries are the odd keys
    _, *pv = lax.sort((jnp.where(k & 1 == 1, k, 2 * n + 1),
                       *(lax.cummax(x) for x in pv)),
                      num_keys=1, is_stable=False)
    p, *vals = (x[:n] for x in pv)
    # an entry's place among its owner's: the distance to the first entry
    # of the same owner, which for the first owner lies off[p] - t0 before
    # t0 (one element read)
    new = jnp.concatenate([jnp.zeros(1, bool), p[1:] != p[:-1]])
    seg = lax.cummax(jnp.where(new, tl, 0))
    lead = (t0 - off[p[0]]).astype(jnp.int32)
    return p, vals, tl - seg + jnp.where(seg == 0, lead, 0)


def tri_tiles(grp, toff, t0, cap: int, block: int):
    """Tiles ``t0 … t0 + cap`` of the tile space: of each (the position
    its first block starts at, the position its second block starts at, the
    last position of their list, the list's centre)."""
    with jax.named_scope("tiles"):
        sa, (end, c), j = _owners(toff, (_runs(grp)[1], grp), t0, cap)
        return sa, sa + j * block, end, c


def _index_wedges(nbr, grp, off, t0, total, batch: int):
    """Index wedges ``t0 … t0 + batch`` of ``total``: (key, centre)."""
    ne = nbr.shape[0]
    with jax.named_scope("expand"):
        p, (c,), j = _owners(off, (grp,), t0, batch)
    with jax.named_scope("partner"):
        # the one gather over a batch: a wedge's two neighbours
        uw = jnp.take(nbr, jnp.minimum(jnp.stack([p, p + 1 + j]), ne - 1))
        inside = (t0 + lax.iota(jnp.int64, batch)) < total
        # neighbours ascend along a list, so u < w
        return jnp.where(inside, _pack(uw[0], uw[1]) | 1, _SENT), c


def _tile_wedges(nbr, sa, sb, end, c, t0, total, batch: int, block: int):
    """The wedges of tiles ``t0 … t0 + batch / block²`` of a table of
    ``total``: (key, centre), ``[block, block, tiles]`` flattened, the tile
    on the minor axis.  A row is masked where it names a position past its
    list's end, or a pair i ≥ j of a tile on the diagonal."""
    ne = nbr.shape[0]
    nt = batch // (block * block)
    with jax.named_scope("blocks"):
        sa, sb, end, c = (lax.dynamic_slice_in_dim(x, t0, nt)
                          for x in (sa, sb, end, c))
        i = lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        # the one gather over a batch: both blocks of every tile
        at = jnp.expand_dims(jnp.stack([sa, sb]), 1) + i
        uw = jnp.take(nbr, jnp.minimum(at, ne - 1))
        ok = at <= end
        real = (t0 + lax.iota(jnp.int64, nt)) < total
    with jax.named_scope("pairs"):
        # (neighbours ascend along a list, so u < w wherever the pair is one)
        upper = jnp.expand_dims(i < i.T, 2)
        pair = (jnp.expand_dims(ok[0] & real, 1) & jnp.expand_dims(ok[1], 0)
                & (upper | (sa != sb)))
        key = jnp.where(pair, _pack(jnp.expand_dims(uw[0], 1),
                                    jnp.expand_dims(uw[1], 0)) | 1, _SENT)
        c = jnp.broadcast_to(c, key.shape)
        return key.reshape(batch), c.reshape(batch)


def tri_wedges(ekey, nbr, lists, t0, total, batch: int, block: int):
    """One batch of wedges joined against the edge keys: (keys of those
    that close, their centres, their number), the hits at the front of
    ``[batch]`` arrays.  ``block`` 0: the index wedges ``t0 … t0 + batch``
    of the short lists, ``lists`` = (grp, off); else the wedges of
    ``batch / block²`` tiles from ``t0`` on, ``lists`` = a table of
    :func:`tri_tiles`."""
    ne = ekey.shape[0]
    if block:
        wkey, c = _tile_wedges(nbr, *lists, t0, total, batch, block)
    else:
        wkey, c = _index_wedges(nbr, *lists, t0, total, batch)
    with jax.named_scope("join"):
        key, c = lax.sort((jnp.concatenate([ekey, wkey]),
                           jnp.concatenate([jnp.zeros(ne, jnp.int32), c])),
                          num_keys=1, is_stable=False)
        # the last edge key at or before each entry; a wedge's own edge
        # sorts directly before it (same pair, low bit 0)
        with jax.named_scope("join_scan"):      # the u64 prefix max
            edge = lax.cummax(jnp.where(key & 1 == 0, key, 0))
        hit = (key & 1 == 1) & (key != _SENT) & (edge == key - 1)
    with jax.named_scope("compact"):
        _, key, c = lax.sort((1 - hit.astype(jnp.int32), key, c),
                             num_keys=1, is_stable=False)
        return key[:batch], c[:batch], jnp.sum(hit.astype(jnp.int32))


def tri_append(kbuf, cbuf, key, c, count):
    """A batch's hits behind the ``count`` the buffer holds: two copies.
    The caller keeps ``count + len(key) <= len(kbuf)``."""
    with jax.named_scope("append"):
        return (lax.dynamic_update_slice_in_dim(kbuf, key, count, 0),
                lax.dynamic_update_slice_in_dim(cbuf, c, count, 0))


def tri_grow(kbuf, cbuf):
    """The buffer at twice its capacity."""
    with jax.named_scope("grow"):
        return (jnp.concatenate([kbuf, jnp.zeros_like(kbuf)]),
                jnp.concatenate([cbuf, jnp.zeros_like(cbuf)]))


def tri_rows(kbuf, cbuf, verts, rows: int, by_id: bool):
    """The buffer's first ``rows`` entries as (centre, u, w) rows of vertex
    ids with their NULL values; u < w.  Where the walk named the vertices
    by rank, the ids are gathered from the vertex table a block of rows at
    a time, so that the three id columns of a hundred million triangles
    never stand beside the result."""
    with jax.named_scope("rows"):
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
            part = jnp.stack(
                [ids(c), ids(k >> 33), ids((k >> 1) & 0xFFFFFFFF)], 1)
            return lax.dynamic_update_slice_in_dim(key, part, i * block, 0)

        return lax.fori_loop(0, rows // block, body,
                             jnp.zeros((rows, 3), jnp.uint64)), null


class _Programs(NamedTuple):
    orient: object
    tiles: object
    wedges: object
    append: object
    grow: object
    rows: object


@functools.lru_cache(maxsize=None)
def _programs(mesh: Optional[Mesh]) -> _Programs:
    """The six programs.  On a mesh everything between the sharded edge
    rows and the result rows is replicated: every device walks the same
    wedges (the walk is one chip's work until it is sharded), and only a
    one-device mesh keeps the rows as a frame of its own."""
    rep = rows = None
    if mesh is not None:
        rep = NamedSharding(mesh, PartitionSpec())
        rows = row_sharding(mesh) if mesh_axis_size(mesh) == 1 else rep
    return _Programs(
        jax.jit(tri_orient, out_shardings=rep,
                static_argnames=("canonical", "by_id", "block", "tiled")),
        jax.jit(tri_tiles, static_argnames=("cap", "block"),
                out_shardings=rep),
        jax.jit(tri_wedges, static_argnames=("batch", "block"),
                out_shardings=rep),
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
    wedges: int                 # Σ k(k-1)/2 over the lists, of either kind
    batches: int                # executions of tri_wedges, of either kind
    edges: int
    max_out_degree: int
    tiles: int
    index_wedges: int           # the wedges of the lists walked by index
    tile_rows: int              # rows the tile batches generated

    @property
    def tile_fill(self) -> float:
        """Wedges that came from tiles over the rows the tile batches
        generated; 0 where no tile batch ran."""
        return ((self.wedges - self.index_wedges) / self.tile_rows
                if self.tile_rows else 0.0)


NO_WALK = Walk(None, None, None, False, 0, 0, 0, 0, 0, 0, 0, 0)


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
    block = _BLOCK
    ekey, grp, nbr, off, toff, counts = prog.orient(
        src, dst, valid, table, canonical=canonical, by_id=by_id,
        block=block, tiled=_TILED)
    # the one read before the loop
    nw, nindex, ntiles, ne, maxk = map(int, np.asarray(counts))
    # a small graph's walk is one small batch, its tile table a small one
    cap = min(_TILES, round_cap(ntiles))
    per = min(cap, max(1, _BATCH // block ** 2))    # tiles a batch
    ibatch = min(_BATCH, round_cap(nindex))

    def batches():
        """Every execution of ``tri_wedges``, dispatched when asked for:
        the tiles table after table, then the short lists by index."""
        for t0 in range(0, ntiles, cap):
            tiles = prog.tiles(grp, toff, jnp.int64(t0), cap=cap, block=block)
            n = min(cap, ntiles - t0)
            for i in range(0, n, per):
                yield prog.wedges(ekey, nbr, tiles, jnp.int64(i), jnp.int64(n),
                                  batch=per * block ** 2, block=block)
        for i in range(0, nindex, ibatch):
            yield prog.wedges(ekey, nbr, (grp, off), jnp.int64(i),
                              jnp.int64(nindex), batch=ibatch, block=0)

    kbuf = cbuf = None
    count = nbatch = 0
    ahead = None
    for nxt in itertools.chain(batches(), [None]):
        # one batch is dispatched before the last one's count is read, so
        # the device does not wait for the host
        if ahead is not None:
            key, c, nhit = ahead
            nbatch += 1
            if kbuf is None:
                # the first batch's hits are the buffer, at twice their room
                kbuf, cbuf = prog.grow(key, c)
            else:
                while count + key.shape[0] > kbuf.shape[0]:
                    kbuf, cbuf = prog.grow(kbuf, cbuf)
                kbuf, cbuf = prog.append(kbuf, cbuf, key, c,
                                         jnp.int32(count))
            count += int(nhit)
        ahead = nxt
    tile_batches = nbatch - -(-nindex // ibatch)
    return Walk(kbuf, cbuf, table, by_id, count, nw, nbatch, ne, maxk,
                ntiles, nindex, tile_batches * per * block ** 2)


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
