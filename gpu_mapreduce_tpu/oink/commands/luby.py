"""luby_find — Luby maximal independent set.

Reference: ``oink/luby_find.cpp:53-95`` (run loop) and its four reduce
callbacks (``reduce_edge_winner`` 140, ``reduce_vert_winner`` 186,
``reduce_vert_loser`` 238, ``reduce_vert_emit`` 289).

Round semantics (identical to the reference composition):

1. **edge_winner** — an edge is alive iff no endpoint was flagged last
   round; alive edge picks its winner = endpoint with smaller (rand, id)
   and emits ``(v : [other, won])`` both directions;
2. **vert_winner** — a vertex that wins *all* its alive edges is a
   round-winner; it tells every neighbour so;
3. **vert_loser** — a vertex with a round-winner neighbour is a loser; it
   tells every neighbour so;
4. **vert_emit** — a vertex whose neighbours are *all* losers joins the
   MIS (this covers round-winners and vertices isolated by removals) and
   the edge list for the next round is rebuilt with dead-markers on any
   edge touching a loser.  Loop until edge_winner emits nothing.

Two TPU-first redesigns vs the reference:

* the reference assigns each vertex a random via ``srand48(v+seed)`` and
  *carries* it through every shuffle in ERAND/VRAND/VFLAG structs,
  discriminating record kinds by ``valuebytes``; our vertex random is a
  pure splitmix64 function of (v, seed) recomputed where needed, so every
  value is one fixed-width ``[other, tag]`` u64 row — no variable-size
  struct zoo, and the shuffles move half the bytes;
* each reduce is one vectorised segment pass (``np.maximum.reduceat``
  over group offsets) instead of a per-group callback.
"""

from __future__ import annotations

import numpy as np

from ...core.runtime import MRError
from ..command import Command, command
from ..kernels import (group_any, host_kmv, kmv_keys, kmv_values, kv_keys,
                       print_vertex, read_edge, seg_ids)

_U = np.uint64


def vertex_rand(v: np.ndarray, seed: int) -> np.ndarray:
    """Deterministic per-vertex random in [0,1): splitmix64(v+seed) →
    top-53-bit float (the reference's srand48(v+seed)/drand48,
    oink/luby_find.cpp:130-134 — consistent across every use of v)."""
    x = v.astype(np.uint64) + _U(seed & 0xFFFFFFFFFFFFFFFF)
    with np.errstate(over="ignore"):
        x = (x + _U(0x9E3779B97F4A7C15))
        z = x
        z = (z ^ (z >> _U(30))) * _U(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> _U(27))) * _U(0x94D049BB133111EB)
        z = z ^ (z >> _U(31))
    return (z >> _U(11)).astype(np.float64) / float(1 << 53)


# ---------------------------------------------------------------------------
# round kernels (batch reduces).  Host bodies below; device bodies (per
# shard, jitted under shard_map) alongside — the mesh backend never pulls
# a frame to the controller inside the round loop.
# ---------------------------------------------------------------------------

import jax.numpy as jnp

from ...parallel.devkernels import (is_sharded_kmv, is_sharded_kv,
                                    kmv_row_state, seg_max_u64, skmv_map,
                                    skv_map)


def _vertex_rand_dev(v, seed):
    """jnp twin of vertex_rand — identical splitmix64 bits.  ``seed`` is a
    traced u64 scalar so a seed sweep re-uses one compiled kernel."""
    x = v.astype(jnp.uint64) + seed.astype(jnp.uint64)
    x = x + jnp.uint64(0x9E3779B97F4A7C15)
    z = x
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    z = z ^ (z >> jnp.uint64(31))
    return (z >> jnp.uint64(11)).astype(jnp.float64) / float(1 << 53)


def _seg_any(cond, seg, valid, gcap):
    return seg_max_u64(cond.astype(jnp.uint64), seg, valid, gcap) > 0


def _edge_winner_dev(uk, nv, vo, vals, gc, vc, seed):
    # seed arrives as a traced u64 scalar (skmv_map `extra`)
    gcap = uk.shape[0]
    seg, rows_valid, groups_valid = kmv_row_state(nv, vo, vals, gc, vc)
    flag = vals if vals.ndim == 1 else vals[:, 0]
    dead = _seg_any(flag != 0, seg, rows_valid, gcap)
    alive = groups_valid & ~dead
    ri = _vertex_rand_dev(uk[:, 0], seed)
    rj = _vertex_rand_dev(uk[:, 1], seed)
    vi_wins = (ri < rj) | ((ri == rj) & (uk[:, 0] < uk[:, 1]))
    w = jnp.where(vi_wins, uk[:, 0], uk[:, 1])
    l = jnp.where(vi_wins, uk[:, 1], uk[:, 0])
    one = jnp.ones(gcap, jnp.uint64)
    okey = jnp.concatenate([w, l])
    oval = jnp.concatenate([jnp.stack([l, one], 1),
                            jnp.stack([w, one - 1], 1)])
    return okey, oval, jnp.concatenate([alive, alive])


def _vert_winner_dev(uk, nv, vo, vals, gc, vc):
    gcap = uk.shape[0]
    seg, rows_valid, _ = kmv_row_state(nv, vo, vals, gc, vc)
    lost_any = _seg_any(vals[:, 1] == 0, seg, rows_valid, gcap)
    tag = (~jnp.take(lost_any, jnp.maximum(seg, 0))).astype(jnp.uint64)
    okey = vals[:, 0]
    oval = jnp.stack([jnp.take(uk, jnp.maximum(seg, 0)), tag], 1)
    return okey, oval, rows_valid


def _vert_loser_dev(uk, nv, vo, vals, gc, vc):
    gcap = uk.shape[0]
    seg, rows_valid, _ = kmv_row_state(nv, vo, vals, gc, vc)
    loser = _seg_any(vals[:, 1] == 1, seg, rows_valid, gcap)
    tag = jnp.take(loser, jnp.maximum(seg, 0)).astype(jnp.uint64)
    okey = vals[:, 0]
    oval = jnp.stack([jnp.take(uk, jnp.maximum(seg, 0)), tag], 1)
    return okey, oval, rows_valid


def _vert_emit_mis_dev(uk, nv, vo, vals, gc, vc):
    """Per-group: all neighbours losers ⇒ group key joins the MIS."""
    gcap = uk.shape[0]
    seg, rows_valid, groups_valid = kmv_row_state(nv, vo, vals, gc, vc)
    survivor_nb = _seg_any(vals[:, 1] == 0, seg, rows_valid, gcap)
    mis = groups_valid & ~survivor_nb
    return uk, jnp.zeros(gcap, jnp.uint8), mis


def _vert_emit_edges_dev(uk, nv, vo, vals, gc, vc):
    """Per row: rebuild the canonical edge with the loser tag as marker."""
    seg, rows_valid, _ = kmv_row_state(nv, vo, vals, gc, vc)
    v = jnp.take(uk, jnp.maximum(seg, 0))
    u = vals[:, 0]
    okey = jnp.stack([jnp.minimum(v, u), jnp.maximum(v, u)], 1)
    return okey, vals[:, 1], rows_valid


def edge_winner(fr, kv, ptr):
    """KMV edge:[flags] → (v : [other, key-won]) per alive edge, both
    directions (reduce_edge_winner, oink/luby_find.cpp:140-182)."""
    if is_sharded_kmv(fr):
        seed = jnp.uint64(int(ptr) & 0xFFFFFFFFFFFFFFFF)
        kv.add_frame(skmv_map(fr, _edge_winner_dev, extra=(seed,)))
        return
    fr = host_kmv(fr)
    if len(fr) == 0:
        return
    e = kmv_keys(fr)                        # [g, 2]
    vals = kmv_values(fr)                   # [n] u8 NULL (round 1) / u64 tag
    dead = group_any(vals != 0, fr)
    e = e[~dead]
    if len(e) == 0:
        return
    seed = ptr
    ri, rj = vertex_rand(e[:, 0], seed), vertex_rand(e[:, 1], seed)
    vi_wins = (ri < rj) | ((ri == rj) & (e[:, 0] < e[:, 1]))
    w = np.where(vi_wins, e[:, 0], e[:, 1])
    l = np.where(vi_wins, e[:, 1], e[:, 0])
    one = np.ones(len(e), _U)
    kv.add_batch(np.concatenate([w, l]),
                 np.concatenate([np.stack([l, one], 1),
                                 np.stack([w, one - 1], 1)]))


def vert_winner(fr, kv, ptr):
    """Group per v of [other, won]: v wins all edges ⇒ round-winner; emit
    (other : [v, v-is-round-winner]) (reduce_vert_winner)."""
    if is_sharded_kmv(fr):
        kv.add_frame(skmv_map(fr, _vert_winner_dev))
        return
    fr = host_kmv(fr)
    if len(fr) == 0:
        return
    vals = kmv_values(fr)                   # [n, 2]
    seg = seg_ids(fr)
    lost_any = group_any(vals[:, 1] == 0, fr)
    tag = (~lost_any[seg]).astype(_U)
    kv.add_batch(vals[:, 0], np.stack([kmv_keys(fr)[seg], tag], 1))


def vert_loser(fr, kv, ptr):
    """Group per v of [other, other-is-round-winner]: any winner neighbour
    ⇒ v is a loser; emit (other : [v, v-is-loser]) (reduce_vert_loser)."""
    if is_sharded_kmv(fr):
        kv.add_frame(skmv_map(fr, _vert_loser_dev))
        return
    fr = host_kmv(fr)
    if len(fr) == 0:
        return
    vals = kmv_values(fr)
    seg = seg_ids(fr)
    loser = group_any(vals[:, 1] == 1, fr)
    tag = loser[seg].astype(_U)
    kv.add_batch(vals[:, 0], np.stack([kmv_keys(fr)[seg], tag], 1))


def vert_emit(fr, kv, ptr):
    """Group per v of [other, other-is-loser]: all neighbours losers ⇒ v
    joins the MIS (into the open accumulator MR via ptr); rebuild next
    round's edges with the loser tag as dead-marker
    (reduce_vert_emit, oink/luby_find.cpp:289-344)."""
    mrv = ptr
    if is_sharded_kmv(fr):
        mrv.kv.add_frame(skmv_map(fr, _vert_emit_mis_dev))
        kv.add_frame(skmv_map(fr, _vert_emit_edges_dev))
        return
    fr = host_kmv(fr)
    if len(fr) == 0:
        return
    vals = kmv_values(fr)
    seg = seg_ids(fr)
    vkeys = kmv_keys(fr)
    survivor_nb = group_any(vals[:, 1] == 0, fr)
    mis = vkeys[~survivor_nb]
    if len(mis):
        mrv.kv.add_batch(mis, np.zeros(len(mis), np.uint8))
    v, u = vkeys[seg], vals[:, 0]
    edges = np.stack([np.minimum(v, u), np.maximum(v, u)], 1)
    kv.add_batch(edges, vals[:, 1])


def _copy_edge_dev(k, v, c):
    valid = (jnp.arange(k.shape[0]) < c) & (k[:, 0] != k[:, 1])
    return k, jnp.zeros(k.shape[0], jnp.uint8), valid


def copy_edge(fr, kv, ptr):
    """Eij:NULL → Eij:NULL working copy, self-loops dropped — a self-loop
    vertex can never win its own edge and would cycle forever (the
    reference's map_vert_random carries them into the same livelock;
    we guard like edge_upper does)."""
    if is_sharded_kv(fr):
        kv.add_frame(skv_map(fr, _copy_edge_dev))
        return
    e = kv_keys(fr)
    e = e[e[:, 0] != e[:, 1]]
    kv.add_batch(e, np.zeros(len(e), np.uint8))


# ---------------------------------------------------------------------------
# command
# ---------------------------------------------------------------------------

@command("luby_find")
class LubyFind(Command):
    """luby_find seed: maximal independent set of an undirected edge list;
    output is one MIS vertex per line (oink/luby_find.cpp:53-115).

    Engines: ``fused`` (default) — one jitted program over a dense state
    vector with the SAME splitmix64 per-vertex priorities as the composed
    engine (models/luby.py): each edge is kept once, pointing from the end
    that loses to the end that beats it, the rows sorted by the losing
    end before the loop; a round then counts, per vertex, the undecided
    and the winning neighbours that beat it (two gathers and two prefix
    sums over the rows, no scatter);
    ``composed`` — the reference's 5-stage MR round below (the tests'
    reference, reached by setting ``LubyFind.engine``).  Both are valid
    MIS constructions;
    selected sets can differ because the composed engine's winner rule is
    edge-local per round."""

    ninputs = 1
    noutputs = 1
    engine: str = "fused"

    def params(self, args):
        if len(args) != 1:
            raise MRError("Illegal luby_find command")
        self.seed = int(args[0])

    def run(self):
        if self.engine not in ("fused", "composed"):
            raise MRError(f"luby_find: unknown engine {self.engine!r} "
                          f"(use 'fused' or 'composed')")
        if self.engine == "composed":
            return self._run_composed()
        obj = self.obj
        mre = obj.input(1, read_edge)

        from jax.sharding import Mesh
        mesh = obj.comm if isinstance(obj.comm, Mesh) else None
        # device staging (VERDICT r2 #2): vertex ranking on device;
        # self-loops never block a MIS: dropped in the valid mask there,
        # before the ranking on the host
        from ...obs import get_tracer, names
        from ...parallel.staging import stage_graph, stage_graph_host
        tr = get_tracer()
        with tr.span(names.LUBY_STAGE, cat=names.HOST) as sp:
            sg = stage_graph(mre, obj.comm, drop_self=True)
            on_device = sg is not None
            if not on_device:
                sg = stage_graph_host(mre, drop_self=True)
            verts, n = sg.verts, sg.n
            # the loop compares priorities and nothing else, so it gets
            # each vertex's rank in the order of (priority, id): the same
            # set exactly, and int32 on a chip whose compiler lowers no
            # float64 ``pmin``
            prio = np.empty(n, np.int32)
            prio[np.lexsort((verts, vertex_rand(verts, self.seed)))] = \
                np.arange(n, dtype=np.int32)
            edges = int(mre.kv.nkv) if mre.kv is not None else 0
            sp.set(n=n, edges=edges, edge_rows=sg.rows)

        with tr.span(names.LUBY_ENGINE, cat=names.ENGINE) as sp:
            # the span ends at the pull of the state vector; ``rows`` is
            # what each of a round's two gathers and prefix sums runs over
            if n == 0:
                # no edge but self loops (or none at all): nothing to decide
                state, iters, rows = np.zeros(0, np.int8), 0, 0
            elif on_device:
                from ...models.luby import _luby_sharded_fn
                state, iters = _luby_sharded_fn(mesh, n, max(n, 1))(
                    sg.src, sg.dst, sg.valid, jnp.asarray(prio))
                rows = sg.src.shape[0]
            elif mesh is not None:
                from ...models.luby import luby_mis_sharded
                from ...parallel.mesh import mesh_axis_size
                state, iters = luby_mis_sharded(mesh, sg.src, sg.dst, prio,
                                                n)
                shards = mesh_axis_size(mesh)
                rows = -(-len(sg.src) // shards) * shards   # as it pads them
            else:
                from ...models.luby import luby_mis
                state, iters = luby_mis(sg.src.astype(np.int32),
                                        sg.dst.astype(np.int32),
                                        jnp.asarray(prio), n)
                rows = len(sg.src)
            state, iters = np.asarray(state), int(iters)
            sp.set(iters=iters, n=n, edges=edges, rows=rows,
                   edge_rows=int(rows))

        mrv = obj.create_mr()
        with tr.span(names.LUBY_EMIT, cat=names.HOST) as sp:
            mis = verts[state == 1]
            self.nset, self.niterate = int(len(mis)), iters
            if self.nset:
                mrv.map(1, lambda i, kv, p: kv.add_batch(
                    mis, np.zeros(len(mis), np.uint8)))
            sp.set(n=self.nset)
        obj.output(1, mrv, print_vertex)
        self.message(f"Luby_find: {self.nset} MIS vertices in "
                     f"{self.niterate} iterations")
        obj.cleanup()

    def _run_composed(self):
        obj = self.obj
        mre = obj.input(1, read_edge)
        mre.aggregate()   # mesh: shard once; the round loop below then
        #                   stays device-resident (serial: no-op)
        mrv = obj.create_mr()
        mrw = obj.create_mr()

        mrw.map_mr(mre, copy_edge, batch=True)
        mrw.clone()

        niterate = 0
        mrv.open()
        while True:
            n = mrw.reduce(edge_winner, ptr=self.seed, batch=True)
            if n == 0:
                break
            mrw.collate()
            mrw.reduce(vert_winner, batch=True)
            mrw.collate()
            mrw.reduce(vert_loser, batch=True)
            mrw.collate()
            mrw.reduce(vert_emit, ptr=mrv, batch=True)
            mrw.collate()
            niterate += 1
        nset = mrv.close()

        self.nset, self.niterate = nset, niterate
        obj.output(1, mrv, print_vertex)
        self.message(f"Luby_find: {nset} MIS vertices in {niterate} "
                     f"iterations")
        obj.cleanup()
