"""sssp — single-source shortest paths by MapReduce Bellman-Ford relaxation.

Reference: ``oink/sssp.cpp:49-180`` (per-source BFS loop) with callbacks
``reorganize_edges`` 187, ``add_source`` 205, ``pick_shortest_distances``
244, ``update_adjacent_distances`` 299, and the DISTANCE/EDGEVALUE structs
of ``oink/sssp.h`` (pred vertex, f32 weight, current flag).

Iteration (identical to the reference composition): candidate distances in
``mrpath`` are shuffled to their vertex, merged into the per-vertex state
``mrvert``; ``pick_shortest`` keeps the best (distance, pred) per vertex
and re-emits changed vertices; changed distances join the pre-aggregated
adjacency ``mredge`` and ``update_adjacent`` relaxes each out-edge into
the next round's candidates.  Converges when no vertex distance changes.

TPU-first redesigns vs the reference:

* the reference discriminates edge-vs-distance values by ``valuebytes``
  (``sssp.cpp:318,341``); we keep one fixed-width lane: every value is a
  ``[tag, a, b, c]`` f64 row — edge ``[0, vj, wt, 0]``, distance
  ``[1, pred, dist, current]``.  Vertex ids stay exact through f64 up to
  2^53 (RMAT-26 ids are < 2^27);
* both relaxation reduces are single vectorised segment passes
  (lexsort + reduceat), not per-group callbacks;
* source selection: the reference seeds srand48 but actually takes the
  first ``ncnt`` keys in arbitrary shuffle order (``sssp.cpp:363-375``);
  we order vertices by (splitmix64(v+seed), v) — random *and*
  deterministic across runs/backends;
* output: the reference prints ``mrpath`` after convergence, which is
  empty by construction (the loop exits only when pick_shortest emitted
  nothing); we print the converged ``mrvert`` state — one
  ``v dist pred`` line per vertex, inf for unreachable (DISTANCE's
  FLT_MAX default, oink/sssp.h:52);
* no-predecessor sentinel: the reference memsets pred to vertex id 0
  (``sssp.h:51``, ``sssp.cpp:384``) and then skips relaxing edges back
  to the predecessor — silently wrong when a real vertex 0 is adjacent
  to the source.  We use -1.0 internally (no u64 vertex maps to it) and
  print 0 for it, keeping the reference's output convention without the
  miss.
"""

from __future__ import annotations

import functools

import numpy as np

from ...core.column import row_columns, write_rows
from ...core.runtime import MRError
from ..command import Command, command
from ..objects import block_attrs
from ..kernels import (cull, edge_to_vertices, group_min_rows, host_kmv,
                       kmv_keys, kmv_values, kv_keys, kv_values,
                       read_edge_weight, seg_ids)
from .luby import vertex_rand

RESULT_LINE = "%d %g %d"         # 'v dist pred', a line a vertex
TAG_EDGE, TAG_DIST = 0.0, 1.0
NO_PRED = -1.0                   # see module docstring: sentinel, not id 0


# ---------------------------------------------------------------------------
# batch kernels — host bodies plus per-shard device bodies (shard_map), so
# the mesh relaxation loop never materialises a frame on the controller.
# ---------------------------------------------------------------------------

import jax.numpy as jnp

from ...parallel.devkernels import (is_sharded_kmv, is_sharded_kv,
                                    kmv_row_state, seg_lex_min2, seg_max_u64,
                                    seg_min_with, skmv_map, skv_map)

_INF = jnp.float64(jnp.inf)


def _reorganize_edges_dev(k, v, c):
    n = k.shape[0]
    valid = jnp.arange(n) < c
    oval = jnp.stack([jnp.zeros(n, jnp.float64),
                      k[:, 1].astype(jnp.float64),
                      v.astype(jnp.float64), jnp.zeros(n, jnp.float64)], 1)
    return k[:, 0], oval, valid


def reorganize_edges(fr, kv, ptr):
    """Eij:wt → vi:[0, vj, wt, 0] (reference reorganize_edges,
    oink/sssp.cpp:187-199 — directed out-edges keyed by source)."""
    if is_sharded_kv(fr):
        kv.add_frame(skv_map(fr, _reorganize_edges_dev))
        return
    e = kv_keys(fr)
    wt = kv_values(fr).astype(np.float64)
    rows = np.stack([np.full(len(e), TAG_EDGE),
                     e[:, 1].astype(np.float64), wt,
                     np.zeros(len(e))], 1)
    kv.add_batch(e[:, 0], rows)


def _init_distance_dev(k, v, c):
    n = k.shape[0]
    valid = jnp.arange(n) < c
    row = jnp.asarray(np.array([TAG_DIST, NO_PRED, np.inf, 1.0]))
    return k, jnp.tile(row, (n, 1)), valid


def init_distance(fr, kv, ptr):
    """v:* → v:[1, NO_PRED, inf, 1] (initialize_vertex_distances,
    oink/sssp.cpp:231-237; DISTANCE() default wt=FLT_MAX, pred sentinel
    corrected per module docstring)."""
    if is_sharded_kv(fr):
        kv.add_frame(skv_map(fr, _init_distance_dev))
        return
    k = kv_keys(fr)
    rows = np.tile(np.array([TAG_DIST, NO_PRED, np.inf, 1.0]), (len(k), 1))
    kv.add_batch(k, rows)


def _pick_shortest_state(uk, nv, vo, vals, gc, vc):
    """Per group: winner (min dist, pred) state row, every valid group."""
    gcap = uk.shape[0]
    seg, rows_valid, groups_valid = kmv_row_state(nv, vo, vals, gc, vc)
    wdist, wpred = seg_lex_min2(vals[:, 2], vals[:, 1], seg, rows_valid,
                                gcap, _INF, _INF)
    out = jnp.stack([jnp.ones(gcap, jnp.float64), wpred, wdist,
                     jnp.ones(gcap, jnp.float64)], 1)
    return uk, out, groups_valid


def _pick_shortest_changed(uk, nv, vo, vals, gc, vc):
    """Per group: the winner row again, but only where it differs from the
    group's previous current row (or no current row existed)."""
    gcap = uk.shape[0]
    seg, rows_valid, groups_valid = kmv_row_state(nv, vo, vals, gc, vc)
    wdist, wpred = seg_lex_min2(vals[:, 2], vals[:, 1], seg, rows_valid,
                                gcap, _INF, _INF)
    is_cur = rows_valid & (vals[:, 3] == 1.0)
    pdist = seg_min_with(vals[:, 2], seg, is_cur, gcap, _INF)
    ppred = seg_min_with(vals[:, 1], seg, is_cur, gcap, _INF)
    has_prev = seg_max_u64(jnp.ones(vals.shape[0], jnp.uint64), seg,
                           is_cur, gcap) > 0
    neq = lambda x, y: ~((x == y) | (jnp.isnan(x) & jnp.isnan(y)))
    changed = groups_valid & (~has_prev | neq(wdist, pdist)
                              | neq(wpred, ppred))
    out = jnp.stack([jnp.ones(gcap, jnp.float64), wpred, wdist,
                     jnp.ones(gcap, jnp.float64)], 1)
    return uk, out, changed


def pick_shortest(fr, kv, ptr):
    """Per-vertex group of distance rows: keep min (dist, pred); emit the
    winner (current=1) back to the vertex state, and into the open
    candidate MR iff it differs from the previous current row
    (pick_shortest_distances, oink/sssp.cpp:244-293)."""
    mrpath = ptr
    if is_sharded_kmv(fr):
        kv.add_frame(skmv_map(fr, _pick_shortest_state))
        mrpath.kv.add_frame(skmv_map(fr, _pick_shortest_changed))
        return
    fr = host_kmv(fr)
    if len(fr) == 0:
        return
    vals = kmv_values(fr)                   # [n, 4] all TAG_DIST
    seg = seg_ids(fr)
    dist, pred, cur = vals[:, 2], vals[:, 1], vals[:, 3]

    # winner per group = lexicographic min (dist, pred); every group has
    # rows, so the present-groups array is exactly arange(len(fr))
    _, win = group_min_rows(seg, dist, pred)

    # previous current row per group (exactly one: init_distance seeds one
    # and every round re-emits one; duplicates from the path merge are
    # byte-identical so any is fine)
    cur_rows = np.flatnonzero(cur == 1.0)
    prev = np.full(len(fr), -1)
    prev[seg[cur_rows]] = cur_rows

    keys = kmv_keys(fr)
    out = np.stack([np.full(len(fr), TAG_DIST), pred[win], dist[win],
                    np.ones(len(fr))], 1)
    kv.add_batch(keys, out)

    changed = (dist[win] != dist[prev]) | (pred[win] != pred[prev])
    changed |= prev < 0
    if np.any(changed):
        mrpath.kv.add_batch(keys[changed], out[changed])


def _update_adjacent_edges(uk, nv, vo, vals, gc, vc):
    """Per row: re-emit the adjacency rows unchanged."""
    seg, rows_valid, _ = kmv_row_state(nv, vo, vals, gc, vc)
    is_edge = vals[:, 0] == TAG_EDGE
    okey = jnp.take(uk, jnp.maximum(seg, 0))
    return okey, vals, rows_valid & is_edge


def _update_adjacent_relax(uk, nv, vo, vals, gc, vc):
    """Per edge row: relax with the group's best arriving distance."""
    gcap = uk.shape[0]
    seg, rows_valid, _ = kmv_row_state(nv, vo, vals, gc, vc)
    is_dist = rows_valid & (vals[:, 0] == TAG_DIST)
    bdist, bpred = seg_lex_min2(vals[:, 2], vals[:, 1], seg, is_dist,
                                gcap, _INF, _INF)
    has_dist = seg_max_u64(jnp.ones(vals.shape[0], jnp.uint64), seg,
                           is_dist, gcap) > 0
    g = jnp.maximum(seg, 0)
    is_edge = rows_valid & (vals[:, 0] == TAG_EDGE)
    vj = vals[:, 1]
    vi = jnp.take(uk, g).astype(jnp.float64)
    relax = (is_edge & jnp.take(has_dist, g) & (vj != jnp.take(bpred, g))
             & (vj != vi) & jnp.isfinite(jnp.take(bdist, g)))
    okey = vj.astype(jnp.uint64)
    n = vals.shape[0]
    oval = jnp.stack([jnp.ones(n, jnp.float64), vi,
                      jnp.take(bdist, g) + vals[:, 2],
                      jnp.zeros(n, jnp.float64)], 1)
    return okey, oval, relax


def update_adjacent(fr, kv, ptr):
    """Per-vertex group of edge rows + changed-distance rows: re-emit the
    adjacency; if a distance arrived, relax every out-edge into the open
    candidate MR — skipping the predecessor and self-loops
    (update_adjacent_distances, oink/sssp.cpp:299-360)."""
    mrpath = ptr
    if is_sharded_kmv(fr):
        kv.add_frame(skmv_map(fr, _update_adjacent_edges))
        mrpath.kv.add_frame(skmv_map(fr, _update_adjacent_relax))
        return
    fr = host_kmv(fr)
    if len(fr) == 0:
        return
    vals = kmv_values(fr)                   # [n, 4] mixed tags
    seg = seg_ids(fr)
    keys = kmv_keys(fr)
    is_dist = vals[:, 0] == TAG_DIST
    is_edge = ~is_dist

    # re-emit adjacency rows
    kv.add_batch(keys[seg[is_edge]], vals[is_edge])

    if not np.any(is_dist):
        return
    # best arriving distance per group
    dseg, ddist, dpred = seg[is_dist], vals[is_dist, 2], vals[is_dist, 1]
    groups, rows = group_min_rows(dseg, ddist, dpred)
    best_dist = np.full(len(fr), np.inf)
    best_pred = np.zeros(len(fr))
    best_dist[groups] = ddist[rows]
    best_pred[groups] = dpred[rows]
    has_dist = np.zeros(len(fr), bool)
    has_dist[dseg] = True

    eseg = seg[is_edge]
    vj = vals[is_edge, 1]
    wt = vals[is_edge, 2]
    vi = keys[seg[is_edge]].astype(np.float64)
    relax = (has_dist[eseg] & (vj != best_pred[eseg]) & (vj != vi)
             & np.isfinite(best_dist[eseg]))
    if np.any(relax):
        nk = vj[relax].astype(np.uint64)
        rows = np.stack([np.full(len(nk), TAG_DIST), vi[relax],
                         best_dist[eseg][relax] + wt[relax],
                         np.zeros(len(nk))], 1)
        mrpath.kv.add_batch(nk, rows)


# ---------------------------------------------------------------------------
# command
# ---------------------------------------------------------------------------

@command("sssp")
class SSSPCommand(Command):
    """sssp ncnt seed: shortest paths from ncnt deterministic-random
    sources over a directed weighted edge list (oink/sssp.cpp).  Output
    per source: 'v dist pred' lines (path suffixed .<i> when ncnt > 1);
    self.results[source] = {v: (dist, pred)} (the fused engine's made
    from its arrays when first read, its file formatted from them:
    ``%d %g %d`` through ``core/column.format_rows``).

    Engines (same contract — any pred realising the shortest distance):
    ``fused`` (default) — whole Bellman-Ford relaxation in one jitted
    ``lax.while_loop`` with the source as a traced operand, so every
    source of the ncnt experiment reuses ONE compiled program
    (models/sssp.py); ``composed`` — the reference's per-round MR
    composition below (the tests' reference, reached by setting
    ``SSSPCommand.engine``)."""

    ninputs = 1
    noutputs = 1
    engine: str = "fused"

    def params(self, args):
        if len(args) != 2:
            raise MRError("Illegal sssp command")
        self.ncnt = int(args[0])
        self.seed = int(args[1])

    @functools.cached_property
    def results(self) -> dict:
        """{source: {v: (dist, pred)}} of the fused engine's last run,
        made from its arrays when first read (the composed engine sets
        the dict it fills)."""
        return {source: dict(zip(verts.tolist(),
                                 zip(dist.tolist(), predv.tolist())))
                for source, (verts, dist, predv) in self._arrays.items()}

    def run(self):
        if self.engine not in ("fused", "composed"):
            raise MRError(f"sssp: unknown engine {self.engine!r} "
                          f"(use 'fused' or 'composed')")
        if self.engine == "composed":
            return self._run_composed()
        obj = self.obj
        mredge = obj.input(1, read_edge_weight)

        from jax.sharding import Mesh
        mesh = obj.comm if isinstance(obj.comm, Mesh) else None
        # device staging (VERDICT r2 #2): vertex ranking on device; the
        # weight column is row-sharded aligned with the ranked endpoints
        # (need_weights guards against interned byte values, whose u64
        # ids are not numbers)
        from ...obs import get_tracer, names
        from ...parallel.staging import stage_graph, stage_graph_host
        tr = get_tracer()
        with tr.span(names.SSSP_STAGE, cat=names.HOST) as sp:
            sg = stage_graph(mredge, obj.comm, need_weights=True)
            # (sg.n == 0 cannot happen: empty datasets return None and
            # without drop_self every valid edge row has real endpoints)
            if sg is not None:
                from ...models.sssp import _bf_sharded_fn, runner
                verts, n = sg.verts, sg.n
                bf = runner(_bf_sharded_fn(mesh, n, max(n, 1)), sg.src,
                            sg.dst, sg.weights, sg.valid, n)
            else:
                sg = stage_graph_host(mredge, need_weights=True)
                verts, n, src, dst = sg.verts, sg.n, sg.src, sg.dst
                if n == 0:
                    raise MRError("sssp: empty edge list")
                w = sg.weights.astype(np.float64)

                from ...models.sssp import (bellman_ford,
                                            prepare_bellman_ford, runner)
                if mesh is not None:
                    # pad + upload the edges ONCE; every source reuses the
                    # compiled program and the device-resident arrays
                    bf = prepare_bellman_ford(mesh, src, dst, w, n)
                else:
                    bf = runner(
                        lambda s, d, w, _v, at: bellman_ford(s, d, w, n, at),
                        src.astype(np.int32), dst.astype(np.int32),
                        jnp.asarray(w), np.ones(len(w), bool), n)

            # deterministic-random source list (same ranking as composed)
            order = np.lexsort((verts, vertex_rand(verts, self.seed)))
            sources = verts[order][:self.ncnt].tolist()
            edges, edge_rows = int(mredge.kv.nkv), sg.rows
            sp.set(n=n, edges=edges, edge_rows=edge_rows)

        self._arrays = {}
        vars(self).pop("results", None)
        self.niters = {}
        outd = obj.outputs[0] if obj.outputs else None
        dist = np.full(n, np.inf)
        pred = np.full(n, -1, np.int64)
        for cnt, source in enumerate(sources):
            sidx = int(np.searchsorted(verts, np.uint64(source)))
            with tr.span(names.SSSP_ENGINE, cat=names.ENGINE) as sp:
                # the span ends at the pull of dist and pred
                dist, pred, niter = bf(sidx)
                nlabeled = int(np.isfinite(dist).sum())
                sp.set(iters=niter, source=int(source), labeled=nlabeled,
                       n=n, edges=edges, edge_rows=edge_rows)
            with tr.span(names.SSSP_EMIT, cat=names.HOST, n=n,
                         source=int(source)) as sp:
                # dict/file view: -1 (source/unreachable) renders as 0 like
                # the composed output path (np.maximum(..., 0))
                predv = np.where(pred >= 0, verts[np.maximum(pred, 0)],
                                 np.uint64(0))
                self._arrays[source] = (verts, dist, predv)
                self.niters[source] = niter
                self.message(f"SSSP: source {source}: {niter} iterations, "
                             f"{nlabeled} vertices labeled")
                block_rows = 0
                if outd is not None and outd.path is not None:
                    path = (f"{outd.path}.{cnt}" if self.ncnt > 1
                            else outd.path)
                    # verts is ascending (np.unique's, the ranking's):
                    # the order of the composed engine's sorted(res)
                    cols = row_columns(RESULT_LINE, (verts, dist, predv))
                    with open(path, "wb") as fp:
                        write_rows(fp, RESULT_LINE, cols,
                                   mredge._ingest_pool())
                    block_rows = n
                sp.set(rows=n, **block_attrs(block_rows))
        if outd is not None and outd.mr_name is not None:
            # named-MR rows keep the composed engine's persisted shape:
            # [TAG_DIST, pred (original id, NO_PRED sentinel intact),
            # dist, current=1] — a consumer can tell "no predecessor"
            # from "predecessor is vertex 0" (see module docstring)
            predf = np.where(pred >= 0,
                             verts[np.maximum(pred, 0)].astype(np.float64),
                             NO_PRED)
            mrv = obj.create_mr()
            rows = np.stack([np.full(n, TAG_DIST), predf, dist,
                             np.ones(n)], axis=1)
            mrv.map(1, lambda i, kv, p: kv.add_batch(verts, rows))
            obj.name_mr(outd.mr_name, mrv)
        obj.cleanup()

    def _run_composed(self):
        obj = self.obj
        mredge = obj.input(1, read_edge_weight)
        mredge.aggregate()   # mesh: shard once; the relaxation loop stays
        #                      device-resident (serial: no-op)

        # vertex universe (no singletons, pre-aggregated — sssp.cpp:63-66)
        mrvert = obj.create_mr()
        mrvert.map_mr(mredge, edge_to_vertices, batch=True)
        mrvert.collate()
        mrvert.reduce(cull, batch=True)

        # deterministic-random source list (see module docstring)
        vcols: list = []
        mrvert.scan_kv(lambda fr, p: vcols.append(kv_keys(fr)), batch=True)
        varr = np.unique(np.concatenate(vcols).astype(np.uint64))
        order = np.lexsort((varr, vertex_rand(varr, self.seed)))
        sources = varr[order][:self.ncnt].tolist()

        # adjacency keyed by source vertex, pre-aggregated (sssp.cpp:75-76)
        mradj = obj.create_mr()
        mradj.map_mr(mredge, reorganize_edges, batch=True)
        mradj.aggregate()

        self.results = {}
        self.niters = {}
        outd = obj.outputs[0] if obj.outputs else None
        for cnt, source in enumerate(sources):
            mrvert.map_mr(mrvert, init_distance, batch=True)
            mredge_w = obj.create_mr()
            mredge_w.add(mradj)

            mrpath = obj.create_mr()
            src_row = np.array([[TAG_DIST, NO_PRED, 0.0, 0.0]])
            mrpath.map(1, lambda i, kv, p: kv.add_batch(
                np.array([source], np.uint64), src_row))

            niter = 0
            while True:
                mrpath.aggregate()
                mrvert.add(mrpath)
                obj.free_mr(mrpath)
                mrpath = obj.create_mr()
                mrpath.open()
                mrvert.compress(pick_shortest, ptr=mrpath, batch=True)
                nchanged = mrpath.close()
                niter += 1
                if nchanged == 0:
                    break
                mredge_w.add(mrpath)
                obj.free_mr(mrpath)
                mrpath = obj.create_mr()
                mrpath.open()
                mredge_w.compress(update_adjacent, ptr=mrpath, batch=True)
                mrpath.close()
            obj.free_mr(mrpath)
            obj.free_mr(mredge_w)

            cols: list = []
            mrvert.scan_kv(lambda fr, p: cols.append(
                (kv_keys(fr), kv_values(fr))), batch=True)
            res = {}
            for ks, vs in cols:
                res.update(zip(
                    ks.astype(np.uint64).tolist(),
                    zip(vs[:, 2].tolist(),
                        np.maximum(vs[:, 1], 0).astype(np.int64).tolist())))
            self.results[source] = res
            self.niters[source] = niter
            nlabeled = sum(1 for d, _ in res.values() if np.isfinite(d))
            self.message(f"SSSP: source {source}: {niter} iterations, "
                         f"{nlabeled} vertices labeled")
            if outd is not None and outd.path is not None:
                path = (f"{outd.path}.{cnt}" if self.ncnt > 1
                        else outd.path)
                with open(path, "w") as fp:
                    for v in sorted(res):
                        d, p = res[v]
                        fp.write(f"{v} {d:g} {p}\n")
        if outd is not None and outd.mr_name is not None:
            obj.name_mr(outd.mr_name, mrvert)
        obj.cleanup()
