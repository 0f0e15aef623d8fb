"""tri_find / neigh_tri — Cohen's MapReduce triangle enumeration.

Reference: ``oink/tri_find.cpp:43-81`` (degree-augment edges, low-degree
vertex emits angles, join angles with original edges) and
``oink/neigh_tri.cpp:40-69`` (per-vertex neighbor+triangle files).

All kernels are batch/vectorised: the O(d²) angle emission builds its pair
index arrays with repeat/cumsum instead of nested loops, and the
valuebytes-discriminated unions of the reference become tagged ``[tag,a,b]``
u64 rows (tag 0 = original edge / plain neighbor, tag 1 = angle / triangle
edge)."""

from __future__ import annotations

import os

import numpy as np

from ...core.runtime import MRError
from ..command import Command, command
from ..kernels import (_parse_cols, edge_both_directions, host_kmv, kmv_keys,
                       kmv_values, kv_keys, kv_values, read_edge,
                       row_template, seg_ids, sum_values)


import jax
import jax.numpy as jnp

from ...parallel.devkernels import (is_sharded_kmv, is_sharded_kv,
                                    kmv_row_state, seg_max_u64, skmv_map,
                                    skv_map)
from ...parallel.sharded import round_cap


def _first_degree_dev(uk, nv, vo, vals, gc, vc):
    seg, rows_valid, _ = kmv_row_state(nv, vo, vals, gc, vc)
    g = jnp.maximum(seg, 0)
    nb = vals.astype(jnp.uint64)
    center = jnp.take(uk, g).astype(jnp.uint64)
    d = jnp.take(nv, g).astype(jnp.uint64)
    lo = jnp.minimum(center, nb)
    hi = jnp.maximum(center, nb)
    is_i = center < nb
    zero = jnp.zeros_like(d)
    oval = jnp.stack([jnp.where(is_i, d, zero),
                      jnp.where(is_i, zero, d)], 1)
    return jnp.stack([lo, hi], 1), oval, rows_valid


def first_degree(fr, kv, ptr):
    """Per-vertex group (neighbors list, size d): emit canonical edge →
    (d,0) or (0,d) depending on which endpoint the center is
    (reduce_first_degree, oink/tri_find.cpp:116-159)."""
    if is_sharded_kmv(fr):
        kv.add_frame(skmv_map(fr, _first_degree_dev))
        return
    fr = host_kmv(fr)
    nb = kmv_values(fr).astype(np.uint64)            # [n] neighbor ids
    center = np.repeat(kmv_keys(fr).astype(np.uint64), fr.nvalues)
    d = np.repeat(np.asarray(fr.nvalues).astype(np.uint64), fr.nvalues)
    lo = np.minimum(center, nb)
    hi = np.maximum(center, nb)
    is_i = center < nb
    zero = np.zeros(len(nb), np.uint64)
    di = np.where(is_i, d, zero)
    dj = np.where(is_i, zero, d)
    kv.add_batch(np.stack([lo, hi], 1), np.stack([di, dj], 1))


def _low_degree_dev(k, v, c):
    valid = jnp.arange(k.shape[0]) < c
    low_is_i = (v[:, 0] < v[:, 1]) | ((v[:, 0] == v[:, 1]) &
                                      (k[:, 0] < k[:, 1]))
    return (jnp.where(low_is_i, k[:, 0], k[:, 1]),
            jnp.where(low_is_i, k[:, 1], k[:, 0]), valid)


def low_degree(fr, kv, ptr):
    """(Eij:(Di,Dj)) → lower-degree endpoint : other endpoint; degree tie
    broken toward Vi (map_low_degree, oink/tri_find.cpp:185-207)."""
    if is_sharded_kv(fr):
        kv.add_frame(skv_map(fr, _low_degree_dev))
        return
    e = kv_keys(fr)
    deg = kv_values(fr)
    low_is_i = (deg[:, 0] < deg[:, 1]) | ((deg[:, 0] == deg[:, 1]) &
                                          (e[:, 0] < e[:, 1]))
    kv.add_batch(np.where(low_is_i, e[:, 0], e[:, 1]),
                 np.where(low_is_i, e[:, 1], e[:, 0]))


def _nsq_angles_dev(uk, nv, vo, vals, gc, vc, out_cap):
    vcap = vals.shape[0]
    seg, rows_valid, _ = kmv_row_state(nv, vo, vals, gc, vc)
    g = jnp.maximum(seg, 0)
    end = jnp.take(vo + nv, g)                       # group end row
    rem = jnp.where(rows_valid,
                    end - jnp.arange(vcap, dtype=jnp.int32) - 1, 0)
    rem = jnp.maximum(rem, 0)
    j_idx = jnp.repeat(jnp.arange(vcap), rem, total_repeat_length=out_cap)
    off = jnp.concatenate([jnp.zeros(1, rem.dtype), jnp.cumsum(rem)])
    total = off[-1]
    pos = jnp.arange(out_cap)
    valid_out = pos < total
    k_idx = jnp.clip(pos - jnp.take(off, j_idx) + j_idx + 1, 0, vcap - 1)
    nb = vals.astype(jnp.uint64)
    vj = jnp.take(nb, j_idx)
    vk = jnp.take(nb, k_idx)
    center = jnp.take(uk, jnp.take(g, j_idx)).astype(jnp.uint64)
    lo = jnp.minimum(vj, vk)
    hi = jnp.maximum(vj, vk)
    one = jnp.ones(out_cap, jnp.uint64)
    oval = jnp.stack([one, center, one - 1], 1)
    return jnp.stack([lo, hi], 1), oval, valid_out


def nsq_angles(fr, kv, ptr):
    """Per-center group: every unordered neighbor pair (Vj,Vk) is an "angle"
    (a triangle missing the Vj-Vk edge): emit canonical (Vj,Vk) → [1,center,0]
    (reduce_nsq_angles, oink/tri_find.cpp:211-276, the O(d²) kernel)."""
    if is_sharded_kmv(fr):
        # static expansion cap: worst shard's Σ d(d-1)/2, from the group
        # sizes (one host fetch of the int32 size column, not the data)
        P, gcap = fr.nprocs, fr.gcap
        nv = np.asarray(fr.nvalues).reshape(P, gcap).astype(np.int64)
        m = np.arange(gcap)[None, :] < fr.gcounts[:, None]
        nv = np.where(m, nv, 0)
        per_shard = (nv * (nv - 1) // 2).sum(axis=1)
        out_cap = round_cap(int(max(1, per_shard.max())))
        kv.add_frame(skmv_map(fr, _nsq_angles_dev, static=(out_cap,)))
        return
    fr = host_kmv(fr)
    nb = kmv_values(fr).astype(np.uint64)
    n = len(nb)
    seg = seg_ids(fr)
    end = np.asarray(fr.offsets)[1:][seg]            # group end per row
    rem = (end - np.arange(n) - 1).astype(np.int64)  # later rows in group
    j_idx = np.repeat(np.arange(n), rem)
    off = np.concatenate([[0], np.cumsum(rem)])
    k_idx = np.arange(int(rem.sum())) - off[j_idx] + j_idx + 1
    vj, vk = nb[j_idx], nb[k_idx]
    center = kmv_keys(fr).astype(np.uint64)[seg[j_idx]]
    lo = np.minimum(vj, vk)
    hi = np.maximum(vj, vk)
    one = np.ones(len(lo), np.uint64)
    kv.add_batch(np.stack([lo, hi], 1),
                 np.stack([one, center, np.zeros(len(lo), np.uint64)], 1))


def _edge_null_tagged_dev(k, v, c):
    valid = jnp.arange(k.shape[0]) < c
    return k, jnp.zeros((k.shape[0], 3), jnp.uint64), valid


def edge_null_tagged(fr, kv, ptr):
    """Eij:NULL → Eij:[0,0,0] — original-edge marker rows for the angle
    join (the reference reuses valuebytes==0)."""
    if is_sharded_kv(fr):
        kv.add_frame(skv_map(fr, _edge_null_tagged_dev))
        return
    e = kv_keys(fr)
    kv.add_batch(e, np.zeros((len(e), 3), np.uint64))


def _emit_triangles_dev(uk, nv, vo, vals, gc, vc):
    gcap = uk.shape[0]
    seg, rows_valid, _ = kmv_row_state(nv, vo, vals, gc, vc)
    g = jnp.maximum(seg, 0)
    is_edge = rows_valid & (vals[:, 0] == 0)
    has_edge = seg_max_u64(jnp.ones(vals.shape[0], jnp.uint64), seg,
                           is_edge, gcap) > 0
    take = rows_valid & (vals[:, 0] != 0) & jnp.take(has_edge, g)
    e = jnp.take(uk, g, axis=0).astype(jnp.uint64)     # [vcap, 2]
    okey = jnp.stack([vals[:, 1], e[:, 0], e[:, 1]], 1)
    return okey, jnp.zeros(vals.shape[0], jnp.uint8), take


def emit_triangles(fr, kv, ptr):
    """Per-edge group of tagged rows: if an original-edge marker is present,
    every angle row (center Vi) completes a triangle (Vi,Vj,Vk)
    (reduce_emit_triangles, oink/tri_find.cpp:280-...)."""
    if is_sharded_kmv(fr):
        kv.add_frame(skmv_map(fr, _emit_triangles_dev))
        return
    fr = host_kmv(fr)
    vals = kmv_values(fr)                            # [n,3] tagged
    seg = seg_ids(fr)
    is_edge = vals[:, 0] == 0
    has_edge = np.zeros(len(fr), bool)
    has_edge[seg[is_edge]] = True
    take = (~is_edge) & has_edge[seg]
    e = kmv_keys(fr).astype(np.uint64)[seg[take]]    # [m,2] the (Vj,Vk) edge
    center = vals[take, 1]
    kv.add_batch(np.stack([center, e[:, 0], e[:, 1]], 1),
                 np.zeros(len(center), np.uint8))


@row_template("%d %d %d", key_fields=3)
def print_tri(k, v, fp):
    fp.write(f"{k[0]} {k[1]} {k[2]}\n")


@command("tri_find")
class TriFind(Command):
    """tri_find: enumerate all triangles of an edge list; output one
    (Vi,Vj,Vk) line per triangle, Vi = the low-degree "center" vertex that
    emitted the angle (oink/tri_find.cpp:43-81).

    Engines: ``fused`` (default) — the degree-ordered wedge walk as device
    programs (models/tri.py: a long out-list paired block against block,
    a short one wedge index by wedge index, each batch of wedges joined
    with the edge keys by a sort; the triangles stay on the device as the
    output MR), the same programs on every backend; ``composed`` — the
    reference's 6-stage MR pipeline below (the tests' reference, reached
    by setting ``TriFind.engine``), whose ``nsq_angles`` holds every
    angle of a shard in one frame and so stops where Σ d(d-1)/2 rows of
    40 bytes outgrow the device.
    Identical triangle sets."""

    ninputs = 1
    noutputs = 1
    engine: str = "fused"

    def params(self, args):
        if args:
            raise MRError("Illegal tri_find command")

    def run(self):
        if self.engine not in ("fused", "composed"):
            raise MRError(f"tri_find: unknown engine {self.engine!r} "
                          f"(use 'fused' or 'composed')")
        if self.engine == "composed":
            return self._run_composed()
        obj = self.obj
        mre = obj.input(1, read_edge)

        from jax.sharding import Mesh
        from ...models import tri
        from ...obs import get_tracer, names
        from ...parallel.mesh import mesh_axis_size
        from ...parallel.sharded import ShardedKV
        from ...parallel.staging import stage_graph, stage_graph_host
        mesh = obj.comm if isinstance(obj.comm, Mesh) else None
        tr = get_tracer()
        # device staging (VERDICT r2 #2): vertices ranked on the device,
        # the ranked edge rows stay there for the walk; the serial backend
        # ranks on the host and hands the same programs its rows
        with tr.span(names.TRI_STAGE, cat=names.HOST) as sp:
            sg = stage_graph(mre, obj.comm)
            if sg is not None:
                src, dst = sg.src, sg.dst
            else:
                sg = stage_graph_host(mre)
                src, dst = sg.src.astype(np.int32), sg.dst.astype(np.int32)
            verts, valid, n = sg.verts, sg.valid, sg.n
            sp.set(n=n, edges=int(mre.kv.nkv) if mre.kv is not None else 0)
        if n >= 2**31:
            raise MRError(f"tri_find: {n} vertices overflow int32 ranks")

        with tr.span(names.TRI_ENGINE, cat=names.ENGINE) as sp:
            # the span ends at the pull of the last batch's hit count
            w = tri.walk(src, dst, valid, verts, mesh) if n else tri.NO_WALK
            sp.set(wedges=w.wedges, batches=w.batches, triangles=w.ntri,
                   edges=w.edges, n=n, max_out_degree=w.max_out_degree,
                   **{names.ATTR_TILES: w.tiles,
                      names.ATTR_INDEX_WEDGES: w.index_wedges,
                      names.ATTR_TILE_FILL: w.tile_fill})

        self.ntri = w.ntri
        mrt = obj.create_mr()
        with tr.span(names.TRI_EMIT, cat=names.HOST) as sp:
            sp.set(triangles=w.ntri)
            if w.ntri:
                key, value = jax.block_until_ready(tri.rows(w, mesh))
                del w
                if mesh is not None and mesh_axis_size(mesh) == 1:
                    frame = ShardedKV(mesh, key, value,
                                      np.asarray([self.ntri], np.int32))
                    mrt.map(1, lambda i, kv, p: kv.add_frame(frame))
                else:
                    # every device of a wider mesh walked the same wedges:
                    # the rows are one host frame, the output one file
                    mrt.map(1, lambda i, kv, p: kv.add_batch(
                        np.asarray(key)[:self.ntri],
                        np.zeros(self.ntri, np.uint8)))
        obj.output(1, mrt, print_tri)
        self.message(f"Tri_find: {self.ntri} triangles")
        obj.cleanup()

    def _run_composed(self):
        obj = self.obj
        mre = obj.input(1, read_edge)
        mre.aggregate()   # mesh: shard once; all stages below stay
        #                   device-resident (serial: no-op)
        mrt = obj.create_mr()

        # augment edges with endpoint degrees: mrt = (Eij, (Di, Dj))
        mrt.map_mr(mre, edge_both_directions, batch=True)
        mrt.collate()
        mrt.reduce(first_degree, batch=True)
        mrt.collate()
        mrt.reduce(sum_values, batch=True)

        # angles from the low-degree endpoint, joined with original edges
        mrt.map_mr(mrt, low_degree, batch=True)
        mrt.collate()
        mrt.reduce(nsq_angles, batch=True)
        tmp = obj.create_mr()
        tmp.map_mr(mre, edge_null_tagged, batch=True)
        mrt.add(tmp)
        mrt.collate()
        ntri = mrt.reduce(emit_triangles, batch=True)

        self.ntri = ntri
        obj.output(1, mrt, print_tri)
        self.message(f"Tri_find: {ntri} triangles")
        obj.cleanup()


# ---------------------------------------------------------------------------
# neigh_tri
# ---------------------------------------------------------------------------

def read_adjacency(itask, filename, kv, ptr):
    """'vi vj vk ...' adjacency lines → (vi : [0,vj,0]) tagged neighbor rows
    (NeighTri::nread, oink/neigh_tri.cpp:76-92)."""
    rows_v, rows_n = [], []
    with open(filename) as f:
        for line in f:
            toks = line.split()
            if not toks:
                continue
            vi = int(toks[0])
            for t in toks[1:]:
                rows_v.append(vi)
                rows_n.append(int(t))
    v = np.asarray(rows_v, np.uint64)
    nb = np.asarray(rows_n, np.uint64)
    zero = np.zeros(len(v), np.uint64)
    kv.add_batch(v, np.stack([zero, nb, zero], 1))


def read_tri(itask, filename, kv, ptr):
    """'vi vj vk' triangle lines → key [vi,vj,vk] : NULL
    (NeighTri::tread, oink/neigh_tri.cpp:96-109)."""
    vi, vj, vk = _parse_cols(filename, (np.uint64,) * 3)
    kv.add_batch(np.stack([vi, vj, vk], 1), np.zeros(len(vi), np.uint8))


def tri_to_vertex_edges(fr, kv, ptr):
    """(Vi,Vj,Vk):NULL → each corner : [1, other1, other2] tagged
    triangle-edge rows (NeighTri::map1, oink/neigh_tri.cpp:143-160)."""
    t = kv_keys(fr)
    one = np.ones(len(t), np.uint64)
    kv.add_batch(
        np.concatenate([t[:, 0], t[:, 1], t[:, 2]]),
        np.concatenate([np.stack([one, t[:, 1], t[:, 2]], 1),
                        np.stack([one, t[:, 0], t[:, 2]], 1),
                        np.stack([one, t[:, 0], t[:, 1]], 1)]))


@command("neigh_tri")
class NeighTri(Command):
    """neigh_tri dirname: per-vertex files dirname/<Vi> listing the vertex's
    neighbors ("vi vj" lines) and its triangles' opposite edges ("vj vk"
    lines) (oink/neigh_tri.cpp:40-69).  Inputs: 1 = adjacency file(s),
    2 = triangle file(s) from tri_find."""

    ninputs = 2
    noutputs = 0  # output is the dirname arg, matching the reference

    def params(self, args):
        if len(args) != 1:
            raise MRError("Illegal neigh_tri command")
        self.dirname = args[0]

    def run(self):
        obj = self.obj
        mrn = obj.input(1, read_adjacency)
        mrt = obj.input(2, read_tri)
        mrnplus = obj.copy_mr(mrn)
        mrnplus.map_mr(mrt, tri_to_vertex_edges, batch=True, addflag=1)
        mrnplus.collate()

        os.makedirs(self.dirname, exist_ok=True)
        nvert = [0]

        def write_vertex(key, vals, ptr):
            vi = int(key)
            with open(os.path.join(self.dirname, str(vi)), "w") as fp:
                for tag, a, b in vals:
                    if int(tag) == 0:
                        fp.write(f"{vi} {int(a)}\n")
                    else:
                        fp.write(f"{int(a)} {int(b)}\n")
            nvert[0] += 1

        mrnplus.scan_kmv(write_vertex)
        self.nvert = nvert[0]
        self.message(f"Neigh_tri: {self.nvert} vertex files in "
                     f"{self.dirname}")
        obj.cleanup()
