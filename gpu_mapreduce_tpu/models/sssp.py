"""Single-source shortest paths — fused on-device Bellman-Ford.

The reference's sssp command relaxes distances through ~6 MapReduce
stages per round (``oink/sssp.cpp:49-180``); like cc_find, that
composition pays one compiled XLA program per stage per shape, and the
iterative driver drowns in recompiles (SURVEY.md §7).  The fused model
runs the whole relaxation to fixpoint in ONE jitted ``lax.while_loop``:

* ``dist`` is a dense replicated vector (vertices pre-densified by the
  command, like PageRank/cc);
* one round = per vertex the least ``dist[src] + w`` over its in-edges
  of the (sharded) edge list, and the smallest source achieving it as
  the predecessor;
* the mesh version combines both over ICI; the only host traffic is the
  final (dist, pred).

Distances run in the weights' type.  ``exact_weights`` hands the loop
int32 weights where that loses nothing (whole, non-negative, every path
sum below 2^31: ``add_weight``'s unit weights), and the round is then
one three-key sort of the edge rows whose first row a vertex is its
answer: on the chip a scatter costs thirty sorts of its size (PERF.md
§6), and a float64 there is emulated.  Other weights keep float64 and
two ``segment_min`` (the v5e sorts no float64: it has no bitcast of
one).  Either way the caller reads float64 distances.

The source vertex is a TRACED operand, so the ncnt-source experiment
(``sssp ncnt seed``) reuses one compiled program for every source.
Predecessor ties break to the smallest vertex index (any pred that
realises the shortest distance is valid — the oracle contract)."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import mesh_axes, mesh_axis_size, row_spec


def _whole(dtype) -> bool:
    return jnp.issubdtype(dtype, jnp.integer)


def _inf(dtype):
    """What an unreached vertex reads."""
    return jnp.iinfo(dtype).max if _whole(dtype) else jnp.inf


def _pmin(x, axes):
    """``lax.pmin``; of float64 distances the v5e's compiler lowers a
    64-bit float all-reduce for sums only (and no bitcast of one), so the
    shards' partial mins are gathered, which moves them like any data,
    and reduced on every shard alike."""
    if _whole(x.dtype):
        return lax.pmin(x, axes)
    return jnp.min(lax.all_gather(x, axes), axis=0)


def _segments(dst, valid, n):
    """Where each vertex's in-edges start among the rows sorted by
    destination, and whether it has any: the same in every round."""
    seg = jnp.sort(jnp.where(valid, dst, n))
    at = jnp.searchsorted(seg, jnp.arange(n + 1, dtype=seg.dtype))
    at = at.astype(jnp.int32)
    return at[:-1], at[1:] > at[:-1]


def _round(dist, pred, src, dst, w, valid, n, segs=None, axes=None):
    """One relaxation round; with ``segs`` (whole distances) by a sort,
    else by two ``segment_min``; with ``axes`` the shards' partial
    answers combine across the mesh."""
    inf = _inf(dist.dtype)
    with jax.named_scope("gather"):
        seg = jnp.where(valid, dst, n)
        d = dist[src]
    with jax.named_scope("relax"):
        relax = jnp.where(valid & (d < inf), d + w, inf)
    # per vertex: the least relaxed distance over this shard's in-edges,
    # and the smallest source that achieves it
    with jax.named_scope("select"):
        if segs is None:
            m = jax.ops.segment_min(relax, seg, num_segments=n + 1)
            cand = jnp.where(relax == m[seg], src, n)
            pm = jax.ops.segment_min(cand, seg, num_segments=n + 1)[:n]
            m = m[:n]
        else:
            first, any_in = segs
            _, r, s = lax.sort((seg, relax, src), num_keys=3)
            m = jnp.where(any_in, r[first], inf)
            pm = jnp.where(any_in, s[first], n)
    if axes is not None:
        with jax.named_scope("merge"):
            least = _pmin(m, axes)
            pm = lax.pmin(jnp.where(m == least, pm, n), axes)
            m = least
    with jax.named_scope("update"):
        nd = jnp.minimum(dist, m)
        improved = nd < dist
        return nd, jnp.where(improved, pm, pred), jnp.any(improved)


def _loop(step, n, maxiter, source, dtype):
    with jax.named_scope("init"):
        dist0 = jnp.full((n,), _inf(dtype), dtype).at[source].set(0)
        pred0 = jnp.full((n,), -1, jnp.int32)

    def cond(state):
        return jnp.logical_and(state[2], state[3] < maxiter)

    def body(state):
        dist, pred, _, it = state
        nd, npred, changed = step(dist, pred)
        return nd, npred, changed, it + 1

    dist, pred, _, iters = lax.while_loop(
        cond, body, (dist0, pred0, jnp.bool_(True), jnp.int32(0)))
    return dist, pred, iters


@functools.partial(jax.jit, static_argnames=("n", "maxiter"))
def bellman_ford(src, dst, w, n: int, source, maxiter: int = 0):
    """Single device.  Returns (dist[n] in ``w``'s type, pred[n],
    iterations); pred is -1 for the source and unreachable vertices."""
    maxiter = maxiter or max(n, 1)
    valid = jnp.ones(src.shape, bool)
    s32, d32 = src.astype(jnp.int32), dst.astype(jnp.int32)
    segs = _segments(d32, valid, n) if _whole(w.dtype) else None

    def step(dist, pred):
        return _round(dist, pred, s32, d32, w, valid, n, segs)

    return _loop(step, n, maxiter, source, w.dtype)


@functools.lru_cache(maxsize=None)
def _bf_sharded_fn(mesh: Mesh, n: int, maxiter: int):
    axes = mesh_axes(mesh)
    rspec = row_spec(mesh)
    rep = NamedSharding(mesh, P())

    @functools.partial(jax.jit, out_shardings=(rep, rep, rep))
    def sssp_loop(src_d, dst_d, w_d, valid_d, source):
        segs = ()
        if _whole(w_d.dtype):       # each shard's own rows, sorted once
            with jax.named_scope("prologue"):
                segs = jax.shard_map(
                    lambda d, v: _segments(d, v, n), mesh=mesh,
                    in_specs=(rspec, rspec), out_specs=(rspec, rspec)
                )(dst_d, valid_d)
        body = jax.shard_map(
            lambda dist, pred, s, d, w, v, *sg: _round(
                dist, pred, s, d, w, v, n, sg or None, axes),
            mesh=mesh,
            in_specs=(P(), P(), rspec, rspec, rspec, rspec) + (rspec,)
            * len(segs),
            # replicated by construction: every shard reduces the same
            # gathered mins, which the checker cannot infer
            out_specs=(P(), P(), P()), check_vma=False)

        def step(dist, pred):
            return body(dist, pred, src_d, dst_d, w_d, valid_d, *segs)

        return _loop(step, n, maxiter, source, w_d.dtype)

    return sssp_loop


@jax.jit
def sssp_weights(w, valid):
    """The weights as int32, whether that lost nothing (whole and not
    negative), and the largest."""
    with jax.named_scope("weights"):
        wi = w.astype(jnp.int32)
        same = (wi >= 0) & (wi.astype(w.dtype) == w)
        return wi, jnp.all(same | ~valid), jnp.max(jnp.where(valid, wi, 0))


def exact_weights(w, valid, n: int):
    """``w`` as int32 where every distance is exact in int32: whole
    weights, none negative, and ``n`` of the largest (more than any
    round relaxes to) below 2^31.  Else ``w`` as it is."""
    wi, whole, top = sssp_weights(w, valid)
    if bool(whole) and int(top) * max(n, 1) < np.iinfo(np.int32).max:
        return wi
    return w


def runner(fn, src, dst, w, valid, n: int):
    """``run(source) → (float64 dist, pred, rounds)`` on the host, over
    edge arrays that stay where they are; ``fn`` is a loop of
    :func:`_bf_sharded_fn`'s signature."""
    w = exact_weights(w, valid, n)

    def run(source: int):
        dist, pred, iters = fn(src, dst, w, valid, jnp.int32(source))
        dist = np.asarray(dist)
        if dist.dtype.kind == "i":
            dist = np.where(dist == np.iinfo(dist.dtype).max, np.inf,
                            dist.astype(np.float64))
        return dist, np.asarray(pred), int(iters)

    return run


def prepare_bellman_ford(mesh: Mesh, src: np.ndarray, dst: np.ndarray,
                         w: np.ndarray, n: int, maxiter: int = 0):
    """Pad + upload the edge arrays ONCE; returns ``run(source) →
    (dist, pred, iters)`` — the ncnt-source experiment re-uses both the
    compiled program and the device-resident edges."""
    from ..models.pagerank import pad_edges_for_mesh

    nprocs = mesh_axis_size(mesh)
    src_p, dst_p, valid_p = pad_edges_for_mesh(
        src.astype(np.int32), dst.astype(np.int32), nprocs)
    w_p = np.concatenate([np.asarray(w, np.float64),
                          np.zeros(len(src_p) - len(w))])
    shard = NamedSharding(mesh, row_spec(mesh))
    from ..parallel.mesh import device_put_chunked
    return runner(_bf_sharded_fn(mesh, n, maxiter or max(n, 1)),
                  device_put_chunked(src_p, shard),
                  device_put_chunked(dst_p, shard),
                  device_put_chunked(w_p, shard),
                  device_put_chunked(valid_p, shard), n)


def bellman_ford_sharded(mesh: Mesh, src: np.ndarray, dst: np.ndarray,
                         w: np.ndarray, n: int, source: int,
                         maxiter: int = 0
                         ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Edge-parallel fused loop over a device mesh (single source; for
    many sources use :func:`prepare_bellman_ford`)."""
    return prepare_bellman_ford(mesh, src, dst, w, n, maxiter)(source)
