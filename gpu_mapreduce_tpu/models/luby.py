"""Luby maximal independent set — fused on-device rounds.

The reference iterates {edge_winner, vert_winner, vert_loser,
vert_emit} MapReduce stages until no edges remain
(``oink/luby_find.cpp:53-95``); the composed twin lives in
oink/commands/luby.py.  This model runs the whole thing in ONE jitted
``lax.while_loop`` over a dense vertex state vector:

* per-vertex priorities are the SAME splitmix64 stream as the composed
  engine (``vertex_rand(v, seed)`` on original ids), handed over as
  their int32 ranks in the order of (priority, id): distinct, so one
  comparison decides; exact, because the set depends on that order
  alone; and integers, because the v5e's compiler lowers no float64
  ``pmin``.  A vertex joins when its rank is smaller than every
  UNDECIDED neighbour's.  With these shared priorities the two engines
  produce identical sets on the golden script input, but only the MIS
  property itself is contractual (the composed rounds cull edges in a
  different order — see the LubyFind docstring);
* one round = one masked segment-min (the smallest undecided
  neighbour's rank) + neighbour-of-winner exclusion, all vectorised;
  the mesh version pmin/pmax-combines over ICI.

States: 0 undecided, 1 in MIS, 2 excluded.  A vertex whose undecided
neighbourhood empties (everyone excluded) sees the largest int32 and
joins — the maximality guarantee."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import mesh_axes, mesh_axis_size, row_spec


def _both_dirs(src, dst, x_by_src):
    """Edge contributions in both directions: (values, targets) where
    value i is x evaluated at the *other* endpoint."""
    return (jnp.concatenate([x_by_src[src], x_by_src[dst]]),
            jnp.concatenate([dst, src]))


def _round(state, prio, src, dst, valid, n, axes=None):
    und = state == 0
    active = valid & und[src] & und[dst]
    act2 = jnp.concatenate([active, active])

    pv, tgt = _both_dirs(src, dst, prio)
    seg = jnp.where(act2, tgt, n)

    # min neighbour priority among undecided neighbours; no two vertices
    # share a priority, so the strict comparison decides alone
    top = jnp.iinfo(prio.dtype).max
    m1 = jax.ops.segment_min(jnp.where(act2, pv, top), seg,
                             num_segments=n + 1)[:n]
    if axes is not None:
        m1 = lax.pmin(m1, axes)
    winner = und & (prio < m1)

    # neighbours of winners become excluded (only undecided ones change)
    wv = jnp.concatenate([winner[src], winner[dst]]).astype(jnp.int32)
    seg_all = jnp.where(jnp.concatenate([valid, valid]), tgt, n)
    wn = jax.ops.segment_max(jnp.where(seg_all < n, wv, 0), seg_all,
                             num_segments=n + 1)[:n]
    if axes is not None:
        wn = lax.pmax(wn, axes)
    lose = und & ~winner & (wn > 0)
    return jnp.where(winner, 1, jnp.where(lose, 2, state)).astype(jnp.int8)


def _loop(step, n, maxiter):
    state0 = jnp.zeros(n, jnp.int8)

    def cond(s):
        state, it = s
        return jnp.logical_and(jnp.any(state == 0), it < maxiter)

    def body(s):
        state, it = s
        return step(state), it + 1

    return lax.while_loop(cond, body, (state0, jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=("n", "maxiter"))
def luby_mis(src, dst, prio, n: int, maxiter: int = 0
             ) -> Tuple[jax.Array, jax.Array]:
    """Single device.  Returns (state[n] ∈ {1 MIS, 2 excluded}, rounds).
    ``prio``: each vertex's int32 rank in the order of (vertex_rand on
    original ids, id); no two alike."""
    maxiter = maxiter or max(n, 1)
    valid = jnp.ones(src.shape, bool)
    s32, d32 = src.astype(jnp.int32), dst.astype(jnp.int32)
    return _loop(lambda st: _round(st, prio, s32, d32, valid, n),
                 n, maxiter)


@functools.lru_cache(maxsize=None)
def _luby_sharded_fn(mesh: Mesh, n: int, maxiter: int):
    axes = mesh_axes(mesh)
    rspec = row_spec(mesh)
    rep = NamedSharding(mesh, P())

    @functools.partial(jax.jit, out_shardings=(rep, rep))
    def luby_loop(src_d, dst_d, valid_d, prio):
        body = jax.shard_map(
            lambda st, pr, s, d, v: _round(st, pr, s, d, v, n, axes),
            mesh=mesh, in_specs=(P(), P(), rspec, rspec, rspec),
            out_specs=P())
        return _loop(lambda st: body(st, prio, src_d, dst_d, valid_d),
                     n, maxiter)

    return luby_loop


def luby_mis_sharded(mesh: Mesh, src: np.ndarray, dst: np.ndarray,
                     prio: np.ndarray, n: int, maxiter: int = 0
                     ) -> Tuple[np.ndarray, int]:
    from ..models.pagerank import pad_edges_for_mesh

    nprocs = mesh_axis_size(mesh)
    src_p, dst_p, valid_p = pad_edges_for_mesh(
        src.astype(np.int32), dst.astype(np.int32), nprocs)
    shard = NamedSharding(mesh, row_spec(mesh))
    run = _luby_sharded_fn(mesh, n, maxiter or max(n, 1))
    from ..parallel.mesh import device_put_chunked, replicated
    state, iters = run(device_put_chunked(src_p, shard),
                       device_put_chunked(dst_p, shard),
                       device_put_chunked(valid_p, shard),
                       device_put_chunked(np.asarray(prio),
                                          replicated(mesh)))
    return np.asarray(state), int(iters)
