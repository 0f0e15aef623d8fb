"""Luby maximal independent set — fused on-device rounds.

The reference iterates {edge_winner, vert_winner, vert_loser,
vert_emit} MapReduce stages until no edges remain
(``oink/luby_find.cpp:53-95``); the composed twin lives in
oink/commands/luby.py.  This model runs the whole thing in ONE jitted
program over a dense vertex state vector:

* per-vertex priorities are the SAME splitmix64 stream as the composed
  engine (``vertex_rand(v, seed)`` on original ids), handed over as
  their int32 ranks in the order of (priority, id): distinct, so one
  comparison decides; exact, because the set depends on that order
  alone.  A vertex joins when its rank is smaller than every UNDECIDED
  neighbour's.  With these shared priorities the two engines produce
  identical sets on the golden script input, but only the MIS property
  itself is contractual (the composed rounds cull edges in a different
  order — see the LubyFind docstring);
* the ranks never change, so of an edge's two ends the same one beats the
  other in every round.  Before the loop each edge becomes ONE row
  ``(hi, lo)`` with ``prio[lo] < prio[hi]`` and each shard's rows are
  sorted by ``hi`` once (self loops and padding behind every run), so
  that the neighbours that beat a vertex are a run of rows with bounds
  ``at[v] .. at[v + 1]``;
* one round = two counts over those runs, each a gather by ``lo`` and a
  prefix sum read at the bounds: an undecided vertex with no undecided
  neighbour that beats it wins; a winner beats all its undecided
  neighbours, so an undecided vertex with a winner among the neighbours
  that beat it is excluded.  No scatter, and no sort inside the loop
  (on the chip a scatter of these rows costs thirty sorts: PERF.md §6);
  the mesh version adds the shards' counts over ICI (``lax.psum``).

States: 0 undecided, 1 in MIS, 2 excluded.  A vertex whose undecided
neighbourhood empties (everyone excluded) counts nobody ahead of it and
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


def _orient(src, dst, valid, prio, n):
    """Each edge once, as the row (hi, lo) with ``prio[lo] < prio[hi]``,
    the rows sorted by ``hi``: returns ``lo`` in that order and where
    each vertex's run starts, ``at[0..n]``.  Self loops and invalid rows
    go behind the last run.  A run's rows are counted, never read in
    order, so the sort need not be stable: the stable one carries a third
    operand and takes the chip's compiler three to four times as long."""
    down = prio[src] < prio[dst]
    hi = jnp.where(valid & (src != dst), jnp.where(down, dst, src), n)
    hi, lo = lax.sort((hi, jnp.where(down, src, dst)), num_keys=1,
                      is_stable=False)
    at = jnp.searchsorted(hi, jnp.arange(n + 1, dtype=hi.dtype))
    return lo, at.astype(jnp.int32)


def _count(flag, lo, at, axes):
    """Per vertex: how many of the neighbours that beat it carry ``flag``,
    as a prefix sum over the rows read at the runs' bounds."""
    with jax.named_scope("gather"):
        beaten = flag[lo]
    with jax.named_scope("prefix"):
        c = jnp.cumsum(beaten, dtype=jnp.int32)
        below = jnp.where(at > 0, c[jnp.maximum(at - 1, 0)], 0)
        k = below[1:] - below[:-1]
    if axes is None:
        return k
    with jax.named_scope("merge"):
        return lax.psum(k, axes)


def _round(state, lo, at, axes):
    with jax.named_scope("select"):
        und = state == 0
        winner = und & (_count(und, lo, at, axes) == 0)
        lose = und & (_count(winner, lo, at, axes) > 0)
        return jnp.where(winner, 1,
                         jnp.where(lose, 2, state)).astype(jnp.int8)


def _mis(src, dst, valid, prio, n, maxiter, axes=None):
    """The whole command on one shard's rows: orient, then rounds until
    no vertex is undecided."""
    with jax.named_scope("orient"):
        lo, at = _orient(src, dst, valid, prio, n)
        state0 = jnp.zeros(n, jnp.int8)

    def cond(s):
        state, it = s
        with jax.named_scope("undecided"):
            return jnp.logical_and(jnp.any(state == 0), it < maxiter)

    def body(s):
        state, it = s
        return _round(state, lo, at, axes), it + 1

    return lax.while_loop(cond, body, (state0, jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=("n", "maxiter"))
def luby_mis(src, dst, prio, n: int, maxiter: int = 0
             ) -> Tuple[jax.Array, jax.Array]:
    """Single device.  Returns (state[n] ∈ {1 MIS, 2 excluded}, rounds).
    ``prio``: each vertex's int32 rank in the order of (vertex_rand on
    original ids, id); no two alike."""
    return _mis(src.astype(jnp.int32), dst.astype(jnp.int32),
                jnp.ones(src.shape, bool), prio, n, maxiter or max(n, 1))


@functools.lru_cache(maxsize=None)
def _luby_sharded_fn(mesh: Mesh, n: int, maxiter: int):
    axes = mesh_axes(mesh)
    rspec = row_spec(mesh)
    rep = NamedSharding(mesh, P())

    @functools.partial(jax.jit, out_shardings=(rep, rep))
    def luby_loop(src_d, dst_d, valid_d, prio):
        return jax.shard_map(
            lambda s, d, v, pr: _mis(s, d, v, pr, n, maxiter, axes),
            mesh=mesh, in_specs=(rspec, rspec, rspec, P()),
            out_specs=P())(src_d, dst_d, valid_d, prio)

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
