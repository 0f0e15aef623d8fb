"""PageRank — the framework's flagship iterative-graph workload.

The reference names PageRank as a headline workload but ships only a
skeleton: ``oink/pagerank.cpp:53-55`` reads edges and builds the vertex
list, then the iteration body is empty.  This module *designs* it from the
reference's composition pattern (SURVEY.md §2.5): out-degree → per-edge
rank scatter (the collate) → damped sum per destination (the reduce),
iterated to a tolerance.

TPU-first design, not a transliteration:

* the graph is a static-shape edge array ``src[m], dst[m]`` (+ valid mask
  for padding); ranks are a dense f32 vector — all ops are vectorised
  segment-sums, no per-pair callbacks;
* one iteration = gather src ranks → scale by 1/out-degree →
  ``segment_sum`` onto dst → damp.  Under ``jit`` this fuses to a couple
  of HBM passes;
* the whole convergence loop runs on device in ``lax.while_loop`` — the
  only host traffic is the final result (the reference's iterative
  commands Allreduce a done-flag per round, e.g. ``oink/cc_find.cpp``;
  we keep even that on device);
* multi-chip: edges are sharded over the mesh axis, ranks replicated;
  each shard segment-sums its local contributions and one ``psum`` over
  ICI merges them (the analogue of aggregate()'s all-to-all, but
  all-reduce shaped because the rank vector is dense).

Numerics: everything is f32 (TPU-native); a ``tol`` below ~1e-7 is under
f32 resolution — the loop then runs to ``maxiter`` (or to an exact f32
fixpoint, depending on summation order).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import mesh_axes, mesh_axis_size, row_spec


def out_degrees(src: jax.Array, n: int, valid=None) -> jax.Array:
    """Out-degree per vertex from an edge list (the degree command's kernel,
    reference oink/degree.cpp:36-60)."""
    ones = jnp.ones_like(src, dtype=jnp.float32)
    if valid is not None:
        ones = jnp.where(valid, ones, 0.0)
    return jax.ops.segment_sum(ones, src, num_segments=n)


def inv_outdegrees(deg: jax.Array) -> jax.Array:
    """1/out-degree with 0 for dangling (degree-0) vertices."""
    return jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1.0), 0.0)


def _dangling_mass(ranks: jax.Array, inv_outdeg: jax.Array) -> jax.Array:
    """Rank mass sitting on dangling vertices, spread uniformly."""
    n = ranks.shape[0]
    return (jnp.sum(ranks) - jnp.sum(ranks * jnp.sign(inv_outdeg))) / n


def pagerank_step(ranks: jax.Array, src: jax.Array, dst: jax.Array,
                  inv_outdeg: jax.Array, damping: float = 0.85,
                  valid: Optional[jax.Array] = None) -> jax.Array:
    """One damped power-iteration step.  Dangling mass is redistributed
    uniformly so the ranks stay a probability distribution."""
    n = ranks.shape[0]
    contrib = ranks[src] * inv_outdeg[src]
    if valid is not None:
        contrib = jnp.where(valid, contrib, 0.0)
    inflow = jax.ops.segment_sum(contrib, dst, num_segments=n)
    return ((1.0 - damping) / n +
            damping * (inflow + _dangling_mass(ranks, inv_outdeg)))


@functools.partial(jax.jit, static_argnames=("n", "maxiter"))
def pagerank(src: jax.Array, dst: jax.Array, n: int, tol: float = 1e-6,
             maxiter: int = 100, damping: float = 0.85
             ) -> Tuple[jax.Array, jax.Array]:
    """Full on-device convergence loop.  Returns (ranks, iterations)."""
    deg = out_degrees(src, n)
    inv = inv_outdegrees(deg)
    r0 = jnp.full((n,), 1.0 / n, jnp.float32)

    def cond(state):
        _, delta, it = state
        return jnp.logical_and(delta > tol, it < maxiter)

    def body(state):
        r, _, it = state
        r2 = pagerank_step(r, src, dst, inv, damping)
        return r2, jnp.max(jnp.abs(r2 - r)), it + 1

    ranks, _, iters = lax.while_loop(cond, body, (r0, jnp.float32(jnp.inf),
                                                  jnp.int32(0)))
    return ranks, iters


# ---------------------------------------------------------------------------
# sharded (multi-chip) path
# ---------------------------------------------------------------------------

# merges of a replicated [n] float32 vector an iteration of the sharded
# loop: the one ``psum`` of the inflows in ``_sharded_step`` (the
# ``pagerank.loop`` span's ``allreduce_bytes``; the degrees' ``psum``
# runs once, ahead of the loop, and is not an iteration's)
PSUMS_PER_ITERATION = 1


def _sharded_step(ranks, src, dst, inv_outdeg, valid, damping, axes):
    """shard_map body: local segment-sum of the shard's edges, then one
    psum (over every mesh axis — ICI within a slice, DCN across for a
    multi-slice mesh) merges per-shard inflows (replicated ranks in,
    replicated ranks out)."""
    n = ranks.shape[0]
    with jax.named_scope("gather_ranks"):
        pulled = ranks[src]
    with jax.named_scope("gather_inv_outdeg"):
        share = inv_outdeg[src]
    with jax.named_scope("scatter_add"):
        contrib = jnp.where(valid, pulled * share, 0.0)
        local = jax.ops.segment_sum(contrib, dst, num_segments=n)
    with jax.named_scope("merge"):
        inflow = lax.psum(local, axes)
    with jax.named_scope("normalise"):
        return ((1.0 - damping) / n +
                damping * (inflow + _dangling_mass(ranks, inv_outdeg)))


def pad_edges_for_mesh(src: np.ndarray, dst: np.ndarray, nprocs: int
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad the edge list to a multiple of nprocs rows; returns
    (src, dst, valid)."""
    m = len(src)
    mpad = -(-max(m, 1) // nprocs) * nprocs
    pad = mpad - m
    src = np.concatenate([src, np.zeros(pad, src.dtype)])
    dst = np.concatenate([dst, np.zeros(pad, dst.dtype)])
    valid = np.concatenate([np.ones(m, bool), np.zeros(pad, bool)])
    return src, dst, valid


@functools.lru_cache(maxsize=None)
def _sharded_run_fn(mesh: Mesh, n: int, tol: float, maxiter: int,
                    damping: float):
    """Compile-once (per mesh/shape/params) sharded convergence loop."""
    rep = NamedSharding(mesh, P())
    axes = mesh_axes(mesh)       # works for flat ("p",) and ("s","c")
    rspec = row_spec(mesh)

    @functools.partial(jax.jit, out_shardings=(rep, rep))
    def pagerank_loop(src_d, dst_d, valid_d):
        with jax.named_scope("out_degrees"):
            deg = jax.shard_map(
                lambda s, v: lax.psum(out_degrees(s, n, valid=v), axes),
                mesh=mesh, in_specs=(rspec, rspec), out_specs=P())(
                    src_d, valid_d)
            inv = inv_outdegrees(deg)
            r0 = jnp.full((n,), 1.0 / n, jnp.float32)

        step = jax.shard_map(
            functools.partial(_sharded_step, damping=damping, axes=axes),
            mesh=mesh,
            in_specs=(P(), rspec, rspec, P(), rspec),
            out_specs=P())

        def cond(state):
            _, delta, it = state
            return jnp.logical_and(delta > tol, it < maxiter)

        def body(state):
            r, _, it = state
            r2 = step(r, src_d, dst_d, inv, valid_d)
            with jax.named_scope("delta"):
                return r2, jnp.max(jnp.abs(r2 - r)), it + 1

        ranks, _, iters = lax.while_loop(
            cond, body, (r0, jnp.float32(jnp.inf), jnp.int32(0)))
        return ranks, iters

    return pagerank_loop


def pagerank_staged(mesh: Mesh, src_d: jax.Array, dst_d: jax.Array,
                    valid_d: jax.Array, n: int, tol: float = 1e-6,
                    maxiter: int = 100, damping: float = 0.85
                    ) -> Tuple[np.ndarray, int]:
    """The sharded loop over edge columns that are ALREADY on the mesh,
    row-sharded: what ``parallel/staging.stage_graph`` leaves there (int32
    ranks and the frame's row mask; a masked row may carry any rank in
    [0, n], it adds nothing to a degree or an inflow).  Only the [n]
    ranks and the iteration count come to the host."""
    ranks, iters = _sharded_run_fn(mesh, n, tol, maxiter, damping)(
        src_d, dst_d, valid_d)
    return np.asarray(ranks), int(iters)


def pagerank_sharded(mesh: Mesh, src: np.ndarray, dst: np.ndarray, n: int,
                     tol: float = 1e-6, maxiter: int = 100,
                     damping: float = 0.85) -> Tuple[np.ndarray, int]:
    """Edge-parallel PageRank over a device mesh (flat or multi-slice),
    from host arrays.  Edges are block-sharded over all mesh axes; ranks
    replicated; one psum per iteration rides ICI (+DCN across slices)."""
    nprocs = mesh_axis_size(mesh)
    src_p, dst_p, valid_p = pad_edges_for_mesh(src, dst, nprocs)
    edge_shard = NamedSharding(mesh, row_spec(mesh))
    # bounded per-device messages (a scale-22 edge column is ~134 MB)
    from ..parallel.mesh import device_put_chunked
    return pagerank_staged(
        mesh, device_put_chunked(src_p, edge_shard),
        device_put_chunked(dst_p, edge_shard),
        device_put_chunked(valid_p, edge_shard), n, tol, maxiter, damping)
