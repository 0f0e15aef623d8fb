"""Connected components — fused on-device label propagation.

The reference's cc_find composes ~9 MapReduce stages per propagation
round (``oink/cc_find.cpp:38-109``) — free in C++, but on XLA every
stage is a compiled program and iterative re-compilation/dispatch
dominates (exactly the cost model SURVEY.md §7 warns about for
iterative graph drivers).  The TPU-first design runs the ENTIRE
convergence loop as one jitted ``lax.while_loop``, like the flagship
PageRank model: labels live in a dense replicated vector, each round is
two segment-mins over the (sharded) edge list plus one pointer-jumping
hop, and the only host traffic is the final labels.

Semantics match the composed command: the fixpoint labels every
component with its minimum vertex id (zone winner = min,
oink/commands/cc.py).  Pointer jumping (``lab = min(lab, lab[lab])``)
compresses label chains so convergence is ~O(log n) rounds instead of
O(diameter).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import mesh_axes, mesh_axis_size, row_spec


def _propagate(lab, src, dst, valid, n):
    """One round: every edge pulls its endpoints toward the smaller
    label, then one pointer-jump hop.  Padded edge rows route to the
    dropped segment n."""
    with jax.named_scope("segment_min_dst"):
        seg_dst = jnp.where(valid, dst, n)
        with jax.named_scope("gather_src"):
            pulled = lab[src]
        m1 = jax.ops.segment_min(pulled, seg_dst, num_segments=n + 1)[:n]
    with jax.named_scope("segment_min_src"):
        seg_src = jnp.where(valid, src, n)
        with jax.named_scope("gather_dst"):
            pulled = lab[dst]
        m2 = jax.ops.segment_min(pulled, seg_src, num_segments=n + 1)[:n]
    with jax.named_scope("pointer_jump"):
        nl = jnp.minimum(lab, jnp.minimum(m1, m2))
        return jnp.minimum(nl, nl[nl])


@functools.partial(jax.jit, static_argnames=("n", "maxiter"))
def cc(src: jax.Array, dst: jax.Array, n: int, maxiter: int = 0
       ) -> Tuple[jax.Array, jax.Array]:
    """Single-device fused loop.  Returns (labels[n], iterations);
    labels[v] = smallest vertex index in v's component."""
    maxiter = maxiter or max(n, 1)
    lab0 = jnp.arange(n, dtype=jnp.int32)
    valid = jnp.ones(src.shape, bool)

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < maxiter)

    def body(state):
        lab, _, it = state
        nl = _propagate(lab, src.astype(jnp.int32), dst.astype(jnp.int32),
                        valid, n)
        return nl, jnp.any(nl != lab), it + 1

    lab, _, iters = lax.while_loop(
        cond, body, (lab0, jnp.bool_(n > 0), jnp.int32(0)))
    return lab, iters


# merges of the replicated [n] int32 labels a round of the sharded loop:
# the one ``pmin`` below (the ``cc.loop`` span's ``allreduce_bytes``)
PMINS_PER_ROUND = 1


@functools.lru_cache(maxsize=None)
def _cc_sharded_fn(mesh: Mesh, n: int, maxiter: int):
    axes = mesh_axes(mesh)
    rspec = row_spec(mesh)
    rep = NamedSharding(mesh, P())

    @functools.partial(jax.jit, out_shardings=(rep, rep))
    def cc_loop(src_d, dst_d, valid_d):
        with jax.named_scope("init"):
            lab0 = jnp.arange(n, dtype=jnp.int32)

        def round_(lab, s, d, v):
            nl = _propagate(lab, s, d, v, n)
            with jax.named_scope("merge"):
                return lax.pmin(nl, axes)

        step = jax.shard_map(
            round_, mesh=mesh, in_specs=(P(), rspec, rspec, rspec),
            out_specs=P())

        def cond(state):
            _, changed, it = state
            return jnp.logical_and(changed, it < maxiter)

        def body(state):
            lab, _, it = state
            nl = step(lab, src_d, dst_d, valid_d)
            with jax.named_scope("changed"):
                return nl, jnp.any(nl != lab), it + 1

        return lax.while_loop(
            cond, body, (lab0, jnp.bool_(n > 0), jnp.int32(0)))[::2]

    return cc_loop


def cc_sharded(mesh: Mesh, src: np.ndarray, dst: np.ndarray, n: int,
               maxiter: int = 0) -> Tuple[np.ndarray, int]:
    """Edge-parallel fused loop over a device mesh (flat or multi-slice):
    edges block-sharded, labels replicated, one pmin per round over
    ICI(+DCN).  Returns (labels[n], iterations)."""
    from ..models.pagerank import pad_edges_for_mesh

    nprocs = mesh_axis_size(mesh)
    src_p, dst_p, valid_p = pad_edges_for_mesh(
        src.astype(np.int32), dst.astype(np.int32), nprocs)
    shard = NamedSharding(mesh, row_spec(mesh))
    run = _cc_sharded_fn(mesh, n, maxiter or max(n, 1))
    from ..parallel.mesh import device_put_chunked
    lab, iters = run(device_put_chunked(src_p, shard),
                     device_put_chunked(dst_p, shard),
                     device_put_chunked(valid_p, shard))
    return np.asarray(lab), int(iters)
