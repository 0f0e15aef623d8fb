"""gather / broadcast over the mesh.

* gather(n): funnel every shard's rows onto the first n shards — the
  reference's rank-matched Send/Recv funnel (``src/mapreduce.cpp:893-1036``)
  becomes one exchange with a constant destination per shard.
* broadcast(root): every shard ends up with a copy of root's rows — the
  reference's per-page MPI_Bcast (``src/mapreduce.cpp:569-623``) becomes an
  ``all_gather`` + select.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.frame import KVFrame
from .mesh import mesh_axes, row_sharding, row_spec
from .sharded import ShardedKV, shard_frame
from .shuffle import exchange, free_if_donated, _replace_kv_frames


def _ensure_sharded(backend, mr):
    frame = mr.kv.one_frame()
    if isinstance(frame, KVFrame):
        if not frame.is_dense():
            return None
        return shard_frame(frame, backend.mesh)
    return frame


def gather_kv(backend, mr, nprocs: int):
    skv = _ensure_sharded(backend, mr)
    if skv is None:
        return  # host-resident data is already "gathered"
    if backend.nprocs == 1:
        # one shard holds every row already: the reference returns at
        # once (src/mapreduce.cpp:903); an exchange here would be a sort
        # and a copy of every row to where it lies
        _replace_kv_frames(mr.kv, skv)
        return
    n = min(nprocs, backend.nprocs)
    # shard i → i % n: the reference's exact funnel layout ("lo procs
    # recv from hi procs with same ID % numprocs",
    # src/mapreduce.cpp:919-928)
    try:
        out = exchange(skv, ("fixed_mod", n), counters=mr.counters)
    except BaseException:
        # donation may have consumed an installed frame: leave a clean
        # empty dataset, not deleted buffers (shuffle.free_if_donated)
        free_if_donated(mr.kv, skv)
        raise
    # per-call stats like aggregate's: gather/scrunch exchanges were
    # invisible to mr.last_exchange (the wire-codec tests read it)
    mr.last_exchange = getattr(out, "exchange_stats", None)
    _replace_kv_frames(mr.kv, out)


@functools.lru_cache(maxsize=None)
def _broadcast_jit(mesh, root: int):
    spec = row_spec(mesh)
    axes = mesh_axes(mesh)
    ax = axes[0] if len(axes) == 1 else axes

    @jax.jit
    def run(key, value):
        def body(k, v):
            allk = lax.all_gather(k, ax)     # [P, cap, ...]
            allv = lax.all_gather(v, ax)
            return allk[root], allv[root]
        return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                             out_specs=(spec, spec))(key, value)

    return run


def broadcast_kv(backend, mr, root: int):
    skv = _ensure_sharded(backend, mr)
    if skv is None:
        return
    mesh = skv.mesh
    k, v = _broadcast_jit(mesh, root)(skv.key, skv.value)
    counts = np.full(backend.nprocs, skv.counts[root], np.int32)
    rowbytes = (skv.key.dtype.itemsize *
                (skv.key.shape[-1] if skv.key.ndim > 1 else 1) +
                skv.value.dtype.itemsize *
                (skv.value.shape[-1] if skv.value.ndim > 1 else 1))
    moved = int(skv.counts[root]) * (backend.nprocs - 1) * rowbytes
    mr.counters.add(cssize=moved, crsize=moved)
    _replace_kv_frames(mr.kv, ShardedKV(mesh, k, v, counts,
                                        key_decode=skv.key_decode,
                                        value_decode=skv.value_decode))
