"""Mesh construction — the framework's MPI_COMM_WORLD.

Flat form: one axis ``"p"`` of "procs" (chips); the reference's rank/size
(``MPI_Comm_rank``/``MPI_Comm_size``) become ``lax.axis_index("p")`` and
the axis size.

Multi-slice form (``make_mesh2``): the proc axis factors into
``("s", "c")`` — slice × chip — so datasets still shard by flat proc id
(row i*C+c lives on slice i, chip c) but the shuffle can route
hierarchically: ICI all-to-all within a slice first (grouping rows by
destination chip), then ONE DCN all-to-all between same-chip-index peers
across slices (shuffle._exchange_blocks).  That is the TPU analogue of
the reference's single-level MPI world (SURVEY.md §5 'multi-slice'
note; their NCCL/MPI stacks do the same hierarchical aggregation
internally)."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXIS = "p"


def make_mesh(ndev: Optional[int] = None, devices: Optional[Sequence] = None
              ) -> Mesh:
    if devices is None:
        devices = jax.devices()
    if ndev is not None:
        devices = devices[:ndev]
    return Mesh(np.asarray(devices), (AXIS,))


def make_mesh2(nslice: int, nchip: Optional[int] = None,
               devices: Optional[Sequence] = None) -> Mesh:
    """Multi-slice mesh: devices [nslice, nchip] over axes ("s", "c")."""
    if devices is None:
        devices = jax.devices()
    if nchip is None:
        nchip = len(devices) // nslice
    devices = np.asarray(devices[:nslice * nchip]).reshape(nslice, nchip)
    return Mesh(devices, ("s", "c"))


def mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def mesh_axis_size(mesh: Mesh) -> int:
    """Total proc count (product over all mesh axes)."""
    n = 1
    for a in mesh.axis_names:
        n *= int(mesh.shape[a])
    return n


def allreduce_bytes(shards: int, n: int, count: int) -> int:
    """Bytes of a replicated ``[n]`` vector of 4-byte elements (int32
    labels, float32 ranks) that ``count`` all-reduces (``psum`` /
    ``pmin``) merge over a mesh of ``shards`` devices: what a fused loop's
    spans record as ``allreduce_bytes``.  0 on one device (or without a
    mesh: ``shards`` 1), where nothing is merged."""
    return int(n) * 4 * int(count) if shards > 1 else 0


def row_spec(mesh: Mesh) -> PartitionSpec:
    """PartitionSpec sharding dim 0 over ALL mesh axes (flat proc id =
    row-major (slice, chip) index)."""
    axes = mesh_axes(mesh)
    return PartitionSpec(axes[0] if len(axes) == 1 else axes)


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Rows split over procs (axis 0 of every dataset array)."""
    return NamedSharding(mesh, row_spec(mesh))


def flat_axis_index(mesh: Mesh):
    """Inside shard_map: this shard's flat proc id (row-major over axes)."""
    axes = mesh_axes(mesh)
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * int(mesh.shape[a]) + lax.axis_index(a)
    return idx


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def h2d_chunk_bytes(default: int = 32 << 20) -> int:
    """The per-message H2D budget, with the MR_H2D_CHUNK_WORDS override
    (u32 words, ×4 bytes) — ONE parse shared by every chunked-transfer
    site so the knob cannot be honored in some paths and not others."""
    import os
    env = os.environ.get("MR_H2D_CHUNK_WORDS")
    if env is None:
        return default
    if int(env) <= 0:
        raise ValueError(f"MR_H2D_CHUNK_WORDS={env}: must be > 0")
    return int(env) * 4


def device_put_chunked(host, sharding: Optional[NamedSharding] = None,
                       chunk_bytes: int = 32 << 20):
    """``jax.device_put`` in bounded per-device messages.

    Used by every bulk H2D: each device's block travels as
    ≤``chunk_bytes`` pieces concatenated on its own device.  Honors the same
    ``MR_H2D_CHUNK_WORDS`` override as the ingest paths (u32 words,
    ×4 bytes).  With ``sharding=None`` the array lands on the default
    device."""
    chunk_bytes = h2d_chunk_bytes(chunk_bytes)
    host = np.asarray(host)
    if host.ndim == 0 or host.nbytes <= chunk_bytes:
        return jax.device_put(host, sharding) if sharding is not None \
            else jax.device_put(host)
    import jax.numpy as jnp

    def put_block(block, dev):
        # dev=None → uncommitted puts on the configured default device
        # (committing to devices()[0] would flip placement semantics on
        # a size threshold the caller never sees — r5 review)
        put = (jax.device_put if dev is None
               else lambda x: jax.device_put(x, dev))
        rowbytes = max(1, int(block.nbytes // max(1, block.shape[0])))
        step = max(1, chunk_bytes // rowbytes)
        if block.shape[0] <= step:
            return put(block)
        parts = [put(block[o:o + step])
                 for o in range(0, block.shape[0], step)]
        return jnp.concatenate(parts)

    if sharding is None:
        return put_block(host, None)
    dmap = sharding.addressable_devices_indices_map(host.shape)
    shards = [put_block(np.ascontiguousarray(host[idx]), dev)
              for dev, idx in dmap.items()]
    return jax.make_array_from_single_device_arrays(
        host.shape, sharding, shards)


def init_multihost(coordinator: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None,
                   local_device_ids: Optional[Sequence[int]] = None) -> int:
    """Join JAX's multi-controller runtime so ``make_mesh()`` spans every
    host's chips — the reference's ``MPI_Init`` for multi-node runs (its
    NCCL/MPI backend scales the same way; SURVEY.md §5 "distributed
    communication backend").  Call once per process BEFORE any other jax
    use; args default to the cluster auto-detection
    (``jax.distributed.initialize``'s env/cloud discovery).  Returns
    this process's index.

    What is and isn't multi-host ready: the SPMD compute paths — the
    exchange collectives, the fused graph engines, per-shard output —
    address only LOCAL shards (``addressable_shards`` /
    ``addressable_devices_indices_map`` everywhere), so each process
    computes and writes its own hosts' slices, with DCN routes via
    ``make_mesh2``.  Dest-sharded decode tables (``ShardTables``) mean
    a process only needs the tables of shards it writes.  Host-side
    INGESTION is per-shard in *placement* but not yet in *reads*: the
    generic ``map_files`` runs every callback in the calling process —
    a multi-controller deployment should hand each process its own
    file slice.  ``to_host`` of the whole dataset and host per-pair
    callbacks stay single-controller conveniences."""
    kw = {}
    if coordinator is not None:
        kw["coordinator_address"] = coordinator
    if num_processes is not None:
        kw["num_processes"] = num_processes
    if process_id is not None:
        kw["process_id"] = process_id
    if local_device_ids is not None:
        kw["local_device_ids"] = list(local_device_ids)
    jax.distributed.initialize(**kw)
    return jax.process_index()
