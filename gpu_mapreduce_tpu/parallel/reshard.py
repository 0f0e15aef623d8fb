"""Live topology resharding — ``mr.reshard(new_mesh)`` as a collective.

The mesh was fixed at MapReduce construction (ROADMAP item 4): losing
or gaining a device meant rebuilding the world and re-ingesting.  This
module redistributes a resident :class:`~.sharded.ShardedKV` /
:class:`~.sharded.ShardedKMV` from an N-way to an M-way mesh as a
collective program, following the portable collective-communication
redistribution recipe (arXiv:2112.01075): the redistribution SCHEDULE
(which global row ranges land on which target shard) is computed
host-side from the per-shard counts — metadata the controller already
holds — while the data itself moves only through the existing two-phase
``lax.all_to_all`` exchange (``shuffle.py``), never through a host
round-trip.

Mechanics, per direction:

* **narrowing (M ≤ N)** — one exchange ON THE OLD MESH with the
  ``("range", offsets, ends)`` destination spec: row r of shard i has
  global index ``offsets[i]+r`` and routes to the target shard whose
  cumulative range covers it (all dests < M ≤ N, so the old mesh's
  collective can deliver them).  The output blocks for shards < M are
  then *re-homed* onto the new mesh — per-device buffer adoption via
  ``make_array_from_single_device_arrays``, zero-copy when old and new
  meshes share a device prefix.
* **widening (M > N)** — re-home first (old blocks become the first N
  shards of an M-wide array, the rest zero-padded), then run the same
  range exchange ON THE NEW MESH, where all M destinations exist.

Because the range destination is monotone in the global row index,
phase 1's stable dest-sort is the identity permutation and the packed
exchange output preserves exact global row order — an N→M→N round trip
is byte-identical (``tests/test_elastic.py``), and the whole thing runs
under the ft/ ``shuffle.exchange`` retry policy like every exchange.

Range exchanges ride the SAME ``exchange()`` core as dest-fn shuffles,
so they inherit the wire codec (``parallel/wire.py``, MRTPU_WIRE —
delta-packed keys, narrow values, tiered caps; the KMV value pass's
1-byte rider ships raw by construction) and feed the same telemetry:
``record_exchange`` sent/pad/wire bytes, ``mr.counters`` cssize/cspad,
and the active RequestAccount — pinned by
``tests/test_wire.py::test_range_reshard_feeds_exchange_metrics``.

KMV datasets reshard at GROUP granularity: groups stay atomic (a
group's value run never splits across shards).  The group-boundary
schedule needs the per-group value counts — an O(groups) metadata pull,
not a data round-trip — and the value rows then follow their groups
through a second range exchange.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .mesh import mesh_axis_size, row_sharding
from .sharded import ShardedKMV, ShardedKV, round_cap, shard_blocks
from .shuffle import exchange


def even_counts(n: int, m: int) -> np.ndarray:
    """The canonical M-way contiguous split (same formula as
    ``sharded.shard_frame`` — the two must never disagree, or a
    reshard and a fresh shard of the same rows would differ)."""
    per = -(-n // m) if n else 0
    starts = np.minimum(np.arange(m) * per, n)
    ends = np.minimum(starts + per, n)
    return (ends - starts).astype(np.int32)


def _offsets(counts) -> Tuple[int, ...]:
    """Exclusive prefix sum: shard i's global row offset."""
    return tuple(int(x) for x in
                 np.concatenate([[0], np.cumsum(counts)])[:-1])


def _assemble(blocks: list, new_mesh: Mesh):
    """Adopt per-shard blocks as one row-sharded array on ``new_mesh``
    — zero-copy for blocks already resident on the target device, a
    device-to-device put otherwise (never through the host)."""
    M = mesh_axis_size(new_mesh)
    assert len(blocks) == M
    cap = blocks[0].shape[0]
    sharding = row_sharding(new_mesh)
    shape = (M * cap,) + tuple(blocks[0].shape[1:])
    dmap = sharding.addressable_devices_indices_map(shape)
    arrs = []
    for dev, idx in dmap.items():
        blk = blocks[(idx[0].start or 0) // cap]
        if dev not in blk.devices():
            blk = jax.device_put(blk, dev)
        arrs.append(blk)
    return jax.make_array_from_single_device_arrays(shape, sharding, arrs)


def _zeros_like_block(block, dev):
    return jax.device_put(jnp.zeros(block.shape, block.dtype), dev)


def _widen(skv: ShardedKV, new_mesh: Mesh) -> ShardedKV:
    """Re-home an N-shard dataset as the first N shards of an M-wide
    mesh (M > N), zero-padding the rest — the pre-pass that lets the
    range exchange run where all M destinations exist."""
    N = skv.nprocs
    M = mesh_axis_size(new_mesh)
    devs = list(np.asarray(new_mesh.devices).reshape(-1))

    def grow(arr):
        blocks = shard_blocks(arr, N)
        pad = [_zeros_like_block(blocks[0], devs[j])
               for j in range(N, M)]
        return _assemble(blocks + pad, new_mesh)

    counts = np.concatenate([skv.counts,
                             np.zeros(M - N, np.int32)]).astype(np.int32)
    out = ShardedKV(new_mesh, grow(skv.key), grow(skv.value), counts,
                    key_decode=skv.key_decode,
                    value_decode=skv.value_decode)
    # the widened arrays ALIAS the original frame's device buffers —
    # donation would delete them out from under a failed exchange's
    # retry, so mark shared (exec.can_donate vetoes)
    out._shared = True
    return out


def _narrow(skv: ShardedKV, new_mesh: Mesh) -> ShardedKV:
    """Adopt the first M shard blocks of a routed exchange output as an
    M-wide dataset (the counts beyond M are zero by construction)."""
    M = mesh_axis_size(new_mesh)
    N = skv.nprocs
    assert all(int(c) == 0 for c in skv.counts[M:]), \
        "narrow: rows routed past the target width"
    return ShardedKV(new_mesh,
                     _assemble(shard_blocks(skv.key, N)[:M], new_mesh),
                     _assemble(shard_blocks(skv.value, N)[:M], new_mesh),
                     skv.counts[:M].copy(),
                     key_decode=skv.key_decode,
                     value_decode=skv.value_decode)


def _exchange_range(skv: ShardedKV, new_mesh: Mesh,
                    ends: Tuple[int, ...], counters) -> ShardedKV:
    """The shared routing core: contiguous-global-order rows of ``skv``
    → target shards per the host-computed ``ends`` schedule, result on
    ``new_mesh``."""
    N = skv.nprocs
    M = mesh_axis_size(new_mesh)
    if M > N:
        skv = _widen(skv, new_mesh)
        out = exchange(skv, ("range", _offsets(skv.counts), ends),
                       counters=counters)
        return out
    out = exchange(skv, ("range", _offsets(skv.counts), ends),
                   counters=counters)
    return _narrow(out, new_mesh)


def reshard_kv(skv: ShardedKV, new_mesh: Mesh,
               counters=None) -> ShardedKV:
    """Redistribute a ShardedKV onto ``new_mesh`` (any width), global
    row order preserved exactly.  The id→bytes decode tables ride along
    unchanged: ``ShardTables.decode_batch`` routes by id hash over its
    OWN table count, independent of row placement."""
    tcounts = even_counts(len(skv), mesh_axis_size(new_mesh))
    ends = tuple(int(x) for x in np.cumsum(tcounts))
    out = _exchange_range(skv, new_mesh, ends, counters)
    return out


def reshard_kmv(skmv: ShardedKMV, new_mesh: Mesh,
                counters=None) -> ShardedKMV:
    """Redistribute a ShardedKMV onto ``new_mesh`` at group
    granularity.  Two range exchanges (groups, then their value runs)
    share one host-computed schedule; the new shard-local value offsets
    are recomputed from the same metadata."""
    N = skmv.nprocs
    M = mesh_axis_size(new_mesh)
    G = len(skmv)
    gcap = skmv.gcap
    # metadata pull: per-group value counts in global (shard-major)
    # group order — the schedule input, not the data
    nv_host = np.asarray(skmv.nvalues)
    nv_global = (np.concatenate(
        [nv_host[i * gcap:i * gcap + int(skmv.gcounts[i])]
         for i in range(N)]).astype(np.int64)
        if G else np.zeros(0, np.int64))

    tg = even_counts(G, M)                       # groups per target shard
    gends = tuple(int(x) for x in np.cumsum(tg))
    vcum = np.concatenate([[0], np.cumsum(nv_global)]).astype(np.int64)
    vends = tuple(int(vcum[e]) for e in gends)   # group-aligned value cuts
    tv = np.diff(np.concatenate([[0], vends])).astype(np.int32)

    # exchange 1: the group-level rows (ukey + nvalues ride together)
    gkv = ShardedKV(skmv.mesh, skmv.ukey, skmv.nvalues,
                    skmv.gcounts.astype(np.int32),
                    key_decode=skmv.key_decode)
    gkv._shared = True      # buffers belong to the live KMV frame
    gout = _exchange_range(gkv, new_mesh, gends, counters)

    # exchange 2: the value rows, routed by the SAME group-aligned cuts
    # (a 1-byte rider fills the KV-shaped exchange's second column)
    rider = jnp.zeros((skmv.values.shape[0],), jnp.int8)
    rider = jax.device_put(rider, row_sharding(skmv.mesh))
    vkv = ShardedKV(skmv.mesh, skmv.values, rider,
                    skmv.vcounts.astype(np.int32),
                    key_decode=skmv.value_decode)
    vkv._shared = True
    vout = _exchange_range(vkv, new_mesh, vends, counters)

    # new shard-local value offsets from the same host schedule
    gcap_new = gout.cap
    voff = np.zeros(M * gcap_new, np.int32)
    gstart = 0
    for j in range(M):
        nvj = nv_global[gstart:gstart + int(tg[j])]
        voff[j * gcap_new:j * gcap_new + int(tg[j])] = np.concatenate(
            [[0], np.cumsum(nvj)])[:-1]
        gstart += int(tg[j])
    from .mesh import device_put_chunked
    voff_dev = device_put_chunked(voff, row_sharding(new_mesh))

    return ShardedKMV(new_mesh, gout.key, gout.value, voff_dev,
                      vout.key, tg, tv,
                      key_decode=skmv.key_decode,
                      value_decode=skmv.value_decode)
