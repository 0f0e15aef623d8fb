"""Device-side staging for the fused graph engines (VERDICT r2 #2).

The fused cc/pagerank/sssp/luby/tri engines need compact vertex ranks
0..n-1 (their labels/ranks/state live in dense replicated vectors).
Round 2 staged this on the controller — ``scan_kv`` pulled the whole edge
list to host numpy and ``np.unique`` ranked it — a funnel the mesh cannot
outgrow
(the reference gives every rank its own slice and never funnels,
``cuda/InvertedIndex.cu:284-312``).

Here the ranking runs on device over the mesh-resident edge KV, in ONE
jitted program (:func:`rank_graph`, ``jit_stage_rank_graph``) built on
one global sort of the flattened endpoints of the sharded [rows, 2] u64
edge keys:

* the sort carries each endpoint's position as a payload; the first
  occurrences of the sorted ids, brought to the front by one more sort,
  are the sorted vertex table (sentinel-padded; a second tiny program
  trims it to ``round_cap(n)`` and replicates it), and their count is
  ``n``.  Only the scalars ``n`` and ``nbad`` sync to the host.
* the prefix sum of the first-occurrence flags IS each sorted endpoint's
  rank, so nothing is searched for: a second sort, keyed by the carried
  positions, puts the ranks back in edge order.  ``src``/``dst``/``valid``
  stay row-sharded in the SAME layout as the input frame, ready for the
  fused models' shard_map loops.

The O(E) edge columns never touch the host; commands pull only the [n]
vertex-id table afterwards for their printed output.  Vertex id
``2^64-1`` is reserved as the padding sentinel.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from ..core.frame import KVFrame
from .mesh import mesh_axis_size, row_spec
from .sharded import ShardedKV, round_cap

SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)


def mesh_kv_frame(mr) -> Optional[ShardedKV]:
    """The mr's KV as ONE ShardedKV frame if it is mesh-resident (several
    sharded frames concatenate on device), else None."""
    kv = getattr(mr, "kv", None)
    if kv is None or not kv._frames:
        return None
    fr = kv.one_frame()
    return fr if isinstance(fr, ShardedKV) else None


def staged_frame(mr) -> Optional[ShardedKV]:
    """Mesh-resident frame of mr's KV, aggregating (shard + hash
    exchange) first if the data is still host-resident.  Returns None
    only when the dataset is empty/absent.  NOTE: byte/object VALUES
    shard as interned u64 ids (``value_decode`` set) — callers that
    consume ``fr.value`` numerically must check ``value_decode``."""
    fr = mesh_kv_frame(mr)
    if fr is None:
        mr.aggregate()
        fr = mesh_kv_frame(mr)
    return fr


class StagedGraph:
    """Result of :func:`stage_graph`: ranked sharded edge arrays plus the
    host-side [n] vertex-id table (pulled once, for output).  From
    :func:`stage_graph_host` the same fields, every one a numpy array."""

    __slots__ = ("verts", "n", "src", "dst", "valid", "weights")

    def __init__(self, verts, n, src, dst, valid, weights):
        self.verts, self.n = verts, n
        self.src, self.dst, self.valid = src, dst, valid
        self.weights = weights

    @property
    def rows(self) -> int:
        """The rows of the staged columns, valid or not (on a mesh the
        shards times the frame's per-shard capacity): what a loop over
        them iterates, the spans' ``edge_rows``.  0 for an empty graph."""
        return 0 if self.src is None else int(self.src.shape[0])


def stage_graph(mr, comm, drop_self: bool = False,
                need_weights: bool = False) -> Optional[StagedGraph]:
    """The fused graph commands' shared staging: mesh-shard the edge KV,
    rank vertices/edges on device.  Returns None when mesh staging does
    not apply (no mesh comm, empty dataset, or — with ``need_weights`` —
    interned byte values, whose ids are not numbers); the caller then
    stages on the host (:func:`stage_graph_host`).  An n==0 result
    carries empty arrays so callers can emit their empty output without
    re-pulling the edge list."""
    from jax.sharding import Mesh
    if not isinstance(comm, Mesh):
        return None
    fr = staged_frame(mr)
    if fr is None or not len(fr):
        return None
    if need_weights and fr.value_decode is not None:
        return None
    verts_d, n, src_d, dst_d, valid_d = rank_graph(fr, drop_self=drop_self)
    if n == 0:
        return StagedGraph(np.zeros(0, np.uint64), 0, None, None, None,
                           None)
    return StagedGraph(np.asarray(verts_d)[:n], n, src_d, dst_d, valid_d,
                       fr.value if need_weights else None)


def stage_graph_host(mr, drop_self: bool = False,
                     need_weights: bool = False) -> StagedGraph:
    """The ranking on the host, for where :func:`stage_graph` does not
    apply (it returned None: the serial backend, an empty dataset,
    interned weights), the fallback of all five graph commands: the edge
    KV scanned into numpy and ranked by ``np.unique``.  ``src``/``dst``
    are each endpoint's rank as ``np.unique`` counts it (int64; a caller whose
    program takes int32 casts), every row is valid — with ``drop_self``
    the self-loop rows are gone, not masked — and ``weights`` is the
    value column as it was read, row for row.  An empty edge list gives
    ``n == 0`` and empty arrays."""
    keys, values = [], []

    def read(fr, _ptr):
        fr = fr if isinstance(fr, KVFrame) else fr.to_host()
        keys.append(np.asarray(fr.key.to_host().data))
        if need_weights:
            values.append(np.asarray(fr.value.to_host().data))

    mr.scan_kv(read, batch=True)
    e = (np.concatenate(keys).astype(np.uint64, copy=False) if keys
         else np.zeros((0, 2), np.uint64))
    w = None
    if need_weights:
        w = np.concatenate(values) if values else np.zeros(0, np.float64)
    if drop_self:
        keep = e[:, 0] != e[:, 1]
        e = e[keep]
        if need_weights:
            w = w[keep]
    verts, inv = np.unique(e.reshape(-1), return_inverse=True)
    inv = inv.reshape(-1, 2)
    return StagedGraph(verts, len(verts), inv[:, 0], inv[:, 1],
                       np.ones(len(inv), bool), w)


def _valid_rows(nrows: int, nprocs: int, counts):
    cap = nrows // nprocs
    idx = jnp.arange(nrows)
    return (idx % cap) < counts[idx // cap]


@functools.lru_cache(maxsize=None)
def _rank_fn(mesh, nrows: int, drop_self: bool):
    rep = NamedSharding(mesh, PartitionSpec())
    shard = NamedSharding(mesh, row_spec(mesh))
    nprocs = mesh_axis_size(mesh)
    m = 2 * nrows
    # each endpoint's position rides the sort as a payload; at pod scale
    # the flattened endpoints can exceed 2^31 and an i32 position (or an
    # i32 cumsum below) would wrap
    pos_t = jnp.int32 if m < 2 ** 31 else jnp.int64

    # the sorted 2E table stays ROW-SHARDED here; only the [round_cap(n)]
    # trim (second dispatch below) replicates — forcing rep on the full
    # array would put O(E) on every device
    @functools.partial(jax.jit,
                       out_shardings=(shard, rep, rep, shard, shard, shard))
    def stage_rank_graph(key, counts):
        with jax.named_scope("endpoints"):
            valid = _valid_rows(nrows, nprocs, counts)
            if drop_self:
                valid = valid & (key[:, 0] != key[:, 1])
            # vertex id 2^64-1 IS the padding sentinel — count real
            # occurrences so the host wrapper can refuse instead of
            # silently dropping the vertex
            nbad = jnp.sum((valid[:, None] & (key == SENTINEL))
                           .astype(jnp.int32))
            # the two columns end to end, not interleaved: position p is
            # row p % nrows, and no [2E] <-> [E, 2] relayout is needed
            # (on the chip that one is a 64x tile-padded copy)
            flat = jnp.concatenate([jnp.where(valid, key[:, 0], SENTINEL),
                                    jnp.where(valid, key[:, 1], SENTINEL)])
        with jax.named_scope("sort"):
            s, origin = lax.sort((flat, lax.iota(pos_t, m)), num_keys=1)
        with jax.named_scope("rank"):
            first = jnp.concatenate([jnp.ones(1, bool), s[1:] != s[:-1]])
            isu = first & (s != SENTINEL)
            # a sorted endpoint's rank is the number of uniques up to
            # it, less one; the sentinels behind them read n - 1
            rank = jnp.cumsum(isu.astype(pos_t)) - 1
            n = rank[-1].astype(jnp.int64) + 1
        with jax.named_scope("table"):
            # uniques to the front, sentinel fill behind them: one more
            # sort, because on the chip the scatter-drop that did this
            # took 1.8 s at 16.8 M endpoints and a sort takes 0.05 s
            verts = jnp.sort(jnp.where(isu, s, SENTINEL))
        with jax.named_scope("return"):
            # the ranks go back to edge order by the carried positions
            _, back = lax.sort((origin, rank.astype(jnp.int32)),
                               num_keys=1)
            return verts, n, nbad, back[:nrows], back[nrows:], valid

    return stage_rank_graph


@functools.lru_cache(maxsize=None)
def _trim_fn(mesh, nout: int):
    rep = NamedSharding(mesh, PartitionSpec())

    @functools.partial(jax.jit, out_shardings=rep)
    def stage_trim_verts(x):
        with jax.named_scope("trim"):
            return x[:nout]

    return stage_trim_verts


def rank_graph(fr: ShardedKV, drop_self: bool = False
               ) -> Tuple[jax.Array, int, jax.Array, jax.Array, jax.Array]:
    """Vertex table and ranked edges of a mesh-resident [rows,2] edge
    frame: (verts, n, src, dst, valid).  ``verts`` is the sorted unique
    endpoint ids, replicated, sentinel-padded to ``round_cap(n)``;
    ``src``/``dst`` are each endpoint's int32 rank in it and ``valid``
    the row mask, each [rows] row-sharded like the frame — feed directly
    to the fused models' sharded loops (invalid/padding rows carry
    valid=False and some rank in [0, n]).  With ``drop_self`` self-loop
    rows are invalid, so endpoints of self-loop-only vertices are
    excluded (the luby convention)."""
    verts, n, nbad, src, dst, valid = _rank_fn(
        fr.mesh, fr.key.shape[0], drop_self)(fr.key, jnp.asarray(fr.counts))
    if int(nbad):
        raise ValueError(
            f"vertex id {SENTINEL} is reserved as the device staging "
            f"sentinel ({int(nbad)} occurrences in the edge list)")
    n = int(n)
    return _trim_fn(fr.mesh, round_cap(n))(verts), n, src, dst, valid
