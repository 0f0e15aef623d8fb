"""Shared OINK kernels — the reusable map/reduce callbacks of
``oink/map_*.cpp`` / ``oink/reduce_*.cpp``, batch-first.

Data conventions (reference ``oink/typedefs.h:22-40``):

* VERTEX = uint64 → a ``[n]`` u64 column;
* EDGE = {vi, vj} → a ``[n, 2]`` u64 column (struct-of-rows, fixed width —
  the TPU fast path, SURVEY.md §7);
* WEIGHT = float64 → ``[n]`` f64 column;
* NULL values → ``[n]`` u8 zeros.

Every kernel here is a *batch* callback (``mr.map_mr(..., batch=True)`` /
``mr.reduce(..., batch=True)``): it receives a whole KVFrame/KMVFrame and
emits columns, so pipelines stay vectorised end-to-end.  Host per-pair
equivalents are what the reference runs; the semantics match 1:1.
"""

from __future__ import annotations

import numpy as np

from ..core.frame import KVFrame

# ---------------------------------------------------------------------------
# file parsers (reference map_read_*.cpp — host I/O, vectorised parse)
# ---------------------------------------------------------------------------


def _null(n: int) -> np.ndarray:
    return np.zeros(n, np.uint8)


def host_kv(fr) -> KVFrame:
    """Normalise a batch-map input to a host KVFrame (mesh backend hands
    ShardedKV; the reference's analog is request_page's disk→mem read)."""
    return fr if isinstance(fr, KVFrame) else fr.to_host()


def host_kmv(fr):
    """Normalise a batch-reduce input to a host KMVFrame."""
    from ..core.frame import KMVFrame
    return fr if isinstance(fr, KMVFrame) else fr.to_host()


def kv_keys(fr) -> np.ndarray:
    return np.asarray(host_kv(fr).key.to_host().data)


def kv_values(fr) -> np.ndarray:
    return np.asarray(host_kv(fr).value.to_host().data)


def kmv_keys(fr) -> np.ndarray:
    return np.asarray(host_kmv(fr).key.to_host().data)


def kmv_values(fr) -> np.ndarray:
    return np.asarray(host_kmv(fr).values.to_host().data)


def seg_ids(fr) -> np.ndarray:
    """Row → group-index map for a KMVFrame's flat value column."""
    fr = host_kmv(fr)
    return np.repeat(np.arange(len(fr)), np.asarray(fr.nvalues))


def group_min_rows(seg: np.ndarray, *keys: np.ndarray):
    """Per-group lexicographic argmin: for rows labelled by ``seg``
    (ascending group ids), return ``(groups, rows)`` — each present group
    and the index of its minimal row by ``keys[0]``, ties broken by
    ``keys[1]``, ...  One idiom for every 'best row per group' reduce
    (sssp's pick_shortest/update_adjacent) so tie-breaking can never
    diverge between call sites."""
    order = np.lexsort(tuple(reversed(keys)) + (seg,))
    gseg = seg[order]
    first = np.ones(len(gseg), bool)
    first[1:] = gseg[1:] != gseg[:-1]
    return gseg[first], order[first]


def group_any(cond: np.ndarray, fr) -> np.ndarray:
    """Per-group OR over a KMV frame's flat value rows — the shared segment
    primitive behind luby's winner/loser votes, tri_find's has-edge test,
    and cc_find's zone joins."""
    offs = np.asarray(host_kmv(fr).offsets)[:-1]
    return np.maximum.reduceat(cond.astype(np.uint8), offs).astype(bool)


def _parse_cols(filename: str, dtypes) -> list:
    """Whitespace table → one exact-dtype array per column (u64 vertex ids
    parse as integers, never through float — ids ≥ 2^53 stay exact).
    Routed through the native C++ parser when built (ingestion is a host
    hot path; the reference parses in C callbacks, oink/map_read_*.cpp)."""
    with open(filename, "rb") as f:
        raw = f.read()
    from .. import native
    if native.available() and all(dt in (np.uint64, np.float64)
                                  for dt in dtypes):
        try:
            return native.parse_table(raw, dtypes)
        except ValueError as e:
            raise ValueError(f"{filename}: {e}")
    toks = np.asarray(raw.split())
    ncols = len(dtypes)
    if len(toks) % ncols:
        raise ValueError(f"{filename}: token count not divisible by {ncols}")
    table = toks.reshape(-1, ncols)
    return [table[:, i].astype(dt) for i, dt in enumerate(dtypes)]


def read_edge(itask, filename, kv, ptr):
    """'vi vj' lines → key=[vi,vj], value=NULL (map_read_edge.cpp:15-25)."""
    vi, vj = _parse_cols(filename, (np.uint64, np.uint64))
    kv.add_batch(np.stack([vi, vj], 1), _null(len(vi)))


def read_edge_weight(itask, filename, kv, ptr):
    """'vi vj wt' lines → key=[vi,vj], value=weight
    (map_read_edge_weight.cpp)."""
    vi, vj, w = _parse_cols(filename, (np.uint64, np.uint64, np.float64))
    kv.add_batch(np.stack([vi, vj], 1), w)


def read_edge_label(itask, filename, kv, ptr):
    """'vi vj label' lines → key=[vi,vj], value=int label
    (map_read_edge_label.cpp)."""
    vi, vj, lab = _parse_cols(filename, (np.uint64, np.uint64, np.int64))
    kv.add_batch(np.stack([vi, vj], 1), lab)


def read_vertex_value(itask, filename, kv, ptr):
    """'v u' lines → key=v, value=u, both u64 (cc_stats input: Vi Zi
    pairs, oink/cc_stats.cpp CCStats::read)."""
    v, u = _parse_cols(filename, (np.uint64, np.uint64))
    kv.add_batch(v, u)


def read_vertex_weight(itask, filename, kv, ptr):
    """'v weight' lines → key=v, value=weight (map_read_vertex_weight.cpp)."""
    v, w = _parse_cols(filename, (np.uint64, np.float64))
    kv.add_batch(v, w)


def read_words(itask, filename, kv, ptr):
    """whitespace words → key=word bytes, value=NULL (map_read_words.cpp).
    The words go to the dataset as ranges of the file's buffer
    (utils/io.word_ranges): no Python object per word."""
    from ..utils.io import word_ranges
    with open(filename, "rb") as f:
        words = word_ranges(f.read())
    if ptr is not None and isinstance(ptr, list):
        ptr.append(filename)  # nfiles counter (reference int* ptr)
    kv.add_batch(words, _null(len(words)))


# ---------------------------------------------------------------------------
# edge/vertex maps (batch: fn(frame, kv, ptr))
# ---------------------------------------------------------------------------

def _dev(name):
    from ..parallel import devkernels
    return getattr(devkernels, name)


def edge_to_vertices(fr, kv, ptr):
    """Eij:NULL → Vi:NULL and Vj:NULL (map_edge_to_vertices.cpp)."""
    from ..parallel.devkernels import is_sharded_kv, skv_map
    if is_sharded_kv(fr):
        kv.add_frame(skv_map(fr, _dev("edge_to_vertices_dev")))
        return
    e = kv_keys(fr)
    both = np.concatenate([e[:, 0], e[:, 1]])
    kv.add_batch(both, _null(len(both)))


def edge_to_vertex(fr, kv, ptr):
    """Eij:NULL → Vi:NULL only (map_edge_to_vertex.cpp)."""
    from ..parallel.devkernels import is_sharded_kv, skv_map
    if is_sharded_kv(fr):
        kv.add_frame(skv_map(fr, _dev("edge_to_vertex_dev")))
        return
    e = kv_keys(fr)
    kv.add_batch(e[:, 0], _null(len(e)))


def edge_to_vertex_pair(fr, kv, ptr):
    """Eij:NULL → Vi:Vj (map_edge_to_vertex_pair.cpp)."""
    from ..parallel.devkernels import is_sharded_kv, skv_map
    if is_sharded_kv(fr):
        kv.add_frame(skv_map(fr, _dev("edge_to_vertex_pair_dev")))
        return
    e = kv_keys(fr)
    kv.add_batch(e[:, 0], e[:, 1])


def edge_both_directions(fr, kv, ptr):
    """Eij:NULL → Vi:Vj and Vj:Vi — the adjacency expansion shared by
    neighbor (oink/neighbor.cpp:84-116) and tri_find's map_edge_vert
    (oink/tri_find.cpp:104-112)."""
    from ..parallel.devkernels import is_sharded_kv, skv_map
    if is_sharded_kv(fr):
        kv.add_frame(skv_map(fr, _dev("edge_both_directions_dev")))
        return
    e = kv_keys(fr)
    kv.add_batch(np.concatenate([e[:, 0], e[:, 1]]),
                 np.concatenate([e[:, 1], e[:, 0]]))


def edge_upper(fr, kv, ptr):
    """Canonicalise to Vi<Vj, drop self-loops (map_edge_upper.cpp:15-24)."""
    from ..parallel.devkernels import is_sharded_kv, skv_map
    if is_sharded_kv(fr):
        kv.add_frame(skv_map(fr, _dev("edge_upper_dev")))
        return
    e = kv_keys(fr)
    keep = e[:, 0] != e[:, 1]
    e = e[keep]
    lo = np.minimum(e[:, 0], e[:, 1])
    hi = np.maximum(e[:, 0], e[:, 1])
    kv.add_batch(np.stack([lo, hi], 1), _null(len(e)))


def invert(fr, kv, ptr):
    """K:V → V:K (map_invert.cpp)."""
    from ..parallel.devkernels import is_sharded_kv, skv_map
    if is_sharded_kv(fr):
        kv.add_frame(skv_map(fr, _dev("invert_dev")))
        return
    fr = host_kv(fr)
    kv.add_batch(fr.value, fr.key)


def add_weight(fr, kv, ptr):
    """Eij:NULL → Eij:1.0 (map_add_weight.cpp — unit edge weights)."""
    from ..parallel.devkernels import is_sharded_kv, skv_map
    if is_sharded_kv(fr):
        kv.add_frame(skv_map(fr, _dev("add_weight_dev")))
        return
    fr = host_kv(fr)
    kv.add_batch(fr.key, np.ones(len(fr), np.float64))


# ---------------------------------------------------------------------------
# reduces — re-exported from ops/reduces.py, which dispatches on frame kind
# (local KMVFrame vs mesh ShardedKMV) so commands run on both backends
# ---------------------------------------------------------------------------

from ..ops.reduces import count, cull, max_values, min_values, sum_values  # noqa: E402,F401


def value_histogram(mr) -> list:
    """The shared histogram tail of histo/degree_stats
    (oink/histo.cpp:59-66, oink/degree_stats.cpp:52-61): invert to
    value:key, group, count, gather, sort descending.  Consumes mr's KV;
    returns [(value, count)] sorted by value descending."""
    mr.map_mr(mr, invert, batch=True)
    mr.collate()
    mr.reduce(count, batch=True)
    mr.gather(1)
    mr.sort_keys(-1)
    stats = []
    mr.scan_kv(lambda k, v, p: stats.append((int(k), int(v))))
    return stats


# ---------------------------------------------------------------------------
# printers (reference per-command print callbacks)
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# name → kernel registries (what oink/Make.py generates as style_map.h /
# style_reduce.h: script text like `mre map/mr mre add_weight` resolves its
# callback through these, reference oink/mrmpi.cpp:354-466)
# ---------------------------------------------------------------------------

MAP_FILE_KERNELS = {
    "read_edge": read_edge,
    "read_edge_weight": read_edge_weight,
    "read_edge_label": read_edge_label,
    "read_vertex_value": read_vertex_value,
    "read_vertex_weight": read_vertex_weight,
    "read_words": read_words,
}

MAP_MR_KERNELS = {
    "edge_to_vertices": edge_to_vertices,
    "edge_to_vertex": edge_to_vertex,
    "edge_to_vertex_pair": edge_to_vertex_pair,
    "edge_both_directions": edge_both_directions,
    "edge_upper": edge_upper,
    "invert": invert,
    "add_weight": add_weight,
}

REDUCE_KERNELS = {
    "count": count,
    "cull": cull,
    "sum": sum_values,
    "min": min_values,
    "max": max_values,
}


def hash_lookup3(keys):
    """The library default key→proc hash, by name (reference scripts pass
    NULL for the same thing; mrmpi.cpp:354-466 resolves named hashes)."""
    from ..parallel.shuffle import default_hash
    return default_hash(keys)


def hash_identity(keys):
    """Low word of the key as the hash — deterministic placement for
    tests/scripts (shard = key % nprocs)."""
    import jax.numpy as jnp
    k = keys[:, 0] if keys.ndim > 1 else keys
    return k.astype(jnp.uint32)


HASH_KERNELS = {
    "lookup3": hash_lookup3,
    "identity": hash_identity,
}


def row_template(template: str, key_fields: int = 1):
    """Declare a printer's line: ``template`` (``core/column.row_fields``:
    ``%d``, ``%g``, ``%.Ng``) over the key's ``key_fields`` words, then
    the value's.  The printer stays the ``printer(k, v, fp)`` callable it
    was; ``Object.output`` formats a dense KV frame whose columns are of
    the template's kinds a block at a time from the declaration, and
    calls the printer a row for everything else (a float under ``%d``
    keeps its ``repr``)."""
    def declare(printer):
        printer.template, printer.key_fields = template, key_fields
        return printer
    return declare


@row_template("%d %d", key_fields=2)
def print_edge(k, v, fp):
    fp.write(f"{k[0]} {k[1]}\n")


@row_template("%d")
def print_vertex(k, v, fp):
    fp.write(f"{k}\n")


@row_template("%d %d")
def print_vertex_value(k, v, fp):
    fp.write(f"{k} {v}\n")


@row_template("%d %d %d", key_fields=2)
def print_edge_value(k, v, fp):
    fp.write(f"{k[0]} {k[1]} {v}\n")


@row_template("%d %.8g")
def print_vertex_rank(k, v, fp):
    fp.write(f"{k} {v:.8g}\n")
