"""Plain numpy references for MR-MPI's ``tri_find``, ``luby_find`` and
``sssp``, and the checks that hold an OINK script job to them.

As in ``refs/graph.py`` the graph is the system's own (``rmat`` on the
device), so the references are functions of the edge list the system made,
after ``check_edges`` / ``check_upper`` have held that list to what R-MAT
promises; the checks here start from the *reference's* edge sets
(``refs.graph._edges`` for ``sssp``, ``refs.graph._upper`` for the other
two), never from ``mru`` or ``mrw``: ``check_weighted`` holds ``mrw`` to
``mre``'s edges with the unit weights the configuration states, and the
sources are picked again here, not read off the program's messages.
Nothing is imported from the program but the accessor that pulls a named
MR's rows.  The slow references are kept in the benchmark's cache by the
edges' digest.

* ``triangles_reference``: every triangle of the upper edges, found by
  out-degree class: the vertices whose (degree, id)-oriented
  out-neighbourhood has exactly k entries form one dense ``[m, k]`` matrix,
  ``np.triu_indices(k, 1)`` names its column pairs, and a pair closes when
  its packed (min, max) is among the sorted packed edges
  (``np.searchsorted``).  Written from the definition, independently of
  ``models/tri.py`` (which walks a flat wedge index space by sorts).
* ``mis_reference``: with per-vertex priorities that never change, "join
  when lower than every undecided neighbour" has one fixed point: the
  greedy set in the order of (priority, id).  The priority is splitmix64 of
  ``v + seed``, top 53 bits, as a float64 (``vertex_rand`` written again).
* ``sssp_reference``: float64 Bellman-Ford over the directed weighted
  edges (with ``add_weight``'s unit weights it is a BFS, but the weights
  are read, not assumed), to the fixed point.
"""

import concurrent.futures
import hashlib
import os
import re

import numpy as np

from benchmark import check
from benchmark.refs import graph

BITS = 21               # ids below 2^21: three of them pack into one u64
CHUNK = 1 << 24         # wedges looked up at a time
WEIGHT = 1.0            # the configuration's ``weights``: add_weight's


# -- references ---------------------------------------------------------------

def _pack3(x, y, z):
    return (x << np.uint64(2 * BITS)) | (y << np.uint64(BITS)) | z


def triangles_reference(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Sorted packed (x < y < z) triples of every triangle of the unique
    undirected edges (a[i], b[i]), a != b, ids below 2^21."""
    if not len(a):
        return np.zeros(0, np.uint64)
    top = int(max(a.max(), b.max())) + 1
    check(top <= 1 << BITS, f"vertex id {top - 1} does not pack into 21 bits")
    shift = np.uint64(32)
    ekeys = np.sort((np.minimum(a, b) << shift) | np.maximum(a, b))
    deg = np.bincount(a.astype(np.int64), minlength=top) \
        + np.bincount(b.astype(np.int64), minlength=top)
    # the edge leaves its lower (degree, id) end
    da, db = deg[a.astype(np.int64)], deg[b.astype(np.int64)]
    flip = (da > db) | ((da == db) & (a > b))
    lo, hi = np.where(flip, b, a), np.where(flip, a, b)
    order = np.argsort((lo << shift) | hi)
    lo, hi = lo[order], hi[order]
    k = np.bincount(lo.astype(np.int64), minlength=top)
    start = np.cumsum(k) - k

    def look_up(job):
        rows, size = job            # first positions of some k-sized lists
        i, j = np.triu_indices(size, 1)
        nb = hi[rows[:, None] + np.arange(size)[None, :]]
        u, w = nb[:, i].ravel(), nb[:, j].ravel()
        key = (np.minimum(u, w) << shift) | np.maximum(u, w)
        at = np.minimum(np.searchsorted(ekeys, key), len(ekeys) - 1)
        hit = ekeys[at] == key
        c = np.repeat(lo[rows], len(i))[hit]
        t = np.sort(np.stack([c, u[hit], w[hit]], 1), axis=1)
        return _pack3(t[:, 0], t[:, 1], t[:, 2])

    jobs = []
    for size in np.unique(k[k >= 2]).tolist():
        rows = start[k == size]
        per = max(1, CHUNK // (size * (size - 1) // 2))
        jobs += [(rows[at:at + per], size) for at in range(0, len(rows), per)]
    # numpy's sorts and searches release the interpreter lock
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(8, os.cpu_count() or 1)) as pool:
        found = list(pool.map(look_up, jobs))
    out = np.concatenate(found) if found else np.zeros(0, np.uint64)
    out.sort()
    return out


def priorities(v: np.ndarray, seed: int) -> np.ndarray:
    """splitmix64(v + seed), top 53 bits, in [0, 1)."""
    u = np.uint64
    with np.errstate(over="ignore"):
        z = v.astype(u) + u(seed & 0xFFFFFFFFFFFFFFFF) + u(0x9E3779B97F4A7C15)
        z = (z ^ (z >> u(30))) * u(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> u(27))) * u(0x94D049BB133111EB)
        z = z ^ (z >> u(31))
    return (z >> u(11)).astype(np.float64) / float(1 << 53)


def _csr(a: np.ndarray, b: np.ndarray, verts: np.ndarray) -> tuple:
    """Undirected adjacency of the edges over ``verts`` (sorted ids):
    (offsets [n+1], neighbour indices)."""
    ia, ib = np.searchsorted(verts, a), np.searchsorted(verts, b)
    src = np.concatenate([ia, ib])
    dst = np.concatenate([ib, ia])
    order = np.argsort(src, kind="stable")
    offsets = np.concatenate([[0], np.cumsum(np.bincount(
        src, minlength=len(verts)))])
    return offsets, dst[order]


def mis_reference(a: np.ndarray, b: np.ndarray, seed: int) -> np.ndarray:
    """The greedy maximal independent set of the edges' graph in the order
    of (priority, id), as sorted vertex ids."""
    verts = np.unique(np.concatenate([a, b]))
    offsets, nbrs = _csr(a, b, verts)
    taken = np.zeros(len(verts), bool)
    blocked = np.zeros(len(verts), bool)
    offsets = offsets.tolist()
    for v in np.lexsort((verts, priorities(verts, seed))).tolist():
        if not blocked[v]:
            taken[v] = True
            blocked[nbrs[offsets[v]:offsets[v + 1]]] = True
    return verts[taken]


def sssp_reference(e: np.ndarray, w: np.ndarray, verts: np.ndarray,
                   source: int) -> np.ndarray:
    """float64 shortest distances from ``source`` along the directed edges
    ``e`` with weights ``w``, for every vertex of ``verts`` (inf where no
    path leads): Bellman-Ford to the fixed point."""
    src, dst = np.searchsorted(verts, e[:, 0]), np.searchsorted(verts, e[:, 1])
    dist = np.full(len(verts), np.inf)
    dist[np.searchsorted(verts, np.uint64(source))] = 0.0
    w = w.astype(np.float64)
    while True:
        reach = np.isfinite(dist[src])      # only edges that can relax
        new = dist.copy()
        np.minimum.at(new, dst[reach], dist[src[reach]] + w[reach])
        if np.array_equal(new, dist):
            return dist
        dist = new


# -- helpers ------------------------------------------------------------------

def mr_rows(mr) -> tuple:
    """Every (key row, value row) of a named MR object, pulled to the host."""
    from gpu_mapreduce_tpu.oink.kernels import kv_keys, kv_values
    keys, values = [], []
    mr.scan_kv(lambda fr, p: (keys.append(kv_keys(fr)),
                              values.append(kv_values(fr))), batch=True)
    return np.concatenate(keys), np.concatenate(values)


def _upper_ends(env) -> tuple:
    packed = graph._upper(env)
    return packed >> np.uint64(32), packed & np.uint64(0xFFFFFFFF)


def _cached(env, what: str, compute) -> dict:
    """A reference kept in the benchmark's cache by the edges' digest and
    the two reference files' source."""
    key = graph._edges_key(env, what)
    with open(__file__, "rb") as f:
        key += "-" + hashlib.sha256(f.read()).hexdigest()[:12]
    ref = env.cache.load(key)
    if ref is None:
        ref = compute()
        env.cache.store(key, ref)
    return ref


def _messages(env, pattern: str) -> list:
    found = [re.search(pattern, m) for m in env.messages]
    return [tuple(int(x) for x in m.groups()) for m in found if m]


def _message(env, pattern: str) -> tuple:
    found = _messages(env, pattern)
    check(len(found) == 1, f"expected one message like {pattern!r}: "
                           f"{env.messages}")
    return found[0]


# -- checks -------------------------------------------------------------------

def check_triangles(env) -> dict:
    """``tri_find``: exactly the triangle set of the upper edges: none
    missing, none extra, none twice; every row's centre one of its three
    vertices (they are distinct, or the sorted triple would not match)."""
    a, b = _upper_ends(env)
    want = _cached(env, "tri", lambda: {"triples": triangles_reference(a, b)}
                   )["triples"]
    rows, _ = mr_rows(env.script.obj.get_mr("mrt"))
    rows = rows.astype(np.uint64).reshape(-1, 3)
    check(len(rows) == 0 or int(rows.max()) < 1 << BITS,
          "tri_find: vertex id out of range")
    t = np.sort(rows, axis=1)
    got = _pack3(t[:, 0], t[:, 1], t[:, 2])
    got.sort()
    check(len(got) == len(want) and np.array_equal(got, want),
          f"tri_find: {len(got)} rows ({len(np.unique(got))} distinct), "
          f"reference {len(want)} triangles")
    ntri, = _message(env, r"Tri_find: (\d+) triangles")
    check(ntri == len(want), f"tri_find reported {ntri} triangles, "
                             f"reference {len(want)}")
    return {"triangles": len(want)}


def check_mis(env) -> dict:
    """``luby_find``: the greedy-by-priority set exactly; and, on its own,
    independent (no upper edge inside it) and maximal (every other vertex
    has a neighbour in it)."""
    seed = int(env.config["luby_seed"])
    a, b = _upper_ends(env)
    want = _cached(env, f"mis{seed}",
                   lambda: {"mis": mis_reference(a, b, seed)})["mis"]
    got = np.sort(np.loadtxt(os.path.join(env.out, "mis"), dtype=np.uint64,
                             ndmin=1))
    verts = np.unique(np.concatenate([a, b]))
    inside = np.zeros(len(verts), bool)
    at = np.searchsorted(verts, got)
    check(len(got) and bool(np.all(verts[np.minimum(at, len(verts) - 1)]
                                   == got)),
          "luby_find: a vertex of the set is not in the graph")
    check(len(np.unique(got)) == len(got), "luby_find: a vertex twice")
    inside[at] = True
    ia, ib = np.searchsorted(verts, a), np.searchsorted(verts, b)
    check(not bool(np.any(inside[ia] & inside[ib])),
          "luby_find: an edge has both ends in the set")
    covered = inside.copy()
    covered[ia[inside[ib]]] = True
    covered[ib[inside[ia]]] = True
    check(bool(covered.all()), "luby_find: the set is not maximal")
    check(np.array_equal(got, want),
          f"luby_find: {len(got)} vertices, the greedy set by priority has "
          f"{len(want)}")
    nset, _iters = _message(env, r"Luby_find: (\d+) MIS vertices in (\d+) "
                                 r"iterations")
    check(nset == len(want), f"luby_find reported {nset} vertices")
    return {"mis_vertices": len(want)}


def check_weighted(env) -> dict:
    """Set-up: ``mrw`` is ``mre``'s edge list, each edge once, with the
    unit weight the configuration states on every one."""
    e, w = mr_rows(env.script.obj.get_mr("mrw"))
    e = e.astype(np.uint64)
    check(np.array_equal(np.sort(graph.pack(e[:, 0], e[:, 1])),
                         graph._packed(env)),
          "add_weight: mrw's edges are not mre's")
    check(bool(np.all(w.astype(np.float64) == WEIGHT)),
          f"add_weight: a weight is not {WEIGHT}")
    return {"weighted_edges": len(e)}


def check_sssp(env) -> dict:
    """``sssp``: the sources the first ``sssp_ncnt`` vertices in the order
    of (splitmix64(v + ``sssp_seed``), id); from each, every vertex's
    distance equal to the float64 reference's over ``mre``'s edges at unit
    weight; the labeled count the message's; every labeled vertex but the
    source reached over an edge from its ``pred`` with ``dist[pred] + w ==
    dist[v]``.  And the traffic has to make the loop work: a job none of
    whose sources reaches half the vertices is refused, because a loop
    that relaxes nothing equals the reference whatever it does."""
    e = graph._edges(env).astype(np.uint64)
    w = np.full(len(e), WEIGHT)
    verts = np.unique(e)
    ncnt, seed = int(env.config["sssp_ncnt"]), int(env.config["sssp_seed"])
    sources = verts[np.lexsort((verts, priorities(verts, seed)))][:ncnt]
    said = _messages(
        env, r"SSSP: source (\d+): (\d+) iterations, (\d+) vertices labeled")
    check([m[0] for m in said] == sources.tolist(),
          f"sssp ran from {[m[0] for m in said]}, the first {ncnt} vertices "
          f"by priority are {sources.tolist()}")
    # to look an edge (pred, v) up among the packed directed edges
    packed = graph._packed(env)
    labeled = []
    for at, source in enumerate(sources.tolist()):
        want = _cached(env, f"sssp{source}", lambda: {
            "dist": sssp_reference(e, w, verts, source)})["dist"]
        name = "sssp" if ncnt == 1 else f"sssp.{at}"
        rows = np.loadtxt(os.path.join(env.out, name), dtype=np.float64,
                          ndmin=2)
        check(rows.shape == (len(verts), 3)
              and np.array_equal(rows[:, 0].astype(np.uint64), verts),
              f"{name}: vertex set differs")
        dist, pred = rows[:, 1], rows[:, 2].astype(np.uint64)
        check(np.array_equal(dist, want),
              f"{name}: {int((dist != want).sum())} distances differ from "
              f"the reference")
        finite = np.isfinite(want)
        labeled.append(int(finite.sum()))
        check(labeled[-1] == said[at][2],
              f"sssp reported {said[at][2]} labeled from {source}, "
              f"reference {labeled[-1]}")
        reached = finite & (verts != np.uint64(source))
        key = graph.pack(pred[reached], verts[reached])
        found = np.minimum(np.searchsorted(packed, key), len(packed) - 1)
        check(bool(np.all(packed[found] == key)),
              f"{name}: a pred is not an in-neighbour")
        dpred = want[np.searchsorted(verts, pred[reached])]
        check(bool(np.all(dpred + WEIGHT == want[reached])),
              f"{name}: a pred does not realise its vertex's distance")
    check(2 * max(labeled) > len(verts),
          f"sssp: no source of {sources.tolist()} reaches half of the "
          f"{len(verts)} vertices (labeled {labeled}): the loop relaxes "
          f"next to nothing, and this comparison would pass any loop")
    return {"vertices": len(verts), "sssp_sources": sources.tolist(),
            "sssp_labeled": labeled}
