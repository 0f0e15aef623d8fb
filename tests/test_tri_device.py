"""ISSUE 32: ``tri_find``'s default engine is the wedge walk as device
programs (``models/tri.py``), the same programs on every backend.  Held
here, on the CPU, to a brute-force oracle and to the composed engine over
graphs that have what R-MAT's hubs have (a vertex whose wedges span
several batches, a hub with no triangle, hubs that share neighbours), with
the batch cap forced below and above the wedge count, on the serial
backend and on a four-device mesh; the two enumerations of ISSUE 41 (a
list of ``_TILED`` out-neighbours or more paired block against block, a
shorter one wedge index by wedge index) over lists on both sides of every
boundary; and the walk's programs to the chip's rule (no scatter).  ``luby_find`` and ``sssp``, which run beside it in
``graph-tri-1chip``, are held to plain references of their own."""

import collections
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpu_mapreduce_tpu.models import tri
from gpu_mapreduce_tpu.oink import ObjectManager, run_command

EXAMPLES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "examples")


# -- graphs ---------------------------------------------------------------------

def _rmat(scale, seed, factor=8, abcd=(0.57, 0.19, 0.19, 0.05)):
    """A numpy R-MAT of the benchmark's parameters, upper edges."""
    rng = np.random.default_rng(seed)
    m = factor << scale
    src = np.zeros(m, np.uint64)
    dst = np.zeros(m, np.uint64)
    for _ in range(scale):
        q = rng.choice(4, size=m, p=abcd)
        src = (src << np.uint64(1)) | (q >> 1).astype(np.uint64)
        dst = (dst << np.uint64(1)) | (q & 1).astype(np.uint64)
    return np.stack([src, dst], 1)


def _complete(ids):
    ids = list(ids)
    return [(a, b) for i, a in enumerate(ids) for b in ids[i + 1:]]


def _star(hub, leaves):
    return [(hub, v) for v in leaves]


GRAPHS = {
    "no-edges": lambda: [],
    "one-triangle": lambda: [(3, 7), (7, 11), (3, 11)],
    "k8": lambda: _complete(range(10, 18)),
    # ids past 31 bits: the walk names the vertices by rank and the rows
    # are translated at the end
    "k8-wide-ids": lambda: (_complete(range(1 << 40, (1 << 40) + 8))
                            + [(5, 1 << 40), (5, (1 << 40) + 1)]),
    # a hub and no triangle: every leaf points at the hub, no wedge at all
    "star-500": lambda: _star(0, range(1, 501)),
    # the hub closes a triangle over every edge of a K6 among its leaves
    "star-500-k6": lambda: _star(0, range(1, 501)) + _complete(range(1, 7)),
    # two hubs over the same 60 neighbours, joined: each neighbour's wedge
    # (hub, hub) closes; plus a chain among the neighbours
    "two-hubs": lambda: (_star(1000, range(60)) + _star(2000, range(60))
                         + [(1000, 2000)]
                         + [(v, v + 1) for v in range(59)]),
    "rmat-10": lambda: _rmat(10, 1),
    "rmat-12": lambda: _rmat(12, 2),
}


def _upper(edges):
    """What ``edge_upper`` leaves: (min, max) rows, no loop, no duplicate."""
    e = np.asarray(edges, np.uint64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    return np.unique(np.stack([e.min(1), e.max(1)], 1), axis=0)


def brute_triangles(e):
    adj = collections.defaultdict(set)
    for a, b in e.tolist():
        adj[a].add(b)
        adj[b].add(a)
    return {frozenset((a, b, c)) for a, b in e.tolist()
            for c in adj[a] & adj[b]}


@pytest.fixture(scope="module")
def cases(tmp_path_factory):
    """name -> (edge file, upper edges, oracle)."""
    d = tmp_path_factory.mktemp("graphs")
    out = {}
    for name, make in GRAPHS.items():
        e = _upper(make())
        path = d / f"{name}.txt"
        path.write_text("".join(f"{a} {b}\n" for a, b in e.tolist()))
        out[name] = (str(path), e, brute_triangles(e))
    return out


def _wedges(e):
    """Σ k(k-1)/2 over Cohen's (degree, id) out-degrees."""
    deg = collections.Counter(e.reshape(-1).tolist())
    k = collections.Counter(min((a, b), key=lambda v: (deg[v], v))
                            for a, b in e.tolist())
    return sum(n * (n - 1) // 2 for n in k.values())


def _out_lists(e):
    """Length of every vertex's (degree, id) out-list."""
    deg = collections.Counter(e.reshape(-1).tolist())
    return collections.Counter(min((a, b), key=lambda v: (deg[v], v))
                               for a, b in e.tolist())


def _enumeration(e, batch):
    """What the walk must count, from the lengths of the out-lists alone:
    (wedges, index wedges, tiles, batches, tile rows) under the module's
    ``_BLOCK`` / ``_TILED`` / ``_TILES`` and a batch cap of ``batch``."""
    from gpu_mapreduce_tpu.parallel.sharded import round_cap
    B, ks = tri._BLOCK, list(_out_lists(e).values())
    index = sum(k * (k - 1) // 2 for k in ks if k < tri._TILED)
    tiles = 0
    for k in ks:
        if k >= tri._TILED:     # block a pairs with blocks a … kb-1; a last
            kb = -(-k // B)     # block of one position has no diagonal tile
            tiles += kb * (kb + 1) // 2 - (k % B == 1)
    cap = min(tri._TILES, round_cap(tiles))
    per = min(cap, max(1, batch // B ** 2))
    tbatches = sum(-(-min(cap, tiles - t0) // per)
                   for t0 in range(0, tiles, cap))
    ibatches = -(-index // min(batch, round_cap(index)))
    return (sum(k * (k - 1) // 2 for k in ks), index, tiles,
            tbatches + ibatches, tbatches * per * B ** 2)


def _rows_of(path):
    with open(path) as f:
        return np.array(f.read().split(), np.uint64).reshape(-1, 3)


def _obj(backend):
    if backend == "serial":
        return ObjectManager()
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    return ObjectManager(comm=make_mesh(int(backend[4:])))


# -- the device engine ----------------------------------------------------------

@pytest.mark.parametrize("backend", ["serial", "mesh4"])
@pytest.mark.parametrize("cap", ["below", "above"])
@pytest.mark.parametrize("name", list(GRAPHS))
def test_device_engine_against_brute_force(cases, tmp_path, monkeypatch,
                                           name, cap, backend):
    path, e, oracle = cases[name]
    nw = _wedges(e)
    # below: five batches or more (a power of two under a fifth of the
    # wedges, 8 at least), so that batch boundaries fall inside the wedges
    # of one vertex wherever a vertex owns more than a batch
    small = max(8, 1 << max(nw // 5, 1).bit_length() - 1)
    monkeypatch.setattr(tri, "_BATCH", small if cap == "below" else 1 << 24)
    out = tmp_path / "tri.out"
    cmd = run_command("tri_find", [], obj=_obj(backend), inputs=[path],
                      outputs=[str(out)], screen=False)
    rows = _rows_of(out)
    got = [frozenset(map(int, r)) for r in rows]
    assert all(len(t) == 3 for t in got)
    assert len(got) == len(set(got)) == cmd.ntri      # each exactly once
    assert set(got) == oracle
    if cap == "below" and nw > 40:
        assert 5 <= -(-nw // small) <= 10
    # the centre is the lowest (degree, id) vertex of its triangle
    deg = collections.Counter(e.reshape(-1).tolist())
    for c, u, w in rows.tolist():
        assert (deg[c], c) == min((deg[v], v) for v in (c, u, w))


@pytest.mark.parametrize("name", list(GRAPHS))
def test_device_engine_equals_composed(cases, tmp_path, monkeypatch, name):
    from gpu_mapreduce_tpu.oink.commands import tri as tmod
    path, e, oracle = cases[name]
    if not len(e):
        pytest.skip("the composed engine has nothing to collate")
    got = {}
    for engine in ("fused", "composed"):
        monkeypatch.setattr(tmod.TriFind, "engine", engine)
        out = tmp_path / f"tri.{engine}"
        cmd = run_command("tri_find", [], inputs=[path],
                          outputs=[str(out)], screen=False)
        rows = _rows_of(out)
        got[engine] = sorted(tuple(sorted(map(int, r))) for r in rows)
        assert cmd.ntri == len(rows)
    assert got["fused"] == got["composed"]
    assert len(got["fused"]) == len(oracle)


@pytest.mark.parametrize("name", ["star-500-k6", "rmat-10"])
def test_walk_counts_and_module_functions(cases, monkeypatch, name):
    """``walk`` says what it walked; ``triangles`` / ``triangles_ranked``
    stay callable as they were, over the same programs."""
    _path, e, oracle = cases[name]
    monkeypatch.setattr(tri, "_BATCH", 1024)
    verts, inv = np.unique(e.reshape(-1), return_inverse=True)
    inv = inv.reshape(-1, 2)
    w = tri.walk(jnp.asarray(inv[:, 0], jnp.int32),
                 jnp.asarray(inv[:, 1], jnp.int32),
                 jnp.ones(len(inv), bool), verts)
    assert w.by_id
    wide = tri.walk(jnp.asarray(inv[:, 0], jnp.int32),
                    jnp.asarray(inv[:, 1], jnp.int32),
                    jnp.ones(len(inv), bool), verts + np.uint64(1 << 33))
    assert not wide.by_id and wide[4:] == w[4:]
    assert np.array_equal(np.asarray(tri.rows(wide)[0])[:w.ntri],
                          np.asarray(tri.rows(w)[0])[:w.ntri]
                          + np.uint64(1 << 33))
    # ``batches`` counts the executions of the wedge program, of either
    # kind: the tile batches of every table, then the index batches
    nw, index, tiles, batches, tile_rows = _enumeration(e, 1024)
    assert nw == _wedges(e)
    if name == "rmat-10":       # both enumerations ran
        assert 0 < index < nw and tiles > 0
    else:                       # every list is short: the index walk alone
        assert index == nw and tiles == 0
    assert (w.wedges, w.batches, w.ntri, w.edges) == (
        nw, batches, len(oracle), len(e))
    assert (w.tiles, w.index_wedges, w.tile_rows) == (tiles, index, tile_rows)
    assert w.tile_fill == ((nw - index) / tile_rows if tiles else 0.0)
    assert w.tile_fill <= 1
    assert w.max_out_degree == max(_out_lists(e).values())
    both = np.concatenate([e, e[:, ::-1], e[:5]])       # not canonical
    for rows in (tri.triangles(both),
                 tri.triangles_ranked(inv[:, 0], inv[:, 1], len(verts),
                                      verts, canonical=True)):
        assert rows.dtype == np.uint64 and rows.shape == (len(oracle), 3)
        assert {frozenset(map(int, r)) for r in rows} == oracle


# -- the two enumerations (ISSUE 41) ----------------------------------------------

_B, _K0 = tri._BLOCK, tri._TILED


def _hub(k, among):
    """A centre whose out-list is exactly ``k`` long: vertex 0 joined to
    1 … k, each of which has k + 1 leaves of its own (so that its degree
    passes the centre's and the edge points at it), and ``among`` them
    every edge, none, or a chain with chords (some wedges close)."""
    nb = list(range(1, k + 1))
    edges = _star(0, nb)
    for i, v in enumerate(nb):
        first = k + 1 + i * (k + 1)
        edges += _star(v, range(first, first + k + 1))
    return edges + {
        "all": _complete(nb), "none": [],
        "some": ([(v, v + 1) for v in nb[:-1]]
                 + [(v, v + 3) for v in nb[:-3:2]])}[among]


TILE_GRAPHS = {
    # a list on each side of the boundary between the enumerations, one
    # that ends with a block and one that ends one position into a block
    **{f"list-{k}": (lambda k=k: _hub(k, "some"))
       for k in (_K0 - 1, _K0, _K0 + 1, 3 * _B, 3 * _B + 1)},
    # a hub of 3B + 1 with every pair closed (K_n: lists of every length
    # below n, by id), and with none
    "hub-complete": lambda: _complete(range(3 * _B + 2)),
    "hub-star": lambda: _hub(3 * _B + 1, "none"),
    # every list shorter than K0: today's program and nothing else
    "all-short": lambda: (_complete(range(_K0)) + _complete(range(100, 106))
                          + _star(200, range(201, 240)) + [(5, 100), (5, 200)]),
}


@pytest.mark.parametrize("how", ["serial", "mesh4", "wide-ids", "small-batch"])
@pytest.mark.parametrize("name", list(TILE_GRAPHS))
def test_tile_and_index_walks_against_brute_force(monkeypatch, name, how):
    """Every triangle once whichever enumeration its centre's list gets,
    and the walk's counts what the lists' lengths say.  ``small-batch``:
    two tiles a batch and eight a table, so that a list's tiles are cut
    across batches and across tables."""
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    from gpu_mapreduce_tpu.parallel.sharded import round_cap
    e = _upper(TILE_GRAPHS[name]())
    oracle = brute_triangles(e)
    batch = 1 << 24
    if how == "small-batch":
        batch = 2 * _B ** 2
        monkeypatch.setattr(tri, "_BATCH", batch)
        monkeypatch.setattr(tri, "_TILES", 8)
    mesh = make_mesh(4) if how == "mesh4" else None
    wide = np.uint64(1 << 33 if how == "wide-ids" else 0)
    verts, inv = np.unique(e.reshape(-1), return_inverse=True)
    inv = inv.reshape(-1, 2)
    w = tri.walk(jnp.asarray(inv[:, 0], jnp.int32),
                 jnp.asarray(inv[:, 1], jnp.int32),
                 jnp.ones(len(inv), bool), verts + wide, mesh)
    assert w.by_id == (how != "wide-ids")
    rows = np.asarray(tri.rows(w, mesh)[0])[:w.ntri] - wide
    got = [frozenset(map(int, r)) for r in rows]
    assert len(got) == len(set(got)) == w.ntri and set(got) == oracle
    nw, index, tiles, batches, tile_rows = _enumeration(e, batch)
    assert nw == _wedges(e)
    assert (w.wedges, w.index_wedges, w.tiles, w.batches, w.tile_rows) == (
        nw, index, tiles, batches, tile_rows)
    longest = max(_out_lists(e).values())
    assert w.max_out_degree == longest
    assert (tiles > 0) == (longest >= _K0)
    if name.startswith("list-"):
        assert longest == int(name[5:])
    if name == "all-short":     # no tile batch ran; ``batches`` is the
        assert w.tile_rows == 0 and w.tile_fill == 0.0  # rule before tiles
        assert w.batches == -(-nw // min(batch, round_cap(nw)))
    elif how == "small-batch" and longest >= 3 * _B:
        # the longest list's six tiles or more, two a batch; K_n's fill
        # more than one table
        assert w.batches >= tiles // 2 >= 3
        assert tiles > 8 or name != "hub-complete"


def test_empty_inputs_give_no_rows():
    assert tri.triangles(np.zeros((0, 2), np.uint64)).shape == (0, 3)
    assert tri.triangles(np.array([[5, 5]], np.uint64)).shape == (0, 3)
    assert tri.triangles_ranked(np.zeros(0, np.int64), np.zeros(0, np.int64),
                                0, np.zeros(0, np.uint64)).shape == (0, 3)


def test_result_cap_is_a_power_of_two_then_a_multiple():
    assert [tri.result_cap(n) for n in (1, 8, 9, 1 << 20)] == [
        8, 8, 16, 1 << 20]
    assert tri.result_cap((1 << 20) + 1) == 2 << 20
    assert tri.result_cap(100_000_000) == 96 << 20


def test_one_device_mesh_keeps_the_triangles_on_the_device(cases):
    """What the chip cell asserts: on a one-device mesh ``mrt`` is a mesh
    frame, and no edge or triangle row was pulled to build it."""
    from gpu_mapreduce_tpu.oink.objects import _mesh_frame
    from gpu_mapreduce_tpu.oink.script import OinkScript
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    from gpu_mapreduce_tpu.parallel.sharded import ShardedKV, ToHostStats
    import io
    path, _e, oracle = cases["rmat-10"]
    script = OinkScript(comm=make_mesh(1), screen=io.StringIO())
    script.run_string(f"edge_upper -i {path} -o NULL mru")
    script.obj.get_mr("mru").aggregate()
    before = ToHostStats.snapshot()
    script.run_string("tri_find -i mru -o NULL mrt")
    assert not any(ToHostStats.delta(before))
    fr = _mesh_frame(script.obj.get_mr("mrt"))
    assert isinstance(fr, ShardedKV) and isinstance(fr.key, jax.Array)
    assert fr.counts.tolist() == [len(oracle)]
    assert fr.key.shape == (tri.result_cap(len(oracle)), 3)
    got = {frozenset(map(int, r))
           for r in np.asarray(fr.key)[:len(oracle)]}
    assert got == oracle
    assert f"Tri_find: {len(oracle)} triangles" in script.screen.getvalue()


def _lowered_ops(fn, *shapes, **static):
    sds = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    text = jax.jit(fn, static_argnames=tuple(static)).lower(
        *sds, **static).as_text()
    return collections.Counter(re.findall(r"stablehlo\.(\w+)", text))


def _gathers(fn, *shapes, **static):
    """Result shape of every gather in a program's jaxpr."""
    sds = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    jaxpr = jax.make_jaxpr(functools.partial(fn, **static))(*sds).jaxpr
    return sorted(q.outvars[0].aval.shape for q in _primitives(jaxpr, [])
                  if q.primitive.name == "gather")


def test_the_wedge_program_holds_no_scatter():
    """The chip's rule (PERF.md §6, PRs 25 and 29): a scatter costs thirty
    sorts there and looks cheap here.  A tile batch is two sorts (the join,
    the compaction), prefix scans and one gather of both blocks of every
    tile; an index batch is four sorts and one gather over the batch (a
    wedge's two neighbours); the tile expansion, once a job, is two sorts and
    no gather (the first owner's offset is one element sliced)."""
    e, b, B = 1 << 10, 1 << 12, tri._BLOCK
    col = lambda dtype, n=e: ((n,), dtype)      # noqa: E731
    scalar = ((), jnp.int64)
    tiles = tuple(col(jnp.int32, 256) for _ in range(4))
    index = (col(jnp.int32), col(jnp.int64))
    for lists, block, sorts, gathers in (
            (tiles, B, 2, [(2, B, b // B ** 2)]),    # nbr[both blocks]
            (index, 0, 4, [(2, b)])):               # nbr[both ends]
        fn = lambda ekey, nbr, t0, total, *ls: tri.tri_wedges(  # noqa: E731
            ekey, nbr, ls, t0, total, batch=b, block=block)
        shapes = (col(jnp.uint64), col(jnp.int32), scalar, scalar) + lists
        ops = _lowered_ops(fn, *shapes)
        assert ops["sort"] == sorts and "scatter" not in ops, (block, ops)
        assert "while" not in ops, (block, ops)     # no searchsorted either
        assert _gathers(fn, *shapes) == gathers, block
    shapes = (col(jnp.int32), col(jnp.int64), scalar)
    ops = _lowered_ops(tri.tri_tiles, *shapes, cap=256, block=B)
    assert ops["sort"] == 2 and not ops.keys() & {"scatter", "while"}, ops
    assert _gathers(tri.tri_tiles, *shapes, cap=256, block=B) == []
    ops = _lowered_ops(
        tri.tri_orient, ((e,), jnp.int32), ((e,), jnp.int32),
        ((e,), jnp.bool_), ((64,), jnp.uint64), canonical=False, by_id=False,
        block=B, tiled=tri._TILED)
    # (five sorts; the two ``jnp.sort`` of the keys lower as one function)
    assert ops["sort"] >= 4 and not ops.keys() & {
        "scatter", "gather", "while"}, ops


def test_models_tri_has_no_host_walk():
    src = open(tri.__file__).read()
    for gone in ("default_backend", "use_device", "np.searchsorted", "_probe"):
        assert gone not in src, gone


# -- luby_find and sssp beside it -----------------------------------------------

def _splitmix_priority(v, seed):
    """``vertex_rand`` written again: splitmix64(v + seed), top 53 bits."""
    mask = (1 << 64) - 1
    x = (int(v) + seed + 0x9E3779B97F4A7C15) & mask
    z = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & mask
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & mask
    z ^= z >> 31
    return (z >> 11) / float(1 << 53)


@pytest.mark.parametrize("backend", ["serial", "mesh4"])
@pytest.mark.parametrize("name", ["star-500-k6", "two-hubs", "rmat-10"])
def test_luby_find_is_the_greedy_set_by_priority(cases, tmp_path, name,
                                                 backend):
    path, e, _ = cases[name]
    seed = 6789
    out = tmp_path / "mis"
    cmd = run_command("luby_find", [str(seed)], obj=_obj(backend),
                      inputs=[path], outputs=[str(out)], screen=False)
    got = set(np.loadtxt(out, dtype=np.uint64).reshape(-1).tolist())
    adj = collections.defaultdict(set)
    for a, b in e.tolist():
        adj[a].add(b)
        adj[b].add(a)
    want = set()
    for v in sorted(adj, key=lambda v: (_splitmix_priority(v, seed), v)):
        if not adj[v] & want:
            want.add(v)
    assert got == want and cmd.nset == len(want)
    assert all(not adj[v] & got for v in got)               # independent
    assert all(v in got or adj[v] & got for v in adj)       # maximal


@pytest.mark.parametrize("backend", ["serial", "mesh4"])
@pytest.mark.parametrize("name", ["two-hubs", "rmat-10"])
def test_sssp_equals_a_float64_reference(cases, tmp_path, name, backend):
    _path, e, _ = cases[name]
    rng = np.random.default_rng(5)
    wt = np.round(rng.uniform(0.5, 2.0, len(e)), 3) if name == "two-hubs" \
        else np.ones(len(e))
    path = tmp_path / "weighted.txt"
    path.write_text("".join(f"{a} {b} {w}\n"
                            for (a, b), w in zip(e.tolist(), wt.tolist())))
    out = tmp_path / "sssp"
    cmd = run_command("sssp", ["1", "12345"], obj=_obj(backend),
                      inputs=[str(path)], outputs=[str(out)], screen=False)
    (source, res), = cmd.results.items()
    # Bellman-Ford in float64 over the directed edges
    verts = np.unique(e)
    dist = {int(v): np.inf for v in verts}
    dist[source] = 0.0
    changed = True
    while changed:
        changed = False
        for (a, b), w in zip(e.tolist(), wt.tolist()):
            if dist[a] + w < dist[b]:
                dist[b] = dist[a] + w
                changed = True
    rows = np.loadtxt(out).reshape(-1, 3)
    assert rows[:, 0].astype(np.uint64).tolist() == verts.tolist()
    into = collections.defaultdict(dict)
    for (a, b), w in zip(e.tolist(), wt.tolist()):
        into[b][a] = w
    labeled = 0
    for v, d, p in rows.tolist():
        v, p = int(v), int(p)
        assert d == pytest.approx(dist[v], rel=1e-5) or (
            np.isinf(d) and np.isinf(dist[v]))
        assert res[v][0] == dist[v] or (np.isinf(dist[v])
                                        and np.isinf(res[v][0]))
        if np.isfinite(d):
            labeled += 1
            if v != source:     # pred realises dist
                assert p in into[v]
                assert res[p][0] + into[v][p] == res[v][0]
    assert labeled == sum(np.isfinite(d) for d in dist.values())


# -- sssp's two number types ------------------------------------------------------

def _line_and_shortcut(wt):
    """0 →1→ 2 →…, a shortcut 0 → 3 and a dead end: (src, dst, w, n)."""
    src = np.array([0, 1, 2, 3, 0, 5], np.int32)
    dst = np.array([1, 2, 3, 4, 3, 0], np.int32)
    return src, dst, np.asarray(wt, np.float64), 6


@pytest.mark.parametrize("wt, whole", [
    ([1, 1, 1, 1, 1, 1], True),             # add_weight's
    ([2, 3, 4, 1, 7, 0], True),             # whole, a zero among them
    ([1, 1, 1, 1, 1.5, 1], False),          # one fraction
    ([1, 1, -1, 1, 1, 1], False),           # a negative weight
    ([1, 1, 1, 1, 2.0 ** 30, 1], False),    # n of them pass 2^31
], ids=["unit", "whole", "fraction", "negative", "too-large"])
def test_sssp_runs_whole_weights_in_int32_and_no_others(wt, whole):
    from gpu_mapreduce_tpu.models import sssp
    src, dst, w, n = _line_and_shortcut(wt)
    valid = np.ones(len(w), bool)
    got = sssp.exact_weights(jnp.asarray(w), valid, n)
    assert (got.dtype == jnp.int32) == whole
    assert got.dtype in (jnp.int32, jnp.float64)
    run = sssp.runner(lambda s, d, x, _v, at: sssp.bellman_ford(s, d, x, n, at),
                      src, dst, jnp.asarray(w), valid, n)
    dist, pred, _rounds = run(0)
    want = np.full(n, np.inf)
    want[0] = 0.0
    for _ in range(n):
        for a, b, x in zip(src, dst, w):
            want[b] = min(want[b], want[a] + x)
    assert dist.dtype == np.float64 and dist.tolist() == want.tolist()
    assert np.isinf(dist[5]) and pred[5] == -1 and pred[0] == -1
    for v in range(1, 5):       # pred realises dist
        at = [i for i in range(len(w)) if dst[i] == v and src[i] == pred[v]]
        assert at and dist[pred[v]] + w[at[0]] == dist[v]


@pytest.mark.parametrize("backend", ["serial", "mesh4"])
def test_the_whole_weight_round_is_a_sort_and_no_scatter(backend):
    """The chip's rule (PERF.md §6): a scatter costs thirty sorts there.
    The float64 round keeps its two ``segment_min``: the v5e sorts no
    float64."""
    from gpu_mapreduce_tpu.models import sssp
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    SDS = jax.ShapeDtypeStruct
    e, b, at = SDS((64,), jnp.int32), SDS((64,), jnp.bool_), SDS((), jnp.int32)
    ops = {}
    for dtype in (jnp.int32, jnp.float64):
        w = SDS((64,), dtype)
        if backend == "serial":
            low = sssp.bellman_ford.lower(e, e, w, 16, at)
        else:
            low = sssp._bf_sharded_fn(make_mesh(4), 16, 16).lower(
                e, e, w, b, at)
        text = low.as_text()
        body = text[text.index("stablehlo.while"):]
        ops[dtype] = set(re.findall(r"stablehlo\.(\w+)", body))
    assert "sort" in ops[jnp.int32] and "scatter" not in ops[jnp.int32]
    assert "scatter" in ops[jnp.float64] and "sort" not in ops[jnp.float64]


# -- luby's counting round ---------------------------------------------------------

LUBY_N, LUBY_ROWS = 16, 64


def _luby_graphs():
    """name -> (edges, ranks): every graph over 16 vertices, so that one
    compilation a caller and a round count serves them all."""
    rng = np.random.default_rng(40)
    ident = np.arange(LUBY_N, dtype=np.int32)
    rand = [tuple(r) for r in rng.integers(0, LUBY_N, (40, 2)).tolist()]
    return {
        # every edge twice or three times, in both directions
        "duplicates": (rand[:14] * 2 + [(b, a) for a, b in rand[:14]],
                       rng.permutation(LUBY_N).astype(np.int32)),
        "self-loops": (rand[14:30] + [(v, v) for v in range(0, LUBY_N, 2)],
                       rng.permutation(LUBY_N).astype(np.int32)),
        # 4 and 5 win at once, 2 and 3 go out, and 15, which lost to both,
        # is left with no undecided neighbour: it joins in round 2
        "isolated-after-exclusion": (
            [(15, 2), (15, 3), (2, 4), (3, 5)],
            np.argsort([4, 5, 2, 3, 0, 1] + list(range(6, LUBY_N))
                       ).astype(np.int32)),
        # the hub first (one round), and the hub last (every leaf at once)
        "star-hub-wins": ([(0, v) for v in range(1, LUBY_N)], ident),
        "star-hub-loses": ([(0, v) for v in range(1, LUBY_N)],
                           ident[::-1].copy()),
        # a path in rank order: one vertex a round can be decided by 0
        "path": ([(v, v + 1) for v in range(LUBY_N - 1)], ident),
        "self-loops-only": ([(v, v) for v in range(5)], ident),
    }


def _plain_luby_rounds(edges, prio, n):
    """The definition: an undecided vertex whose rank is below every
    undecided neighbour's joins, then the undecided neighbours of those
    go out.  Self loops dropped first.  The state after each round."""
    adj = collections.defaultdict(set)
    for a, b in edges:
        if a != b:
            adj[a].add(b)
            adj[b].add(a)
    state, states = np.zeros(n, np.int8), []
    while (state == 0).any():
        und = state == 0
        win = [v for v in range(n) if und[v] and all(
            prio[v] < prio[u] for u in adj[v] if und[u])]
        out = [u for v in win for u in adj[v] if und[u]]
        state = state.copy()
        state[out] = 2
        state[win] = 1
        states.append(state)
    return states


def _luby_caller(caller):
    """(src, dst, prio, maxiter) -> (state, rounds) through one of the
    module's three entries; the staged loop gets padding rows that name
    real vertices and are not valid."""
    from gpu_mapreduce_tpu.models import luby
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    kind, _, width = caller.partition("-mesh")

    def run(src, dst, prio, maxiter):
        if kind == "luby_mis":
            return luby.luby_mis(src, dst, jnp.asarray(prio), LUBY_N,
                                 maxiter=maxiter)
        mesh = make_mesh(int(width))
        if kind == "luby_mis_sharded":
            return luby.luby_mis_sharded(mesh, src, dst, prio, LUBY_N,
                                         maxiter=maxiter)
        m = len(src)
        rng = np.random.default_rng(m)
        rows = rng.integers(0, LUBY_N, (2, LUBY_ROWS)).astype(np.int32)
        at = rng.permutation(LUBY_ROWS)[:m]     # the real rows, scattered
        rows[0, at], rows[1, at] = src, dst
        valid = np.zeros(LUBY_ROWS, bool)
        valid[at] = True
        return luby._luby_sharded_fn(mesh, LUBY_N, maxiter)(
            rows[0], rows[1], valid, jnp.asarray(prio))
    return run


@pytest.mark.parametrize("caller", [
    "luby_mis", "luby_loop-mesh1", "luby_loop-mesh4", "luby_loop-mesh8",
    "luby_mis_sharded-mesh4"])
@pytest.mark.parametrize("name", list(_luby_graphs()))
def test_luby_state_after_every_round_is_the_plain_rounds(name, caller):
    edges, prio = _luby_graphs()[name]
    src, dst = np.asarray(edges, np.int32).reshape(-1, 2).T
    want = _plain_luby_rounds(edges, prio, LUBY_N)
    assert 1 <= len(want) <= 8, len(want)
    if name == "isolated-after-exclusion":
        assert want[0][15] == 0 and want[1][15] == 1
    run = _luby_caller(caller)
    for k, state in enumerate(want, 1):
        got, rounds = run(src, dst, prio, k)
        assert np.asarray(got).tolist() == state.tolist(), k
        assert int(rounds) == k
    got, rounds = run(src, dst, prio, LUBY_N)       # and it stops there
    assert np.asarray(got).tolist() == want[-1].tolist()
    assert int(rounds) == len(want)


@pytest.mark.parametrize("backend", ["serial", "mesh1", "mesh4"])
def test_luby_rounds_on_the_golden_script_input(backend, tmp_path,
                                                monkeypatch):
    """``examples/in.luby``'s set and round count (tests/test_script.py
    holds the serial run of the file itself to the same line)."""
    import io
    from gpu_mapreduce_tpu.oink import OinkScript
    monkeypatch.chdir(tmp_path)
    out = io.StringIO()
    s = OinkScript(comm=_obj(backend).comm, screen=out)
    s.run_file(os.path.join(EXAMPLES, "in.luby"))
    assert "Luby_find: 1123 MIS vertices in 5 iterations" in out.getvalue()


def _primitives(jaxpr, found):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


@pytest.mark.parametrize("backend", ["serial", "mesh1", "mesh4"])
def test_the_luby_round_is_two_gathers_two_prefix_sums_and_no_scatter(
        backend):
    """The chip's rule (PERF.md §6): a scatter costs thirty sorts there.
    The program sorts once, before the loop; a round reads the rows by two
    gathers and two prefix sums, and the runs' bounds by small gathers."""
    from gpu_mapreduce_tpu.models import luby
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    SDS = jax.ShapeDtypeStruct
    rows, n = 1 << 10, 48
    e, p = SDS((rows,), jnp.int32), SDS((n,), jnp.int32)
    if backend == "serial":
        fn, args, static, shard = luby.luby_mis, (e, e, p, n), (3,), rows
    else:
        width = int(backend[4:])
        fn = luby._luby_sharded_fn(make_mesh(width), n, n)
        args, static = (e, e, SDS((rows,), jnp.bool_), p), ()
        shard = rows // width
    assert "scatter" not in fn.lower(*args).as_text()
    eqns = _primitives(
        jax.make_jaxpr(fn, static_argnums=static)(*args).jaxpr, [])
    names = collections.Counter(q.primitive.name for q in eqns)
    assert names["sort"] == 1 and not any("scatter" in k for k in names)
    (loop,) = [q for q in eqns if q.primitive.name == "while"
               and any(v.aval.dtype == jnp.int8 for v in q.outvars)]
    body = _primitives(loop.params["body_jaxpr"].jaxpr, [])
    inside = collections.Counter(q.primitive.name for q in body)
    assert not inside.keys() & {"sort", "while"} and not any(
        "scatter" in k or "segment" in k for k in inside), inside
    assert inside["cumsum"] == 2, inside
    big = [q for q in body if q.primitive.name == "gather"
           and q.outvars[0].aval.shape == (shard,)]
    assert len(big) == 2 and inside["gather"] == 4, inside
    if backend != "serial":
        sums = [k for k in inside.elements() if k.startswith("psum")]
        assert len(sums) == 2 and not inside.keys() & {"pmin", "pmax"}

