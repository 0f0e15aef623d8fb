"""The graph engines on a mesh, through OINK (ISSUE 46): the job of the
benchmark's ``graph-iter`` cells — ``cc_find 0`` and ``pagerank 1e-6 100
0.85`` over named MRs that ``rmat`` / ``edge_upper`` left on the mesh —
on a mesh of four devices beside a mesh of one.

Held here on CPU meshes, as the chip run holds it at RMAT-22: both meshes
give the numpy references' components exactly and their ranks within L1
1e-5; four devices give one device's label file byte for byte, the same
iteration counts, and ranks within L1 1e-6 of one device's (the ``psum``
changes the order of float32 sums, nothing else); the edge columns never
come to the host inside the job.  Two graphs: ``rmat 10 8`` (R-MAT's hubs
and its isolated vertices) and a hand-made one with duplicate edges, self
loops, an isolated pair and a row count that leaves padding rows in every
shard.  The references are plain numpy, written here: union-find by
minimum label, and a float64 power iteration.
"""

import glob
import io
import re

import numpy as np
import pytest

from gpu_mapreduce_tpu.obs import get_tracer, names
from gpu_mapreduce_tpu.oink.script import OinkScript
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
from gpu_mapreduce_tpu.parallel.staging import mesh_kv_frame

U64 = np.uint64
TOL, MAXITER, DAMPING = 1e-6, 100, 0.85


def _handmade() -> np.ndarray:
    """37 directed edges over ids that are not 0..n-1: a chain with a
    duplicate of every third edge, three self loops (one on a vertex with
    no other edge), a hub, an isolated pair and a cycle.  37 rows over four
    shards of capacity 8 or 16 cannot fill any of them."""
    chain = [(10 + i, 11 + i) for i in range(9)]
    dups = chain[::3]
    loops = [(12, 12), (500, 500), (15, 15)]
    hub = [(1000, v) for v in (10, 13, 2000, 2001, 2002, 2003)]
    pair = [(7000, 7001)]
    cycle = [(2 ** 33 + i, 2 ** 33 + (i + 1) % 5) for i in range(5)]
    back = [(2003, 1000), (2001, 2002), (18, 10), (7001, 7000)]
    extra = [(2002, 2 ** 33), (13, 10), (2000, 2000 + 3), (11, 10),
             (19, 2 ** 33 + 2), (2 ** 33 + 4, 19)]
    e = np.asarray(chain + dups + loops + hub + pair + cycle + back + extra,
                   dtype=U64)
    assert len(e) == 37
    return e


def _build(script: OinkScript, graph: str) -> None:
    """``mre`` and ``mru`` as the cell's set-up leaves them: named MRs on
    the script's mesh."""
    if graph == "rmat10":
        script.run_string("rmat 10 8 0.57 0.19 0.19 0.05 0.0 1 -o NULL mre")
    else:
        script.run_string("mr mre")
        e = _handmade()
        mre = script.obj.get_mr("mre")
        mre.map(1, lambda i, kv, p: kv.add_batch(
            e, np.zeros(len(e), np.uint8)))
        mre.aggregate()
    script.run_string("edge_upper -i mre -o NULL mru")


def _edges(mr) -> np.ndarray:
    from gpu_mapreduce_tpu.oink.kernels import kv_keys
    rows = []
    mr.scan_kv(lambda fr, p: rows.append(kv_keys(fr)), batch=True)
    return np.concatenate(rows).astype(U64)


def _pairs(prefix: str, dtype):
    rows = [np.loadtxt(f, dtype=dtype, ndmin=2)
            for f in sorted(glob.glob(prefix + "*"))]
    rows = np.concatenate([r for r in rows if len(r)])
    order = np.argsort(rows[:, 0], kind="stable")
    return rows[order, 0], rows[order, 1]


def _raw(prefix: str) -> bytes:
    out = b""
    for f in sorted(glob.glob(prefix + "*")):
        with open(f, "rb") as fh:
            out += fh.read()
    return out


def _run(nprocs: int, graph: str, out) -> dict:
    tr = get_tracer()
    script = OinkScript(comm=make_mesh(nprocs), screen=io.StringIO())
    _build(script, graph)
    frames = {nm: mesh_kv_frame(script.obj.get_mr(nm))
              for nm in ("mre", "mru")}
    assert all(fr is not None for fr in frames.values())
    padding = {nm: bool((np.asarray(fr.counts)
                         < fr.key.shape[0] // nprocs).all())
               for nm, fr in frames.items()}
    edges = _edges(script.obj.get_mr("mre"))
    was = tr.enabled
    tr.enable(ring=1 << 16)
    tr.clear()
    try:
        at = script.screen.tell()
        script.run_string(f"cc_find 0 -i mru -o {out}/cc NULL")
        script.run_string(f"pagerank {TOL} {MAXITER} {DAMPING} -i mre "
                          f"-o {out}/pr NULL")
        events = tr.events()
    finally:
        tr.clear()
        if not was:
            tr.disable()
    said = script.screen.getvalue()[at:]
    return {
        "edges": edges, "padding": padding, "events": events,
        "cc": _pairs(f"{out}/cc", U64), "cc_raw": _raw(f"{out}/cc"),
        "pr": _pairs(f"{out}/pr", np.float64),
        "components": int(re.search(r"CC_find: (\d+) components", said)[1]),
        "cc_iters": int(re.search(r"components in (\d+) iter", said)[1]),
        "pr_iters": int(re.search(r"edges, (\d+) iterations", said)[1]),
    }


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """(graph, devices) -> the job's outputs, each job run once."""
    done = {}

    def get(graph, nprocs):
        if (graph, nprocs) not in done:
            out = tmp_path_factory.mktemp(f"{graph}-{nprocs}")
            done[graph, nprocs] = _run(nprocs, graph, out)
        return done[graph, nprocs]

    return get


# -- plain references ----------------------------------------------------------

def _components(e: np.ndarray) -> dict:
    """vertex -> smallest vertex id of its component, over the edges' end
    points (self loops keep their vertex, as ``edge_upper`` drops the row
    and the vertex with it unless another edge names it)."""
    parent = {}

    def find(v):
        while parent[v] != v:
            parent[v] = parent[parent[v]]
            v = parent[v]
        return v

    for a, b in e.tolist():
        for v in (a, b):
            parent.setdefault(v, v)
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
    return {v: find(v) for v in parent}


def _pagerank(e: np.ndarray, iters: int):
    verts, inv = np.unique(e.reshape(-1), return_inverse=True)
    src, dst = inv.reshape(-1, 2).T
    n = len(verts)
    deg = np.bincount(src, minlength=n).astype(np.float64)
    inv_deg = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    r, deltas = np.full(n, 1.0 / n), []
    for _ in range(iters):
        inflow = np.bincount(dst, weights=(r * inv_deg)[src], minlength=n)
        r2 = (1 - DAMPING) / n + DAMPING * (inflow + r[deg == 0].sum() / n)
        deltas.append(float(np.abs(r2 - r).max()))
        r = r2
    return verts, r, deltas


GRAPHS = ["rmat10", "handmade"]


@pytest.mark.parametrize("nprocs", [1, 4])
@pytest.mark.parametrize("graph", GRAPHS)
def test_components_are_the_references(runs, graph, nprocs):
    got = runs(graph, nprocs)
    e = got["edges"]
    upper = e[e[:, 0] != e[:, 1]]
    want = _components(upper)
    verts, zones = got["cc"]
    assert verts.tolist() == sorted(want)
    assert zones.tolist() == [want[v] for v in sorted(want)]
    assert got["components"] == len(set(want.values()))
    if graph == "handmade":
        # the isolated pair is a component of its own; the vertex whose
        # only edge is a self loop is in no upper edge and so in no output
        assert want[7001] == 7000 and 500 not in want
        assert got["components"] == 2


@pytest.mark.parametrize("nprocs", [1, 4])
@pytest.mark.parametrize("graph", GRAPHS)
def test_ranks_are_within_1e5_of_a_float64_power_iteration(runs, graph,
                                                           nprocs):
    got = runs(graph, nprocs)
    verts, want, deltas = _pagerank(got["edges"], got["pr_iters"])
    pr_v, pr = got["pr"]
    assert pr_v.astype(U64).tolist() == verts.tolist()
    assert 1 < got["pr_iters"] < MAXITER
    # the reference agrees that this was the step to stop at
    assert deltas[-1] <= TOL * 1.02 and min(deltas[:-1]) > TOL * 0.98
    assert np.abs(pr - want).sum() < 1e-5
    assert abs(pr.sum() - 1.0) < 1e-5


@pytest.mark.parametrize("graph", GRAPHS)
def test_four_devices_give_one_devices_result(runs, graph):
    one, four = runs(graph, 1), runs(graph, 4)
    assert four["cc_raw"] == one["cc_raw"] and one["cc_raw"]
    assert four["components"] == one["components"]
    assert four["cc_iters"] == one["cc_iters"] >= 1
    assert four["pr_iters"] == one["pr_iters"]
    assert four["pr"][0].tolist() == one["pr"][0].tolist()
    assert np.abs(four["pr"][1] - one["pr"][1]).sum() < 1e-6


@pytest.mark.parametrize("graph", GRAPHS)
def test_every_shard_of_four_holds_padding_rows(runs, graph):
    """What the sharded loops must mask: no shard of either dataset is
    full to its capacity (a power of two the hash never fills evenly)."""
    assert runs(graph, 4)["padding"] == {"mre": True, "mru": True}


@pytest.mark.parametrize("nprocs", [1, 4])
@pytest.mark.parametrize("graph", GRAPHS)
def test_the_edge_columns_stay_on_the_mesh(runs, graph, nprocs):
    """``on_device`` 1 on both stage spans, no ``scan_kv`` under either
    command, and the loop spans say what the mesh merged: ``shards`` and
    ``allreduce_bytes`` = n * 4 * one all-reduce an iteration * iters on
    four devices, 0 on one."""
    got = runs(graph, nprocs)
    events = got["events"]
    assert not [e for e in events if e["name"] == "scan_kv"]
    args = {e["name"]: e["args"] for e in events}
    for stage in (names.CC_STAGE, names.PAGERANK_STAGE):
        assert args[stage]["on_device"] == 1
        assert args[stage]["shards"] == nprocs
    for loop, iters in ((names.CC_ENGINE, got["cc_iters"]),
                        (names.PAGERANK_ENGINE, got["pr_iters"])):
        a = args[loop]
        assert a["iters"] == iters and a["shards"] == nprocs
        want = a["n"] * 4 * iters if nprocs > 1 else 0
        assert a["allreduce_bytes"] == want
    assert args[names.CC_ENGINE]["n"] == len(got["cc"][0])
    assert args[names.PAGERANK_ENGINE]["n"] == len(got["pr"][0])
