"""ISSUE 32: the one-chip triangle / independent-set / shortest-path cell
and the metrics it brings, tiny, through the harness on the CPU, and its
references on their own (``python -m pytest benchmark/tests``, not tier-1).

``test_harness.tiny_cell`` sizes a cell by its job kind from a table that
this PR may not edit; as ``test_wordfreq_4chip.py`` does, this file enters
the kind it adds as it is imported."""

import itertools
import json
import os
import sys

import numpy as np
import pytest

from benchmark import CheckFailure, cells, kernels_tri
from benchmark.refs import graph_tri
from benchmark.tests import test_harness
from benchmark.tests.test_harness import cpu_trace, tiny_cell  # noqa: F401

# pytest imports the test files of this directory by their bare names (it
# has no __init__.py), so its ``test_harness`` is another module object than
# the one imported above, and this file is collected before it: import it
# under that name here, and enter the kind in both
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_harness as _collected  # noqa: E402

for _module in (test_harness, _collected):
    _module.TINY.setdefault("graph_tri", {"scale": 8})

CELL = "graph-tri-1chip"
NEW = ("tri_find_s", "luby_find_s", "sssp_s", "tri_wedge_dev_s",
       "luby_loop_dev_s", "sssp_loop_dev_s", "tri_wedge_roofline",
       "enum_host_s", "enum_rounds", "wedges_per_triangle")
JOINED = ("entry_glue_s", "stage_dev_s")
NO_DEVICE = {"peak_hbm_gib"}    # the CPU stand-in has no memory statistics
DEAD_SEED = 10  # at the tiny scale its first source has no out-edge


# -- the references on their own -------------------------------------------------

def _graph(seed, n=60, m=400):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, size=(m, 2)).astype(np.uint64)
    e = e[e[:, 0] != e[:, 1]]
    e = np.unique(np.stack([e.min(1), e.max(1)], 1), axis=0)
    return e[:, 0], e[:, 1]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_triangles_reference_against_three_loops(seed):
    a, b = _graph(seed)
    edges = set(zip(a.tolist(), b.tolist()))
    want = sorted((x << 42) | (y << 21) | z
                  for x, y, z in itertools.combinations(range(60), 3)
                  if (x, y) in edges and (y, z) in edges and (x, z) in edges)
    assert graph_tri.triangles_reference(a, b).tolist() == want
    assert len(want) > 20


def test_triangles_reference_of_nothing():
    none = np.zeros(0, np.uint64)
    assert len(graph_tri.triangles_reference(none, none)) == 0


@pytest.mark.parametrize("seed", [1, 2])
def test_mis_reference_is_the_sequential_greedy_set(seed):
    a, b = _graph(seed)
    got = graph_tri.mis_reference(a, b, 6789).tolist()
    adj = {}
    for x, y in zip(a.tolist(), b.tolist()):
        adj.setdefault(x, set()).add(y)
        adj.setdefault(y, set()).add(x)
    pr = dict(zip(adj, graph_tri.priorities(
        np.array(list(adj), np.uint64), 6789).tolist()))
    want = set()
    for v in sorted(adj, key=lambda v: (pr[v], v)):
        if not adj[v] & want:
            want.add(v)
    assert got == sorted(want)


def test_priorities_are_splitmix64():
    # splitmix64's first output for the state 0 is 0xE220A8397B1DCDAF
    got = graph_tri.priorities(np.array([0], np.uint64), 0)[0]
    assert got == (0xE220A8397B1DCDAF >> 11) / float(1 << 53)


def test_sssp_reference_is_dijkstra(seed=4):
    import heapq
    a, b = _graph(seed)
    e = np.stack([a, b], 1)
    w = np.random.default_rng(seed).uniform(0.5, 2.0, len(e))
    verts = np.unique(e)
    got = graph_tri.sssp_reference(e, w, verts, int(verts[0]))
    dist = {int(verts[0]): 0.0}
    heap = [(0.0, int(verts[0]))]
    out = {}
    for (x, y), c in zip(e.tolist(), w.tolist()):
        out.setdefault(x, []).append((y, c))
    while heap:
        d, v = heapq.heappop(heap)
        if d > dist.get(v, np.inf):
            continue
        for y, c in out.get(v, []):
            if d + c < dist.get(y, np.inf):
                dist[y] = d + c
                heapq.heappush(heap, (d + c, y))
    assert [dist.get(int(v), np.inf) for v in verts] == got.tolist()


def test_references_use_nothing_of_the_program():
    src = open(graph_tri.__file__).read()
    head = src.split("def mr_rows")[0]
    assert "gpu_mapreduce_tpu" not in head.split('"""', 2)[2]
    assert src.count("gpu_mapreduce_tpu") == 1      # the accessor's import


def test_wedge_bytes_counts_what_its_docstring_says():
    assert kernels_tri.wedge_bytes(10, 2, 5, 3) == 160 + 80 + 36
    assert kernels_tri.wedge_bytes(0, 0, 5, 0) == 0


# -- the cell --------------------------------------------------------------------

def test_the_cell_and_its_metrics_are_declared_as_the_issue_names_them():
    from gpu_mapreduce_tpu.obs import names
    spec = cells.load_benchmark()
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.config_name == "mrmpi-rmat-tri-1chip"
    assert cell.traffic["kind"] == "graph_tri"
    assert cell.config["reduced"] == ["scale", "sssp_ncnt"]
    assert cell.config["scale"] in cell.config["ladder"]["rungs"]
    base = cells.load_cell("graph-iter-1chip").config
    for key in ("scale", "edge_factor", "a", "b", "c", "d", "fraction",
                "rmat_seed"):
        assert cell.config[key] == base[key], key
    assert (cell.config["luby_seed"], cell.config["sssp_seed"]) == (6789,
                                                                    12345)
    assert 1 <= cell.config["sssp_ncnt"] < 10       # in.sssp's 10, cut
    # that the cell is there as the issue names it; how many cells there
    # are, and how many may take four chips, is test_contract.py's
    named = [w for w in spec["workloads"] if w["name"] == CELL]
    assert len(named) == 1 and named[0]["traffic"] == "graph-tri"
    assert [c["name"] for c in spec["configs"]].count(cell.config_name) == 1
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert e2e == {"job_s", "edge_rate", "setup_s"}
    listed = {m["name"]: m for m in spec["per_layer"]}
    perf = open(os.path.join(cells.ROOT, "PERF.md")).read()
    for name in NEW + JOINED:
        assert CELL in listed[name]["workloads"], name
    for name in NEW:
        m = listed[name]
        assert m["workloads"] == [CELL] and m["moves"] == "job_s"
        assert m["layer"] == "graph engines"
        with open(os.path.join(cells.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        for key in ("names", "spans"):
            assert set(args.get(key, [])) <= set(names.SPANS), (name, key)
        assert set(args.get("modules", [])) <= set(names.PROGRAMS), name
        assert f"`{name}`" in perf, name


def test_cell_traced_reports_every_new_metric(cpu_harness, cpu_trace, capsys):
    cell = tiny_cell(CELL)
    line = cpu_harness.run_cell(cell, seed=(1 << 31) + 7, seconds=1.0,
                                trace=True, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    declared = {m["name"]: m for m in cell.metrics["per_layer"]}
    missing = set(declared) - set(line["metrics"])
    assert missing <= NO_DEVICE | {n for n, m in declared.items()
                                   if m["source"] == "device_trace"}
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["compiles_in_window"] == 0
    for name in ("tri_find_s", "luby_find_s", "sssp_s", "enum_host_s"):
        assert value[name] > 0, name
    assert value["enum_rounds"] >= 3            # a batch, a round, a round
    assert 1.0 < value["wedges_per_triangle"] < 100
    out = capsys.readouterr().out
    checked = next(ln for ln in out.splitlines()
                   if ln.startswith("bench: warm-up job checked "))
    facts = json.loads(checked[checked.index("{"):])
    assert facts["triangles"] > 0 and facts["mis_vertices"] > 0
    # some source reaches most of the graph: the loop relaxes edges
    assert 2 * max(facts["sssp_labeled"]) > facts["vertices"]
    assert set(facts["stages"]) == {"tri_find", "luby_find", "sssp"}


def test_the_roofline_reader_gets_the_walks_counts(cpu_harness):
    """On the CPU no program event reaches the trace, so the share itself
    is left out; what the job module hands the reader is read here."""
    from benchmark.jobs import graph_tri as job_module
    from gpu_mapreduce_tpu.obs import get_tracer, names
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    import jax
    from benchmark.cache import Cache
    cell = tiny_cell(CELL)
    job = job_module.Job(cell.config, cell.traffic,
                         make_mesh(devices=jax.devices()[:1]), 1, Cache())
    job.prepare()
    tracer = get_tracer()
    tracer.clear()
    assert job.info() == {"programs": {}, "bytes_moved": {}}
    tracer.enable(ring=1 << 12)
    try:
        out = os.path.join(Cache().path("work"), "roofline-job")
        os.makedirs(out, exist_ok=True)
        result = job.run(out)
        info, w = job.info(), job._walk()
    finally:
        tracer.disable()
        tracer.clear()
    assert info["programs"] == {"tri_wedges": names.TRI_WEDGES}
    ntri = int(result["messages"][0].split()[1])
    assert w["triangles"] == ntri and w["batches"] >= 1
    assert info["bytes_moved"]["tri_wedges"] == kernels_tri.wedge_bytes(
        w["wedges"], w["batches"], w["edges"], ntri)


def test_cell_untraced_reports_edge_rate(cpu_harness):
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=3, seconds=0.5,
                                trace=False, t_process=0.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"job_s", "edge_rate", "setup_s"}


# -- a wrong result makes ``correct`` false ---------------------------------------

def _wrong_triangles(change):
    real = graph_tri.mr_rows

    def rows(mr):
        keys, values = real(mr)
        if keys.ndim == 2 and keys.shape[1] == 3:
            keys = change(keys)
        return keys, values
    return rows


@pytest.mark.parametrize("change", [
    lambda k: k[1:],                                    # one dropped
    lambda k: np.concatenate([k, [[1, 2, 3]]]).astype(k.dtype),  # one added
    lambda k: np.concatenate([k, k[:1]]),               # one twice
], ids=["dropped", "added", "duplicated"])
def test_a_wrong_triangle_set_makes_correct_false(cpu_harness, monkeypatch,
                                                  capsys, change):
    monkeypatch.setattr(graph_tri, "mr_rows", _wrong_triangles(change))
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                                trace=False, t_process=0.0)
    assert line["correct"] is False
    assert "tri_find:" in capsys.readouterr().out


def _rewrite(monkeypatch, name, change):
    """Let the job write ``name`` and change the file before the check."""
    from benchmark.jobs import graph_tri as job_module
    real = job_module.Job.check

    def check(self, result, outdir):
        path = os.path.join(outdir, name)
        with open(path) as f:
            lines = f.read().splitlines()
        with open(path, "w") as f:
            f.write("\n".join(change(lines, self)) + "\n")
        return real(self, result, outdir)

    monkeypatch.setattr(job_module.Job, "check", check)


def _drop_one(lines, job):
    return lines[1:]


def _add_a_neighbour(lines, job):
    """A vertex outside the set that is adjacent to one inside it."""
    from benchmark.refs import graph
    e = graph.mr_edges(job.shared.obj.get_mr("mru"))
    inside = {int(v) for v in lines}
    extra = next(int(b) for a, b in e.tolist()
                 if int(a) in inside and int(b) not in inside)
    return lines + [str(extra)]


@pytest.mark.parametrize("change", [_drop_one, _add_a_neighbour],
                         ids=["one-removed", "one-adjacent-pair"])
def test_a_wrong_independent_set_makes_correct_false(
        cpu_harness, monkeypatch, capsys, change):
    _rewrite(monkeypatch, "mis", change)
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                                trace=False, t_process=0.0)
    assert line["correct"] is False
    assert "luby_find:" in capsys.readouterr().out


def _live(outdir):
    """The ``sssp`` output file of a source that reaches other vertices."""
    for name in sorted(os.listdir(outdir)):
        if name.startswith("sssp"):
            with open(os.path.join(outdir, name)) as f:
                if any(ln.split()[1] not in ("0", "inf") for ln in f):
                    return name
    raise AssertionError("no source reached another vertex")


def _one_more_hop(lines, job):
    at = next(i for i, ln in enumerate(lines)
              if ln.split()[1] not in ("0", "inf"))
    v, d, p = lines[at].split()
    lines[at] = f"{v} {float(d) + 1:g} {p}"
    return lines


def _another_pred(lines, job):
    """A reached vertex's pred replaced by a vertex with no edge to it."""
    from benchmark.refs import graph
    e = graph.mr_edges(job.shared.obj.get_mr("mre")).tolist()
    into = {}
    for a, b in e:
        into.setdefault(int(b), set()).add(int(a))
    at = next(i for i, ln in enumerate(lines)
              if ln.split()[1] not in ("0", "inf"))
    v, d, _p = lines[at].split()
    other = next(int(ln.split()[0]) for ln in lines
                 if int(ln.split()[0]) not in into[int(v)])
    lines[at] = f"{v} {d} {other}"
    return lines


@pytest.mark.parametrize("change, said", [
    (_one_more_hop, "distances differ"),
    (_another_pred, "a pred is not an in-neighbour"),
], ids=["one-more-hop", "pred-no-neighbour"])
def test_a_wrong_distance_makes_correct_false(cpu_harness, monkeypatch,
                                              capsys, change, said):
    from benchmark.jobs import graph_tri as job_module
    real = job_module.Job.check

    def check(self, result, outdir):
        path = os.path.join(outdir, _live(outdir))
        with open(path) as f:
            lines = f.read().splitlines()
        with open(path, "w") as f:
            f.write("\n".join(change(lines, self)) + "\n")
        return real(self, result, outdir)

    monkeypatch.setattr(job_module.Job, "check", check)
    line = cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                                trace=False, t_process=0.0)
    assert line["correct"] is False
    assert said in capsys.readouterr().out


def test_sources_that_reach_nothing_are_refused(cpu_harness, capsys):
    """A job whose loop relaxes no edge equals the reference whatever the
    loop does (the first reading of this cell: REVIEW of PR 32), so the
    check refuses the traffic.  ``DEAD_SEED``'s first source here has no
    out-edge."""
    cell = tiny_cell(CELL)
    cell.config.update(sssp_ncnt=1, sssp_seed=DEAD_SEED)
    line = cpu_harness.run_cell(cell, seed=5, seconds=0.2, trace=False,
                                t_process=0.0)
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert "reaches half" in out and "(labeled [1])" in out


def test_a_wrong_weight_or_another_source_is_refused(cpu_harness, monkeypatch,
                                                     capsys):
    """The reference takes neither its edges, its weights nor its sources
    from the program: ``mrw`` with one weight of 2 fails in set-up."""
    real = graph_tri.mr_rows

    def rows(mr):
        keys, values = real(mr)
        if values.ndim == 1 and len(values) and values.dtype.kind == "f":
            values = values.copy()
            values[0] = 2.0
        return keys, values

    monkeypatch.setattr(graph_tri, "mr_rows", rows)
    with pytest.raises(CheckFailure, match="a weight is not 1.0"):
        cpu_harness.run_cell(tiny_cell(CELL), seed=5, seconds=0.2,
                             trace=False, t_process=0.0)


def test_a_tree_without_the_wedge_program_is_refused_in_prepare(monkeypatch):
    from benchmark.jobs import graph_tri as job_module
    from gpu_mapreduce_tpu.obs import names
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    import jax
    monkeypatch.delattr(names, "TRI_WEDGES")
    cell = tiny_cell(CELL)
    job = job_module.Job(cell.config, cell.traffic,
                         make_mesh(devices=jax.devices()[:1]), 1, None)
    with pytest.raises(CheckFailure, match="no device wedge walk"):
        job.prepare()
