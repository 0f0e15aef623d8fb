"""OINK command suite vs dict/numpy oracles — the reference's
printed-invariant test style (SURVEY.md §4) made into real assertions."""

import collections

import numpy as np
import pytest

from gpu_mapreduce_tpu.models.rmat import generate_unique
from gpu_mapreduce_tpu.oink import ObjectManager, run_command
from gpu_mapreduce_tpu.oink.command import COMMANDS


@pytest.fixture
def edge_file(tmp_path, rng):
    """Random directed multigraph file; returns (path, edges array)."""
    e = rng.integers(0, 30, size=(300, 2)).astype(np.uint64)
    path = tmp_path / "edges.txt"
    path.write_text("\n".join(f"{a} {b}" for a, b in e) + "\n")
    return str(path), e


def test_registry_has_core_commands():
    for name in ("rmat", "rmat2", "degree", "degree_stats", "degree_weight",
                 "histo", "edge_upper", "vertex_extract", "neighbor",
                 "wordfreq"):
        assert name in COMMANDS, name


def test_rmat_generates_exact_unique_count(tmp_path):
    out = tmp_path / "rmat.out"
    cmd = run_command("rmat", ["6", "4", ".25", ".25", ".25", ".25", "0", "42"],
                      outputs=[str(out)], screen=False)
    assert cmd.nunique == (1 << 6) * 4
    edges = np.loadtxt(out, dtype=np.uint64).reshape(-1, 2)
    assert len(edges) == 256
    assert len(np.unique(edges, axis=0)) == 256        # truly unique
    assert edges.max() < 64                            # within 2^N vertices


def test_rmat2_matches_rmat_count(tmp_path):
    out = tmp_path / "rmat2.out"
    cmd = run_command("rmat2", ["5", "2", ".45", ".25", ".15", ".15", "0", "1"],
                      outputs=[str(out)], screen=False)
    edges = np.loadtxt(out, dtype=np.uint64).reshape(-1, 2)
    assert len(edges) == (1 << 5) * 2
    assert len(np.unique(edges, axis=0)) == len(edges)


def test_rmat_noisy_fraction_runs():
    cmd = run_command("rmat", ["5", "2", ".3", ".3", ".2", ".2", ".5", "9"],
                      screen=False)
    assert cmd.nunique == 64


def test_degree_both_endpoints(edge_file, tmp_path):
    path, e = edge_file
    out = tmp_path / "deg.out"
    cmd = run_command("degree", ["0"], inputs=[path],
                      outputs=[str(out)], screen=False)
    oracle = collections.Counter(np.concatenate([e[:, 0], e[:, 1]]).tolist())
    got = {int(a): int(b) for a, b in np.loadtxt(out, dtype=np.int64)}
    assert got == dict(oracle)
    assert cmd.nvert == len(oracle) and cmd.nedge == len(e)


def test_degree_duplicate_flag(edge_file, tmp_path):
    path, e = edge_file
    out = tmp_path / "deg1.out"
    run_command("degree", ["1"], inputs=[path], outputs=[str(out)],
                screen=False)
    oracle = collections.Counter(e[:, 0].tolist())
    got = {int(a): int(b) for a, b in np.loadtxt(out, dtype=np.int64)}
    assert got == dict(oracle)


def test_degree_stats_histogram(edge_file):
    path, e = edge_file
    cmd = run_command("degree_stats", ["0"], inputs=[path], screen=False)
    deg = collections.Counter(np.concatenate([e[:, 0], e[:, 1]]).tolist())
    hist = collections.Counter(deg.values())
    assert dict(cmd.stats) == dict(hist)
    # sorted descending by degree
    degrees = [d for d, _ in cmd.stats]
    assert degrees == sorted(degrees, reverse=True)


def test_edge_upper(edge_file, tmp_path):
    path, e = edge_file
    out = tmp_path / "upper.out"
    cmd = run_command("edge_upper", [], inputs=[path], outputs=[str(out)],
                      screen=False)
    nonself = e[e[:, 0] != e[:, 1]]
    canon = np.stack([np.minimum(nonself[:, 0], nonself[:, 1]),
                      np.maximum(nonself[:, 0], nonself[:, 1])], 1)
    want = np.unique(canon, axis=0)
    got = np.loadtxt(out, dtype=np.uint64).reshape(-1, 2)
    got = got[np.lexsort((got[:, 1], got[:, 0]))]
    np.testing.assert_array_equal(got, want)
    assert cmd.nunique == len(want)


def test_vertex_extract(tmp_path, rng):
    e = rng.integers(0, 20, size=(100, 2)).astype(np.uint64)
    w = rng.random(100)
    path = tmp_path / "ew.txt"
    path.write_text("\n".join(f"{a} {b} {x:.6f}" for (a, b), x in zip(e, w)))
    out = tmp_path / "verts.out"
    cmd = run_command("vertex_extract", [], inputs=[str(path)],
                      outputs=[str(out)], screen=False)
    want = sorted(set(np.concatenate([e[:, 0], e[:, 1]]).tolist()))
    got = sorted(np.loadtxt(out, dtype=np.uint64).tolist())
    assert got == want and cmd.nvert == len(want)


def test_neighbor_adjacency(edge_file, tmp_path):
    path, e = edge_file
    out = tmp_path / "neigh.out"
    run_command("neighbor", [], inputs=[path], outputs=[str(out)],
                screen=False)
    adj = collections.defaultdict(list)
    for a, b in e.tolist():
        adj[a].append(b)
        adj[b].append(a)
    got = {}
    for line in out.read_text().splitlines():
        toks = [int(t) for t in line.split()]
        got[toks[0]] = sorted(toks[1:])
    assert got == {k: sorted(v) for k, v in adj.items()}


def test_histo_on_named_mr(tmp_path, rng):
    keys = rng.integers(0, 10, 500).astype(np.uint64)
    obj = ObjectManager()
    mr = obj.create_mr()
    mr.map(1, lambda i, kv, p: kv.add_batch(
        keys, np.zeros(len(keys), np.uint8)))
    obj.name_mr("mine", mr)
    out = tmp_path / "histo.out"
    cmd = run_command("histo", [], obj=obj, inputs=["mine"],
                      outputs=[str(out)], screen=False)
    oracle = collections.Counter(keys.tolist())
    got = {int(a): int(b) for a, b in np.loadtxt(out, dtype=np.int64)}
    assert got == dict(oracle)
    assert dict(cmd.stats) == dict(collections.Counter(oracle.values()))


def test_degree_weight(edge_file, tmp_path):
    path, e = edge_file
    # degree file from the degree command (dupflag 0)
    degf = tmp_path / "deg.out"
    run_command("degree", ["0"], inputs=[path], outputs=[str(degf)],
                screen=False)
    out = tmp_path / "ewt.out"
    cmd = run_command("degree_weight", [], inputs=[path, str(degf)],
                      outputs=[str(out)], screen=False)
    deg = collections.Counter(np.concatenate([e[:, 0], e[:, 1]]).tolist())
    lines = out.read_text().splitlines()
    # one output edge per input edge occurrence (duplicates kept, like the
    # reference's per-neighbor emit); weights must equal 1/degree(vi)
    assert cmd.nedge == len(lines) == len(e)
    got_edges = collections.Counter()
    for line in lines:
        a, b, w = line.split()
        assert float(w) == pytest.approx(1.0 / deg[int(a)])
        got_edges[(int(a), int(b))] += 1
    want_edges = collections.Counter((int(a), int(b)) for a, b in e.tolist())
    assert got_edges == want_edges


def test_wordfreq_command(tmp_path):
    words = ("apple banana apple cherry banana apple "
             "date cherry apple banana").split()
    f = tmp_path / "words.txt"
    f.write_text(" ".join(words))
    out = tmp_path / "wc.out"
    cmd = run_command("wordfreq", ["3"], inputs=[str(f)],
                      outputs=[str(out)], screen=False)
    oracle = collections.Counter(words)
    got = dict(line.split() for line in out.read_text().splitlines())
    assert {k: int(v) for k, v in got.items()} == dict(oracle)
    assert cmd.nwords == len(words) and cmd.nunique == 4
    assert cmd.top[0] == (b"apple", 4)
    counts = [c for _, c in cmd.top]
    assert counts == sorted(counts, reverse=True)


def test_degree_on_mesh_backend(edge_file, tmp_path):
    """Commands run unchanged on the mesh backend (ShardedKMV reduces)."""
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    path, e = edge_file
    out = tmp_path / "deg_mesh.out"
    obj = ObjectManager(comm=make_mesh(4))
    cmd = run_command("degree", ["0"], obj=obj, inputs=[path],
                      outputs=[str(out)], screen=False)
    oracle = collections.Counter(np.concatenate([e[:, 0], e[:, 1]]).tolist())
    # r4: per-shard output files on the P=4 mesh; union == oracle
    shard_files = sorted(tmp_path.glob("deg_mesh.out.*"))
    assert len(shard_files) == 4
    rows = np.concatenate([np.loadtxt(f, dtype=np.int64).reshape(-1, 2)
                           for f in shard_files if f.stat().st_size])
    got = {int(a): int(b) for a, b in rows}
    assert got == dict(oracle)
    assert cmd.nvert == len(oracle)


def test_run_command_cleans_up_after_error(edge_file, tmp_path):
    """A failed command must not leak descriptors into the next run."""
    from gpu_mapreduce_tpu.core.runtime import MRError
    path, e = edge_file
    obj = ObjectManager()
    with pytest.raises((MRError, FileNotFoundError)):
        run_command("degree", ["0"], obj=obj, inputs=["/nonexistent/file"],
                    screen=False)
    assert obj.inputs == [] and obj.outputs == []
    out = tmp_path / "deg2.out"
    cmd = run_command("degree", ["0"], obj=obj, inputs=[path],
                      outputs=[str(out)], screen=False)
    assert cmd.nedge == len(e)


def test_generate_unique_helper():
    edges, niter = generate_unique(3, 5, 2)
    assert len(edges) == (1 << 5) * 2
    assert len(np.unique(edges, axis=0)) == len(edges)
    # deterministic under the same seed
    edges2, _ = generate_unique(3, 5, 2)
    np.testing.assert_array_equal(edges, edges2)


# ---------------------------------------------------------------------------
# Object.output: blocks where the printer declares its line and the columns
# are of its kinds, a printer call a row everywhere else

def _output_spans(traced, run) -> list:
    return [e["args"] for e in traced(run)[1] if e["name"] == "oink.output"]


@pytest.mark.parametrize("command,params", [
    ("degree", ["0"]), ("edge_upper", []), ("vertex_extract", [])])
def test_mesh_shard_files_from_columns_are_the_printers_bytes(
        edge_file, tmp_path, monkeypatch, traced, command, params):
    """On a mesh of four every shard's path.<p> is formatted from its own
    block (a shard after another) and holds what the per-row loop writes."""
    from gpu_mapreduce_tpu.oink import objects
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    path, _e = edge_file

    def run(out):
        run_command(command, params, obj=ObjectManager(comm=make_mesh(4)),
                    inputs=[path], outputs=[str(out)], screen=False)

    (sp,) = _output_spans(traced, lambda: run(tmp_path / "block"))
    monkeypatch.setattr(objects, "_block_columns", lambda printer, fr: None)
    (rowsp,) = _output_spans(traced, lambda: run(tmp_path / "rows"))
    for p in range(4):
        got = (tmp_path / f"block.{p}").read_bytes()
        assert got == (tmp_path / f"rows.{p}").read_bytes()
    assert sp["block_rows"] == sp["rows"] > 0
    assert rowsp["block_rows"] == 0 and rowsp["rows"] == sp["rows"]
    assert sp["bytes"] == rowsp["bytes"] > 0


def test_byte_keys_groups_and_plain_callbacks_print_a_row_at_a_time(
        edge_file, tmp_path, traced):
    path, e = edge_file
    words = tmp_path / "words.txt"
    words.write_text("pear fig pear plum fig pear\n")
    obj = ObjectManager()
    mr = obj.create_mr()
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.arange(5, dtype=np.uint64), np.linspace(0.0, 1.0, 5)))
    obj.add_output(path=str(tmp_path / "cb.out"))

    spans = _output_spans(traced, lambda: [
        run_command("wordfreq", ["2"], inputs=[str(words)],
                    outputs=[str(tmp_path / "wf.out")], screen=False),
        run_command("neighbor", [], inputs=[path],
                    outputs=[str(tmp_path / "nb.out")], screen=False),
        obj.output(1, mr, lambda k, v, fp: fp.write(f"{k}:{v}\n")),
        obj.output(1, mr)])
    assert len(spans) == 4
    assert all(sp["rows"] > 0 and (sp["block_rows"], sp["native"]) == (0, 0)
               for sp in spans)
    assert sorted((tmp_path / "wf.out").read_text().split("\n")[:-1]) \
        == ["fig 2", "pear 3", "plum 1"]
    assert (tmp_path / "cb.out").read_text() \
        == "0 0.0\n1 0.25\n2 0.5\n3 0.75\n4 1.0\n"


def test_frames_of_both_kinds_share_one_file(tmp_path, traced):
    """The decision is a frame's: integer rows leave as a block, float
    rows beside them by their repr, in the dataset's order."""
    from gpu_mapreduce_tpu.oink.kernels import print_vertex_value
    obj = ObjectManager()
    mr = obj.create_mr()
    k = np.arange(3, dtype=np.uint64)
    mr.map(1, lambda i, kv, p: kv.add_batch(k, np.array([0.5, 1.5, 2.0])))
    mr.map(1, lambda i, kv, p: kv.add_batch(k + 10, k * 7), addflag=1)
    mr.map(1, lambda i, kv, p: kv.add_batch(k + 20, np.array([1e-7] * 3)),
           addflag=1)
    if mr.kv.nframes != 3:
        pytest.skip("the maps' batches were merged into one frame")
    obj.add_output(path=str(tmp_path / "mixed.out"))
    (sp,) = _output_spans(traced, lambda: obj.output(1, mr, print_vertex_value))
    assert (sp["rows"], sp["block_rows"]) == (9, 3)
    assert (tmp_path / "mixed.out").read_text() == (
        "0 0.5\n1 1.5\n2 2.0\n10 0\n11 7\n12 14\n"
        "20 1e-07\n21 1e-07\n22 1e-07\n")
