"""What the measurement reads of the program (ISSUE 24): every jitted
program on a benchmark cell's path lowers under the name ``obs/names.py``
declares for it and no two share one; the host-phase and engine spans are
emitted under the parents the span model gives them, with ``rounds`` /
``iters`` equal to the numbers in the commands' messages; a run with the
tracer off constructs no ``Span`` and gives the same results; the
``jax.named_scope``s inside the programs are metadata only."""

import contextlib
import io
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpu_mapreduce_tpu.obs import get_tracer, names
from gpu_mapreduce_tpu.obs import tracer as tracer_mod

SDS = jax.ShapeDtypeStruct


@pytest.fixture(scope="module")
def mesh():
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    return make_mesh()


def _programs(mesh):
    """(declared name, lowered program) of every named program, at tiny
    shapes on the 8-device CPU mesh."""
    from gpu_mapreduce_tpu.apps import invertedindex as app, terasort
    from gpu_mapreduce_tpu.models import cc, luby, pagerank, rmat, sssp, tri
    from gpu_mapreduce_tpu.parallel import (devkernels, group, sharded,
                                            shuffle, staging)
    u64, i32, u32 = jnp.uint64, jnp.int32, jnp.uint32
    key, val = SDS((64, 2), u64), SDS((64,), u64)
    cnt, cnt2 = SDS((8,), i32), SDS((64,), i32)
    col, small = SDS((64,), u64), SDS((8,), u64)
    edges = (SDS((64,), i32), SDS((64,), i32), SDS((64,), jnp.bool_))
    return [
        (names.INVINDEX_EXTRACT,
         app._extract_mesh_fn(mesh, 8, False, False, False).lower(
             SDS((8 * 64,), u32), SDS((8,), i32), SDS((8,), u32))),
        (names.INVINDEX_COLLISIONS, app._collision_count_jit.lower(
            (col,), (col,), (SDS((64,), jnp.bool_),))),
        (names.CONVERT_SORT,
         group._convert_phase1_jit(mesh).lower(key, val, cnt)),
        (names.CONVERT_LAYOUT, group._convert_phase2_jit(mesh, 8).lower(
            key, SDS((64,), jnp.bool_), cnt)),
        (names.REDUCE_SEGMENTS, group._reduce_jit(mesh, 8, "sum", None).lower(
            col, cnt2, cnt2, val, cnt)),
        (names.GROUP_FIRST, group._first_jit(mesh).lower(col, cnt2, val)),
        (names.SORT_MULTIVALUES, group._sortmv_jit(mesh, False).lower(
            cnt2, cnt2, val, cnt)),
        (names.SORT_ROWS,
         group._sort_jit(mesh, "key", False).lower(col, val, cnt)),
        (names.SORT_INTERNED,
         group._sort_interned_jit(mesh, 64, "key", False).lower(
             col, val, cnt, small, SDS((8,), jnp.int64))),
        (names.SHUFFLE_PHASE1,
         shuffle._phase1_jit(mesh, ("hash", None), False).lower(
             key, val, cnt)),
        (names.SHUFFLE_PHASE2,
         shuffle._phase2_jit(mesh, 8, 1, 8).lower(key, val, cnt2)),
        (names.SHUFFLE_PHASE2_WIRE,
         shuffle._phase2_wire_jit(mesh, (8,), 8, None, None).lower(
             key, val, cnt2, SDS((64, 4), u64))),
        (names.STAGE_RANK_GRAPH,
         staging._rank_fn(mesh, 64, False).lower(key, cnt)),
        (names.STAGE_TRIM_VERTS, staging._trim_fn(mesh, 8).lower(col)),
        (names.PLACE_ROWS, sharded._place_rows_jit(mesh, 8).lower(
            key, val, cnt, cnt)),
        (names.CONCAT_ROWS, devkernels._concat_jit(mesh, 8).lower(
            key, val, cnt, key, val, cnt)),
        (names.LEVEL_ROWS, devkernels._level_jit(mesh, 8, 8, 8).lower(
            key, val, cnt, cnt, SDS((8, 8), i32), SDS((8, 8), i32))),
        (names.REMAP_IDS,
         devkernels._remap_ids_jit(mesh, 8).lower(col, small, small)),
        (names.KV_MAP_PREFIX + "edge_upper", devkernels._skv_map_jit(
            mesh, devkernels.edge_upper_dev, (), 0).lower(key, val, cnt)),
        (names.CC_LOOP, cc._cc_sharded_fn(mesh, 16, 16).lower(*edges)),
        (names.PAGERANK_LOOP,
         pagerank._sharded_run_fn(mesh, 16, 1e-6, 10, 0.85).lower(*edges)),
        (names.RMAT_EDGES, rmat.rmat_edges.lower(
            jax.random.PRNGKey(0), 64, 4,
            np.asarray([0.57, 0.19, 0.19, 0.05]), 0.0, noisy=False)),
        (names.RMAT_EDGE_ROWS, rmat.rmat_edge_rows.lower(col, col)),
        (names.TRI_ORIENT, tri._programs(mesh).orient.lower(
            *edges, small, canonical=False, by_id=True, block=8, tiled=16)),
        (names.TRI_TILES, tri._programs(mesh).tiles.lower(
            cnt2, SDS((64,), jnp.int64), SDS((), jnp.int64), cap=8, block=8)),
        # an index batch; a tile batch is the same function, held to the
        # same name below
        (names.TRI_WEDGES, tri._programs(mesh).wedges.lower(
            col, cnt2, (cnt2, SDS((64,), jnp.int64)), SDS((), jnp.int64),
            SDS((), jnp.int64), batch=64, block=0)),
        (names.TRI_APPEND, tri._programs(mesh).append.lower(
            SDS((128,), u64), SDS((128,), i32), col, cnt2, SDS((), i32))),
        (names.TRI_GROW, tri._programs(mesh).grow.lower(col, cnt2)),
        (names.TRI_ROWS, tri._programs(mesh).rows.lower(
            col, cnt2, small, rows=16, by_id=False)),
        (names.LUBY_LOOP, luby._luby_sharded_fn(mesh, 16, 16).lower(
            *edges, SDS((16,), i32))),
        (names.SSSP_LOOP, sssp._bf_sharded_fn(mesh, 16, 16).lower(
            edges[0], edges[1], SDS((64,), jnp.float64), edges[2],
            SDS((), i32))),
        (names.SSSP_WEIGHTS, sssp.sssp_weights.lower(
            SDS((64,), jnp.float64), edges[2])),
        (names.TERASORT_SAMPLE_KEYS, terasort._sample_jit(mesh, 4).lower(
            SDS((64, 3), u32), cnt, cnt)),
        (names.TERASORT_JOIN_RECORDS,
         terasort._join_jit(100, 10, 4).lower(
             SDS((64, 3), u32), SDS((64, 23), u32), SDS((), i32))),
        (names.JOIN_ROWS, group._join_jit(mesh).lower(
            SDS((64, 2), u32), cnt, SDS((64, 2), u32), cnt)),
        (names.JOIN_TAKE, group._join_take_jit(mesh, 4).lower(
            SDS((128,), i32), SDS((128, 2), i32),
            SDS((64, 2), u32), SDS((64, 4), u32), SDS((64, 1), u32))),
        (names.TAKE_ROWS, devkernels._take_rows_jit(mesh, 4).lower(
            key, val, cnt2)),
        (names.COMBINE, group._combine_jit(mesh, "sum").lower(
            key, SDS((64, 3), jnp.int64), cnt)),
    ]


def test_every_program_lowers_under_its_declared_name(mesh):
    from gpu_mapreduce_tpu.models import tri
    seen = set()
    for want, lowered in _programs(mesh):
        got = re.search(r"module @(\w+)", lowered.as_text()).group(1)
        assert got == want, (want, got)
        assert names.declared_program(got)
        assert got not in seen, f"{got} names two programs"
        seen.add(got)
        if want == names.CONVERT_LAYOUT:
            # the chip's rule (PERF.md §6, PRs 25 and 29): a scatter costs
            # 30 sorts there and looks cheap here, so hold the program to it
            ops = set(re.findall(r"stablehlo\.(\w+)", lowered.as_text()))
            assert "sort" in ops and not ops & {"scatter", "gather"}, ops
        if want == names.SHUFFLE_PHASE1:
            # the same rule a fourth time (PR 33): the rows ride one sort
            # by destination, counts are masked sums; no ``while`` either
            ops = set(re.findall(r"stablehlo\.(\w+)", lowered.as_text()))
            assert "sort" in ops and not ops & {
                "scatter", "gather", "while"}, ops
        if want == names.SORT_ROWS:
            # and a fifth (PR 36): the rows ride ONE sort through
            # ops/sort.sort_carrying; descending is complemented words,
            # not a reversal by scatter
            from gpu_mapreduce_tpu.parallel import group
            for descending in (False, True):
                text = group._sort_jit(mesh, "value", descending).lower(
                    SDS((64,), jnp.uint64), SDS((64,), jnp.uint64),
                    SDS((8,), jnp.int32)).as_text()
                ops = re.findall(r"stablehlo\.(\w+)", text)
                assert ops.count("sort") == 1 and not set(ops) & {
                    "scatter", "gather", "while"}, ops
        if want == names.JOIN_ROWS:
            # and for the join (PR 43): two sorts that carry nothing, the
            # values taken afterwards; tests/test_join.py pins the counts
            ops = re.findall(r"stablehlo\.(\w+)", lowered.as_text())
            assert ops.count("sort") == 2 and not set(ops) & {
                "scatter", "gather", "while"}, ops
        if want == names.COMBINE:
            # and for the combiner (ISSUE 50): the rows are read where they
            # lie; nothing orders, gathers or scatters them
            ops = set(re.findall(r"stablehlo\.(\w+)", lowered.as_text()))
            assert "while" in ops and not ops & {
                "sort", "scatter", "gather"}, ops
        if want == names.TERASORT_JOIN_RECORDS:
            # and for the part writer's join (ISSUE 51): a window cut at a
            # traced offset and word arithmetic over it, on one shard's own
            # block (no mesh, no collective); the sorted rows are not
            # ordered or moved again
            ops = set(re.findall(r"stablehlo\.(\w+)", lowered.as_text()))
            assert "dynamic_slice" in ops and not ops & {
                "sort", "scatter", "gather", "while"}, ops
            assert "sdy.manual_computation" not in lowered.as_text()
        if want in (names.TRI_ORIENT, names.TRI_TILES, names.TRI_WEDGES):
            # the same rule for the wedge walk: sorts, no scatter, and no
            # ``while`` (a searchsorted is a gather a round)
            texts = [lowered.as_text()]
            if want == names.TRI_WEDGES:
                # both kinds of batch run under the one name the metrics
                # sum (ISSUE 41): the tile batch too
                tile = SDS((8,), jnp.int32)
                texts.append(tri._programs(mesh).wedges.lower(
                    SDS((64,), jnp.uint64), tile, (tile,) * 4,
                    SDS((), jnp.int64), SDS((), jnp.int64), batch=128,
                    block=8).as_text())
                assert re.search(r"module @(\w+)", texts[1]).group(1) == want
            for text in texts:
                ops = set(re.findall(r"stablehlo\.(\w+)", text))
                assert "sort" in ops and not ops & {"scatter", "while"}, ops
    assert set(names.PROGRAMS) <= seen
    assert len(set(names.PROGRAMS)) == len(names.PROGRAMS)
    assert len(set(names.SPANS)) == len(names.SPANS)
    for old in ("jit_run", "jit_body", "jit_phase1", "jit_phase2"):
        assert not names.declared_program(old)


def _collision_checks(mesh, rows_per_shard=8):
    """One round of (ids, alts, counts) as the mesh map stage hands them
    to the global check: columns sharded by row, counts on the host."""
    from gpu_mapreduce_tpu.parallel.mesh import mesh_axis_size, row_sharding
    P = mesh_axis_size(mesh)
    ids = jax.device_put(jnp.arange(P * rows_per_shard, dtype=jnp.uint64),
                         row_sharding(mesh))
    return ((ids, ids, np.full(P, 2, np.int32)),)


def test_the_collision_count_program_has_its_name(mesh):
    """Hoisted out of its caller (PR 26): the program the caller
    dispatches is the module-level function, lowered here at the caller's
    own arguments."""
    from gpu_mapreduce_tpu.apps import invertedindex as app
    assert app._collision_count_jit.__wrapped__ \
        is app.invindex_collision_count
    (ids, alts, _counts), = _collision_checks(mesh)
    valid = SDS(ids.shape, jnp.bool_)
    text = app._collision_count_jit.lower((ids,), (alts,),
                                          (valid,)).as_text()
    assert re.search(r"module @(\w+)", text).group(1) \
        == names.INVINDEX_COLLISIONS
    assert app._mesh_collision_count(_collision_checks(mesh)) == 0


def test_the_collision_count_program_is_built_once(mesh, monkeypatch):
    """Two jobs at one shape trace the program once: the second call
    dispatches the first one's executable (it was jitted inside its caller,
    a new function object and a new trace per job)."""
    from gpu_mapreduce_tpu.apps import invertedindex as app
    traced = []
    real = app._count_collisions

    def counting(*a):
        traced.append(a[0].shape)
        return real(*a)

    monkeypatch.setattr(app, "_count_collisions", counting)
    app._collision_count_jit.clear_cache()
    for _job in range(2):
        assert app._mesh_collision_count(_collision_checks(mesh, 24)) == 0
    assert len(traced) == 1
    # another shape (a second round) is another program
    two = _collision_checks(mesh, 24) + _collision_checks(mesh, 24)
    assert app._mesh_collision_count(two) == 0
    assert len(traced) == 2


# -- spans ---------------------------------------------------------------------

def _graph_script(mesh, out):
    from gpu_mapreduce_tpu.oink.script import OinkScript
    s = OinkScript(comm=mesh, screen=io.StringIO())
    for line in (
            "rmat 7 8 0.57 0.19 0.19 0.05 0.0 1 -o NULL mre",
            f"edge_upper -i mre -o {out}/upper mru",
            # a FILE input: the parser runs under oink.input
            f"cc_find 0 -i {out}/upper.* -o {out}/cc NULL",
            f"pagerank 1e-6 100 0.85 -i mre -o {out}/pr NULL"):
        s.run_string(line)
    files = {}
    for fn in sorted(os.listdir(out)):
        with open(os.path.join(out, fn), "rb") as f:
            files[fn] = f.read()
    return s.screen.getvalue(), files


def _enum_script(mesh, out):
    """The rest of the graph suite (ISSUE 32): triangles, Luby's set and
    shortest paths, the weighted twin made as ``in.sssp`` makes it."""
    from gpu_mapreduce_tpu.oink.script import OinkScript
    s = OinkScript(comm=mesh, screen=io.StringIO())
    for line in (
            "rmat 7 8 0.57 0.19 0.19 0.05 0.0 1 -o NULL mre",
            "edge_upper -i mre -o NULL mru",
            "mr mrw",
            "mrw map/mr mre add_weight",
            f"tri_find -i mru -o {out}/tri mrt",
            f"luby_find 6789 -i mru -o {out}/mis NULL",
            f"sssp 1 12345 -i mrw -o {out}/sssp NULL"):
        s.run_string(line)
    files = {}
    for fn in sorted(os.listdir(out)):
        with open(os.path.join(out, fn), "rb") as f:
            files[fn] = f.read()
    return s, s.screen.getvalue(), files


def _host_batch(mesh):
    """A user map callback that adds a HOST batch to a mesh MR, then
    ``aggregate``: the one way left onto ``aggregate.intern`` and
    ``aggregate.shard`` (rmat's rows stay on the mesh since PR 27)."""
    from gpu_mapreduce_tpu import MapReduce
    mr = MapReduce(mesh)
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.arange(64, dtype=np.uint64), np.zeros(64, np.uint8)))
    return mr.aggregate()


def _invindex(mesh, corpus, out):
    from gpu_mapreduce_tpu.apps.invertedindex import InvertedIndex
    idx = InvertedIndex(comm=mesh)
    counts = idx.run(corpus, outdir=out)
    parts = {}
    for fn in sorted(os.listdir(out)):
        with open(os.path.join(out, fn), "rb") as f:
            parts[fn] = f.read()
    return counts, parts, idx


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    d = tmp_path_factory.mktemp("corpus")
    paths = []
    for i in range(4):
        p = d / f"part-{i}.html"
        p.write_text("".join(
            f'<p>text {j} <a href="http://site{(i * 7 + j) % 13}.org/'
            f'page{j % 5}">x</a> filler filler filler</p>\n'
            for j in range(40)))
        paths.append(str(p))
    return paths


@pytest.fixture
def traced():
    tr = get_tracer()
    was = tr.enabled
    tr.enable(ring=1 << 16)
    tr.clear()
    yield tr
    tr.clear()
    if not was:
        tr.disable()


def _tree(events):
    by_id = {e["id"]: e for e in events}

    def parent(e):
        p = by_id.get(e["parent"])
        return p["name"] if p else None

    return [(e["name"], e["cat"], parent(e), e["args"]) for e in events]


def _attrs(tree):
    """name -> [attrs of each span of that name, in order]."""
    out = {}
    for name, _cat, _parent, a in tree:
        out.setdefault(name, []).append(a)
    return out


def _where(tree):
    """name -> {(category, parent's name)} over a run's spans."""
    out = {}
    for name, cat, parent, _a in tree:
        out.setdefault(name, set()).add((cat, parent))
    return out


def test_graph_commands_emit_the_host_and_engine_spans(mesh, traced,
                                                       tmp_path):
    screen, _files = _graph_script(mesh, str(tmp_path))
    assert _host_batch(mesh) == 64
    tree = _tree(traced.events())
    parents = _where(tree)
    H, E = names.HOST, names.ENGINE
    want = {
        names.AGGREGATE_ONE_FRAME: {(H, "aggregate")},
        names.AGGREGATE_INTERN: {(H, "aggregate")},
        names.AGGREGATE_SHARD: {(H, "aggregate")},
        names.CONVERT_COUNT_SYNC: {(H, "convert")},
        names.RMAT_GENERATE: {(H, "oink.rmat")},
        names.OINK_INPUT: {(H, "oink.cc_find")},
        names.OINK_OUTPUT: {(H, "oink.edge_upper"), (H, "oink.cc_find"),
                            (H, "oink.pagerank")},
        names.CC_STAGE: {(H, "oink.cc_find")},
        names.CC_ENGINE: {(E, "oink.cc_find")},
        names.CC_EMIT: {(H, "oink.cc_find")},
        names.PAGERANK_STAGE: {(H, "oink.pagerank")},
        names.PAGERANK_ENGINE: {(E, "oink.pagerank")},
        names.PAGERANK_EMIT: {(H, "oink.pagerank")},
    }
    for name, where in want.items():
        assert parents.get(name) == where, (name, parents.get(name))

    # work counts are numbers on spans, equal to the messages' numbers
    said = dict(re.findall(r"(RMAT|CC_find|PageRank):.*?(\d+) iterations",
                           screen))
    args = {n: a for n, _c, _p, a in tree}
    assert args["oink.rmat"]["rounds"] == int(said["RMAT"]) > 1
    assert args[names.CC_ENGINE]["iters"] == int(said["CC_find"]) >= 1
    assert args[names.PAGERANK_ENGINE]["iters"] == int(said["PageRank"]) > 1
    ngen = sum(n == names.RMAT_GENERATE for n, *_ in tree)
    assert ngen == args["oink.rmat"]["rounds"]

    # attrs the metrics and PERF.md quote
    one = [a for n, _c, _p, a in tree if n == names.AGGREGATE_ONE_FRAME]
    assert all({"rows", "frames", "to_host_bytes", "to_device_bytes"}
               <= set(a) for a in one)
    # rmat's second round adds its rows to a sharded dataset ON the mesh
    # (PR 27): two frames, and nothing comes back through the host, in
    # any aggregate of the script; the generator pulls nothing either
    assert any(a["frames"] == 2 for a in one)
    assert all(a["to_host_bytes"] == 0 for a in one)
    gen = [a for n, _c, _p, a in tree if n == names.RMAT_GENERATE]
    assert gen and all(a["d2h_bytes"] == 0 for a in gen)
    assert args[names.AGGREGATE_SHARD]["bytes"] > 0      # the host batch
    assert args[names.CONVERT_COUNT_SYNC]["groups"] > 0
    out = [a for n, _c, _p, a in tree if n == names.OINK_OUTPUT]
    assert all(a["rows"] > 0 and a["bytes"] > 0 and a["path"] for a in out)
    assert args[names.OINK_INPUT]["rows"] > 0
    assert args[names.CC_STAGE]["n"] == args[names.CC_EMIT]["n"] > 0


@pytest.mark.parametrize("nprocs", [1, 4, None],
                         ids=["mesh1", "mesh4", "serial"])
def test_stage_spans_say_where_the_ranking_ran(nprocs, traced, tmp_path):
    """``cc.stage`` and ``pagerank.stage`` carry ``on_device`` beside ``n``
    and ``edges`` (ISSUE 44): 1 on a mesh, where no ``scan_kv`` opens
    under either command, 0 on the serial backend, where the host ranks
    through one."""
    from gpu_mapreduce_tpu.oink.script import OinkScript
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    s = OinkScript(comm=make_mesh(nprocs) if nprocs else None,
                   screen=io.StringIO())
    for line in ("rmat 7 8 0.57 0.19 0.19 0.05 0.0 1 -o NULL mre",
                 "edge_upper -i mre -o NULL mru",
                 "cc_find 0 -i mru -o NULL NULL",
                 f"pagerank 1e-6 100 0.85 -i mre -o {tmp_path}/pr NULL"):
        s.run_string(line)
    events = traced.events()
    by_id = {e["id"]: e for e in events}
    from gpu_mapreduce_tpu.parallel.staging import mesh_kv_frame
    for stage, cmd, mr in ((names.CC_STAGE, "oink.cc_find", "mru"),
                           (names.PAGERANK_STAGE, "oink.pagerank", "mre")):
        (a,) = [e["args"] for e in events if e["name"] == stage]
        assert a["on_device"] == (1 if nprocs else 0), stage
        assert a["n"] > 0 and a["edges"] > 0
        # the rows the ranking ran over (ISSUE 48): the frame's, padding
        # and all, on a mesh; the edges themselves on the host
        frame = mesh_kv_frame(s.obj.get_mr(mr)) if nprocs else None
        rows = frame.key.shape[0] if nprocs else a["edges"]
        assert a[names.ATTR_EDGE_ROWS] == rows >= a[names.ATTR_EDGES], stage
        if nprocs:
            assert rows % nprocs == 0
        scans = [e for e in events if e["name"] == "scan_kv"
                 and by_id[e["parent"]]["name"] == stage]
        assert len(scans) == (0 if nprocs else 1), (stage, cmd)


@pytest.mark.parametrize("nprocs", [1, 4, None],
                         ids=["mesh1", "mesh4", "serial"])
def test_loop_spans_say_what_the_mesh_merged(nprocs, traced, tmp_path):
    """``cc.loop`` and ``pagerank.loop`` carry ``shards`` (the mesh's size,
    1 without a mesh) and ``allreduce_bytes`` beside ``iters``, ``n`` and
    ``edges`` (ISSUE 46): the replicated [n] vector merged over the mesh,
    ``n`` * 4 * one all-reduce a round * ``iters``, and 0 on one device,
    where nothing is merged; the stage spans carry ``shards`` too."""
    from gpu_mapreduce_tpu.models.cc import PMINS_PER_ROUND
    from gpu_mapreduce_tpu.models.pagerank import PSUMS_PER_ITERATION
    from gpu_mapreduce_tpu.oink.script import OinkScript
    from gpu_mapreduce_tpu.parallel.mesh import allreduce_bytes, make_mesh
    mesh = make_mesh(nprocs) if nprocs else None
    s = OinkScript(comm=mesh, screen=io.StringIO())
    for line in ("rmat 7 8 0.57 0.19 0.19 0.05 0.0 1 -o NULL mre",
                 "edge_upper -i mre -o NULL mru",
                 "cc_find 0 -i mru -o NULL NULL",
                 f"pagerank 1e-6 100 0.85 -i mre -o {tmp_path}/pr NULL"):
        s.run_string(line)
    args = {e["name"]: e["args"] for e in traced.events()}
    shards = nprocs or 1
    for stage in (names.CC_STAGE, names.PAGERANK_STAGE):
        assert args[stage]["shards"] == shards, stage
    assert (PMINS_PER_ROUND, PSUMS_PER_ITERATION) == (1, 1)
    for loop in (names.CC_ENGINE, names.PAGERANK_ENGINE):
        a = args[loop]
        assert a["shards"] == shards and a["iters"] >= 1 and a["n"] > 0
        want = a["n"] * 4 * a["iters"] if shards > 1 else 0
        assert a["allreduce_bytes"] == want, loop
        # what the loop iterates, beside the valid edges (ISSUE 48): the
        # staged columns' rows, which its stage span says as well
        stage = args[loop.replace(".loop", ".stage")]
        assert a[names.ATTR_EDGE_ROWS] == stage[names.ATTR_EDGE_ROWS] \
            >= a[names.ATTR_EDGES] == stage[names.ATTR_EDGES] > 0, loop
        assert a[names.ATTR_EDGE_ROWS] % shards == 0
    # the helper by itself: bytes of ``count`` merges of an [n] vector
    assert allreduce_bytes(1, 10, 3) == 0
    assert allreduce_bytes(4, 10, 3) == 120


def test_enumeration_commands_emit_their_spans(mesh, traced, tmp_path):
    """``tri_find``, ``luby_find`` and ``sssp`` (ISSUE 32): stage, engine
    and emit spans under their commands, the counts on the engine spans
    equal to the messages'."""
    s, screen, files = _enum_script(mesh, str(tmp_path))
    assert set(files) >= {"mis", "sssp"}
    tree = _tree(traced.events())
    parents = _where(tree)
    H, E = names.HOST, names.ENGINE
    for cmd, stage, engine, emit in (
            ("tri_find", names.TRI_STAGE, names.TRI_ENGINE, names.TRI_EMIT),
            ("luby_find", names.LUBY_STAGE, names.LUBY_ENGINE,
             names.LUBY_EMIT),
            ("sssp", names.SSSP_STAGE, names.SSSP_ENGINE, names.SSSP_EMIT)):
        assert parents.get(stage) == {(H, "oink." + cmd)}, stage
        assert parents.get(engine) == {(E, "oink." + cmd)}, engine
        assert parents.get(emit) == {(H, "oink." + cmd)}, emit
    args = {n: a for n, _c, _p, a in tree}
    walk = args[names.TRI_ENGINE]
    ntri = int(re.search(r"Tri_find: (\d+) triangles", screen).group(1))
    assert walk["triangles"] == ntri == args[names.TRI_EMIT]["triangles"] > 0
    assert walk["wedges"] >= ntri and walk["batches"] >= 1
    # the two enumerations (ISSUE 41): RMAT-7's hubs are tiled, the rest of
    # its lists walked by index
    assert walk[names.ATTR_TILES] >= 1
    assert 0 < walk[names.ATTR_INDEX_WEDGES] < walk["wedges"]
    assert 0 < walk[names.ATTR_TILE_FILL] <= 1
    assert walk["edges"] == s.obj.get_mr("mru").kv.nkv
    assert walk["n"] == args[names.TRI_STAGE]["n"] > walk["max_out_degree"] > 1
    nset, rounds = map(int, re.search(
        r"Luby_find: (\d+) MIS vertices in (\d+) iterations", screen).groups())
    loop = args[names.LUBY_ENGINE]
    assert loop["iters"] == rounds >= 1
    # every loop and stage span of the suite says its rows (ISSUE 48)
    for span in (names.LUBY_STAGE, names.LUBY_ENGINE, names.SSSP_STAGE,
                 names.SSSP_ENGINE):
        a = args[span]
        assert a[names.ATTR_EDGE_ROWS] >= a[names.ATTR_EDGES] > 0, span
    assert loop[names.ATTR_EDGE_ROWS] == loop["rows"]
    # what names.py promises of the span: how much a round reads
    assert loop["edges"] == args[names.LUBY_STAGE]["edges"] \
        == s.obj.get_mr("mru").kv.nkv
    assert loop["rows"] >= loop["edges"]
    assert loop["n"] == args[names.LUBY_STAGE]["n"] > nset
    assert args[names.LUBY_EMIT]["n"] == nset > 0
    source, iters, labeled = map(int, re.search(
        r"SSSP: source (\d+): (\d+) iterations, (\d+) vertices labeled",
        screen).groups())
    loop = args[names.SSSP_ENGINE]
    assert (loop["source"], loop["iters"], loop["labeled"]) == (
        source, iters, labeled)
    assert args[names.SSSP_EMIT]["source"] == source


def test_invertedindex_emits_the_map_and_part_file_spans(mesh, traced,
                                                         corpus, tmp_path):
    (npairs, nunique), parts, _idx = _invindex(mesh, corpus, str(tmp_path))
    assert npairs == 160 and nunique > 0 and len(parts) == 8
    tree = _tree(traced.events())
    where = _where(tree)
    H = names.HOST
    assert where[names.MAP_PLAN] == {(H, "map")}
    assert where[names.MAP_PAD] == {(H, "map")}
    assert where[names.PARTS_PULL] == {(H, "stage.reduce")}
    assert where[names.PARTS_WRITE] == {(H, "stage.reduce")}
    args = _attrs(tree)
    assert args[names.MAP_PLAN][0]["bytes"] == sum(
        os.path.getsize(p) for p in corpus)
    assert args[names.MAP_PAD][0]["bytes"] >= args[names.MAP_PLAN][0]["bytes"]
    assert len(args[names.PARTS_PULL]) == len(args[names.PARTS_WRITE]) == 8
    assert sum(a["groups"] for a in args[names.PARTS_WRITE]) == nunique
    assert sum(a["bytes"] for a in args[names.PARTS_WRITE]) == sum(
        len(b) for b in parts.values())
    # a line is its url, its files and its newline, each one range gathered
    assert sum(a["pieces"] for a in args[names.PARTS_WRITE]) == sum(
        2 + len(line.split(b"\t")[1].split(b" "))
        for b in parts.values() for line in b.splitlines())
    assert {a["recoded"] for a in args[names.PARTS_WRITE]} == {0}


def test_invertedindex_on_four_devices_says_which_shard(traced, corpus,
                                                         tmp_path):
    """What exists only when P > 1 (PR 26): the global collision check has
    a span of its own, the serial per-shard staging says which shard, and
    the exchange says how evenly its rows landed."""
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    (npairs, nunique), parts, _idx = _invindex(make_mesh(4), corpus,
                                               str(tmp_path))
    assert len(parts) == 4
    tree = _tree(traced.events())
    where = _where(tree)
    assert where[names.MAP_COLLISIONS] == {(names.HOST, "stage.map_device")}
    args = _attrs(tree)
    (coll,) = args[names.MAP_COLLISIONS]
    assert (coll["rows"], coll["rounds"], coll["shards"]) == (npairs, 1, 4)
    reads = args["stage.read"]
    assert [a["shard"] for a in reads] == [0, 1, 2, 3]
    (pad,) = args[names.MAP_PAD]
    assert pad["shard_bytes"] == [a["bytes"] for a in reads]
    assert sum(pad["shard_bytes"]) <= pad["bytes"]
    (ud,) = args["stage.url_dict"]
    assert (ud["urls"], ud["shards"]) == (npairs, 4)
    (ex,) = args[names.SHUFFLE_EXCHANGE]
    assert ex["recv_rows_mean"] == npairs / 4
    assert ex["recv_rows_mean"] <= ex["recv_rows_max"] <= npairs


def _terasort(mesh, out):
    """TeraSort (ISSUE 36) over three small files of seeded records: the
    record map, the sampled splitters, the part files' bytes."""
    from gpu_mapreduce_tpu.apps.terasort import TeraSort
    rng = np.random.default_rng(36)
    paths = []
    for i in range(3):
        paths.append(os.path.join(out, f"in-{i}.dat"))
        rng.integers(0, 256, (200 + i, 100), dtype=np.uint8).tofile(paths[-1])
    ts = TeraSort(comm=mesh)
    n = ts.run(paths, outdir=os.path.join(out, "parts"))
    parts = []
    for path in ts.parts:
        with open(path, "rb") as f:
            parts.append(f.read())
    return n, parts


def _tpch(mesh, out):
    """TPC-H Query 3 (ISSUE 43) and Query 1 (ISSUE 50) over tiny seeded
    tables through the OINK commands: their lines and what they said."""
    import io
    from benchmark.gen import tpch as gen
    from gpu_mapreduce_tpu.oink.script import OinkScript
    paths = gen.make_tables(os.path.join(out, "tables"), 0.002, 43)
    script = OinkScript(comm=mesh, screen=io.StringIO())
    for t, files in paths.items():
        script.run_string(f"variable f{t} index {' '.join(files)}")
    script.run_string("tpch_load -i v_fcustomer v_forders v_flineitem "
                      "-o NULL customer -o NULL orders -o NULL lineitem")
    script.run_string(f"tpch_q3 BUILDING 1995-03-15 -i customer orders "
                      f"lineitem -o {out}/q3.txt mrq3")
    script.run_string(f"tpch_q1 90 -i lineitem -o {out}/q1.txt mrq1")
    with open(os.path.join(out, "q3.txt")) as f, \
            open(os.path.join(out, "q1.txt")) as g:
        return f.read() + g.read(), script.screen.getvalue()


def test_terasort_on_four_devices_says_how_its_rows_were_sent(traced,
                                                             tmp_path):
    """ISSUE 39: what TeraSort has only when P > 1.  The sample says what
    it pulled; the exchange says that its destinations are a total order
    and whether it built a phase 1 of its own (the first job of a process
    does, the second, over the same shapes, does not); every shard has a
    pull and a write."""
    from gpu_mapreduce_tpu.parallel import shuffle
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    shuffle.PHASE1_CACHE.clear()
    mesh = make_mesh(4)
    for job in range(2):
        (tmp_path / str(job)).mkdir()
        n, parts = _terasort(mesh, str(tmp_path / str(job)))
        assert len(parts) == 4 and sum(map(len, parts)) == n * 100
    tree = _tree(traced.events())
    where, args = _where(tree), _attrs(tree)
    assert where[names.TERASORT_SAMPLE] == {(names.HOST, names.TERASORT_RUN)}
    for sample in args[names.TERASORT_SAMPLE]:
        # every key is sampled (603 < SAMPLE), three words a key, and the
        # fullest shard's share of slots is pulled from each of the four
        assert (sample["sampled"], sample["splitters"]) == (n, 3)
        assert n * 12 <= sample["d2h_bytes"] <= 4 * 202 * 12
    ex = args[names.SHUFFLE_EXCHANGE]
    assert [a["dest"] for a in ex] == ["order", "order"]
    assert [a["phase1_built"] for a in ex] == [1, 0]
    assert all(a["rows"] == n and a["nprocs"] == 4 for a in ex)
    for name in (names.TERASORT_PULL, names.TERASORT_WRITE):
        assert [a["shard"] for a in args[name]] == [0, 1, 2, 3] * 2
        assert sum(a["records"] for a in args[name]) == 2 * n
    assert sum(a["bytes"] for a in args[names.TERASORT_WRITE]) == 2 * n * 100


def _wordfreq_script(mesh, corpus):
    from gpu_mapreduce_tpu.oink.script import OinkScript
    s = OinkScript(comm=mesh, screen=io.StringIO())
    s.run_string("variable files index " + " ".join(corpus))
    s.run_string("wordfreq 3 -i v_files -o NULL mrw")
    return s.screen.getvalue()


def test_wordfreq_emits_the_word_map_and_top_n_spans(traced, corpus):
    """ISSUE 30: the word map of the file map says what it tokenized and
    interned, shard by shard; ``convert`` says how large its hub group is;
    the top-N tail has a span."""
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    screen = _wordfreq_script(make_mesh(4), corpus)
    nwords, nunique = map(int, re.search(
        r"(\d+) words, (\d+) unique", screen).groups())
    tree = _tree(traced.events())
    where, args = _where(tree), _attrs(tree)
    H = names.HOST
    assert where[names.INGEST_TOKENIZE] == {(H, "ingest.read")}
    # a root of its pool thread each (ISSUE 35), inside map_files in time
    assert where[names.INGEST_INTERN] == {(H, None)}
    assert where[names.WORDFREQ_TOPN] == {(H, "oink.wordfreq")}
    tok = args[names.INGEST_TOKENIZE]
    intern = sorted(args[names.INGEST_INTERN], key=lambda a: a["shard"])
    assert [a["shard"] for a in tok] == [a["shard"] for a in intern] \
        == [0, 1, 2, 3]
    assert [a["bytes"] for a in tok] == [os.path.getsize(p) for p in corpus]
    assert [a["words"] for a in tok] == [a["words"] for a in intern]
    assert sum(a["words"] for a in tok) == nwords
    assert all(0 < a["unique"] <= a["words"] and a["table_bytes"] > 0
               and a["added"] + a["checked"] == a["unique"] for a in intern)
    assert sum(a["added"] for a in intern) == nunique
    assert intern[0]["checked"] == 0 < intern[3]["checked"]
    by_name = {e["name"]: e for e in traced.events()}
    spans = [e for e in traced.events() if e["name"] == names.INGEST_INTERN]
    op = by_name["map_files"]
    assert all(op["ts"] <= e["ts"] and e["ts"] + e["dur"] <= op["ts"]
               + op["dur"] and e["trace"] == op["trace"] for e in spans)
    # a task's seconds are taken round its span, so they hold the span and
    # whatever the thread waited to open and close it (under loaded cores
    # more than a millisecond: the old upper bound of the spans' sum plus
    # 1 ms failed under six workers), and each task runs inside map_files
    busy = op["args"]["intern_busy_s"]
    assert sum(e["dur"] for e in spans) * 1e-6 - 1e-3 <= busy \
        <= len(spans) * op["dur"] * 1e-6 + 1e-3
    (conv,) = args[names.CONVERT_SPAN]
    assert conv[names.ATTR_ROWS] == nwords
    assert conv[names.ATTR_GROUPS] == nunique
    top = int(screen.splitlines()[1].split()[0])
    assert conv[names.ATTR_GROUP_ROWS_MAX] == top > 1
    (topn,) = args[names.WORDFREQ_TOPN]
    assert topn["rows"] == nunique
    ex = args[names.SHUFFLE_EXCHANGE]           # the aggregate's, the gather's
    assert len(ex) == 2 and ex[1]["recv_rows_max"] == nunique
    assert ex[0]["recv_rows_mean"] == nwords / 4 < ex[0]["recv_rows_max"]


def test_host_convert_says_its_hub_group(traced):
    from gpu_mapreduce_tpu import MapReduce
    mr = MapReduce()
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.array([7, 7, 7, 8, 9, 9], np.uint64), np.zeros(6, np.uint8)))
    mr.convert()
    (conv,) = _attrs(_tree(traced.events()))[names.CONVERT_SPAN]
    assert (conv[names.ATTR_ROWS], conv[names.ATTR_GROUPS],
            conv[names.ATTR_GROUP_ROWS_MAX]) == (6, 3, 3)


@pytest.mark.parametrize("vdtype,words", [(np.uint8, (2, 1, 0)),
                                          (np.float64, (2, 0, 2))],
                         ids=["u8_rides", "f64_by_index"])
def test_mesh_convert_says_how_its_sort_carried_the_value(mesh, traced,
                                                          vdtype, words):
    """ISSUE 38: ``jit_convert_sort`` is one payload sort, and the
    ``convert`` span says what `ops/sort.riding` decided for the value:
    ``taken_words`` 0 is a program without a gather."""
    from gpu_mapreduce_tpu import MapReduce
    mr = MapReduce(mesh)
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.arange(64, dtype=np.uint64) % 5, np.arange(64).astype(vdtype)))
    mr.aggregate()
    assert mr.convert() == 5
    (conv,) = _attrs(_tree(traced.events()))[names.CONVERT_SPAN]
    assert (conv[names.ATTR_KEY_WORDS], conv[names.ATTR_RODE_WORDS],
            conv[names.ATTR_TAKEN_WORDS]) == words
    assert (conv[names.ATTR_ROWS], conv[names.ATTR_GROUPS]) == (64, 5)


def _ancestors(events):
    by_id = {e["id"]: e for e in events}

    def chain(e):
        while e["parent"]:
            e = by_id[e["parent"]]
            yield e["id"]

    return {e["id"]: set(chain(e)) for e in events}


@pytest.mark.parametrize("entry", ["invindex", "oink", "oink-include",
                                   "terasort"])
def test_an_entry_point_call_is_one_root_span(mesh, traced, corpus,
                                              tmp_path, entry):
    """ISSUE 34: one ``entry`` span a call into an entry point, parent 0,
    above every other span of the calling thread; an ``include`` runs
    inside its caller's and opens none; every span of the run carries its
    thread's CPU seconds and the rest of its wall."""
    if entry == "invindex":
        _invindex(mesh, corpus, str(tmp_path))
        name, calls = names.INVINDEX_RUN, 1
    elif entry == "terasort":
        _terasort(mesh, str(tmp_path))
        name, calls = names.TERASORT_RUN, 1
    else:
        from gpu_mapreduce_tpu.oink.script import OinkScript
        lines = ["rmat 6 4 0.57 0.19 0.19 0.05 0.0 1 -o NULL mre",
                 "edge_upper -i mre -o NULL mru"]
        s = OinkScript(comm=mesh, screen=io.StringIO())
        if entry == "oink":
            for line in lines:              # a job of two calls: two roots
                s.run_string(line)
            calls = 2
        else:
            inc = tmp_path / "in.upper"
            inc.write_text(lines[1] + "\n")
            s.run_string(f"{lines[0]}\ninclude {inc}\n")
            calls = 1
        name = names.OINK_SCRIPT
    events = traced.events()
    roots = [e for e in events if e["cat"] == names.ENTRY]
    assert [e["name"] for e in roots] == [name] * calls
    assert all(e["parent"] == 0 for e in roots)
    above = _ancestors(events)
    root_ids = {e["id"] for e in roots}
    (tid,) = {e["tid"] for e in roots}
    mine = [e for e in events if e["tid"] == tid and e["id"] not in root_ids]
    assert mine and all(len(above[e["id"]] & root_ids) == 1 for e in mine)
    if entry == "oink-include":
        assert {"oink.rmat", "oink.edge_upper"} <= {e["name"] for e in mine}
    for e in events:
        a, dur = e["args"], e["dur"] * 1e-6
        assert a[names.ATTR_CPU_S] >= 0 and a[names.ATTR_OFF_CPU_S] >= 0
        assert abs(a[names.ATTR_CPU_S] + a[names.ATTR_OFF_CPU_S] - dur) \
            <= max(0.01 * dur, 1e-3), (e["name"], a, dur)
    for e in roots:
        assert {names.ATTR_PROC_CPU_S, names.ATTR_SYS_CPU_S,
                names.ATTR_VOL_SWITCHES,
                names.ATTR_INVOL_SWITCHES, names.ATTR_JIT_LOWERINGS,
                names.ATTR_JIT_LOWER_S, names.ATTR_JIT_BACKEND_S,
                names.ATTR_JIT_CACHE_LOADS} <= set(e["args"])


def test_tracer_off_constructs_no_span_and_changes_nothing(
        mesh, corpus, tmp_path, monkeypatch):
    tr = get_tracer()
    # another file's test on this xdist worker may have left the process
    # tracer on (the serve metrics bridge and the flight recorder
    # subscribe to it): this test owns its state from here
    tr.reset()
    built = []
    real_init = tracer_mod.Span.__init__

    def counting(self, *a, **kw):
        built.append(a[1] if len(a) > 1 else kw.get("name"))
        real_init(self, *a, **kw)

    monkeypatch.setattr(tracer_mod.Span, "__init__", counting)
    (tmp_path / "g0").mkdir(), (tmp_path / "i0").mkdir()
    off_graph = _graph_script(mesh, str(tmp_path / "g0"))
    off_counts, off_parts, _ = _invindex(mesh, corpus, str(tmp_path / "i0"))
    off_rows = _host_batch(mesh)
    off_words = _wordfreq_script(mesh, corpus)
    (tmp_path / "e0").mkdir(), (tmp_path / "e1").mkdir()
    off_enum = _enum_script(mesh, str(tmp_path / "e0"))[1:]
    (tmp_path / "t0").mkdir(), (tmp_path / "t1").mkdir()
    off_sorted = _terasort(mesh, str(tmp_path / "t0"))
    (tmp_path / "q0").mkdir(), (tmp_path / "q1").mkdir()
    off_q3 = _tpch(mesh, str(tmp_path / "q0"))
    assert built == []          # every site returned NULL_SPAN

    tr.enable(ring=1 << 16)
    try:
        (tmp_path / "g1").mkdir(), (tmp_path / "i1").mkdir()
        on_graph = _graph_script(mesh, str(tmp_path / "g1"))
        on_counts, on_parts, _ = _invindex(mesh, corpus,
                                           str(tmp_path / "i1"))
        on_rows = _host_batch(mesh)
        on_words = _wordfreq_script(mesh, corpus)
        on_enum = _enum_script(mesh, str(tmp_path / "e1"))[1:]
        on_sorted = _terasort(mesh, str(tmp_path / "t1"))
        on_q3 = _tpch(mesh, str(tmp_path / "q1"))
    finally:
        tr.clear()
        tr.disable()
    # every declared span name is one the program really opens
    assert set(names.SPANS) <= set(built)
    assert on_graph == off_graph and on_enum == off_enum
    assert on_sorted == off_sorted and on_sorted[0] == 603
    assert on_q3 == off_q3 and len(on_q3[0].splitlines()) == 10 + 4
    assert (on_counts, on_parts, on_rows, on_words) == (
        off_counts, off_parts, off_rows, off_words)


# -- scopes --------------------------------------------------------------------

def _strip(hlo: str) -> str:
    """HLO text without what is metadata: each instruction's
    ``metadata={...}`` and the module's tables of source locations."""
    hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
    return "\n".join(ln for ln in hlo.splitlines()
                     if ln not in tables and not re.match(r"\d+ [{\"]", ln))


@pytest.fixture
def no_compile_cache():
    """The persistent cache keys a program without its metadata, so it
    would hand the second compile the first one's executable."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_named_scopes_are_metadata_only(mesh, monkeypatch, no_compile_cache):
    """The convert layout program compiles to the same HLO with its
    ``jax.named_scope``s and with them replaced by nothing."""
    from gpu_mapreduce_tpu.parallel import group
    avals = (SDS((64, 2), jnp.uint64), SDS((64,), jnp.bool_),
             SDS((8,), jnp.int32))
    build = group._convert_phase2_jit.__wrapped__       # past the lru_cache
    with_scopes = build(mesh, 8).lower(*avals).compile().as_text()
    assert "shard_map/layout/flagged_rows_first/" in with_scopes

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = build(mesh, 8).lower(*avals).compile().as_text()
    assert "flagged_rows" not in bare and "shard_map/layout" not in bare
    assert "sort(" in _strip(bare)           # the code is what is compared
    assert _strip(bare) == _strip(with_scopes)


def test_the_scan_programs_are_named_for_their_bodies(mesh):
    """``skv_scan`` runs one program a kernel body, ``jit_kv_scan_<body>``,
    which orders the kept rows by a sort of one operand where ``skv_map``'s
    packs by a scatter; Q3's scans are the three the benchmark's
    ``scan_dev_s`` names."""
    import gpu_mapreduce_tpu.apps.tpch as tpch
    from gpu_mapreduce_tpu.parallel import devkernels
    cnt, date = SDS((8,), jnp.int32), SDS((), jnp.uint32)
    seen = set()
    for table, words in (("customer", 4), ("orders", 9), ("lineitem", 15)):
        cells = tpch.SCAN[table].__closure__
        (dev,) = [c.cell_contents for c in cells
                  if getattr(c.cell_contents, "__name__", "") ==
                  "tpch_" + table]
        text = devkernels._skv_rows_jit(mesh, dev, (), 1, True).lower(
            SDS((64, 2), jnp.uint32), SDS((64, words), jnp.uint32), cnt,
            date).as_text()
        got = re.search(r"module @(\w+)", text).group(1)
        assert got == names.KV_SCAN_PREFIX + "tpch_" + table
        assert names.declared_program(got) and got in tpch.SCAN_PROGRAMS
        ops = re.findall(r"stablehlo\.(\w+)", text)
        assert ops.count("sort") == 1 and not set(ops) & {
            "scatter", "gather", "while"}, ops
        seen.add(got)
    assert seen == set(tpch.SCAN_PROGRAMS)
    # a map that keeps every row is a plain map of the rows where they lie
    (dev,) = [c.cell_contents for c in tpch._BY_ORDERKEY.__closure__
              if getattr(c.cell_contents, "__name__", "") ==
              "tpch_by_orderkey"]
    text = devkernels._skv_rows_jit(mesh, dev, (), 0, False).lower(
        SDS((64, 2), jnp.uint32), SDS((64, 5), jnp.uint32), cnt).as_text()
    assert re.search(r"module @(\w+)", text).group(1) == (
        names.KV_MAP_PREFIX + "tpch_by_orderkey")
    assert not set(re.findall(r"stablehlo\.(\w+)", text)) & {
        "sort", "scatter", "gather", "while"}


def test_query_1s_programs_are_named_for_its_map(mesh):
    """ISSUE 50: ``skv_keep`` counts the rows the map keeps under the
    scan's name, and the combiner applies the map inside a program of its
    own, ``jit_combine_<body>``; neither orders, gathers or scatters a row,
    and the count program reads nothing it does not need."""
    import gpu_mapreduce_tpu.apps.tpch as tpch
    from gpu_mapreduce_tpu.parallel import devkernels, group
    (dev,) = [c.cell_contents for c in tpch._Q1_SCAN.__closure__
              if getattr(c.cell_contents, "__name__", "") == "tpch_q1"]
    table = (SDS((64, 2), jnp.uint32), SDS((64, 15), jnp.uint32),
             SDS((8,), jnp.int32), SDS((), jnp.uint32))
    count = devkernels._skv_keep_jit(mesh, dev, (), 1).lower(*table)
    fold = group._combine_jit(mesh, "sum", dev, (), 1).lower(*table)
    got = [re.search(r"module @(\w+)", low.as_text()).group(1)
           for low in (count, fold)]
    assert tuple(got) == tpch.Q1_PROGRAMS == (
        names.KV_SCAN_PREFIX + "tpch_q1", names.COMBINE_PREFIX + "tpch_q1")
    assert all(names.declared_program(p) for p in got)
    for low in (count, fold):
        ops = set(re.findall(r"stablehlo\.(\w+)", low.as_text()))
        assert not ops & {"sort", "scatter", "gather"}, ops
    ops = set(re.findall(r"stablehlo\.(\w+)", count.as_text()))
    assert not ops & {"while", "multiply"}, ops     # the predicate alone
    out = jax.eval_shape(
        group._combine_jit(mesh, "sum", dev, (), 1),
        *(SDS(x.shape, x.dtype) for x in table))
    assert [(o.shape, str(o.dtype)) for o in out] == [
        ((8 * 16, 2), "uint32"), ((8 * 16, 6), "int64"), ((8,), "int32"),
        ((8,), "int32")]


def test_the_new_spans_and_attrs_are_declared():
    for span in (names.TPCH_Q1, names.COMBINE_COUNT_SYNC):
        assert span in names.SPANS
    assert names.COMPRESS_SPAN == "compress"
    for attr in (names.ATTR_VALUE_WORDS, names.ATTR_COMBINED,
                 names.ATTR_GROUPS, names.ATTR_GROUP_ROWS_MAX,
                 names.ATTR_KEY_WORDS):
        assert attr in names.SPAN_ATTRS
    assert names.COMBINE in names.PROGRAMS
    assert names.COMBINE_PREFIX in names.PROGRAM_PREFIXES
    assert names.steps_of("jit_combine_tpch_q1") == names.STEPS[
        names.COMBINE_PREFIX]
    assert set(names.STEPS[names.COMBINE]) < set(
        names.STEPS[names.COMBINE_PREFIX])
    for span in (names.TPCH_Q3, names.TPCH_LOAD, names.TPCH_SCAN,
                 names.TPCH_TOPN, names.TPCH_EMIT):
        assert span in names.SPANS and span.startswith("tpch.")
    assert names.JOIN_SPAN == "join"
    for attr in (names.ATTR_PROBE_ROWS, names.ATTR_BUILD_ROWS,
                 names.ATTR_MATCHED_ROWS, names.ATTR_ROWS_IN,
                 names.ATTR_ROWS_OUT, names.ATTR_ROW_WORDS_IN):
        assert attr in names.SPAN_ATTRS
    assert names.JOIN_ROWS in names.PROGRAMS
    assert names.KV_SCAN_PREFIX in names.PROGRAM_PREFIXES


# -- steps (ISSUE 48) -----------------------------------------------------------

def _count_dev(uk, nv, vo, vals, gc, vc):
    return uk, nv, nv > 0


def _keep_even_dev(k, v, c):
    return k, v, k[:, 0] % 2 == 0


def _step_programs(mesh):
    """declared name (or prefix) -> lowered programs that between them
    run every step ``names.STEPS`` declares for it: ``_programs``, with
    other arguments where the tiny ones take a branch that skips a step."""
    from gpu_mapreduce_tpu.models import sssp, tri
    from gpu_mapreduce_tpu.parallel import devkernels, group, shuffle
    u64, i32 = jnp.uint64, jnp.int32
    key, col, cnt = SDS((64, 2), u64), SDS((64,), u64), SDS((8,), i32)
    f64 = SDS((64,), jnp.float64)       # a value that never rides a sort
    edges = (SDS((64,), i32), SDS((64,), i32), SDS((64,), jnp.bool_))
    out = {name: [lowered] for name, lowered in _programs(mesh)}
    out[names.KV_MAP_PREFIX] = out.pop(names.KV_MAP_PREFIX + "edge_upper")
    out[names.KMV_MAP_PREFIX] = [devkernels._skmv_map_jit(
        mesh, _count_dev, (), 0).lower(
            col, SDS((64,), i32), SDS((64,), i32), col, cnt, cnt)]
    out[names.KV_SCAN_PREFIX] = [devkernels._skv_rows_jit(
        mesh, _keep_even_dev, (), 0, True).lower(key, col, cnt)]
    out[names.COMBINE_PREFIX] = [group._combine_jit(
        mesh, "sum", _keep_even_dev, (), 0).lower(key, col, cnt)]
    out[names.CONVERT_SORT] = [
        group._convert_phase1_jit(mesh).lower(key, f64, cnt)]
    out[names.SORT_ROWS] = [
        group._sort_jit(mesh, "key", False).lower(col, f64, cnt)]
    # groups cut to fewer than the rows: the offsets are a slice of them
    out[names.CONVERT_LAYOUT] = [group._convert_phase2_jit(mesh, 4).lower(
        key, SDS((64,), jnp.bool_), cnt)]
    out[names.SHUFFLE_PHASE1] = [shuffle._phase1_jit(
        mesh, ("hash", None), False, wire=(True, True)).lower(col, col, cnt)]
    tile = SDS((8,), i32)
    out[names.TRI_WEDGES].append(tri._programs(mesh).wedges.lower(
        col, tile, (tile,) * 4, SDS((), jnp.int64), SDS((), jnp.int64),
        batch=128, block=8))
    # whole weights: each shard's rows sorted once, ahead of the loop
    out[names.SSSP_LOOP] = [sssp._bf_sharded_fn(mesh, 16, 16).lower(
        edges[0], edges[1], SDS((64,), i32), edges[2], SDS((), i32))]
    return out


@pytest.fixture(scope="module")
def step_programs(mesh):
    return _step_programs(mesh)


def _scope_components(lowered) -> set:
    """Every component but the last (the primitive's own name) of every
    operation's name-stack path in the lowered program's debug info."""
    out = set()
    for path in re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)):
        out.update(path.split("/")[:-1])
    return out


@pytest.mark.parametrize("program", sorted(names.STEPS))
def test_every_declared_step_is_a_scope_of_its_program(step_programs,
                                                       program):
    """What a metric file quotes is in the program: each step of
    ``names.STEPS[program]`` is a component of some operation's scope path
    in the program as lowered (which is what the compiler copies into every
    instruction's ``op_name``, and the profiler into the trace)."""
    steps = names.STEPS[program]
    assert len(set(steps)) == len(steps) > 0
    for step in steps:
        assert re.fullmatch(r"[a-z][a-z0-9_]*", step), step
        assert step not in names.STEP_RESERVED, step
    found = set().union(*map(_scope_components, step_programs[program]))
    assert set(steps) <= found, (program, set(steps) - found)
    assert names.steps_of(program + ("x" if program.endswith("_") else "")) \
        == steps


def test_steps_cover_the_declared_programs_and_the_source():
    """``STEPS`` has every declared program and prefix and nothing else;
    every ``jax.named_scope("...")`` literal of the package is a declared
    step of some program, and every declared step is such a literal: a
    scope renamed in the source alone, or in ``names.py`` alone, fails
    here before it empties a metric."""
    assert set(names.STEPS) == set(names.PROGRAMS) | set(
        names.PROGRAM_PREFIXES)
    assert names.steps_of("jit_run") == ()
    root = os.path.dirname(os.path.abspath(names.__file__))
    root = os.path.dirname(root)            # gpu_mapreduce_tpu/
    literals = set()
    for d, _dirs, files in os.walk(root):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(d, fn)) as f:
                    literals.update(re.findall(
                        r'jax\.named_scope\(\s*"([^"]+)"', f.read()))
    declared = {s for steps in names.STEPS.values() for s in steps}
    assert literals == declared, (literals ^ declared)


@pytest.fixture(scope="module")
def lowered_twice(mesh):
    """name -> (text as lowered, text lowered with ``jax.named_scope``
    replaced by nothing), debug info stripped, of every ``_programs``
    program.  The builders and JAX cache their traces, so the caches go
    before the second lowering, and again after it."""
    with_scopes = {n: low.as_text() for n, low in _programs(mesh)}
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    jax.clear_caches()
    try:
        bare = {n: (low.as_text(), _scope_components(low))
                for n, low in _programs(mesh)}
    finally:
        jax.named_scope = real
        jax.clear_caches()
    return with_scopes, bare


@pytest.mark.parametrize(
    "program", names.PROGRAMS + (names.KV_MAP_PREFIX + "edge_upper",))
def test_scopes_change_no_operation_of_a_program(lowered_twice, program):
    """Scopes are metadata (ISSUE 48): the StableHLO of a program, debug
    info stripped, is the same text with its ``jax.named_scope``s and
    without, so the persistent cache (whose key strips debug info) serves
    the parent's executable and nothing recompiles."""
    with_scopes, bare = lowered_twice
    text, components = bare[program]
    assert not components & set(names.steps_of(program)), program
    assert with_scopes[program] == text
