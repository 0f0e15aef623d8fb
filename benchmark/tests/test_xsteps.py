"""The step table and its two readers (ISSUE 48), on the two traces recorded
on the TPU v5e: ``data/tiny_tpu.xplane.pb`` (``record_trace.py``, PR 23) and
``data/tiny_mesh.xplane.pb`` (``record_collective_trace.py``, PR 46: two
jobs, each the package's sharded cc loop as it was scoped then and a program
outside the names).  And the rule that what the step metrics quote, the
program declares."""

import json
import os
import shutil
import types

import pytest

from benchmark import cells, xsteps, xtrace
from benchmark.readers import step_named_share, step_seconds

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ONE_CHIP = os.path.join(DATA, "tiny_tpu.xplane.pb")
MESH = os.path.join(DATA, "tiny_mesh.xplane.pb")
CC = "jit_cc_loop"
SHARD = "jit(cc_loop)/while/body/shard_map/"


def _by_module(table):
    return {prog["module"]: (pid, prog) for pid, prog in table.items()}


def test_the_table_of_the_recorded_four_chip_trace():
    table = xsteps.table(MESH)
    assert xsteps.table(MESH) is table          # read once a process
    progs = _by_module(table)
    assert set(progs) == {CC, "jit_other_psum"}
    # a program's id is the number in its ``XLA Modules`` events' names
    raw = xtrace.load(MESH, {xtrace.JOB_SPAN})
    named = {xtrace.module_name(nm): int(xsteps.PROGRAM_ID.search(nm).group(1))
             for _a, _b, nm in raw["devices"][0]["modules"]}
    assert named == {m: pid for m, (pid, _p) in progs.items()}
    steps = progs[CC][1]["steps"]
    assert len(steps) == 170
    assert steps["pmin.8"] == SHARD + "pmin"
    # the mapping PERF.md §5 used to get by compiling for a described chip
    assert steps["fusion.27"] == SHARD + "segment_min_dst/scatter-min"
    assert steps["fusion.31"] == SHARD + "segment_min_dst/gather"
    assert steps["fusion.29"] == SHARD + "segment_min_src/scatter-min"
    assert steps["fusion.32"] == SHARD + "segment_min_src/gather"
    assert steps["while.3"] == "jit(cc_loop)/while"
    assert len(set(steps.values())) == 44         # "" among them
    # every operation that ran has an instruction of its name in the table
    ran = {xsteps.instruction(nm) for dev in raw["devices"].values()
           for _a, _b, nm in dev["ops"]}
    both = set(steps) | set(progs["jit_other_psum"][1]["steps"])
    assert ran <= both and len(ran) == 18


def test_the_table_of_the_recorded_one_chip_trace_and_of_no_plane(tmp_path):
    progs = _by_module(xsteps.table(ONE_CHIP))
    assert set(progs) == {"jit_extract", "jit_tail"}
    assert "jit(tail)/gather" in progs["jit_tail"][1]["steps"].values()
    # a file without the plane (a CPU run), and an empty one
    from jax.profiler import ProfileData
    p = tmp_path / "cpu.xplane.pb"
    p.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/host:CPU" }'))
    assert xsteps.table(str(p)) == {}
    assert xsteps.seconds(str(p)) is None
    q = tmp_path / "empty.xplane.pb"
    q.write_bytes(b"")
    assert xsteps.table(str(q)) == {}


@pytest.mark.parametrize("path", [ONE_CHIP, MESH],
                         ids=["tiny_tpu", "tiny_mesh"])
def test_the_walk_equals_the_protobuf_librarys_parse(path):
    """Where ``tensorflow`` imports: the descriptors give the field numbers
    the walk hard-codes, and the library's parse of the same file gives the
    same table."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")

    def number(message, field):
        return message.DESCRIPTOR.fields_by_name[field].number

    op_metadata = hlo_pb2.HloInstructionProto.DESCRIPTOR.fields_by_name[
        "metadata"].message_type
    assert (number(xplane_pb2.XSpace, "planes"),
            number(xplane_pb2.XPlane, "name"),
            number(xplane_pb2.XPlane, "event_metadata"),
            number(xplane_pb2.XPlane, "stat_metadata"),
            number(xplane_pb2.XEventMetadata, "id"),
            number(xplane_pb2.XEventMetadata, "name"),
            number(xplane_pb2.XEventMetadata, "stats"),
            number(xplane_pb2.XStat, "metadata_id"),
            number(xplane_pb2.XStat, "bytes_value"),
            number(xplane_pb2.XStatMetadata, "name"),
            number(hlo_pb2.HloProto, "hlo_module"),
            number(hlo_pb2.HloModuleProto, "name"),
            number(hlo_pb2.HloModuleProto, "computations"),
            number(hlo_pb2.HloComputationProto, "instructions"),
            number(hlo_pb2.HloInstructionProto, "name"),
            number(hlo_pb2.HloInstructionProto, "metadata"),
            number(hlo_pb2.HloInstructionProto, "id"),
            number(hlo_pb2.HloInstructionProto, "operand_ids"),
            op_metadata.fields_by_name["op_name"].number) == (
        xsteps.XSPACE_PLANES, xsteps.XPLANE_NAME,
        xsteps.XPLANE_EVENT_METADATA, xsteps.XPLANE_STAT_METADATA,
        xsteps.XEVENTMETADATA_ID, xsteps.XEVENTMETADATA_NAME,
        xsteps.XEVENTMETADATA_STATS, xsteps.XSTAT_METADATA_ID,
        xsteps.XSTAT_BYTES_VALUE, xsteps.XSTATMETADATA_NAME,
        xsteps.HLOPROTO_MODULE, xsteps.HLOMODULE_NAME,
        xsteps.HLOMODULE_COMPUTATIONS, xsteps.HLOCOMPUTATION_INSTRUCTIONS,
        xsteps.HLOINSTRUCTION_NAME, xsteps.HLOINSTRUCTION_METADATA,
        xsteps.HLOINSTRUCTION_ID, xsteps.HLOINSTRUCTION_OPERAND_IDS,
        xsteps.OPMETADATA_OP_NAME)

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    (plane,) = [p for p in space.planes if p.name == xsteps.METADATA_PLANE]
    assert not plane.lines
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    want = {}
    for pid, event in plane.event_metadata.items():
        (stat,) = [s for s in event.stats
                   if stat_names[s.metadata_id] == xsteps.HLO_STAT]
        hlo = hlo_pb2.HloProto()
        hlo.ParseFromString(stat.bytes_value)
        assert event.name == f"{hlo.hlo_module.name}({pid})"
        insts = [i for c in hlo.hlo_module.computations
                 for i in c.instructions]
        steps = {i.name: i.metadata.op_name for i in insts}
        by_id = {i.id: i.name for i in insts}
        want[pid] = {"module": hlo.hlo_module.name, "steps": steps,
                     "inherited": xsteps._inherit(steps, {
                         i.name: [by_id[o] for o in i.operand_ids]
                         for i in insts})}
    assert xsteps.table(path) == want
    assert any(p["inherited"] for p in want.values())


def test_an_instruction_without_a_path_takes_its_inputs():
    """The compiler's own operations and a cached lowering's (every prefix
    scan on the TPU) carry no scope path: they are booked to the nearest
    instruction that made their input and has one."""
    steps = {"fusion.1": "jit(f)/pack/convert_element_type",
             "reduce-window.1": "", "slice.2": "",
             "add_fusion": "reduce_window_sum",      # the cached lowering's
             "param.0": "key", "copy.3": "", "loop.a": "", "loop.b": "",
             "fusion.9": "jit(f)/kernel/min"}
    operands = {"reduce-window.1": ["fusion.1"], "slice.2": ["reduce-window.1"],
                "add_fusion": ["slice.2", "fusion.9"], "copy.3": ["param.0"],
                "loop.a": ["loop.b"], "loop.b": ["loop.a"]}
    pack = "jit(f)/pack/convert_element_type"
    assert xsteps._inherit(steps, operands) == {
        "reduce-window.1": pack, "slice.2": pack, "add_fusion": pack}
    # in the recorded loop the all-reduce's copies are the merge's inputs'
    (_pid, loop), = [(k, p) for k, p in xsteps.table(MESH).items()
                     if p["module"] == CC]
    assert all("/" not in loop["steps"][n] and "/" in p
               for n, p in loop["inherited"].items())
    assert loop["inherited"]


def test_step_of_takes_the_innermost_declared_scope_and_never_the_operation():
    steps = ("join", "join_scan", "gather")
    assert xsteps.step_of("jit(f)/join/join_scan/cummax", steps) == "join_scan"
    assert xsteps.step_of("jit(f)/join/sort", steps) == "join"
    # the last component is JAX's name for the operation, not a scope
    assert xsteps.step_of("jit(f)/shard_map/gather", steps) is None
    assert xsteps.step_of("jit(f)/shard_map/gather/gather", steps) == "gather"
    assert xsteps.step_of("", steps) is None
    assert xsteps.instruction("%fusion.12 = u64[8]{0} fusion(...)") \
        == "fusion.12"
    assert xsteps.instruction("sort.3") == "sort.3"


def _recorded_run(tmp_path, path=MESH):
    """A run whose trace directory holds the recorded file where the
    harness writes a cell's."""
    d = tmp_path / "trace" / "plugins" / "profile" / "2026_10_03"
    d.mkdir(parents=True)
    shutil.copy(path, d / "host.xplane.pb")
    return types.SimpleNamespace(
        trace={"traced": True},
        warmup=types.SimpleNamespace(outdir=str(tmp_path / "job00000")))


def test_step_seconds_on_the_recorded_four_chip_trace(tmp_path):
    run = _recorded_run(tmp_path)
    # the scopes the recording holds (PR 46's: the gathers are not apart)
    held = ["segment_min_dst", "segment_min_src", "pointer_jump"]

    def read(steps, modules=(CC,)):
        return step_seconds.read(run, {"modules": list(modules),
                                       "steps": steps})

    parts = [read([s]) for s in held]
    assert all(p > 0 for p in parts)
    assert read(held) == pytest.approx(sum(parts), rel=1e-6)
    # a part of the program's seconds, and most of them: every execution is
    # 15.36-15.42 ms on every chip, 13.75 ms of it inside operations
    raw = xtrace.load(xsteps.trace_file(run), {xtrace.JOB_SPAN})
    runs = [b - a for dev in raw["devices"].values()
            for a, b, nm in dev["modules"] if xtrace.module_name(nm) == CC]
    assert len(runs) == 8
    assert 0.85 * min(runs) < read(held) <= max(runs)
    # the merge is under no scope in the recording; the file's own table
    # says which instruction it is
    assert read(["merge"]) is None
    # a step of another program, a program that did not run, no trace
    assert read(["gather_ranks"]) is None
    assert read(held, ["jit_pagerank_loop"]) is None
    assert read(held, [CC, "jit_pagerank_loop"]) == pytest.approx(read(held))
    run.trace = None
    assert read(held) is None


def test_step_seconds_takes_the_innermost_declared_step(tmp_path,
                                                        monkeypatch):
    """The recording's gathers lie under ``segment_min_*`` alone; were the
    operation's own name a scope, ``gather`` would claim them."""
    run = _recorded_run(tmp_path)
    args = {"modules": [CC], "steps": ["gather"]}
    assert step_seconds.read(run, args) is None
    whole = step_seconds.read(run, {"modules": [CC],
                                    "steps": ["segment_min_dst"]})
    # declare a step inside it that the recording has as a path component:
    # nothing has, so the seconds stay where they are
    monkeypatch.setitem(xsteps._DECLARED, CC,
                        list(xsteps.declared(CC)) + ["scatter-min"])
    assert step_seconds.read(run, {"modules": [CC], "steps": [
        "segment_min_dst"]}) == pytest.approx(whole)


def test_step_named_share_on_the_recorded_four_chip_trace(tmp_path,
                                                          monkeypatch):
    run = _recorded_run(tmp_path)
    share = step_named_share.read(run, {})
    # the recorded loop names its two scatters with their gathers and the
    # pointer jump; its pmin and the loop's own arithmetic lie outside
    assert 90.0 < share < 100.0
    # a made-up step list: nothing of the program falls on it
    monkeypatch.setitem(xsteps._DECLARED, CC, ["no_such_step"])
    assert step_named_share.read(run, {}) == 0.0
    # no declared program ran: nothing, not zero
    monkeypatch.delitem(xsteps._DECLARED, CC)
    assert step_named_share.read(run, {}) is None
    run.trace = None
    assert step_named_share.read(run, {}) is None


def test_the_one_chip_recording_runs_no_declared_program(tmp_path):
    run = _recorded_run(tmp_path, ONE_CHIP)
    assert step_named_share.read(run, {}) is None
    assert step_seconds.read(run, {"modules": ["jit_tail"],
                                   "steps": ["gather"]}) is None


# -- what the metric files quote, the program declares ---------------------------

def _metric_files():
    d = os.path.join(cells.BENCH_DIR, "layer_metrics")
    for fn in sorted(os.listdir(d)):
        with open(os.path.join(d, fn)) as f:
            yield fn[:-len(".json")], json.load(f)


def test_the_benchmarks_step_table_is_the_programs():
    from gpu_mapreduce_tpu.obs import names
    with open(xsteps.STEPS_FILE) as f:
        table = json.load(f)["steps"]
    assert table == {k: list(v) for k, v in names.STEPS.items()}
    assert set(table) == set(names.PROGRAMS) | set(names.PROGRAM_PREFIXES)
    assert xsteps.declared("jit_kv_map_edge_upper") == names.steps_of(
        "jit_kv_map_edge_upper") == ("kernel", "pack")
    assert xsteps.declared("jit_run") == ()


def test_every_step_a_metric_file_quotes_is_declared_for_its_module():
    from gpu_mapreduce_tpu.obs import names
    spec = cells.load_benchmark()
    listed = {m["name"]: m for m in spec["per_layer"]}
    quoting = 0
    for name, body in _metric_files():
        if body["reader"] != "step_seconds":
            continue
        quoting += 1
        assert listed[name]["source"] == "device_trace", name
        args = body["args"]
        for module in args["modules"]:
            assert names.declared_program(module), (name, module)
            assert set(args["steps"]) <= set(names.steps_of(module)), (
                name, module)
    assert quoting == 12
    # the attrs of the two fill shares and the spans of the five twins
    for name in ("pagerank_fill_share", "loop_fill_share"):
        args = dict(_metric_files())[name]["args"]
        assert set(args["spans"]) <= set(names.SPANS)
        assert args["num"] == [names.ATTR_EDGES]
        assert args["den"] == [names.ATTR_EDGE_ROWS]
    # what these metrics are for is PERF.md's to say
    with open(os.path.join(cells.ROOT, "PERF.md")) as f:
        text = f.read()
    for name, body in _metric_files():
        if body["reader"] in ("step_seconds", "step_named_share"):
            assert f"`{name}`" in text, name


def test_every_cell_reports_the_coverage_and_its_step_metrics():
    want = {
        "graph-iter-1chip": {"pagerank_gather_dev_s", "pagerank_scatter_dev_s",
                             "cc_gather_dev_s", "cc_scatter_dev_s",
                             "stage_sort_dev_s"},
        "graph-iter-4chip": {"pagerank_gather_dev_s", "pagerank_scatter_dev_s",
                             "cc_gather_dev_s", "cc_scatter_dev_s",
                             "stage_sort_dev_s"},
        "graph-tri-1chip": {"stage_sort_dev_s", "wedge_compact_dev_s",
                            "wedge_join_dev_s"},
        "graph-build-1chip": {"pack_dev_s", "group_first_gather_dev_s"},
        "graph-build-4chip": {"pack_dev_s", "group_first_gather_dev_s"},
        "terasort-hbm-1chip": {"record_take_dev_s"},
        "terasort-4chip": {"record_take_dev_s"},
        "invindex-puma-1chip": {"extract_mark_dev_s", "extract_tail_dev_s"},
        "invindex-puma-4chip": {"extract_mark_dev_s", "extract_tail_dev_s"},
        "tpch-q3-1chip": set(), "wordfreq-zipf-4chip": set()}
    assert set(want) == set(cells.cell_names())
    for cell, metrics in want.items():
        per_layer = cells.load_cell(cell).metrics["per_layer"]
        got = {m["name"] for m in per_layer if m["reader"] == "step_seconds"}
        assert got == metrics, cell
        assert "step_named_share" in {m["name"] for m in per_layer}, cell
