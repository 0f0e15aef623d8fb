"""TPC-H Query 3 through ``OinkScript`` (``tpch_load`` + ``tpch_q3``) on
seeded tables at a tiny scale, held to the plain numpy reference
(``benchmark/refs/tpch.py``) in every group and in its ten lines, on the
serial backend, a mesh of one and the CPU mesh of four."""

import io
import os

import numpy as np
import pytest

from benchmark import CheckFailure
from benchmark.gen import tpch as gen
from benchmark.refs import tpch as ref
from gpu_mapreduce_tpu import MRError
from gpu_mapreduce_tpu.apps import tpch as app
from gpu_mapreduce_tpu.obs import get_tracer, names
from gpu_mapreduce_tpu.oink.script import OinkScript
from gpu_mapreduce_tpu.parallel.mesh import make_mesh

BACKENDS = ["serial", "mesh1", "mesh4"]
SF, SEED = 0.004, 4


def _comm(backend):
    return {"serial": None, "mesh1": make_mesh(1),
            "mesh4": make_mesh(4)}[backend]


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch")
    paths = gen.make_tables(str(d), SF, SEED)
    return paths, [ref.read_table(t, paths[t]) for t in ref.TABLES]


def _written(tmp_path, customer, orders, lineitem) -> dict:
    """Table files of hand-made tables."""
    paths = {}
    for t, (key, value) in zip(ref.TABLES, (customer, orders, lineitem)):
        paths[t] = [str(tmp_path / f"{t}.dat")]
        ref.pack(t, key, value).tofile(paths[t][0])
    return paths


def _loaded(comm, paths):
    script = OinkScript(comm=comm, screen=io.StringIO())
    for t in ref.TABLES:
        script.run_string(f"variable f{t} index {' '.join(paths[t])}")
    script.run_string("tpch_load -i v_fcustomer v_forders v_flineitem "
                      "-o NULL customer -o NULL orders -o NULL lineitem")
    return script


def _q3(script, out, segment="BUILDING", date="1995-03-15"):
    script.run_string(f"tpch_q3 {segment} {date} -i customer orders "
                      f"lineitem -o {out} mrq3")
    with open(out) as f:
        return f.read().splitlines()


def _groups(script):
    kv = script.obj.get_mr("mrq3").kv
    if not kv.nkv:
        z = np.zeros(0, np.int64)
        return dict(orderkey=z, revenue=z, orderdate=z, shippriority=z)
    fr = kv.one_frame().to_host()
    key = np.asarray(fr.key.to_host().data).astype(np.int64)
    return {"orderkey": (key[:, 0] << 32) | key[:, 1],
            "revenue": np.asarray(fr.value.to_host().data),
            "orderdate": key[:, 2], "shippriority": key[:, 3]}


def _bits(script):
    """Every word of the three tables as the named MR objects hold them."""
    out = []
    for t in ref.TABLES:
        fr = script.obj.get_mr(t).kv.one_frame()
        out.append((np.asarray(getattr(fr.key, "data", fr.key)).copy(),
                    np.asarray(getattr(fr.value, "data", fr.value)).copy()))
    return out


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("segment, date", [
    ("BUILDING", "1995-03-15"), ("MACHINERY", "1993-07-01"),
    ("AUTOMOBILE", "1997-12-24"), ("HOUSEHOLD", "1992-02-01")])
def test_q3_equals_the_reference(tables, tmp_path, backend, segment, date):
    paths, tabs = tables
    script = _loaded(_comm(backend), paths)
    printed = _q3(script, str(tmp_path / "q3.txt"), segment, date)
    want = ref.q3(*tabs, segment, date)
    facts = ref.check_q3(want, _groups(script), printed)
    assert facts["groups"] == len(want["orderkey"]) > 0
    assert len(printed) == min(10, facts["groups"])
    said = script.screen.getvalue().splitlines()
    assert said[0] == (f"TPC-H: {len(tabs[0][0])} customer rows, "
                       f"{len(tabs[1][0])} orders rows, "
                       f"{len(tabs[2][0])} lineitem rows")
    kept = ", ".join(f"{t} {want['scanned'][t][1]} of {want['scanned'][t][0]}"
                     for t in ref.TABLES)
    assert said[1] == (
        f"TPC-H Q3 {segment} {date}: rows kept {kept}; "
        f"{want['matched']['orders'][1]} orders and "
        f"{want['matched']['lineitem'][1]} lines joined; "
        f"{facts['groups']} groups, {len(printed)} lines")


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_date_that_selects_nothing_gives_an_empty_file(tables, tmp_path,
                                                         backend):
    paths, tabs = tables
    script = _loaded(_comm(backend), paths)
    assert _q3(script, str(tmp_path / "q3.txt"), "BUILDING",
               "1992-01-01") == []
    assert script.obj.get_mr("mrq3").kv.nkv == 0
    assert "0 groups, 0 lines" in script.screen.getvalue()


@pytest.mark.parametrize("backend", BACKENDS)
def test_three_jobs_leave_the_tables_bit_for_bit(tables, tmp_path, backend):
    paths, tabs = tables
    script = _loaded(_comm(backend), paths)
    before = _bits(script)
    frames = [script.obj.get_mr(t).kv.one_frame() for t in ref.TABLES]
    first = _q3(script, str(tmp_path / "q3-0.txt"))
    for i in (1, 2):
        assert _q3(script, str(tmp_path / f"q3-{i}.txt")) == first
    for t, fr, (k, v), (k1, v1) in zip(ref.TABLES, frames, before,
                                       _bits(script)):
        assert script.obj.get_mr(t).kv.one_frame() is fr, t
        np.testing.assert_array_equal(k, k1)
        np.testing.assert_array_equal(v, v1)
    for (key, value), (k, v) in zip(tabs, before):
        if backend == "serial":     # the file's rows, in the file's order
            np.testing.assert_array_equal(
                (k[:, 0].astype(np.uint64) << np.uint64(32)) | k[:, 1], key)
            np.testing.assert_array_equal(v, value)


@pytest.mark.parametrize("backend", BACKENDS)
def test_ties_in_the_ten_come_in_either_order_of_their_keys(tmp_path,
                                                            backend):
    """Every line of every order at the same price and discount, every
    order of the same date: the groups tie in (revenue, o_orderdate) by
    the hundred (an order of seven lines: 7 x 950.0000), and the ten lines
    are held by those two columns."""
    ck, cv = gen.customer(SF, SEED)
    (ok, ov), (lk, lv) = gen.orders_chunk(SF, SEED, 0)
    ov[:, ref.col("orders", "orderdate")] = ref.day("1995-03-01")
    lv[:, ref.col("lineitem", "shipdate")] = ref.day("1995-04-01")
    lv[:, ref.col("lineitem", "extendedprice")] = 100000
    lv[:, ref.col("lineitem", "discount")] = 5
    paths = _written(tmp_path, (ck, cv), (ok, ov), (lk, lv))
    tabs = [ref.read_table(t, paths[t]) for t in ref.TABLES]
    want = ref.q3(*tabs, "BUILDING", "1995-03-15")
    assert ref.tied(want) and len(want["orderkey"]) > 100
    script = _loaded(_comm(backend), paths)
    printed = _q3(script, str(tmp_path / "q3.txt"))
    assert ref.check_q3(want, _groups(script), printed)["tie_in_the_ten"]
    assert [l.split("|")[1] for l in printed] == ["6650.0000"] * 10


# -- the check can fail -------------------------------------------------------

def test_a_float32_sum_is_caught(tables, tmp_path, monkeypatch):
    import jax.numpy as jnp
    from gpu_mapreduce_tpu.parallel import group
    paths, tabs = tables
    real = group.segment_reduce_rows

    def lossy(x, seg, valid, gcap, op):
        if op == "sum" and x.dtype == jnp.int64:
            return real(x.astype(jnp.float32), seg, valid, gcap,
                        op).astype(jnp.int64)
        return real(x, seg, valid, gcap, op)
    monkeypatch.setattr(group, "segment_reduce_rows", lossy)
    group._reduce_cached.cache_clear()
    try:
        script = _loaded(make_mesh(1), paths)
        printed = _q3(script, str(tmp_path / "q3.txt"))
        with pytest.raises(CheckFailure, match="groups differ"):
            ref.check_q3(ref.q3(*tabs, "BUILDING", "1995-03-15"),
                         _groups(script), printed)
    finally:
        monkeypatch.undo()
        group._reduce_cached.cache_clear()


@pytest.mark.parametrize("backend", ["mesh1", "mesh4"])
def test_a_join_that_loses_a_shards_last_build_row_is_caught(
        tmp_path, monkeypatch, backend):
    """Every customer of the segment, and the last one (whose key, a
    multiple of three, would have no order) given an early order: the row
    a shard loses is then one with partners."""
    from gpu_mapreduce_tpu.parallel import group
    ck, cv = gen.customer(SF, SEED)
    (ok, ov), (lk, lv) = gen.orders_chunk(SF, SEED, 0)
    cv[:, ref.col("customer", "mktsegment")] = 1
    ov[0, ref.col("orders", "custkey")] = ck[-1]
    ov[0, ref.col("orders", "orderdate")] = ref.day("1995-03-01")
    lv[lk == ok[0], ref.col("lineitem", "shipdate")] = ref.day("1995-04-01")
    paths = _written(tmp_path, (ck, cv), (ok, ov), (lk, lv))
    tabs = [ref.read_table(t, paths[t]) for t in ref.TABLES]
    want = ref.q3(*tabs, "BUILDING", "1995-03-15")
    real = group.join_rows_body
    monkeypatch.setattr(
        group, "join_rows_body",
        lambda pk, pc, bk, bc: real(pk, pc, bk, bc - 1))
    group._join_jit.cache_clear()
    try:
        script = _loaded(_comm(backend), paths)
        printed = _q3(script, str(tmp_path / "q3.txt"))
        said = script.screen.getvalue().splitlines()[1]
        # the command's counts are part of the result (the benchmark's
        # check holds the message to the reference's): fewer orders joined
        assert f"{want['matched']['orders'][1]} orders and" not in said
        with pytest.raises(CheckFailure):
            ref.check_q3(want, _groups(script), printed)
    finally:
        monkeypatch.undo()
        group._join_jit.cache_clear()
    script = _loaded(_comm(backend), paths)     # and whole, it is right
    ref.check_q3(want, _groups(script) if _q3(
        script, str(tmp_path / "q3.txt")) else {}, open(
            tmp_path / "q3.txt").read().splitlines())


def test_a_duplicate_primary_key_fails_the_job(tmp_path):
    ck, cv = gen.customer(SF, SEED)
    (ok, ov), (lk, lv) = gen.orders_chunk(SF, SEED, 0)
    seg = cv[:, ref.col("customer", "mktsegment")] == 1
    ck[np.flatnonzero(seg)[1]] = ck[np.flatnonzero(seg)[0]]
    paths = _written(tmp_path, (ck, cv), (ok, ov), (lk, lv))
    for comm in (None, make_mesh(4)):
        script = _loaded(comm, paths)
        with pytest.raises(MRError, match="occur more than once"):
            _q3(script, str(tmp_path / "q3.txt"))


def test_bad_arguments_are_refused(tables, tmp_path):
    paths, _ = tables
    script = _loaded(None, paths)
    out = str(tmp_path / "q3.txt")
    with pytest.raises(MRError, match="no market segment"):
        _q3(script, out, "GARDENING")
    with pytest.raises(MRError, match="is no date"):
        _q3(script, out, "BUILDING", "15.03.1995")
    with pytest.raises(MRError, match="Illegal tpch_q3"):
        script.run_string(f"tpch_q3 BUILDING -i customer orders lineitem "
                          f"-o {out} mrq3")
    with pytest.raises(MRError, match="three named tables"):
        script.run_string(f"tpch_q3 BUILDING 1995-03-15 -i "
                          f"{paths['customer'][0]} orders lineitem "
                          f"-o {out} mrq3")
    with pytest.raises(MRError, match="not MR objects"):
        script.run_string("tpch_load -i customer orders lineitem "
                          "-o NULL a -o NULL b -o NULL c")
    short = str(tmp_path / "short.dat")
    open(short, "wb").write(b"x" * 25)
    script.run_string(f"variable fshort index {short}")
    with pytest.raises(MRError, match="no whole number"):
        script.run_string("tpch_load -i v_fshort v_fshort v_fshort "
                          "-o NULL a -o NULL b -o NULL c")


def test_the_application_and_the_reference_agree_on_the_layout():
    assert app.COLUMNS == ref.COLUMNS and app.SEGMENTS == ref.SEGMENTS
    assert app.EPOCH == ref.EPOCH and app.KEY_BYTES == ref.KEY_BYTES
    assert {t: f.record_bytes for t, f in app.FORMATS.items()} == {
        "customer": 24, "orders": 44, "lineitem": 68}
    assert app.day("1995-03-15") == ref.day("1995-03-15") == 1169
    assert app.iso(1169) == "1995-03-15"
    assert app.line((0, 7, 1169, 0), 12345678) == "7|1234.5678|1995-03-15|0"
    assert app.line((1, 2, 0, 3), 5) == f"{(1 << 32) + 2}|0.0005|1992-01-01|3"


@pytest.mark.parametrize("backend", ["mesh1", "mesh4"])
def test_the_query_emits_its_spans(tables, tmp_path, backend):
    paths, tabs = tables
    tracer = get_tracer()
    tracer.enable()
    try:
        tracer.clear()
        script = _loaded(_comm(backend), paths)
        _q3(script, str(tmp_path / "q3.txt"))
        events = tracer.events()
    finally:
        tracer.disable()
    want = ref.q3(*tabs, "BUILDING", "1995-03-15")
    by = lambda name: [e for e in events if e["name"] == name]
    loads = {e["args"]["table"]: e["args"] for e in by(names.TPCH_LOAD)}
    assert {t: a["rows"] for t, a in loads.items()} == {
        t: len(tab[0]) for t, tab in zip(ref.TABLES, tabs)}
    assert loads["lineitem"]["bytes"] == 68 * len(tabs[2][0])
    (root,) = by(names.TPCH_Q3)
    assert root["cat"] == names.ENTRY and root["args"]["segment"] == "BUILDING"
    scans = {e["args"]["table"]: e["args"] for e in by(names.TPCH_SCAN)}
    assert {t: [a[names.ATTR_ROWS_IN], a[names.ATTR_ROWS_OUT]]
            for t, a in scans.items()} == want["scanned"]
    assert {t: a[names.ATTR_ROW_WORDS_IN] for t, a in scans.items()} == {
        "customer": 6, "orders": 11, "lineitem": 17}
    joins = [[e["args"][names.ATTR_PROBE_ROWS],
              e["args"][names.ATTR_MATCHED_ROWS]]
             for e in by(names.JOIN_SPAN)]
    assert joins == [want["matched"]["orders"], want["matched"]["lineitem"]]
    assert by(names.JOIN_SPAN)[1]["args"][names.ATTR_BUILD_ROWS] == \
        want["matched"]["orders"][1]
    (topn,), (emit,) = by(names.TPCH_TOPN), by(names.TPCH_EMIT)
    assert topn["args"]["rows"] == len(want["orderkey"])
    assert emit["args"]["rows"] == 10 and emit["args"]["bytes"] > 0
    # every span of the job lies inside the command's, the scans first
    lo, hi = root["ts"], root["ts"] + root["dur"]
    for e in by(names.TPCH_SCAN) + by(names.JOIN_SPAN) + [topn, emit]:
        assert lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1
    programs = {names.KV_SCAN_PREFIX + "tpch_" + t for t in ref.TABLES}
    assert set(app.SCAN_PROGRAMS) == programs
    assert all(names.declared_program(p) for p in programs)


# -- Query 1 (ISSUE 50) -------------------------------------------------------

from benchmark.refs import tpch_q1 as refq1      # noqa: E402


def _q1(script, out, delta=90):
    script.run_string(f"tpch_q1 {delta} -i lineitem -o {out} mrq1")
    with open(out) as f:
        return f.read().splitlines()


def _q1_groups(script):
    kv = script.obj.get_mr("mrq1").kv
    fr = kv.one_frame()
    fr = fr if hasattr(fr.key, "data") else fr.to_host()
    key = np.asarray(fr.key.to_host().data).reshape(-1, 2)
    value = np.asarray(fr.value.to_host().data).reshape(-1, 6)
    return {"returnflag": key[:, 0], "linestatus": key[:, 1],
            **{name: value[:, i] for i, name in enumerate(refq1.SUMS)}}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("delta", [90, 0, 1270, 2000])
def test_q1_equals_the_reference(tables, tmp_path, backend, delta):
    """Every sum and count of every group, and the lines in the ORDER BY's
    order; the later DELTAs cut whole groups away (N/O is shipped after
    1995-06-17, N/F received after it)."""
    paths, tabs = tables
    script = _loaded(_comm(backend), paths)
    printed = _q1(script, str(tmp_path / "q1.txt"), delta)
    want = refq1.q1(tabs[2], delta)
    facts = refq1.check_q1(want, _q1_groups(script), printed)
    assert [l[:3] for l in printed] == {
        90: ["A|F", "N|F", "N|O", "R|F"], 0: ["A|F", "N|F", "N|O", "R|F"],
        1270: ["A|F", "N|F", "R|F"], 2000: ["A|F", "R|F"]}[delta]
    assert facts["groups"] == len(printed)
    rows, kept = want["scanned"]["lineitem"]
    assert script.screen.getvalue().splitlines()[1] == (
        f"TPC-H Q1 DELTA {delta}: {rows} lineitem rows scanned, {kept} "
        f"kept; {facts['groups']} groups, {len(printed)} lines")
    assert 0 < kept <= rows and (kept == rows) == (delta == 0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_delta_that_keeps_nothing_gives_an_empty_file(tables, tmp_path,
                                                        backend):
    paths, tabs = tables
    script = _loaded(_comm(backend), paths)
    for delta in (2526, 5000):      # the day before the first; before day 0
        assert _q1(script, str(tmp_path / "q1.txt"), delta) == []
        assert script.obj.get_mr("mrq1").kv.nkv == 0
        assert refq1.q1(tabs[2], delta)["scanned"]["lineitem"][1] == 0
    assert "0 kept; 0 groups, 0 lines" in script.screen.getvalue()


@pytest.mark.parametrize("backend", BACKENDS)
def test_three_q1_jobs_leave_the_tables_bit_for_bit(tables, tmp_path,
                                                    backend):
    paths, tabs = tables
    script = _loaded(_comm(backend), paths)
    before = _bits(script)
    frames = [script.obj.get_mr(t).kv.one_frame() for t in ref.TABLES]
    first = _q1(script, str(tmp_path / "q1-0.txt"))
    for i in (1, 2):
        assert _q1(script, str(tmp_path / f"q1-{i}.txt")) == first
    assert len(first) == 4
    for t, fr, (k, v), (k1, v1) in zip(ref.TABLES, frames, before,
                                       _bits(script)):
        assert script.obj.get_mr(t).kv.one_frame() is fr, t
        np.testing.assert_array_equal(k, k1)
        np.testing.assert_array_equal(v, v1)


def _q1_checked(paths, tabs, tmp_path, comm):
    script = _loaded(comm, paths)
    printed = _q1(script, str(tmp_path / "q1.txt"))
    return refq1.check_q1(refq1.q1(tabs[2], 90), _q1_groups(script), printed)


def test_a_float_sum_in_q1_is_caught(tables, tmp_path, monkeypatch):
    """float32; a float64 sum is exact at this size (under 2^53) and is
    planted at cell size on the chip (PERF.md §6, PR 50)."""
    import jax.numpy as jnp
    lossy = "float32"
    from gpu_mapreduce_tpu.parallel import group
    paths, tabs = tables
    real = group._FOLD["sum"]
    monkeypatch.setitem(group._FOLD, "sum", (
        lambda x, m: real[0](x.astype(lossy), m).astype(jnp.int64),
        *real[1:]))
    group._combine_jit.cache_clear()
    try:
        with pytest.raises(CheckFailure, match="groups differ"):
            _q1_checked(paths, tabs, tmp_path, make_mesh(1))
    finally:
        monkeypatch.undo()
        group._combine_jit.cache_clear()
    assert _q1_checked(paths, tabs, tmp_path, make_mesh(1))["groups"] == 4


@pytest.mark.parametrize("backend", ["mesh1", "mesh4"])
def test_a_fold_that_loses_a_tiles_last_row_is_caught(tables, tmp_path,
                                                      monkeypatch, backend):
    """Tiles of 256 rows, so that every shard's block is several and the
    row each one loses is a row of the table."""
    import jax.numpy as jnp
    from gpu_mapreduce_tpu.parallel import group
    paths, tabs = tables
    real = group._FOLD["sum"]

    def short(x, m):
        last = jnp.arange(m.shape[0]).reshape(m.shape[:1] + (1,) * (
            m.ndim - 1)) == m.shape[0] - 1
        return real[0](x, m & ~last)
    monkeypatch.setattr(group, "COMBINE_TILE", 256)
    monkeypatch.setitem(group._FOLD, "sum", (short, *real[1:]))
    group._combine_jit.cache_clear()
    try:
        with pytest.raises(CheckFailure, match="groups differ"):
            _q1_checked(paths, tabs, tmp_path, _comm(backend))
        monkeypatch.setitem(group._FOLD, "sum", real)   # whole, by tiles
        group._combine_jit.cache_clear()
        assert _q1_checked(paths, tabs, tmp_path,
                           _comm(backend))["groups"] == 4
    finally:
        monkeypatch.undo()
        group._combine_jit.cache_clear()


def test_a_dropped_group_and_a_wrong_line_are_caught(tables, tmp_path):
    paths, tabs = tables
    script = _loaded(make_mesh(1), paths)
    printed = _q1(script, str(tmp_path / "q1.txt"))
    want, got = refq1.q1(tabs[2], 90), _q1_groups(script)
    smallest = int(np.argmin(got["count"]))             # N/F
    assert chr(got["returnflag"][smallest]) + chr(
        got["linestatus"][smallest]) == "NF"
    fewer = {k: np.delete(v, smallest) for k, v in got.items()}
    with pytest.raises(CheckFailure, match="3 groups where the reference"):
        refq1.check_q1(want, fewer, printed)
    with pytest.raises(CheckFailure, match="printed lines differ"):
        refq1.check_q1(want, got, printed[:smallest] + printed[smallest + 1:])
    with pytest.raises(CheckFailure, match="printed lines differ"):
        refq1.check_q1(want, got, printed[::-1])
    off = dict(got, sum_charge=got["sum_charge"] + (got["count"] == max(
        got["count"])))
    with pytest.raises(CheckFailure, match="1 groups differ"):
        refq1.check_q1(want, off, printed)


def test_bad_q1_arguments_are_refused(tables, tmp_path):
    paths, _ = tables
    script = _loaded(None, paths)
    out = str(tmp_path / "q1.txt")
    for args in ("", "ninety", "-90", "90 91"):
        with pytest.raises(MRError, match="Illegal tpch_q1"):
            script.run_string(f"tpch_q1 {args} -i lineitem -o {out} mrq1")
    with pytest.raises(MRError, match="named lineitem table"):
        script.run_string(f"tpch_q1 90 -i {paths['lineitem'][0]} -o {out} "
                          f"mrq1")
    for delta in (-1, 1.5, True, "90"):
        with pytest.raises(MRError, match="no number of days"):
            app.q1(script.obj.create_mr, script.obj.get_mr("lineitem"),
                   delta)


def test_q1_and_its_reference_agree_on_the_letters_and_the_lines():
    assert app.RETURNFLAGS == refq1.RETURNFLAGS == "ARN"
    assert app.LINESTATUSES == refq1.LINESTATUSES == "FO"
    assert app.Q1_ANCHOR == refq1.ANCHOR == "1998-12-01"
    assert refq1.bound(90) == app.day("1998-09-02") + 1
    sums = (7, 1999, 10 ** 10 + 5, -(10 ** 12) - 7, 1, 2)
    assert app.q1_line((65, 70), sums) == (
        "A|F|7|19.99|1000000.0005|-1000000.000007|3.50|10.00|0.0050|2")
    one = {"returnflag": [78], "linestatus": [79],
           **{n: [x] for n, x in zip(refq1.SUMS, (7, 1999, 5, 7, 1, 2))}}
    assert refq1.lines(one) == [app.q1_line((78, 79), (7, 1999, 5, 7, 1, 2))]
    assert refq1.lines(one) == ["N|O|7|19.99|0.0005|0.000007|3.50|10.00|"
                                "0.0050|2"]


@pytest.mark.parametrize("backend", ["mesh1", "mesh4"])
def test_q1_emits_its_spans(tables, tmp_path, backend):
    paths, tabs = tables
    tracer = get_tracer()
    tracer.enable()
    try:
        tracer.clear()
        script = _loaded(_comm(backend), paths)
        _q1(script, str(tmp_path / "q1.txt"))
        events = tracer.events()
    finally:
        tracer.disable()
    want = refq1.q1(tabs[2], 90)
    by = lambda name: [e for e in events if e["name"] == name]
    (root,) = by(names.TPCH_Q1)
    assert root["cat"] == names.ENTRY and root["args"]["delta"] == 90
    (scan,) = by(names.TPCH_SCAN)
    assert scan["args"]["table"] == "lineitem"
    assert [scan["args"][names.ATTR_ROWS_IN],
            scan["args"][names.ATTR_ROWS_OUT]] == want["scanned"]["lineitem"]
    assert scan["args"][names.ATTR_ROW_WORDS_IN] == 17
    (compress,) = by(names.COMPRESS_SPAN)
    nshards = 1 if backend == "mesh1" else 4
    assert compress["args"][names.ATTR_COMBINED] == 1
    assert compress["args"][names.ATTR_ROWS] == want["scanned"]["lineitem"][1]
    assert 4 <= compress["args"][names.ATTR_GROUPS] <= 4 * nshards
    assert compress["args"][names.ATTR_KEY_WORDS] == 2
    assert compress["args"][names.ATTR_VALUE_WORDS] == 12
    assert compress["args"][names.ATTR_GROUP_ROWS_MAX] <= max(want["count"])
    if nshards == 1:
        assert compress["args"][names.ATTR_GROUP_ROWS_MAX] == max(
            want["count"])
    (sync,) = by(names.COMBINE_COUNT_SYNC)
    assert sync["args"]["groups"] == compress["args"][names.ATTR_GROUPS]
    (emit,) = by(names.TPCH_EMIT)
    assert emit["args"]["rows"] == 4 and emit["args"]["bytes"] > 0
    lo, hi = root["ts"], root["ts"] + root["dur"]
    for e in [scan, compress, sync, emit] + by(names.CONVERT_SPAN):
        assert lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1
    assert app.Q1_PROGRAMS == (names.KV_SCAN_PREFIX + "tpch_q1",
                               names.COMBINE_PREFIX + "tpch_q1")
    assert all(names.declared_program(p) for p in app.Q1_PROGRAMS)
