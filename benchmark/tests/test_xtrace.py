"""The trace reduction: on a hand-made ``.xplane.pb`` whose numbers can be
worked out by eye, and on the small trace recorded on the TPU v5e
(``data/tiny_tpu.xplane.pb``, made by ``record_trace.py``)."""

import os

import pytest

from benchmark import xtrace
from benchmark.readers import device_idle_share, program_hbm_share

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tiny_tpu.xplane.pb")

MS = 10 ** 9        # picoseconds in a millisecond


def _plane(pid, name, lines):
    """``lines``: {line name: [(event name, start_ms, dur_ms)]}."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for i, (ln, evs) in enumerate(lines.items()):
        out.append(f'  lines {{ id: {i + 1} name: "{ln}" timestamp_ns: 0')
        for n, start, dur in evs:
            out.append(f"    events {{ metadata_id: {ids[n]} offset_ps: "
                       f"{int(start * MS)} duration_ps: {int(dur * MS)} }}")
        out.append("  }")
    for n, i in ids.items():
        out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}')
    out.append("}")
    return "\n".join(out)


@pytest.fixture
def handmade(tmp_path):
    """Two traced jobs, 0-100 ms and 100-200 ms.  Device 0 is busy 10-40
    (the extract program; a ``while`` holds a fusion), 60-70 and 120-150;
    device 1 is busy 10-30 and 120-140."""
    from jax.profiler import ProfileData
    text = "\n".join([
        _plane(1, "/device:TPU:0", {
            "XLA Modules": [("jit_body(123)", 10, 30), ("jit_tail(9)", 60, 10),
                            ("jit_body(123)", 120, 30)],
            "XLA Ops": [("%while.1 = u64[8] while(...)", 10, 30),
                        ("%fusion.2 = u64[8] fusion(...)", 15, 10),
                        ("sort.3", 60, 10),
                        ("%while.1 = u64[8] while(...)", 120, 30)],
            "Steps": [("0", 0, 200)]}),
        _plane(2, "/device:TPU:1", {
            "XLA Modules": [("jit_body(123)", 10, 20),
                            ("jit_body(123)", 120, 20)],
            "XLA Ops": [("sort.3", 10, 20), ("sort.3", 120, 20)]}),
        _plane(3, "/host:CPU", {
            "python3": [("bench.job", 0, 100), ("bench.job", 100, 100),
                        ("stage.map", 5, 50), ("stage.map_device", 8, 40),
                        ("PjitFunction(body)", 9, 1),
                        ("stage.reduce", 55, 40),
                        ("stage.map", 105, 50)],
            "worker": [("stage.read", 0, 500)]}),
        _plane(4, "/host:metadata", {})])
    path = tmp_path / "handmade.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_load_finds_devices_lines_and_host_threads(handmade):
    raw = xtrace.load(handmade)
    assert sorted(raw["devices"]) == [0, 1]
    assert len(raw["devices"][0]["ops"]) == 4
    assert len(raw["devices"][0]["modules"]) == 3
    assert raw["planes"]["/device:TPU:0"] == {
        "XLA Modules": 3, "XLA Ops": 4, "Steps": 1}
    assert any(k.startswith("python3") for k in raw["host"])


def test_busy_union_idle_share_and_programs(handmade):
    r = xtrace.reduce(xtrace.load(handmade),
                      {"stage.map", "stage.map_device", "stage.reduce"})
    assert r["traced_jobs"] == 2
    assert r["window_s"] == pytest.approx(0.200)
    # device 0: 30 + 10 + 30 = 70 ms (the fusion is inside the while);
    # device 1: 20 + 20 = 40 ms; mean 55 ms of 200
    assert r["busy_s_per_device"] == pytest.approx([0.070, 0.040])
    assert r["busy_s"] == pytest.approx(0.055)
    assert r["idle_share"] == pytest.approx(1 - 0.055 / 0.200)
    # self time per operation, mean over the two devices
    ops = dict(r["device_ops"])
    assert ops["while.1"] == pytest.approx((20 + 30) / 2 / 1000)
    assert ops["fusion.2"] == pytest.approx(10 / 2 / 1000)
    assert ops["sort.3"] == pytest.approx((10 + 40) / 2 / 1000)
    # the extract program's executions, by device
    assert r["programs"]["jit_body"][0] == pytest.approx([0.030, 0.030])
    assert r["programs"]["jit_body"][1] == pytest.approx([0.020, 0.020])
    assert r["programs"]["jit_tail"][0] == pytest.approx([0.010])
    assert r["program_job_seconds"]["jit_body"] == {
        0: pytest.approx([0.030, 0.030]), 1: pytest.approx([0.020, 0.020])}
    assert r["program_job_seconds"]["jit_tail"] == {0: pytest.approx([0.010])}


def test_idle_gaps_go_to_the_innermost_program_span(handmade):
    r = xtrace.reduce(xtrace.load(handmade),
                      {"stage.map", "stage.map_device", "stage.reduce"})
    gaps = dict(r["idle_gaps"])
    # device 0 idle: 0-10, 40-60, 70-120, 150-200 ms
    #   bench.job: 0-5, 95-100, 100-105, 155-200 = 60
    #   stage.map: 5-8, 48-55, 105-120, 150-155 = 30
    #   stage.map_device: 8-10, 40-48           = 10
    #   stage.reduce: 55-60, 70-95              = 30
    assert gaps["bench.job"] == pytest.approx(0.060)
    assert gaps["stage.map"] == pytest.approx(0.030)
    assert gaps["stage.map_device"] == pytest.approx(0.010)
    assert gaps["stage.reduce"] == pytest.approx(0.030)
    # a runtime TraceMe and another thread's span are not program spans here
    assert "PjitFunction(body)" not in gaps and "stage.read" not in gaps
    assert sum(gaps.values()) == pytest.approx(0.200 - 0.070)


def test_readers_over_the_reduction(handmade):
    from benchmark import harness
    run = harness.Run(
        cell=None, setup_seconds=1.0, warmup=None, jobs=[],
        window_t0=0.0, work={}, compiles={}, memory_peak_bytes=0,
        device_kind="TPU v5 lite",
        info={"programs": {"extract": "jit_body"},
              "bytes_moved": {"extract": 819e9 * 0.00025}},
        trace=xtrace.reduce(xtrace.load(handmade), set()))
    assert device_idle_share.read(run, {}) == pytest.approx(72.5)
    # 0.25 ms at the peak over a median of 25 ms a job: 1 %
    assert program_hbm_share.read(run, {"program": "extract"}) == \
        pytest.approx(1.0)
    assert program_hbm_share.read(run, {"program": "sort"}) is None
    # a job that dispatches the program once per batch: the executions of
    # one job are summed (50 ms a job), not taken one by one
    run.trace["program_job_seconds"]["jit_body"] = {0: [0.030 + 0.020] * 2}
    assert program_hbm_share.read(run, {"program": "extract"}) == \
        pytest.approx(0.5)
    run.trace = None
    assert device_idle_share.read(run, {}) is None


def test_executions_of_one_job_are_summed(tmp_path):
    """One program dispatched twice in the first job (two batches) and once
    in the second."""
    from jax.profiler import ProfileData
    text = "\n".join([
        _plane(1, "/device:TPU:0", {
            "XLA Modules": [("jit_body(1)", 10, 20), ("jit_body(1)", 50, 30),
                            ("jit_body(1)", 110, 40)],
            "XLA Ops": [("fusion.1", 10, 20), ("fusion.1", 50, 30),
                        ("fusion.1", 110, 40)]}),
        _plane(2, "/host:CPU", {
            "python3": [("bench.job", 0, 100), ("bench.job", 100, 100)]})])
    p = tmp_path / "batches.xplane.pb"
    p.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    r = xtrace.reduce(xtrace.load(str(p)))
    assert r["programs"]["jit_body"][0] == pytest.approx([0.020, 0.030, 0.040])
    assert r["program_job_seconds"]["jit_body"][0] == pytest.approx(
        [0.050, 0.040])


def test_a_trace_without_device_or_job_is_refused(tmp_path):
    from jax.profiler import ProfileData
    for text, why in [
            (_plane(1, "/host:CPU", {"python3": [("bench.job", 0, 10)]}),
             "no /device:TPU"),
            (_plane(1, "/device:TPU:0", {"XLA Ops": [("sort.3", 0, 1)]}),
             "no 'bench.job'")]:
        p = tmp_path / "t.xplane.pb"
        p.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
        with pytest.raises(ValueError, match=why):
            xtrace.reduce(xtrace.load(str(p)))


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded TPU trace beside the tests")
def test_recorded_tpu_trace():
    """Three annotated jobs on one v5e chip: ``extract`` then, after 2 ms of
    host work inside ``stage.reduce``, ``tail``."""
    raw = xtrace.load(RECORDED)
    assert list(raw["devices"]) == [0]
    r = xtrace.reduce(raw, {"stage.map_device", "stage.reduce"})
    assert r["traced_jobs"] == 3
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0.0 < r["idle_share"] < 1.0
    # the profiler missed the first execution's module event
    assert len(r["programs"]["jit_extract"][0]) == 2
    assert len(r["programs"]["jit_tail"][0]) == 3
    assert all(0 < s < 0.1 for s in r["programs"]["jit_extract"][0])
    gaps = dict(r["idle_gaps"])
    # the sleeps: 2 ms in each stage.reduce, 1 ms between jobs
    assert gaps["stage.reduce"] >= 3 * 0.002
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
