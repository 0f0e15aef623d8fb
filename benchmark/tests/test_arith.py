"""Metric arithmetic on hand-made numbers and spans."""

import types

import pytest

from benchmark import arith, harness
from benchmark.readers import (counter_share, job_median, self_seconds,
                               span_seconds, stage_seconds,
                               warm_start_seconds, work_rate)


def test_median_and_rate():
    assert arith.median([3.0, 1.0, 2.0]) == 2.0
    assert arith.median([4.0, 1.0, 2.0, 3.0]) == 2.5
    with pytest.raises(ValueError):
        arith.median([])
    # 3 jobs of 10 units, last done 6 s after the window opened
    assert arith.rate(10.0, 3, 100.0, 106.0) == 5.0
    with pytest.raises(ValueError):
        arith.rate(10.0, 0, 100.0, 106.0)


def test_union_gaps_and_self_time():
    assert arith.merge([(5, 6), (1, 2), (1.5, 3), (4, 4)]) == [(1, 3), (5, 6)]
    assert arith.union_length([(1, 2), (1.5, 3), (5, 6)]) == 3.0
    assert arith.gaps([(1, 2), (1.5, 3), (5, 6)], 0, 7) == [
        (0, 1), (3, 5), (6, 7)]
    # children overlap each other and stick out of the span
    assert arith.self_time((0, 10), [(1, 4), (2, 5), (9, 12)]) == 5.0


def test_innermost_and_attribution():
    spans = [(0, 10, "job"), (1, 4, "x"), (2, 3, "y"), (12, 13, "w")]
    segs = arith.innermost(spans)
    assert segs == [(0, 1, "job"), (1, 2, "x"), (2, 3, "y"), (3, 4, "x"),
                    (4, 10, "job"), (12, 13, "w")]
    got = arith.attribute([(0.5, 2.5), (11, 12.5)], segs)
    assert got == {"job": 0.5, "x": 1.0, "y": 0.5, "w": 0.5,
                   "(no program span open)": 1.0}


def _span(name, cat, t0, t1, **args):
    return {"name": name, "cat": cat, "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6,
            "args": args}


def _run(jobs, **kw):
    base = dict(cell=None, setup_seconds=30.0, warmup=None, jobs=jobs,
                window_t0=100.0, work={"edges": 8e6}, compiles={},
                memory_peak_bytes=0, device_kind="cpu", info={},
                span_epoch=0.0)
    base.update(kw)
    return harness.Run(**base)


def test_readers_on_hand_made_jobs():
    """Two jobs of 4 s and 6 s: collate covers part of each, the command
    span covers all of it and is not a child."""
    def job(i, t0, t1, spans, stages):
        return harness.JobRecord(i, t0, t1, "", {"stages": stages}, spans)
    a = job(1, 100.0, 104.0, [
        _span("oink.rmat", "oink", 100.0, 104.0),
        _span("collate", "mr_op", 100.5, 103.0),
        _span("aggregate", "mr_op", 100.5, 101.0,
              shuffle_sent_bytes=300, shuffle_pad_bytes=100),
        _span("shuffle.exchange", "shuffle", 100.6, 100.9,
              shuffle_sent_bytes=300, shuffle_pad_bytes=100),
        _span("convert", "mr_op", 101.0, 103.0),
        _span("reduce", "mr_op", 103.2, 103.7)], {"rmat": 4.0})
    b = job(2, 104.0, 110.0, [
        _span("oink.rmat", "oink", 104.0, 110.0),
        _span("collate", "mr_op", 105.0, 109.0),
        _span("aggregate", "mr_op", 105.0, 106.0, shuffle_sent_bytes=300),
        _span("convert", "mr_op", 106.0, 109.0)], {"rmat": 6.0})
    warm = harness.JobRecord(0, 50.0, 70.0, "", {})
    run = _run([a, b], warmup=warm)
    assert job_median.read(run, {}) == 5.0
    # 2 jobs x 8 Medges, last done 10 s after the window opened
    assert work_rate.read(run, {"work": "edges", "scale": 1e-6}) == \
        pytest.approx(1.6)
    assert work_rate.read(run, {"work": "corpus_bytes"}) is None
    below = {"child_cats": ["mr_op", "shuffle"], "child_names": []}
    # self time: 4 - (2.5 + 0.5) = 1.0 and 6 - 4 = 2.0 -> median 1.5
    assert self_seconds.read(run, below) == pytest.approx(1.5)
    assert span_seconds.read(run, {"names": ["convert", "reduce"]}) == \
        pytest.approx(2.75)
    assert span_seconds.read(run, {"names": ["aggregate"]}) == \
        pytest.approx(0.75)
    assert stage_seconds.read(run, {"stages": ["rmat"]}) == 5.0
    assert stage_seconds.read(run, {"stages": ["cc_find"]}) is None
    # pad 100 of sent 600 + pad 100, read from the op span only
    share = counter_share.read(run, {
        "spans": ["aggregate"], "num": ["shuffle_pad_bytes"],
        "den": ["shuffle_sent_bytes", "shuffle_pad_bytes"]})
    assert share == pytest.approx(100.0 * 100 / 700)
    assert counter_share.read(run, {
        "spans": ["nothing"], "num": ["a"], "den": ["b"]}) is None
    assert warm_start_seconds.read(run, {}) == pytest.approx(15.0)


def test_span_readers_return_nothing_without_spans():
    j = harness.JobRecord(1, 0.0, 1.0, "", {"stages": {}})
    run = _run([j])
    assert span_seconds.read(run, {"names": ["convert"]}) is None
    assert self_seconds.read(run, {"child_cats": ["mr_op"]}) is None
