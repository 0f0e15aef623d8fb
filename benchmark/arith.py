"""Metric arithmetic: medians, interval unions, self time, rates.

Pure functions over numbers and ``(start, end)`` intervals, so that they can
be checked on hand-made spans (``tests/test_arith.py``).
"""

import bisect
from typing import Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]


def median(xs: Sequence[float]) -> float:
    xs = sorted(xs)
    if not xs:
        raise ValueError("median of nothing")
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of the intervals as sorted, disjoint intervals."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union_length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in merge(intervals))


def gaps(busy: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """What ``[lo, hi]`` holds besides the union of ``busy``."""
    out, at = [], lo
    for a, b in merge(clip(busy, lo, hi)):
        if a > at:
            out.append((at, a))
        at = b
    if hi > at:
        out.append((at, hi))
    return out


def self_time(span: Interval, children: Iterable[Interval]) -> float:
    """A span's duration minus the part of it its child spans cover."""
    lo, hi = span
    return (hi - lo) - union_length(clip(children, lo, hi))


def rate(work_per_job: float, njobs: int, t_start: float,
         t_last_done: float) -> float:
    """Work completed per second, from the window's start to the last
    completion (mean-based: a stall between jobs shows here and not in
    the median job time)."""
    if njobs <= 0 or t_last_done <= t_start:
        raise ValueError("no job completed in the window")
    return work_per_job * njobs / (t_last_done - t_start)


def innermost(spans: Iterable[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """Flatten nested named spans of ONE thread into disjoint segments, each
    named by the innermost span open there (a span that starts later, or
    starts together and ends sooner, is the inner one)."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, float, str]] = []
    at = None

    def emit(until):
        nonlocal at
        if stack and until > at:
            out.append((at, until, stack[-1][2]))
        at = until

    for s in spans:
        while stack and stack[-1][1] <= s[0]:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(s[0])
        at = s[0]
        stack.append(s)
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def attribute(gap_list: Iterable[Interval],
              segments: Iterable[Tuple[float, float, str]],
              other: str = "(no program span open)") -> dict:
    """Seconds of the gaps under each segment's name; what no segment covers
    goes to ``other``."""
    segments = sorted(segments)         # disjoint, so ends are sorted too
    ends = [s[1] for s in segments]
    out: dict = {}
    for a, b in gap_list:
        covered = 0.0
        for s0, s1, name in segments[bisect.bisect_right(ends, a):]:
            if s0 >= b:
                break
            lo, hi = max(a, s0), min(b, s1)
            out[name] = out.get(name, 0.0) + hi - lo
            covered += hi - lo
        if b - a - covered > 1e-12:
            out[other] = out.get(other, 0.0) + b - a - covered
    return out
