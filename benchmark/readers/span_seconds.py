"""Median per job of the seconds covered by the program's spans of the given
names (obs tracer): the union of their intervals, so that a stage span and
the op span inside it are not counted twice."""

from benchmark import arith


def read(run, args):
    names = set(args["names"])
    rows = []
    for j in run.jobs:
        if not j.spans:
            return None         # the tracer was off: nothing to read
        rows.append(1e-6 * arith.union_length(
            (e["ts"], e["ts"] + e["dur"]) for e in j.spans
            if e["name"] in names))
    return arith.median(rows) if rows else None
