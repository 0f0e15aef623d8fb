"""Median wall seconds of the jobs completed in the window."""

from benchmark import arith


def read(run, args):
    if not run.jobs:
        return None
    return arith.median([j.wall for j in run.jobs])
