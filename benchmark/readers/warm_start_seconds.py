"""The warm-up job's wall minus the median job of the window: what the first
job of a process pays on top — tracing, and loading (or, cold, compiling)
every program."""

from benchmark import arith


def read(run, args):
    if not run.jobs:
        return None
    return run.warmup.wall - arith.median([j.wall for j in run.jobs])
