"""The repository's benchmark: one command per cell, driven by data files.

``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` — see ``run.py``.  Everything that decides a number lives
here (generators, references, metric arithmetic, the trace reduction, the
peaks table); from the program the benchmark takes only the system under
test and its spans, counters and kernel names.
"""


class CheckFailure(Exception):
    """A job's result differs from its reference, or a device assertion
    (spread over the mesh, rows exchanged) does not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailure(what)
