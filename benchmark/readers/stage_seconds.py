"""Median per job of the sum of the named stages, from the seconds the job
module reports per stage (InvertedIndex: ``idx.timer.times``; an OINK
script: the harness's clock around each command)."""

from benchmark import arith


def read(run, args):
    rows = [sum(j.result["stages"][s] for s in args["stages"])
            for j in run.jobs
            if all(s in j.result["stages"] for s in args["stages"])]
    return arith.median(rows) if rows else None
