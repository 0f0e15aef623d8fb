"""Median per job of a work count the program records as span attributes:
the sum of the attributes ``attrs`` over the job's spans named ``spans``
(rounds of a command, iterations of a device loop).  Nothing when no such
span carries one of the attributes: the tracer was off, or the program
does not record the count."""

from benchmark import arith


def read(run, args):
    spans, attrs = set(args["spans"]), args["attrs"]
    counts = [[e["args"][k] for e in j.spans if e["name"] in spans
               for k in attrs if k in e["args"]] for j in run.jobs]
    return arith.median([sum(c) for c in counts]) if any(counts) else None
