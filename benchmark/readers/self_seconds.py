"""Median per job of the entry point's self time: the job's wall minus the
part of it covered by spans of the layers below (``child_cats``: span
categories; ``child_names``: span names).  What is left is Python glue in
the application or the command: host staging, ``np.unique``, printers,
part files — and any device work the program has no span around."""

from benchmark import arith


def read(run, args):
    cats, names = set(args.get("child_cats", [])), set(args.get("child_names", []))
    rows = []
    for j in run.jobs:
        if not j.spans:
            return None
        t0 = (j.t0 - run.span_epoch) * 1e6
        t1 = (j.t1 - run.span_epoch) * 1e6
        rows.append(1e-6 * arith.self_time((t0, t1), (
            (e["ts"], e["ts"] + e["dur"]) for e in j.spans
            if e["cat"] in cats or e["name"] in names)))
    return arith.median(rows) if rows else None
