"""Percent: the sum of the counter deltas ``num`` over the sum of ``den``,
both read from the spans named ``spans`` (top-level op spans, so that a
delta a child span also carries is counted once).  Nothing when the
denominator is zero — one device, no exchange."""


def read(run, args):
    spans = set(args["spans"])
    num = den = 0.0
    for j in run.jobs:
        for e in j.spans:
            if e["name"] in spans:
                num += sum(e["args"].get(k, 0) for k in args["num"])
                den += sum(e["args"].get(k, 0) for k in args["den"])
    return 100.0 * num / den if den else None
