"""Work completed per second: ``args["work"]`` names the per-job amount the
job module reports, ``args["scale"]`` its unit (1e-6: mega); over the time
from the window's start to the last completion."""

from benchmark import arith


def read(run, args):
    if not run.jobs or args["work"] not in run.work:
        return None
    return arith.rate(run.work[args["work"]] * float(args.get("scale", 1.0)),
                      len(run.jobs), run.window_t0, run.jobs[-1].t1)
