"""Device seconds per traced job that the programs named ``modules`` spent
in collective operations, on the device that spent most.

From the profiler trace as ``xtrace.load`` gives it: on each device, the
``XLA Ops`` events whose HLO opcode starts with one of ``prefixes``
(``all-reduce``, ``all-gather`` ...: the start and done halves of an
asynchronous collective both count) and which start inside an ``XLA
Modules`` event of one of ``modules`` (names without the run id, as
``obs/names.py`` declares them).  An event is named by the instruction's
text, ``%pmin.8 = s32[65536]{0} all-reduce(s32[65536]{0} %min.25), ...``:
the instruction's own name is the JAX primitive's (``pmin``, ``psum``) as
often as the opcode's, so the opcode is read from behind the result's
shape.  The union of the events' intervals (a ``done`` nested in its
``start`` is not counted twice), summed over the module executions of one
traced job; the median over the traced jobs, and of the devices the
largest.  An execution belongs to the job its interval overlaps most: the
device's clock runs some tenths of a millisecond apart from the host's, so
a program dispatched as a job begins can start "before" it.  Time a
collective spends waiting for the slowest device is in it; time it
overlaps with compute is too.

``xtrace.reduce`` keeps no operation's interval, so the trace file is read
a second time, from where the harness wrote it (``<workdir>/trace``, beside
the warm-up job's output directory).  Nothing when the run was not traced,
the file is gone, or none of the modules ran in a traced job; 0 when they
ran and held no collective (one device).
"""

import bisect
import os
import re

from benchmark import arith, xtrace

OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


def opcode(name: str) -> str:
    """``%pmin.8 = s32[8]{0} all-reduce(...)`` -> ``all-reduce``; an event
    that is only an instruction's name (``all-reduce.7``) is its own."""
    _, eq, text = name.partition(" = ")
    m = OPCODE.search(text) if eq else None
    return m.group(1) if m else xtrace.op_name(name)


def collective_seconds(raw: dict, modules, prefixes):
    """``raw``: ``xtrace.load``'s result.  The metric, or None."""
    jobs = max(([e for e in events if e[2] == xtrace.JOB_SPAN]
                for events in raw["host"].values()), key=len, default=[])
    jobs = sorted(j[:2] for j in jobs)
    modules, prefixes = set(modules), tuple(prefixes)
    per_device, collective = [], {}
    for dev in raw["devices"].values():
        # the named programs' executions, each under the job it overlaps most
        runs = []
        for a, b, nm in dev["modules"]:
            if xtrace.module_name(nm) in modules:
                shared, k = max(((min(b, j1) - max(a, j0), k)
                                 for k, (j0, j1) in enumerate(jobs)),
                                default=(0.0, None))
                if shared > 0.0:
                    runs.append((a, b, k))
        runs.sort()
        if not runs:
            continue
        starts = [r[0] for r in runs]
        found = {k: [] for _a, _b, k in runs}
        for a, b, nm in dev["ops"]:
            # a trace holds a few hundred distinct names a million times
            if nm not in collective:
                collective[nm] = opcode(nm).startswith(prefixes)
            if not collective[nm]:
                continue
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < runs[i][1]:
                found[runs[i][2]].append((a, min(b, runs[i][1])))
        per_device.append(arith.median(
            [arith.union_length(iv) for iv in found.values()]))
    return max(per_device) if per_device else None


def read(run, args):
    if run.trace is None:
        return None
    logdir = os.path.join(os.path.dirname(run.warmup.outdir), "trace")
    try:
        path = xtrace.find_xplane(logdir)
    except FileNotFoundError:
        return None
    raw = xtrace.load(path, {xtrace.JOB_SPAN})
    return collective_seconds(raw, args["modules"], args["prefixes"])
