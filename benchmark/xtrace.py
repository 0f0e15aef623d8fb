"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

What the trace of a TPU run holds (looked at by hand, PR 23; see PERF.md):
one plane per chip named ``/device:TPU:<n>``, with a line ``XLA Modules``
(one event per execution of a jitted program, named ``jit_<fn>(<id>)``)
and a line ``XLA Ops`` (one event per HLO operation executed, nested where
an operation such as ``while`` contains others); and a plane ``/host:CPU``
with one line per host thread, which holds the ``TraceAnnotation`` events:
the harness's ``bench.job`` around each traced job and, through
``obs/tracer.py``, every program span (``stage.*``, ``oink.*``, MR ops,
``shuffle.*``, ``ingest.*``) — on the same clock as the device lines.

``load`` turns the file into plain lists of ``(start_s, end_s, name)``;
``reduce`` works on those lists alone, so the tests can feed it hand-made
ones as well as a recorded file.
"""

import glob
import os
import re

from benchmark import arith

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
JOB_SPAN = "bench.job"      # the harness's annotation around each traced job
TOP = 10


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load(path: str, host_names=None) -> dict:
    """``{"devices": {n: {"ops": [...], "modules": [...]}}, "host": {line:
    [...]}, "planes": {plane: {line: number of events}}}``, times in seconds
    on the trace's clock.  ``host_names``: keep only the host events of
    these names (the runtime's own threads can hold millions of events:
    1.77 M on each ``pjrt-tpu-tasks`` line of a 50 s graph-build trace)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": {}, "planes": {}}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        host = plane.name == HOST_PLANE
        counts = out["planes"].setdefault(plane.name, {})
        for i, line in enumerate(plane.lines):
            events = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                       e.name) for e in line.events]
            counts[line.name] = len(events)
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dev = out["devices"].setdefault(
                    int(m.group(1)), {"ops": [], "modules": []})
                dev["ops" if line.name == OPS_LINE else "modules"] = events
            elif host:
                out["host"][f"{line.name}#{i}"] = [
                    e for e in events
                    if host_names is None or e[2] in host_names]
    return out


def op_name(name: str) -> str:
    """``%fusion.12 = u64[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ")[0].lstrip("%").strip()[:80]


def module_name(name: str) -> str:
    """``jit_body(1234567)`` -> ``jit_body``."""
    return re.sub(r"\(\d+\)$", "", name.strip())


def reduce(trace: dict, span_names=()) -> dict:
    """Device busy/idle, top operations, idle gaps by host span, and the
    per-program device seconds (per execution and per job), over the window
    of the traced jobs.

    ``span_names``: the program's span names (from the obs tracer's ring);
    only host events with these names, or the harness's job annotation,
    count as program spans when a gap is attributed."""
    job_line, jobs = None, []
    for name, events in trace["host"].items():
        found = [e for e in events if e[2] == JOB_SPAN]
        if len(found) > len(jobs):
            job_line, jobs = name, found
    if not jobs:
        raise ValueError(f"no {JOB_SPAN!r} annotation in the host plane")
    if not trace["devices"]:
        raise ValueError("the trace has no /device:TPU:<n> plane")
    lo, hi = min(j[0] for j in jobs), max(j[1] for j in jobs)
    window = hi - lo
    keep = set(span_names) | {JOB_SPAN}
    segments = arith.innermost(
        e for e in trace["host"][job_line] if e[2] in keep)

    busy, op_seconds, programs, per_job = [], {}, {}, {}
    ndev = len(trace["devices"])
    first = min(trace["devices"])
    for n, dev in sorted(trace["devices"].items()):
        ops = [(max(a, lo), min(b, hi), nm) for a, b, nm in dev["ops"]
               if min(b, hi) > max(a, lo)]
        busy.append(arith.union_length((a, b) for a, b, _ in ops))
        # self time per operation: a ``while`` is not charged its body
        for a, b, nm in arith.innermost(ops):
            nm = op_name(nm)
            op_seconds[nm] = op_seconds.get(nm, 0.0) + (b - a) / ndev
        for a, b, nm in dev["modules"]:
            if b > lo and a < hi:
                nm = module_name(nm)
                programs.setdefault(nm, {}).setdefault(n, []).append(b - a)
                for k, j in enumerate(jobs):
                    if j[0] <= a < j[1]:    # the job that dispatched it
                        sums = per_job.setdefault(nm, {}).setdefault(n, {})
                        sums[k] = sums.get(k, 0.0) + b - a
        if n == first:
            idle = arith.attribute(
                arith.gaps(((a, b) for a, b, _ in ops), lo, hi), segments)
    busy_s = sum(busy) / ndev

    def top(d):
        return [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "window_s": window, "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window,
        "busy_s_per_device": busy, "traced_jobs": len(jobs),
        "device_ops": top(op_seconds), "idle_gaps": top(idle),
        # program -> device -> the device seconds of each execution
        "programs": programs,
        # program -> device -> its executions summed over each traced job
        # that held one
        "program_job_seconds": {
            nm: {n: [sums[k] for k in sorted(sums)] for n, sums in per.items()}
            for nm, per in per_job.items()},
    }
