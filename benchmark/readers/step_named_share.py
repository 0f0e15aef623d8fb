"""Percent: of the device seconds (self time of the ``XLA Ops`` events)
inside the executions of the declared programs in the traced jobs, the part
that falls on an instruction whose ``op_name`` carries a step the benchmark
declares for that program (``benchmark/steps.json``: every program and
mapper prefix of ``obs/names.py`` with its ``jax.named_scope`` steps).
Summed over devices and jobs.  The coverage of every ``step_seconds``
metric: it reads low when operations lie outside the scopes, and when the
trace's executables predate them (an executable served from a compile cache
that an older tree filled carries the older tree's scopes).

Nothing when the run was not traced, the trace has no ``/host:metadata``
plane, or no declared program ran in a traced job."""

from benchmark import xsteps


def read(run, args):
    path = xsteps.trace_file(run)
    found = xsteps.seconds(path) if path else None
    if found is None:
        return None
    named = total = 0.0
    for module, devices in found[1].items():
        steps = xsteps.declared(module)
        if not steps:
            continue
        for jobs in devices.values():
            for paths in jobs.values():
                for p, s in paths.items():
                    total += s
                    if xsteps.step_of(p, steps):
                        named += s
    return 100.0 * named / total if total else None
