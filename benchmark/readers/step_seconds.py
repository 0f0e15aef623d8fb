"""Device seconds per traced job of named steps inside named programs: the
``XLA Ops`` events (self time: a ``while`` is not charged its body) that
start inside an ``XLA Modules`` execution of one of ``modules`` and whose
instruction, looked up in the HLO the trace itself holds
(``benchmark/xsteps.py``), was traced under one of ``steps``: a
``jax.named_scope`` of the program, a component of the instruction's
``op_name``; where scopes nest, the innermost of the steps the benchmark
declares for the program (``benchmark/steps.json``) is the instruction's.
Jobs, devices and medians as ``program_seconds`` has them: for each module
the median, over the traced jobs and the devices, of its executions' step
seconds summed inside one job, and the sum of those over the modules.

Nothing when the run was not traced, the trace has no ``/host:metadata``
plane, none of the modules ran in a traced job, or no instruction of those
that ran carries one of the steps (a program from before the scopes, or an
executable an older tree left in the compile cache)."""

from benchmark import arith, xsteps


def read(run, args):
    path = xsteps.trace_file(run)
    found = xsteps.seconds(path) if path else None
    if found is None:
        return None
    table, per_job = found
    want, medians = set(args["steps"]), []
    for m in args["modules"]:
        declared = set(xsteps.declared(m)) | want
        if not any(xsteps.step_of(p, declared) in want
                   for prog in table.values() if prog["module"] == m
                   for p in prog["steps"].values()):
            continue
        sums = [sum(s for p, s in paths.items()
                    if xsteps.step_of(p, declared) in want)
                for jobs in per_job.get(m, {}).values()
                for paths in jobs.values()]
        if sums:
            medians.append(arith.median(sums))
    return sum(medians) if medians else None
