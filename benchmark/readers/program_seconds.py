"""Device seconds per traced job of the programs named ``modules`` (names
of ``XLA Modules`` events without the run id, as the program's
``obs/names.py`` declares them): for each module the median, over the
traced jobs and the devices, of its executions summed inside one job
(``xtrace.reduce``'s ``program_job_seconds``), and the sum of those over
the modules.  Medians are taken per module because a job that held no
execution of a module leaves no entry for it, so jobs cannot be lined up
across modules; every job of a cell does the same work, so the two agree.
Nothing when none of the modules ran (another cell, or a program from
before the names)."""

from benchmark import arith


def read(run, args):
    if run.trace is None:
        return None
    per_job = run.trace["program_job_seconds"]
    medians = [
        arith.median([s for jobs in per_job[m].values() for s in jobs])
        for m in args["modules"] if per_job.get(m)]
    return sum(medians) if medians else None
