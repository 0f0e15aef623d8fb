"""Seconds JAX spent lowering programs and in the backend (a compile, or a
load from the persistent cache) outside the window's jobs: the process's
totals, which the program's counters keep while its tracer is on, less what
the window's jobs paid under their root spans (``spans``, ``attrs``: the
counter deltas those spans state).  What is left was paid before the
window, by set-up and the warm-up job; with it whatever the harness itself
built between jobs, which ``compiles_in_window`` holds to none.  Nothing
when the tracer was off or the program keeps no such totals."""


def read(run, args):
    spans, attrs = set(args["spans"]), args["attrs"]
    if not any(j.spans for j in run.jobs):
        return None
    try:
        from gpu_mapreduce_tpu.core.runtime import global_counters
        totals = global_counters().snapshot()
        total = sum(totals[k] for k in attrs)
    except (ImportError, KeyError):
        return None             # a program from before these counters
    in_jobs = sum(e["args"].get(k, 0) for j in run.jobs for e in j.spans
                  if e["name"] in spans for k in attrs)
    return total - in_jobs
