"""Percent of the HBM roofline of one program: the bytes it must move per
job on one device (``benchmark/kernels.py``, through the job module's
``info()["bytes_moved"]``) over the peak, divided by the device seconds the
program took per traced job (``XLA Modules`` events named by
``info()["programs"]``; the executions inside one job are summed, so that a
job which dispatches the program once per batch is not overstated by the
number of batches; median over the traced jobs and devices)."""

from benchmark import arith, kernels


def read(run, args):
    if run.trace is None:
        return None
    module = run.info["programs"].get(args["program"])
    nbytes = run.info["bytes_moved"].get(args["program"])
    per_device = run.trace["program_job_seconds"].get(module)
    if not module or not nbytes or not per_device:
        return None
    seconds = arith.median([s for jobs in per_device.values() for s in jobs])
    return kernels.hbm_share(nbytes, seconds, run.device_kind)
