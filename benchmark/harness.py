"""The closed loop: set-up, one checked warm-up job, then the same job again
and again for the window; then checks, metrics and the result line.

One client, one process, no threads of its own.  A job is timed on the
host's clock from the call into the job module's ``run`` until it returns,
which is after the results are on disk and every device array the job
keeps is ready.  Checks of the window's jobs run after the window closes.
"""

import contextlib
import dataclasses
import importlib
import json
import os
import shutil
import tempfile
import time
import traceback

from benchmark import CheckFailure, arith, compiles, kernels, xtrace
from benchmark.cache import Cache

TRACED_JOBS = 2         # whole jobs under the JAX profiler in a --trace 1 run
MAX_FAILURES = 3        # consecutive raising jobs before the window is given up


class NoChip(Exception):
    """The machine does not hold the chips the cell asks for."""


def require_chips(chips: int) -> list:
    """The TPU devices of this machine, at least ``chips`` of them, or
    ``NoChip``: there is no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(
            f"needs {chips} TPU chip(s), found platform "
            f"{devices[0].platform!r} ({devices[0].device_kind}, "
            f"{len(devices)} device(s)); nothing was run")
    return devices


@dataclasses.dataclass
class JobRecord:
    index: int
    t0: float
    t1: float
    outdir: str
    result: dict
    spans: list = dataclasses.field(default_factory=list)

    @property
    def wall(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: object
    setup_seconds: float
    warmup: JobRecord
    jobs: list              # the window's completed jobs
    window_t0: float
    work: dict              # per job: name -> amount
    compiles: dict          # "setup" / "window" -> {"requests", "hits"}
    memory_peak_bytes: int
    device_kind: str
    info: dict              # from the job module: programs, bytes_moved
    span_epoch: float = 0.0 # perf_counter value at the span clock's zero
    trace: dict = None      # xtrace.reduce's result, in a --trace 1 run


def _log(what: str, **facts) -> None:
    print(f"bench: {what} " + json.dumps(facts, default=str), flush=True)


def _run_one(job, workdir: str, index: int, annotate: bool) -> JobRecord:
    outdir = os.path.join(workdir, f"job{index:05d}")
    os.makedirs(outdir)
    span = contextlib.nullcontext()
    if annotate:
        import jax
        span = jax.profiler.TraceAnnotation(xtrace.JOB_SPAN)
    t0 = time.perf_counter()
    with span:
        result = job.run(outdir)
    return JobRecord(index, t0, time.perf_counter(), outdir, result)


def _start_profiler(logdir: str) -> None:
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # TraceAnnotations, not every call
    options.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=options)


def _metrics(run: Run, group: str) -> dict:
    out = {}
    for m in run.cell.metrics[group]:
        reader = importlib.import_module("benchmark.readers." + m["reader"])
        value = reader.read(run, m.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_process: float) -> dict:
    """Run one cell; returns the result line as a dict."""
    devices = require_chips(cell.chips)
    import jax
    import gpu_mapreduce_tpu  # noqa: F401  (arms the compile cache)
    from gpu_mapreduce_tpu import native
    from gpu_mapreduce_tpu.obs import get_tracer
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    counter = compiles.CompileCounter().install()
    used = devices[:cell.chips]
    mesh = make_mesh(devices=used)
    cache = Cache()
    os.makedirs(cache.path("work"), exist_ok=True)
    workdir = tempfile.mkdtemp(prefix=cell.name + "-", dir=cache.path("work"))
    tracer = get_tracer()
    if trace:
        tracer.enable(ring=1 << 20)
    _log("start", cell=cell.name, config=cell.config_name,
         traffic=cell.traffic_name, rung=cell.config.get("rung"), seed=seed,
         seconds=seconds, trace=trace, devices=len(devices),
         kind=devices[0].device_kind,
         compile_cache=jax.config.jax_compilation_cache_dir,
         native=native.available(), import_s=time.perf_counter() - t_process)
    profiling = False
    try:
        job = importlib.import_module(
            "benchmark.jobs." + cell.traffic["kind"]).Job(
                cell.config, cell.traffic, mesh, seed, cache)
        t0 = time.perf_counter()
        facts = job.prepare()
        _log("prepared", seconds=time.perf_counter() - t0, **facts)

        warm = _run_one(job, workdir, 0, annotate=False)
        correct = True
        t0 = time.perf_counter()
        try:
            _log("warm-up job checked", wall=warm.wall,
                 stages=warm.result["stages"],
                 **job.check(warm.result, warm.outdir))
        except CheckFailure as e:
            correct = False
            _log("WRONG RESULT", why=str(e))
        job.seal(warm.result)
        want = job.digest(warm.result, warm.outdir)
        info = job.info()
        for k in range(1, job.warmup_jobs):     # shapes the first job taught
            extra = _run_one(job, workdir, -k, annotate=False)
            job.seal(extra.result)
            same = job.digest(extra.result, extra.outdir) == want
            correct = correct and same
            _log("extra warm-up job", wall=extra.wall, same_result=same)
        _log("reference", seconds=time.perf_counter() - t0,
             compiles=counter.snapshot())

        # ---- the window ----------------------------------------------------
        tracer.clear()
        before = counter.snapshot()
        setup = time.perf_counter() - t_process
        window_t0 = time.perf_counter()
        jobs, attempted, failed, streak = [], 0, 0, 0
        logdir = os.path.join(workdir, "trace")
        while True:
            if trace and attempted == 0:
                _start_profiler(logdir)
                profiling = True
            attempted += 1
            try:
                rec = _run_one(job, workdir, attempted, annotate=profiling)
                job.seal(rec.result)
                jobs.append(rec)
                streak = 0
            except Exception:
                failed += 1
                streak += 1
                _log("JOB FAILED", index=attempted,
                     error=traceback.format_exc(limit=8))
            if profiling and (attempted >= TRACED_JOBS or streak):
                jax.profiler.stop_trace()
                profiling = False
            if (time.perf_counter() - window_t0 >= seconds
                    or streak >= MAX_FAILURES):
                break
        in_window = compiles.delta(counter.snapshot(), before)

        # ---- after the window: checks, then metrics -------------------------
        for rec in jobs:
            if job.digest(rec.result, rec.outdir) != want:
                correct = False
                failed += 1
                _log("JOB DIFFERS from the warm-up job", index=rec.index)
        if trace:
            events = tracer.events()
            for rec in jobs:
                lo = (rec.t0 - tracer.epoch) * 1e6
                hi = (rec.t1 - tracer.epoch) * 1e6
                rec.spans = [e for e in events
                             if e["ts"] >= lo and e["ts"] + e["dur"] <= hi + 1]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
        run = Run(cell=cell, setup_seconds=setup, warmup=warm,
                  jobs=jobs, window_t0=window_t0, work=job.work(),
                  compiles={"setup": before, "window": in_window},
                  memory_peak_bytes=int(peak),
                  device_kind=devices[0].device_kind, info=info,
                  span_epoch=tracer.epoch)
        _log("window", attempted=attempted, failed=failed,
             walls=[round(r.wall, 4) for r in jobs],
             window_compiles=in_window, setup_seconds=setup,
             stages=[{k: round(v, 4) for k, v in r.result["stages"].items()}
                     for r in jobs])
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": int(peak)}
        line = {"correct": correct, "attempted": attempted, "failed": failed}
        if trace:
            names = {e["name"] for e in events}
            raw = xtrace.load(xtrace.find_xplane(logdir),
                              names | {xtrace.JOB_SPAN})
            _log("trace", planes=raw["planes"])
            run.trace = xtrace.reduce(raw, names)
            kernels.peaks(run.device_kind)      # an unknown kind is an error
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            line["metrics"] = _metrics(run, "per_layer")
            line["breakdown"] = {"device_ops": run.trace["device_ops"],
                                 "idle_gaps": run.trace["idle_gaps"]}
            _log("programs", seconds={
                k: {d: [round(x, 6) for x in v] for d, v in per.items()}
                for k, per in run.trace["programs"].items()})
        else:
            line["metrics"] = _metrics(run, "end_to_end")
        line["device"] = device
        return line
    finally:
        if profiling:
            jax.profiler.stop_trace()
        shutil.rmtree(workdir, ignore_errors=True)
