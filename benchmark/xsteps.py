"""Device seconds by step: what a JAX profiler trace says of the inside of a
program.

An ``XLA Ops`` event carries the compiler's name for the operation
(``%fusion.20 = ...``) and nothing of where the operation came from; what
says that is in the same file, where ``jax.profiler.ProfileData`` (which
``xtrace.load`` reads with) does not look.  Read as a plain protobuf, an
``.xplane.pb`` of a TPU run holds a plane ``/host:metadata`` with no lines
and one ``event_metadata`` entry a program that ran, named as the ``XLA
Modules`` events are (``jit_cc_loop(8386974742483796195)``: the number is
the ``program_id``), with one stat, ``Hlo Proto``: the optimised module as
the chip ran it, every instruction with its ``metadata.op_name``, the path
of ``jax.named_scope``s it was traced under
(``jit(cc_loop)/while/body/shard_map/segment_min_dst/gather``).  The steps
a program declares (``gpu_mapreduce_tpu/obs/names.STEPS``; the benchmark's
own copy is ``benchmark/steps.json``) are components of that path.

``table`` walks the file for that plane alone, by the wire format (standard
library only: the chip hosts are not known to have ``tensorflow`` or
``xprof``); the field numbers are those of ``tsl/profiler/protobuf/
xplane.proto`` and ``xla/service/hlo.proto``, held to the descriptors by
``benchmark/tests/test_xsteps.py`` where ``tensorflow`` imports.
``seconds`` puts the table beside the events: self seconds of every
operation inside every program execution of the traced jobs, by program,
device, job and ``op_name``.  Both are memoised by path: a dozen metrics
read one file.  An instruction without a path of its own (the compiler's
copies and slices, a cached lowering's operations: every prefix scan on the
TPU) is booked to the path of what produced its input (``_inherit``).

An instruction inside a fused computation never runs as an event of its own;
the fusion instruction's ``op_name`` (the compiler's choice, as a rule the
fusion root's) is what its seconds are booked to.
"""

import bisect
import json
import mmap
import os
import re
import time

from benchmark import arith, xtrace

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
STEPS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "steps.json")

# field numbers (message: field)
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
XEVENTMETADATA_ID, XEVENTMETADATA_NAME, XEVENTMETADATA_STATS = 1, 2, 5
XSTAT_METADATA_ID, XSTAT_BYTES_VALUE = 1, 6
XSTATMETADATA_NAME = 2
HLOPROTO_MODULE = 1
HLOMODULE_NAME, HLOMODULE_COMPUTATIONS = 1, 3
HLOCOMPUTATION_INSTRUCTIONS = 2
HLOINSTRUCTION_NAME, HLOINSTRUCTION_METADATA = 1, 7
HLOINSTRUCTION_ID, HLOINSTRUCTION_OPERAND_IDS = 35, 36
OPMETADATA_OP_NAME = 2

_TABLES: dict = {}
_SECONDS: dict = {}
_DECLARED: dict = {}


def _varint(buf, i: int):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, start: int, end: int):
    """``(field number, value)`` of a message's fields between two
    offsets: an int for a varint, ``(from, to)`` for a length-delimited
    field (skipped by its length, never copied), None for a fixed one."""
    i = start
    while i < end:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield tag >> 3, value


def _first(buf, span, field):
    for f, v in _fields(buf, *span):
        if f == field:
            return v
    return None


def _text(buf, span) -> str:
    return "" if span is None else bytes(buf[span[0]:span[1]]).decode()


def _module(buf, span) -> dict:
    """``HloProto`` bytes -> {"module": name, "steps": {instruction:
    op_name}, "inherited": {instruction: op_name}}, over every computation
    of the module (``inherited``: :func:`_inherit`)."""
    mod = _first(buf, span, HLOPROTO_MODULE)
    out = {"module": "", "steps": {}, "inherited": {}}
    if mod is None:
        return out
    names, operands = {}, {}
    for f, v in _fields(buf, *mod):
        if f == HLOMODULE_NAME:
            out["module"] = _text(buf, v)
        elif f == HLOMODULE_COMPUTATIONS:
            for g, inst in _fields(buf, *v):
                if g != HLOCOMPUTATION_INSTRUCTIONS:
                    continue
                name = meta = ident = None
                ids = []
                for h, w in _fields(buf, *inst):
                    if h == HLOINSTRUCTION_NAME:
                        name = _text(buf, w)
                    elif h == HLOINSTRUCTION_METADATA:
                        meta = w
                    elif h == HLOINSTRUCTION_ID:
                        ident = w
                    elif h == HLOINSTRUCTION_OPERAND_IDS:
                        if isinstance(w, tuple):        # packed
                            i = w[0]
                            while i < w[1]:
                                x, i = _varint(buf, i)
                                ids.append(x)
                        else:
                            ids.append(w)
                op = _first(buf, meta, OPMETADATA_OP_NAME) if meta else None
                out["steps"][name] = _text(buf, op)
                names[ident], operands[name] = name, ids
    out["inherited"] = _inherit(out["steps"], {
        n: [names[i] for i in ids if i in names]
        for n, ids in operands.items()})
    return out


def _inherit(steps: dict, operands: dict, depth: int = 12) -> dict:
    """For each instruction whose ``op_name`` is no path (no ``/``: the
    compiler's own copies, slices and custom calls, and what a cached
    lowering emits, which names its operations for itself alone: on the TPU
    every ``cumsum`` / ``cummax`` is ``reduce-window``s under ``""`` and
    fusions under ``reduce_window_sum``), the ``op_name`` of the nearest
    instruction that produced its input and has one: operands in their
    order, depth first, ``depth`` deep.  A prefix scan is so booked to the
    scope that computed what it scans."""
    found = {}

    def look(name, left):
        if "/" in steps.get(name, ""):
            return steps[name]
        if name in found or not left:
            return found.get(name)
        found[name] = None                  # a cycle finds nothing
        for operand in operands.get(name, ()):
            path = look(operand, left - 1)
            if path:
                found[name] = path
                break
        return found[name]

    return {n: p for n in steps if "/" not in steps[n]
            for p in [look(n, depth)] if p}


def _walk(buf) -> dict:
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != XSPACE_PLANES:
            continue
        if _text(buf, _first(buf, plane, XPLANE_NAME)) != METADATA_PLANE:
            continue            # a device plane's events: skipped whole
        hlo_stat = None
        for g, v in _fields(buf, *plane):
            if g == XPLANE_STAT_METADATA:
                meta = _first(buf, v, MAP_VALUE)
                if meta and _text(buf, _first(
                        buf, meta, XSTATMETADATA_NAME)) == HLO_STAT:
                    hlo_stat = _first(buf, v, MAP_KEY)
        for g, v in _fields(buf, *plane):
            if g != XPLANE_EVENT_METADATA:
                continue
            event = _first(buf, v, MAP_VALUE)
            if event is None:
                continue
            pid = _first(buf, event, XEVENTMETADATA_ID) or _first(
                buf, v, MAP_KEY)
            for h, stat in _fields(buf, *event):
                if h == XEVENTMETADATA_STATS and _first(
                        buf, stat, XSTAT_METADATA_ID) == hlo_stat:
                    hlo = _first(buf, stat, XSTAT_BYTES_VALUE)
                    if hlo is not None:
                        out[pid] = _module(buf, hlo)
    return out


def table(path: str) -> dict:
    """``{program_id: {"module": "jit_cc_loop", "steps": {instruction
    name: op_name}}}`` of the programs the trace at ``path`` recorded; empty
    where the file has no ``/host:metadata`` plane (a CPU run)."""
    if path not in _TABLES:
        with open(path, "rb") as f:
            if os.fstat(f.fileno()).st_size == 0:
                _TABLES[path] = {}
            else:
                with mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as m:
                    _TABLES[path] = _walk(m)
    return _TABLES[path]


# -- steps ----------------------------------------------------------------------

def declared(module: str) -> tuple:
    """The steps ``benchmark/steps.json`` declares for a program (by its
    name, or by the prefix of a generic mapper's: a key that ends in
    ``_``); none for a program the file does not have."""
    if not _DECLARED:
        with open(STEPS_FILE) as f:
            _DECLARED.update(json.load(f)["steps"])
    if module in _DECLARED:
        return tuple(_DECLARED[module])
    for prefix, steps in _DECLARED.items():
        if prefix.endswith("_") and module.startswith(prefix):
            return tuple(steps)
    return ()


def step_of(op_name: str, steps) -> str:
    """The innermost component of an ``op_name`` path that is one of
    ``steps``; the last component is the operation's own name (JAX's
    ``gather``, ``sort``), never a scope.  None where there is none."""
    for part in reversed(op_name.split("/")[:-1]):
        if part in steps:
            return part
    return None


# -- seconds --------------------------------------------------------------------

def instruction(event_name: str) -> str:
    """``%fusion.12 = u64[...] fusion(...)`` -> ``fusion.12``: an ``XLA
    Ops`` event's instruction, as the HLO names it."""
    return event_name.split(" = ")[0].lstrip("%").strip()


PROGRAM_ID = re.compile(r"\((\d+)\)$")


def job_seconds(raw: dict, steps: dict) -> dict:
    """``{module: {device: {job: {op_name: self seconds}}}}`` over the
    executions of the traced jobs.  ``raw``: ``xtrace.load``'s result;
    ``steps``: ``table``'s.  An execution belongs to the job it overlaps
    most (the device's clock runs a fraction of a millisecond ahead of the
    host's), an operation to the execution it starts in; self time, so a
    ``while`` is not charged its body.  An operation the table does not
    have (a program without its HLO) is booked under ``""``."""
    jobs = max(([e for e in events if e[2] == xtrace.JOB_SPAN]
                for events in raw["host"].values()), key=len, default=[])
    jobs = sorted(j[:2] for j in jobs)
    out = {}
    for n, dev in raw["devices"].items():
        runs = []
        for a, b, nm in dev["modules"]:
            shared, k = max(((min(b, j1) - max(a, j0), k)
                             for k, (j0, j1) in enumerate(jobs)),
                            default=(0.0, None))
            if shared > 0.0:
                m = PROGRAM_ID.search(nm.strip())
                ops = steps.get(int(m.group(1)), {}) if m else {}
                sums = out.setdefault(xtrace.module_name(nm), {}).setdefault(
                    n, {}).setdefault(k, {})
                runs.append((a, b, {**ops.get("steps", {}),
                                    **ops.get("inherited", {})}, sums))
        runs.sort(key=lambda r: r[:2])
        starts = [r[0] for r in runs]
        names = {}              # a few hundred distinct names, a million times
        for a, b, nm in arith.innermost(dev["ops"]):
            i = bisect.bisect_right(starts, a) - 1
            if i < 0 or a >= runs[i][1]:
                continue
            _a, end, ops, sums = runs[i]
            if nm not in names:
                names[nm] = instruction(nm)
            path = ops.get(names[nm], "")
            sums[path] = sums.get(path, 0.0) + min(b, end) - a
    return out


def trace_file(run):
    """The trace the harness wrote for ``run`` (``<workdir>/trace``, beside
    the warm-up job's output directory), or None: an untraced run."""
    if run.trace is None:
        return None
    logdir = os.path.join(os.path.dirname(run.warmup.outdir), "trace")
    try:
        return xtrace.find_xplane(logdir)
    except FileNotFoundError:
        return None


def seconds(path: str):
    """``(table(path), job_seconds(...))`` of the trace at ``path``, read
    once a process; None where the file has no ``/host:metadata`` plane."""
    if path not in _SECONDS:
        t0 = time.perf_counter()
        steps = table(path)
        t1 = time.perf_counter()
        _SECONDS[path] = (steps, job_seconds(
            xtrace.load(path, {xtrace.JOB_SPAN}), steps)) if steps else None
        # what the third pass over the file costs, after the window, and
        # the whole reading for the log: the ledger keeps the metrics'
        print("bench: steps " + json.dumps({
            "file_mb": round(os.path.getsize(path) / 1e6, 3),
            "programs": len(steps),
            "instructions": sum(len(p["steps"]) for p in steps.values()),
            "table_s": round(t1 - t0, 4),
            "events_s": round(time.perf_counter() - t1, 4),
            "by_step": by_step(_SECONDS[path][1]) if steps else {}}),
            flush=True)
    return _SECONDS[path]


def by_step(per_job: dict, least: float = 0.0005) -> dict:
    """``{program: {step: seconds a traced job}}``, medians over devices
    and jobs, of the declared programs' declared steps (``""``: under
    none); steps under ``least`` seconds are left out."""
    out = {}
    for module, devices in per_job.items():
        steps = declared(module)
        if not steps:
            continue
        rows = [paths for jobs in devices.values() for paths in jobs.values()]
        names = {step_of(p, steps) or "" for paths in rows for p in paths}
        sums = {n: arith.median([
            sum(s for p, s in paths.items() if (step_of(p, steps) or "") == n)
            for paths in rows]) for n in names}
        out[module] = {n: round(s, 6) for n, s in sorted(
            sums.items(), key=lambda kv: -kv[1]) if s >= least}
    return out
