"""Job kind ``oink_script``: OINK commands (MR-MPI's graph suite) through
``OinkScript(comm=mesh).run_string``.

The traffic file gives the commands as templates over the configuration's
keys plus ``{seed}`` and ``{out}``:

* ``setup``: commands run once, in set-up, on a script object every job
  then shares (a graph built once); ``setup_checks`` hold their results.
* ``job``: the commands of one job, each timed on the harness's clock
  (``stages``).  With no ``setup`` every job gets a fresh script object.
* ``job_checks`` / ``setup_checks``: ``"module:function"`` names under
  ``benchmark/`` (``refs.graph:check_cc``), run on the warm-up job only.
* ``device_results``: named MR objects whose device arrays the job waits
  for; later jobs are compared with the warm-up job by a device checksum
  of their key rows.  ``exchanged``: those whose last exchange must have
  moved rows when the mesh has more than one device.
* ``work``: per job, name -> an expression over the configuration's keys.

Files a job writes under ``{out}`` are compared by their SHA-256, and the
messages the commands print (counts, iterations) must repeat exactly.
"""

import hashlib
import importlib
import io
import os
import time
import types

from benchmark import devutil


def _resolve(name: str):
    module, fn = name.split(":")
    return getattr(importlib.import_module("benchmark." + module), fn)


class Job:
    def __init__(self, config, traffic, mesh, seed, cache):
        self.config, self.traffic, self.mesh = config, traffic, mesh
        self.seed, self.cache = seed, cache
        from gpu_mapreduce_tpu.parallel.mesh import mesh_axis_size
        self.ndev = mesh_axis_size(mesh)
        # on a mesh the exchange learns its capacities from the first run
        # (parallel/shuffle._SPEC_CACHE), so the second job may dispatch
        # programs the first did not: warm up twice there
        self.warmup_jobs = 2 if self.ndev > 1 else 1
        self.shared = None
        self.memo = {}          # what checks leave for later checks
        self.values = {k: v for k, v in config.items()
                       if isinstance(v, (int, float, str))}

    def _script(self):
        from gpu_mapreduce_tpu.oink.script import OinkScript
        return OinkScript(comm=self.mesh, screen=io.StringIO())

    def _lines(self, which: str, out: str) -> list:
        return [line.format(seed=self.seed, out=out, **self.values)
                for line in self.traffic.get(which, [])]

    def _env(self, script, out, messages):
        return types.SimpleNamespace(
            script=script, out=out, messages=messages, config=self.config,
            cache=self.cache, memo=self.memo)

    # -- set-up ---------------------------------------------------------------
    def prepare(self) -> dict:
        lines = self._lines("setup", out="")
        if not lines:
            return {}
        self.shared = self._script()
        seconds = {}
        for line in lines:
            t0 = time.perf_counter()
            self.shared.run_string(line)
            seconds[line.split()[0]] = time.perf_counter() - t0
        messages = self.shared.screen.getvalue().strip().splitlines()
        facts = {"setup_seconds": seconds, "setup_messages": messages}
        env = self._env(self.shared, "", messages)
        for name in self.traffic.get("setup_checks", []):
            facts.update(_resolve(name)(env))
        return facts

    def work(self) -> dict:
        return {k: eval(expr, {"__builtins__": {}}, dict(self.values))
                for k, expr in self.traffic.get("work", {}).items()}

    # -- the job --------------------------------------------------------------
    def run(self, outdir: str) -> dict:
        script = self.shared if self.shared is not None else self._script()
        at = script.screen.tell()
        seconds = {}
        for line in self._lines("job", out=outdir):
            t0 = time.perf_counter()
            script.run_string(line)
            seconds[line.split()[0]] = time.perf_counter() - t0
        for name in self.traffic.get("device_results", []):
            devutil.block(script.obj.get_mr(name))
        messages = script.screen.getvalue()[at:].strip().splitlines()
        return {"script": script, "messages": messages, "stages": seconds}

    def seal(self, result: dict) -> None:
        """Leave a device checksum and the row counts of each device result
        in place of the script object (and, with it, the arrays)."""
        from gpu_mapreduce_tpu.oink.objects import _mesh_frame
        script = result.pop("script")
        sums = {}
        for name in self.traffic.get("device_results", []):
            fr = _mesh_frame(script.obj.get_mr(name))
            sums[name] = (devutil.key_checksum(fr), fr.counts.tolist())
        result["checksums"] = sums

    # -- checking -------------------------------------------------------------
    def check(self, result: dict, outdir: str) -> dict:
        from gpu_mapreduce_tpu.oink.objects import _mesh_frame
        script = result["script"]
        env = self._env(script, outdir, result["messages"])
        facts = {"messages": result["messages"]}
        for name in self.traffic.get("job_checks", []):
            facts.update(_resolve(name)(env))
        if self.ndev > 1:
            for name in self.traffic.get("device_results", []):
                devutil.check_spread(name, _mesh_frame(script.obj.get_mr(name)),
                                     self.ndev)
            for name in self.traffic.get("exchanged", []):
                facts["exchange"] = devutil.check_exchange(
                    name, script.obj.get_mr(name))
        return facts

    def digest(self, result: dict, outdir: str) -> str:
        h = hashlib.sha256("\n".join(result["messages"]).encode())
        for name, (total, counts) in sorted(result["checksums"].items()):
            h.update(f"{name} {int(total)} {sum(counts)}".encode())
        for dirpath, _dirs, files in sorted(os.walk(outdir)):
            for fn in sorted(files):
                p = os.path.join(dirpath, fn)
                h.update(os.path.relpath(p, outdir).encode())
                devutil.hash_file(h, p)
        return h.hexdigest()

    def info(self) -> dict:
        return {"programs": {}, "bytes_moved": {}}
