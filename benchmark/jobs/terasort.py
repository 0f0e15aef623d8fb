"""Job kind ``terasort``: the ``oink_script`` job over seeded Sort
Benchmark records read from disk inside the job.

The job itself is ``oink_script``'s (a fresh ``OinkScript(comm=mesh)`` per
job, the traffic file's commands: the OINK ``terasort`` command, which is
``apps/terasort.TeraSort.run`` over the files; the sorted dataset waited
for).  This module adds what that one has no place for: the records made
from ``--seed`` (``gen/records.py``) and what ``refs/terasort.summary``
says of them, kept together in the benchmark's cache; the ``{paths}`` the
commands name; ``corpus_bytes`` for ``corpus_rate``; valsort's checks of
the warm-up job's part files in full (``refs/terasort.validate``); and a
digest of a window job that demands equal part-file bytes only where the
reference found no two equal keys (ties may come out in any order), and
the key column alone otherwise.  Configuration keys read: ``files``,
``file_records``, ``record_bytes``, ``key_bytes``, ``prefix_twin_rate``,
``sample`` (held against the application's constant, as the record's shape
is).

It needs the program to have the application: ``prepare`` refuses at once
a tree without ``gpu_mapreduce_tpu.apps.terasort``.
"""

import glob
import hashlib
import importlib.util
import json
import os

from benchmark import check, devutil, kernels_sort
from benchmark.gen import records
from benchmark.jobs import oink_script
from benchmark.refs import terasort as ref

SORT_PROGRAM = "record_sort"    # the name the roofline metric asks for


def _source_hash() -> str:
    h = hashlib.sha256()
    for module in (records, ref):
        with open(module.__file__, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


class Job(oink_script.Job):
    def prepare(self) -> dict:
        check(importlib.util.find_spec("gpu_mapreduce_tpu.apps.terasort")
              is not None,
              "this tree has no gpu_mapreduce_tpu.apps.terasort: it cannot "
              "sort fixed-width records by their bytes, which is this cell")
        from gpu_mapreduce_tpu.apps import terasort as app
        from gpu_mapreduce_tpu.obs import names
        cfg = self.config
        check((app.RECORD_BYTES, app.KEY_BYTES) == (ref.RECORD, ref.KEY)
              == (records.RECORD, records.KEY)
              == (int(cfg["record_bytes"]), int(cfg["key_bytes"])),
              "the application, the reference, the generator and the "
              "configuration disagree on the record's shape")
        check(app.SAMPLE == int(cfg["sample"]),
              f"the application samples {app.SAMPLE} keys for its "
              f"splitters, the configuration says {cfg['sample']}")
        self.sort_program = names.SORT_ROWS
        nfiles, nrec = int(cfg["files"]), int(cfg["file_records"])
        twin = float(cfg["prefix_twin_rate"])

        def build(tmp):
            paths = records.make_records(os.path.join(tmp, "files"), nfiles,
                                         nrec, self.seed, twin)
            with open(os.path.join(tmp, "summary.json"), "w") as f:
                json.dump(ref.summary(paths), f)

        key = (f"records-{self.seed}-{nfiles}x{nrec}-t{twin:g}-"
               f"{_source_hash()[:12]}")
        hit = os.path.isdir(self.cache.path(key))
        d = self.cache.entry(key, build)
        paths = sorted(glob.glob(os.path.join(d, "files", "part-*.dat")))
        with open(os.path.join(d, "summary.json")) as f:
            self.want = json.load(f)
        self.corpus_bytes = sum(os.path.getsize(p) for p in paths)
        self.values.update(paths=" ".join(paths))
        return {"corpus_cache_hit": hit, "files": len(paths),
                "corpus_bytes": self.corpus_bytes, **self.want}

    def work(self) -> dict:
        return {"corpus_bytes": self.corpus_bytes}

    def _parts(self, outdir: str) -> list:
        return sorted(glob.glob(os.path.join(outdir, "parts", "part-*")))

    def check(self, result: dict, outdir: str) -> dict:
        facts = super().check(result, outdir)
        parts = self._parts(outdir)
        check(len(parts) == self.ndev,
              f"{len(parts)} part files for {self.ndev} shards")
        facts.update(ref.validate(parts, self.want))
        return facts

    def digest(self, result: dict, outdir: str) -> str:
        if not self.want["duplicate_keys"]:
            return super().digest(result, outdir)
        # two records share a key: their order is the sort's to choose, so
        # only the key column of the part files is held to the warm-up's
        h = hashlib.sha256("\n".join(result["messages"]).encode())
        for name, (total, counts) in sorted(result["checksums"].items()):
            h.update(f"{name} {int(total)} {sum(counts)}".encode())
        h.update(ref.key_sha256(
            ref.records(p) for p in self._parts(outdir)).encode())
        return h.hexdigest()

    def info(self) -> dict:
        return {"programs": {SORT_PROGRAM: self.sort_program},
                "bytes_moved": {SORT_PROGRAM: kernels_sort.sort_bytes(
                    self.want["records"], ref.RECORD) / self.ndev}}
