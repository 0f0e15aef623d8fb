"""Job kind ``tpch_q1``: the ``oink_script`` job over seeded TPC-H tables
that set-up loads onto the mesh, every job asking Query 1 of the resident
``lineitem``.

The job itself is ``oink_script``'s (the script object of set-up, shared;
the traffic file's command: ``tpch_q1``; the result ``mrq1`` waited for).
This module adds what that one has no place for, as ``jobs/tpch.py`` does
for Query 3 (whose table checksum and source hash it borrows): the tables
made from ``--seed`` (``gen/tpch.py``) and the reference's answer
(``refs/tpch_q1.q1``), kept together in the benchmark's cache; the
``{customer}`` / ``{orders}`` / ``{lineitem}`` paths the set-up commands
name; ``corpus_bytes`` for ``corpus_rate`` (the bytes of ``lineitem``'s
rows, the one table the job reads); the warm-up job held to the reference
in every sum and count of every group and in its lines
(``refs/tpch_q1.check_q1``), its message and its scan and ``compress`` spans
to the reference's row counts; a digest that holds a window job's
``q1.txt``, ``mrq1``'s checksum and a checksum of the three tables as they
lie on the device when the digests are taken (after the window, as
``jobs/tpch.py`` does; read once there, not once a job).  Configuration keys read: ``scale_factor``,
``delta_days``, ``columns``.

It needs the program to have the query: ``prepare`` refuses at once a tree
without ``gpu_mapreduce_tpu.apps.tpch.q1``.
"""

import importlib.util
import json
import os

import numpy as np

from benchmark import check, kernels_combine
from benchmark.gen import tpch as gen
from benchmark.jobs import oink_script
from benchmark.jobs.tpch import _source_hash, _table_sum
from benchmark.refs import tpch as ref
from benchmark.refs import tpch_q1 as refq1

COMBINE_PROGRAM = "combine"     # the name the roofline metric asks for
RESULT = "mrq1"
LINES = "q1.txt"
KEY_BYTES, VALUE_BYTES = 8, 48  # a mapped row: two u32 words, six int64


class Job(oink_script.Job):
    def prepare(self) -> dict:
        check(importlib.util.find_spec("gpu_mapreduce_tpu.apps.tpch")
              is not None,
              "this tree has no gpu_mapreduce_tpu.apps.tpch: it cannot "
              "run TPC-H Query 1, which is this cell")
        from gpu_mapreduce_tpu.apps import tpch as app
        check(hasattr(app, "q1"),
              "this tree's gpu_mapreduce_tpu.apps.tpch has no q1: it has "
              "no Query 1 (no tpch_q1 command, no device combiner under "
              "compress) and cannot run this cell")
        from gpu_mapreduce_tpu.obs import names
        cfg = self.config
        check(app.COLUMNS == ref.COLUMNS == {
            t: tuple(c) for t, c in cfg["columns"].items()}
              and app.EPOCH == ref.EPOCH
              and app.RETURNFLAGS == refq1.RETURNFLAGS
              and app.LINESTATUSES == refq1.LINESTATUSES
              and app.Q1_ANCHOR == refq1.ANCHOR,
              "the application, the reference and the configuration "
              "disagree on the tables' columns or the flags' letters")
        self.combine_program = app.Q1_PROGRAMS[1]
        self.spans = (names.TPCH_SCAN, names.COMPRESS_SPAN)
        sf, delta = float(cfg["scale_factor"]), int(cfg["delta_days"])

        def build(tmp):
            paths = gen.make_tables(os.path.join(tmp, "tables"), sf,
                                    self.seed)
            rows = {t: sum(map(os.path.getsize, ps)) // ref.record_bytes(t)
                    for t, ps in paths.items()}
            want = refq1.q1(ref.read_table("lineitem", paths["lineitem"]),
                            delta)
            np.savez(os.path.join(tmp, "q1.npz"), **{
                k: want[k] for k in ("returnflag", "linestatus") + refq1.SUMS})
            with open(os.path.join(tmp, "facts.json"), "w") as f:
                json.dump({"scanned": want["scanned"], "rows": rows,
                           "files": {t: [os.path.basename(p) for p in ps]
                                     for t, ps in paths.items()}}, f)

        key = (f"tpch-q1-{self.seed}-sf{sf:g}-d{delta}-"
               f"{_source_hash()[:12]}-{_q1_hash()[:12]}")
        hit = os.path.isdir(self.cache.path(key))
        d = self.cache.entry(key, build)
        with open(os.path.join(d, "facts.json")) as f:
            self.facts = json.load(f)
        with np.load(os.path.join(d, "q1.npz")) as z:
            self.want = {k: z[k] for k in z.files}
        self.want["scanned"] = self.facts["scanned"]
        paths = {t: [os.path.join(d, "tables", n) for n in names_]
                 for t, names_ in self.facts["files"].items()}
        self.rows = self.facts["rows"]
        self.corpus_bytes = self.rows["lineitem"] * ref.record_bytes(
            "lineitem")
        self.values.update({t: " ".join(ps) for t, ps in paths.items()})
        facts = super().prepare()
        # what the tables hold of the device, and the most the load held
        stats = self.mesh.devices.flat[0].memory_stats() or {}
        facts["hbm_after_load"] = {k: stats.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use")}
        self.tables_sum = self._tables_sum()
        self._tables_read = None
        return {"corpus_cache_hit": hit, "rows": self.rows,
                "corpus_bytes": self.corpus_bytes,
                "kept": self.facts["scanned"]["lineitem"][1],
                "groups": len(self.want["count"]),
                "group_rows": self.want["count"].tolist(),
                "tables_sum": self.tables_sum, **facts}

    def work(self) -> dict:
        return {"corpus_bytes": self.corpus_bytes}

    def run(self, outdir: str) -> dict:
        self._tables_read = None    # a job ran: the digests read them anew
        return super().run(outdir)

    # -- the tables, where set-up left them -----------------------------------
    def _tables_sum(self) -> int:
        """The three tables' checksum, computed on the device from the
        frames the named MR objects hold now."""
        from gpu_mapreduce_tpu.oink.objects import _mesh_frame
        total = 0
        for t in ref.TABLES:
            fr = _mesh_frame(self.shared.obj.get_mr(t))
            check(fr is not None and len(fr) == self.rows[t],
                  f"table {t}: not one mesh frame of {self.rows[t]} rows")
            total += int(_table_sum()(fr.key, fr.value,
                                      np.asarray(fr.counts, np.int64)))
        return total % (1 << 64)

    # -- checking -------------------------------------------------------------
    def groups(self, script) -> dict:
        """Every group ``mrq1`` holds, as the reference names them."""
        fr = script.obj.get_mr(RESULT).kv.one_frame()
        fr = fr if hasattr(fr.key, "data") else fr.to_host()
        key = np.asarray(fr.key.data).astype(np.int64).reshape(-1, 2)
        value = np.asarray(fr.value.data).reshape(-1, len(refq1.SUMS))
        return {"returnflag": key[:, 0], "linestatus": key[:, 1],
                **{name: value[:, i] for i, name in enumerate(refq1.SUMS)}}

    def check(self, result: dict, outdir: str) -> dict:
        facts = super().check(result, outdir)
        with open(os.path.join(outdir, LINES)) as f:
            printed = f.read().splitlines()
        facts.update(refq1.check_q1(self.want, self.groups(result["script"]),
                                    printed))
        said = self._message(len(printed))
        check(result["messages"] == [said],
              f"the command said {result['messages']}, the reference's "
              f"counts say {said!r}")
        check(self._tables_sum() == self.tables_sum,
              "a table differs from what set-up loaded")
        facts["spans"] = self._spans()
        return facts

    def _message(self, lines: int) -> str:
        """The command's message as the reference's counts make it."""
        rows, kept = self.facts["scanned"]["lineitem"]
        return (f"TPC-H Q1 DELTA {int(self.config['delta_days'])}: {rows} "
                f"lineitem rows scanned, {kept} kept; "
                f"{len(self.want['count'])} groups, {lines} lines")

    def _spans(self) -> dict:
        """What the warm-up job's scan and ``compress`` spans said, held to
        the reference's counts; nothing with the tracer off."""
        from gpu_mapreduce_tpu.obs import get_tracer
        scan_span, compress_span = self.spans
        events = get_tracer().events()
        scans = [[e["args"]["rows_in"], e["args"]["rows_out"]]
                 for e in events if e["name"] == scan_span][-1:]
        folds = [e["args"] for e in events if e["name"] == compress_span][-1:]
        if not scans:
            return {}
        check(scans == [self.facts["scanned"]["lineitem"]],
              f"the scan kept {scans}, the reference "
              f"{self.facts['scanned']['lineitem']}")
        fold = folds[0] if folds else {}
        check(fold.get("combined") == 1
              and fold.get("rows") == self.facts["scanned"]["lineitem"][1]
              and fold.get("groups", 0) >= len(self.want["count"])
              and fold.get("group_rows_max") <= int(max(
                  self.want["count"], default=0)),
              f"the compress span said {fold}, the reference "
              f"{self.facts['scanned']['lineitem'][1]} rows in groups of "
              f"{self.want['count'].tolist()}")
        return {"scan": scans[0], "compress": {k: fold[k] for k in (
            "rows", "groups", "group_rows_max", "key_words", "value_words",
            "combined")}}

    def digest(self, result: dict, outdir: str) -> str:
        """The tables' checksum as they lie when the digest is taken (once
        for all the digests between two jobs: a window holds five hundred
        jobs, and a checksum reads 5.4 GB), then ``oink_script``'s: the
        message, ``mrq1``'s checksum and ``q1.txt``."""
        import hashlib
        if self._tables_read is None:
            self._tables_read = self._tables_sum()
        h = hashlib.sha256(f"tables {self._tables_read}".encode())
        h.update(super().digest(result, outdir).encode())
        return h.hexdigest()

    def info(self) -> dict:
        """The combiner's program and the bytes its one execution a job
        must move (``kernels_combine.combine_bytes`` over the reference's
        counts: the kept rows, an 8-byte key and six int64 a row, a row a
        group out)."""
        moved = kernels_combine.combine_bytes(
            self.facts["scanned"]["lineitem"][1], KEY_BYTES, VALUE_BYTES,
            len(self.want["count"]))
        return {"programs": {COMBINE_PROGRAM: self.combine_program},
                "bytes_moved": {COMBINE_PROGRAM: moved / self.ndev}}


def _q1_hash() -> str:
    import hashlib
    with open(refq1.__file__, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()
