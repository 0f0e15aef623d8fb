"""Job kind ``tpch``: the ``oink_script`` job over seeded TPC-H tables that
set-up loads onto the mesh and every job queries where they lie.

The job itself is ``oink_script``'s (the script object of set-up, shared;
the traffic file's commands: ``tpch_q3``; the result ``mrq3`` waited for).
This module adds what that one has no place for: the tables made from
``--seed`` (``gen/tpch.py``) and the reference's answer
(``refs/tpch.q3``), kept together in the benchmark's cache; the
``{customer}`` / ``{orders}`` / ``{lineitem}`` paths the set-up commands
name; ``corpus_bytes`` for ``corpus_rate`` (the bytes of the three tables'
rows, which every job scans); the warm-up job held to the reference in
every group and in its ten lines (``refs/tpch.check_q3``), and its spans
to the reference's row counts; a digest that holds a window job's
``q3.txt`` to the warm-up's byte for byte unless the reference found a tie
in (revenue, o_orderdate) among the lines, and that includes a checksum
of the three tables as they lie on the device, so that a job which
changed a table it only reads fails the run.  Configuration keys read:
``scale_factor``, ``segment``, ``date``, ``columns``.

It needs the program to have the application: ``prepare`` refuses at once
a tree without ``gpu_mapreduce_tpu.apps.tpch``.
"""

import functools
import hashlib
import importlib.util
import json
import os

import numpy as np

from benchmark import check, kernels_join
from benchmark.gen import tpch as gen
from benchmark.jobs import oink_script
from benchmark.refs import tpch as ref

JOIN_PROGRAM = "join"       # the name the roofline metric asks for
RESULT = "mrq3"
LINES = "q3.txt"
KEY_BYTES = ref.KEY_BYTES


def _source_hash() -> str:
    h = hashlib.sha256()
    for module in (gen, ref):
        with open(module.__file__, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


@functools.lru_cache(maxsize=None)
def _table_sum():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def table_sum(key, value, counts):
        """A wrapping u64 sum over the valid rows: column j of (key ++
        value) times the j-th odd multiplier.  Any word that changes
        changes it."""
        nshards = counts.shape[0]
        cap = key.shape[0] // nshards
        row = jnp.arange(key.shape[0])
        valid = (row % cap) < counts[row // cap]
        total = jnp.uint64(0)
        j = 0
        for block in (key, value):
            for c in range(block.shape[1]):
                total += jnp.sum(jnp.where(valid, block[:, c], 0),
                                 dtype=jnp.uint64) * jnp.uint64(
                                     0x9E3779B97F4A7C15 * (2 * j + 1)
                                     % (1 << 64))
                j += 1
        return total
    return table_sum


class Job(oink_script.Job):
    def prepare(self) -> dict:
        check(importlib.util.find_spec("gpu_mapreduce_tpu.apps.tpch")
              is not None,
              "this tree has no gpu_mapreduce_tpu.apps.tpch: it has no "
              "keyed join of two datasets and cannot run TPC-H Query 3, "
              "which is this cell")
        from gpu_mapreduce_tpu.apps import tpch as app
        from gpu_mapreduce_tpu.obs import names
        cfg = self.config
        check(app.COLUMNS == ref.COLUMNS == {
            t: tuple(c) for t, c in cfg["columns"].items()}
              and app.SEGMENTS == ref.SEGMENTS and app.EPOCH == ref.EPOCH,
              "the application, the reference and the configuration "
              "disagree on the tables' columns")
        self.join_program = names.JOIN_ROWS
        self.spans = (names.TPCH_SCAN, names.JOIN_SPAN)
        sf = float(cfg["scale_factor"])
        segment, date = cfg["segment"], cfg["date"]

        def build(tmp):
            paths = gen.make_tables(os.path.join(tmp, "tables"), sf,
                                    self.seed)
            tables = [ref.read_table(t, paths[t]) for t in ref.TABLES]
            want = ref.q3(*tables, segment, date)
            np.savez(os.path.join(tmp, "q3.npz"), **{
                k: want[k] for k in ("orderkey", "revenue", "orderdate",
                                     "shippriority")})
            with open(os.path.join(tmp, "facts.json"), "w") as f:
                json.dump({"scanned": want["scanned"],
                           "matched": want["matched"],
                           "files": {t: [os.path.basename(p) for p in ps]
                                     for t, ps in paths.items()}}, f)

        key = (f"tpch-{self.seed}-sf{sf:g}-{segment}-{date}-"
               f"{_source_hash()[:12]}")
        hit = os.path.isdir(self.cache.path(key))
        d = self.cache.entry(key, build)
        with open(os.path.join(d, "facts.json")) as f:
            self.facts = json.load(f)
        with np.load(os.path.join(d, "q3.npz")) as z:
            self.want = {k: z[k] for k in z.files}
        paths = {t: [os.path.join(d, "tables", n) for n in names_]
                 for t, names_ in self.facts["files"].items()}
        self.rows = {t: n for t, (n, _) in self.facts["scanned"].items()}
        self.corpus_bytes = sum(self.rows[t] * ref.record_bytes(t)
                                for t in ref.TABLES)
        self.values.update({t: " ".join(ps) for t, ps in paths.items()})
        facts = super().prepare()
        # what the tables hold of the device, and the most the load held
        stats = self.mesh.devices.flat[0].memory_stats() or {}
        facts["hbm_after_load"] = {k: stats.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use")}
        self.tables_sum = self._tables_sum()
        return {"corpus_cache_hit": hit, "rows": self.rows,
                "corpus_bytes": self.corpus_bytes,
                "groups": len(self.want["orderkey"]),
                "tie_in_the_ten": ref.tied(self.want),
                "tables_sum": self.tables_sum, **self.facts["matched"],
                **facts}

    def work(self) -> dict:
        return {"corpus_bytes": self.corpus_bytes}

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
        """Every group ``mrq3`` holds, as the reference names them."""
        fr = script.obj.get_mr(RESULT).kv.one_frame()
        fr = fr if hasattr(fr.key, "data") else fr.to_host()
        key = np.asarray(fr.key.data).astype(np.int64).reshape(-1, 4)
        return {"orderkey": (key[:, 0] << 32) | key[:, 1],
                "revenue": np.asarray(fr.value.data).reshape(-1),
                "orderdate": key[:, 2], "shippriority": key[:, 3]}

    def check(self, result: dict, outdir: str) -> dict:
        facts = super().check(result, outdir)
        with open(os.path.join(outdir, LINES)) as f:
            printed = f.read().splitlines()
        facts.update(ref.check_q3(self.want, self.groups(result["script"]),
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
        """The command's message as the reference's counts make it: every
        scan's rows and rows kept, both joins' matches, the groups."""
        scanned, matched = self.facts["scanned"], self.facts["matched"]
        kept = ", ".join(f"{t} {scanned[t][1]} of {scanned[t][0]}"
                         for t in ref.TABLES)
        return (f"TPC-H Q3 {self.config['segment']} {self.config['date']}: "
                f"rows kept {kept}; {matched['orders'][1]} orders and "
                f"{matched['lineitem'][1]} lines joined; "
                f"{len(self.want['orderkey'])} groups, {lines} lines")

    def _spans(self) -> dict:
        """What the warm-up job's scan and join spans said, held to the
        reference's counts; nothing with the tracer off."""
        from gpu_mapreduce_tpu.obs import get_tracer
        scan_span, join_span = self.spans
        events = get_tracer().events()
        scans = {e["args"]["table"]: [e["args"]["rows_in"],
                                      e["args"]["rows_out"]]
                 for e in events if e["name"] == scan_span}
        joins = [[e["args"]["probe_rows"], e["args"]["matched_rows"]]
                 for e in events if e["name"] == join_span][-2:]
        if not scans:
            return {}
        check(scans == self.facts["scanned"],
              f"the scans kept {scans}, the reference "
              f"{self.facts['scanned']}")
        check(joins == [self.facts["matched"]["orders"],
                        self.facts["matched"]["lineitem"]],
              f"the joins matched {joins}, the reference "
              f"{self.facts['matched']}")
        return {"scans": scans, "joins": joins}

    def digest(self, result: dict, outdir: str) -> str:
        h = hashlib.sha256(f"tables {self._tables_sum()}".encode())
        if not ref.tied(self.want):
            h.update(super().digest(result, outdir).encode())
            return h.hexdigest()
        # two of the lines tie in (revenue, o_orderdate): which key comes
        # first is the job's to choose, so those two columns alone are
        # held to the warm-up's
        h.update("\n".join(result["messages"]).encode())
        for name, (total, counts) in sorted(result["checksums"].items()):
            h.update(f"{name} {int(total)} {sum(counts)}".encode())
        with open(os.path.join(outdir, LINES)) as f:
            for l in f.read().splitlines():
                h.update("|".join(l.split("|")[1:3]).encode())
        return h.hexdigest()

    def info(self) -> dict:
        """The join program and the bytes its two executions a job must
        move (``kernels_join.join_bytes`` over the reference's counts):
        orders (4 value words) with the segment's customers (1 word),
        then lineitem (2 words) with the open orders (2 words)."""
        scanned, matched = self.facts["scanned"], self.facts["matched"]
        early, open_orders = matched["orders"]
        late, joined = matched["lineitem"]
        moved = (kernels_join.join_bytes(early, scanned["customer"][1],
                                         open_orders, KEY_BYTES, 16, 4)
                 + kernels_join.join_bytes(late, open_orders, joined,
                                           KEY_BYTES, 8, 8))
        return {"programs": {JOIN_PROGRAM: self.join_program},
                "bytes_moved": {JOIN_PROGRAM: moved / self.ndev}}
