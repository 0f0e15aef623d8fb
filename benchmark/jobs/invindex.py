"""Job kind ``invindex``: the flagship InvertedIndex application over a
seeded PUMA-density corpus read from disk inside the job.

One job = ``InvertedIndex(comm=mesh).run(paths, outdir=...)`` with the
default engine, timed until the part files are written and every device
array the application keeps is ready.  Configuration keys read: ``files``,
``file_bytes``.  The reference is a regex scan of the same files
(``gen/corpus.py``), kept with the corpus in the benchmark's cache.
"""

import glob
import hashlib
import os
import pickle
import re

from benchmark import check, devutil, kernels
from benchmark.gen import corpus


def _source_hash() -> str:
    with open(corpus.__file__, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


class Job:
    def __init__(self, config, traffic, mesh, seed, cache):
        self.config, self.mesh, self.seed, self.cache = config, mesh, seed, cache
        from gpu_mapreduce_tpu.parallel.mesh import mesh_axis_size
        self.ndev = mesh_axis_size(mesh)
        # on a mesh the exchange learns its capacities from the first run
        # (parallel/shuffle._SPEC_CACHE): warm up twice there
        self.warmup_jobs = 2 if self.ndev > 1 else 1
        self.extract_module = None

    # -- set-up ---------------------------------------------------------------
    def prepare(self) -> dict:
        from gpu_mapreduce_tpu.apps import invertedindex as app
        check(app.MAX_URL == corpus.MAX_URL and app.PATTERN == corpus.PATTERN,
              "the reference's MAX_URL/PATTERN differ from the application's")
        nfiles, nbytes = int(self.config["files"]), int(self.config["file_bytes"])

        def build(tmp):
            paths = corpus.make_corpus(os.path.join(tmp, "files"), nfiles,
                                       nbytes, self.seed)
            with open(os.path.join(tmp, "reference.pkl"), "wb") as f:
                pickle.dump(corpus.index_reference(paths), f)

        key = f"corpus-{self.seed}-{nfiles}x{nbytes}-{_source_hash()[:12]}"
        hit = os.path.isdir(self.cache.path(key))
        d = self.cache.entry(key, build)
        self.paths = sorted(glob.glob(os.path.join(d, "files", "part-*.html")))
        with open(os.path.join(d, "reference.pkl"), "rb") as f:
            by_index, self.want_pairs = pickle.load(f)
        self.want = {u: [self.paths[i] for i in fs]
                     for u, fs in by_index.items()}
        self.corpus_bytes = sum(os.path.getsize(p) for p in self.paths)
        return {"corpus_cache_hit": hit, "files": len(self.paths),
                "corpus_bytes": self.corpus_bytes}

    def work(self) -> dict:
        return {"corpus_bytes": self.corpus_bytes}

    # -- the job --------------------------------------------------------------
    def run(self, outdir: str) -> dict:
        from gpu_mapreduce_tpu.apps import invertedindex as app
        idx = app.InvertedIndex(comm=self.mesh)          # default engine
        npairs, nunique = idx.run(self.paths,
                                  outdir=os.path.join(outdir, "index"))
        devutil.block(idx.mr)
        return {"npairs": int(npairs), "nunique": int(nunique), "idx": idx,
                "stages": dict(idx.timer.times), "map_stats": dict(idx.stats)}

    def seal(self, result: dict) -> None:
        """Keep the job's numbers, let its device arrays go."""
        del result["idx"]

    # -- checking -------------------------------------------------------------
    def check(self, result: dict, outdir: str) -> dict:
        """The warm-up job against the regex reference, and the assertions
        of the smoke: default engine, the Mosaic kernel in the program that
        was dispatched, one non-empty part file per shard, and on more than
        one device a result that spans them and an exchange that moved
        rows."""
        idx = result["idx"]
        check(idx.engine == "pallas", f"default engine is {idx.engine!r}")
        check(result["npairs"] == self.want_pairs,
              f"npairs {result['npairs']} != reference {self.want_pairs}")
        check(result["nunique"] == len(self.want),
              f"nunique {result['nunique']} != reference {len(self.want)}")
        parts = sorted(glob.glob(os.path.join(outdir, "index", "part-*")))
        got = {}
        for part in parts:
            with open(part) as f:
                for line in f:
                    url, names = line.rstrip("\n").split("\t")
                    check(url not in got, f"{url!r} is in two part files")
                    got[url] = names.split(" ")
        check(got == self.want, "the part files differ from the regex "
              f"reference ({len(got)} vs {len(self.want)} urls)")
        check(len(parts) == self.ndev
              and all(os.path.getsize(p) for p in parts),
              f"{len(parts)} part files for {self.ndev} shards, or an "
              f"empty one")
        fn, avals = idx.extract_program
        text = fn.lower(*avals).as_text()
        mosaic = "tpu_custom_call" in text
        check(mosaic == (not idx.interpret),
              f"interpret={idx.interpret} but Mosaic custom call "
              f"{'present' if mosaic else 'absent'} in the extract program")
        self.extract_module = re.search(r"module @(\S+)", text).group(1)
        facts = {"npairs": result["npairs"], "nunique": result["nunique"],
                 "parts": len(parts), "interpret": idx.interpret,
                 "mosaic_custom_call": mosaic, "map_stats": dict(idx.stats)}
        if self.ndev > 1:
            for fr in devutil.frames(idx.mr):
                devutil.check_spread("invertedindex counts", fr, self.ndev)
            facts["exchange"] = devutil.check_exchange("invertedindex", idx.mr)
        return facts

    def digest(self, result: dict, outdir: str) -> str:
        h = hashlib.sha256(f"{result['npairs']} {result['nunique']}".encode())
        for part in sorted(glob.glob(os.path.join(outdir, "index", "part-*"))):
            h.update(os.path.basename(part).encode())
            devutil.hash_file(h, part)
        return h.hexdigest()

    def info(self) -> dict:
        """Names and byte counts the trace readers need."""
        return {"programs": {"extract": self.extract_module},
                "bytes_moved": {"extract": kernels.extract_bytes(
                    self.corpus_bytes) / self.ndev}}
