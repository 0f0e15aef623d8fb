"""Job kind ``wordfreq``: the ``oink_script`` job over a seeded Zipf corpus.

The job itself is ``oink_script``'s (a fresh ``OinkScript(comm=mesh)`` per
job, the traffic file's commands, device results waited for, messages
compared).  This module adds what that one has no place for: the corpus
made from ``--seed`` (``gen/text.py``) with its plain reference
(``refs/wordcount.py``), kept together in the benchmark's cache; the
``{paths}`` and ``{ntop}`` the commands name; ``corpus_bytes`` for
``corpus_rate``; and a checksum of the counts beside the one of the words.
Configuration keys read: ``files``, ``file_bytes``, ``shapes.vocabulary``,
``shapes.ntop``.

It needs the program to carry a file's words as ranges of its buffer
(``BytesColumn.from_ranges``): a tree without that would spend minutes and
gigabytes on one Python object per word, so ``prepare`` refuses it at once.
"""

import glob
import hashlib
import os
import pickle
import types

from benchmark import check, devutil
from benchmark.gen import text
from benchmark.jobs import oink_script
from benchmark.refs import wordcount


def _source_hash() -> str:
    h = hashlib.sha256()
    for module in (text, wordcount):
        with open(module.__file__, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


class Job(oink_script.Job):
    def prepare(self) -> dict:
        from gpu_mapreduce_tpu.core.column import BytesColumn
        check(hasattr(BytesColumn, "from_ranges"),
              "this tree has no BytesColumn.from_ranges: its file map makes "
              "one Python object per word, which this cell does not start")
        shapes = self.config["shapes"]
        nfiles, nbytes = int(self.config["files"]), int(self.config["file_bytes"])
        vocabulary = int(shapes["vocabulary"])

        def build(tmp):
            paths = text.make_corpus(os.path.join(tmp, "files"), nfiles,
                                     nbytes, self.seed, vocabulary)
            with open(os.path.join(tmp, "reference.pkl"), "wb") as f:
                pickle.dump(wordcount.count_words(paths), f,
                            protocol=pickle.HIGHEST_PROTOCOL)

        key = (f"text-{self.seed}-{nfiles}x{nbytes}-v{vocabulary}-"
               f"{_source_hash()[:12]}")
        hit = os.path.isdir(self.cache.path(key))
        d = self.cache.entry(key, build)
        paths = sorted(glob.glob(os.path.join(d, "files", "part-*.txt")))
        with open(os.path.join(d, "reference.pkl"), "rb") as f:
            self.memo["want"] = pickle.load(f)
        self.corpus_bytes = sum(os.path.getsize(p) for p in paths)
        self.values.update(paths=" ".join(paths), ntop=int(shapes["ntop"]))
        return {"corpus_cache_hit": hit, "files": len(paths),
                "corpus_bytes": self.corpus_bytes,
                "words": sum(self.memo["want"].values()),
                "unique": len(self.memo["want"])}

    def work(self) -> dict:
        return {"corpus_bytes": self.corpus_bytes}

    def seal(self, result: dict) -> None:
        """``oink_script``'s checksum of the key rows (the word ids), and
        beside it the same checksum of the value rows (the counts)."""
        from gpu_mapreduce_tpu.oink.objects import _mesh_frame
        script = result["script"]
        sums = {}
        for name in self.traffic.get("device_results", []):
            fr = _mesh_frame(script.obj.get_mr(name))
            sums[name + " counts"] = (devutil.key_checksum(
                types.SimpleNamespace(key=fr.value, counts=fr.counts)),
                fr.counts.tolist())
        super().seal(result)
        result["checksums"].update(sums)
