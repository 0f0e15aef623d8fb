"""The benchmark's own cache of inputs and reference answers.

A directory under the benchmark (``benchmark/cache/``, ignored by git),
so that the runs of a cell after its first need not regenerate a corpus
or recompute a numpy reference.  Keys are made by the caller from the
seed, the parameters and a hash of the generator's and reference's source;
an entry is a directory (``entry``) or an ``.npz`` of arrays
(``load``/``store``).  Old entries are evicted so that a sweep over many
seeds cannot fill the disk.
"""

import os
import shutil

import numpy as np

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cache")
KEEP = 2        # entries kept per kind (the part of the key before the first '-')


class Cache:
    def __init__(self, root: str = None):
        self.root = root or ROOT
        os.makedirs(self.root, exist_ok=True)

    def path(self, key: str) -> str:
        return os.path.join(self.root, key)

    def _evict(self, key: str) -> None:
        kind = key.split("-", 1)[0] + "-"
        names = [n for n in os.listdir(self.root)
                 if n.startswith(kind) and ".tmp" not in n]
        names.sort(key=lambda n: os.path.getmtime(self.path(n)))
        for n in names[:-KEEP]:
            p = self.path(n)
            shutil.rmtree(p) if os.path.isdir(p) else os.remove(p)

    def load(self, key: str):
        """The arrays stored under ``key``, or None."""
        p = self.path(key + ".npz")
        if not os.path.exists(p):
            return None
        with np.load(p) as z:
            return {k: z[k] for k in z.files}

    def store(self, key: str, arrays: dict) -> None:
        tmp = self.path(key + ".tmp.npz")
        np.savez(tmp, **arrays)
        os.replace(tmp, self.path(key + ".npz"))
        self._evict(key)

    def entry(self, key: str, build) -> str:
        """Directory ``key``, filled by ``build(tmpdir)`` when absent."""
        p = self.path(key)
        if not os.path.isdir(p):
            tmp = p + ".tmp"
            shutil.rmtree(tmp, ignore_errors=True)
            os.makedirs(tmp)
            build(tmp)
            os.replace(tmp, p)
            self._evict(key)
        return p
