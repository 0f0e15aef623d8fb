"""OINK object manager — named/temporary MapReduce objects + I/O descriptors.

Re-designs ``oink/object.{h,cpp}``: the registry of wrapped MR objects that
commands create, consume, and hand back to the script layer.

* named MRs persist across commands (``mr`` script objects); temporaries
  from :meth:`create_mr` die at :meth:`cleanup` (``object.cpp`` MRwrap
  lifecycle, ``oink/object.h:91-98``);
* input descriptors (``-i`` in scripts, ``oink/object.h:117-155``) are
  either file path globs (command reads them with a parser callback) or an
  existing named MR (used directly — commands copy-on-write if permanent,
  mirroring ``obj->permanent(mr) ⇒ copy_mr``);
* output descriptors (``-o``) carry a file path (the command's print
  callback writes it) and/or a name to register the result MR under;
* per-script MR defaults (the ``set`` command, ``oink/object.h:100-113``):
  verbosity/timer/memsize/outofcore/minpage/maxpage/freepage/zeropage/
  fpath applied to every MR the manager creates.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Union

from ..core.mapreduce import MapReduce
from ..core.runtime import MRError
from ..obs import get_tracer, names


@dataclass
class InputDescriptor:
    paths: Optional[List[str]] = None     # file/glob mode
    mr_name: Optional[str] = None         # named-MR mode


@dataclass
class OutputDescriptor:
    path: Optional[str] = None            # write file via print callback
    mr_name: Optional[str] = None         # register result as named MR


class ObjectManager:
    """Holds named MRs, temporaries, descriptors, and MR defaults."""

    # settings the `set` script command may override (doc: oinkdoc/set.txt;
    # `fuse` is ours — plan/ fused pipelines, doc/plan.md — as is
    # `onfault`, the ft/ failed-map-input policy, doc/reliability.md)
    MR_SETTINGS = ("verbosity", "timer", "memsize", "outofcore", "minpage",
                   "maxpage", "freepage", "zeropage", "fpath", "fuse",
                   "onfault")

    def __init__(self, comm=None):
        self.comm = comm
        self.named: Dict[str, MapReduce] = {}
        self._temps: List[MapReduce] = []
        self._anon_names: List[str] = []
        self._anon_counter = 0
        self.defaults: Dict[str, object] = {}
        self.pinned: Dict[str, object] = {}
        self.inputs: List[InputDescriptor] = []
        self.outputs: List[OutputDescriptor] = []

    # -- settings ----------------------------------------------------------
    def set_default(self, name: str, value):
        if name not in self.MR_SETTINGS:
            raise MRError(f"unknown set parameter {name!r}")
        if name in self.pinned and value != self.pinned[name]:
            # serve/ tenancy: budget settings the daemon seeded are not
            # the tenant's to change — a script `set maxpage 100000`
            # must fail its session loudly, not escape its allowance
            raise MRError(f"setting {name!r} is pinned by the server "
                          f"(tenant budget; doc/serve.md)")
        self.defaults[name] = value

    def pin(self, **settings):
        """Install settings as defaults AND lock them: later
        ``set_default`` calls (the script `set` command) for these keys
        raise instead of overriding — the serve/ tenant-budget
        enforcement point."""
        for name, value in settings.items():
            self.set_default(name, value)
            self.pinned[name] = value

    # -- MR lifecycle ------------------------------------------------------
    def create_mr(self) -> MapReduce:
        mr = MapReduce(self.comm, **self.defaults)
        self._temps.append(mr)
        return mr

    def permanent(self, mr: MapReduce) -> bool:
        return any(m is mr for m in self.named.values())

    def copy_mr(self, mr: MapReduce) -> MapReduce:
        cp = mr.copy()
        self._temps.append(cp)
        return cp

    def name_mr(self, name: str, mr: MapReduce):
        self.named[name] = mr
        self._temps = [m for m in self._temps if m is not mr]

    def get_mr(self, name: str) -> MapReduce:
        if name not in self.named:
            raise MRError(f"no MapReduce object named {name!r}")
        return self.named[name]

    def free_mr(self, mr: MapReduce):
        """Free a temporary's data mid-command (iterative commands create
        MRs per round; deferring to cleanup() would grow memory linearly
        with iteration count)."""
        if mr.kv is not None:
            mr.kv.free()
            mr.kv = None
        if mr.kmv is not None:
            mr.kmv.free()
            mr.kmv = None
        self._temps = [m for m in self._temps if m is not mr]

    def delete_mr(self, name: str):
        mr = self.named.pop(name, None)
        if mr is not None:
            if mr.kv is not None:
                mr.kv.free()
            if mr.kmv is not None:
                mr.kmv.free()

    def cleanup(self):
        """Free temporaries and drop anonymous input registrations
        (reference Object::cleanup).  Anonymous MRs are caller-owned, so
        only the registry entry is released, not their data."""
        for mr in self._temps:
            if mr.kv is not None:
                mr.kv.free()
            if mr.kmv is not None:
                mr.kmv.free()
        self._temps = []
        for name in self._anon_names:
            self.named.pop(name, None)
        self._anon_names = []
        self.inputs = []
        self.outputs = []

    # -- descriptors -------------------------------------------------------
    def add_input(self, source: Union[str, "os.PathLike",
                                      Sequence[str], MapReduce]):
        """Add the next -i descriptor: path(s) or a named MR (by name)."""
        if isinstance(source, os.PathLike):
            source = os.fspath(source)
        if isinstance(source, MapReduce):
            self._anon_counter += 1
            name = f"_anon{self._anon_counter}"
            self.named[name] = source
            self._anon_names.append(name)
            self.inputs.append(InputDescriptor(mr_name=name))
        elif isinstance(source, str) and source in self.named:
            self.inputs.append(InputDescriptor(mr_name=source))
        else:
            paths = [source] if isinstance(source, str) else list(source)
            self.inputs.append(InputDescriptor(paths=paths))

    def add_output(self, path: Optional[str] = None,
                   mr_name: Optional[str] = None):
        self.outputs.append(OutputDescriptor(path=path, mr_name=mr_name))

    # -- the command-facing protocol (reference obj->input/obj->output) ----
    def input(self, index: int, parser: Optional[Callable] = None,
              ptr=None) -> MapReduce:
        """Resolve -i descriptor #index (1-based).  File mode runs
        ``parser(itask, filename, kv, ptr)`` over the paths; MR mode
        returns the named MR as-is (reference oink/object.cpp add_input)."""
        if index > len(self.inputs):
            raise MRError(f"command input {index} not provided")
        d = self.inputs[index - 1]
        if d.mr_name is not None:
            return self.get_mr(d.mr_name)
        if parser is None:
            raise MRError("file input requires a parser callback")
        with get_tracer().span(names.OINK_INPUT, cat=names.HOST,
                               source=" ".join(d.paths)) as sp:
            mr = self.create_mr()
            rows = mr.map_files(d.paths, parser, ptr)
            sp.set(rows=int(rows), bytes=sum(
                os.path.getsize(p) for p in d.paths if os.path.isfile(p)))
        return mr

    def output(self, index: int, mr: MapReduce,
               printer: Optional[Callable] = None, ptr=None):
        """Handle -o descriptor #index: write ``printer(key, value, fp)``
        lines to the path if given; register mr under the name if given.
        Missing descriptor ⇒ no-op (commands always call output; scripts
        decide, reference oink/object.cpp:237-370).

        A mesh-resident dataset on P>1 shards writes PER-SHARD files —
        ``path.<p>``, or the first ``%`` in the path replaced by the
        shard id (the reference's expandpath postpend/substitute rules,
        oink/object.cpp:900-941) — each from its own shard block, so
        output never funnels the dataset through the controller.  Host
        datasets (and P==1) keep the exact single path: our serial tier
        intentionally omits the reference's ``.0`` suffix so script
        goldens address one file."""
        mr._flush_plan()   # a pending fused plan must land before we read
        if index > len(self.outputs):
            return
        d = self.outputs[index - 1]
        if d.path is not None:
            with get_tracer().span(names.OINK_OUTPUT, cat=names.HOST,
                                   path=d.path) as sp:
                _ensure_parent(d.path)
                nbytes = 0
                fr = _mesh_frame(mr)
                if fr is not None and fr.nprocs > 1:
                    for p in range(fr.nprocs):
                        if "%" in d.path:
                            path = d.path.replace("%", str(p), 1)
                        else:
                            path = f"{d.path}.{p}"
                        host = fr.shard_to_host(p)
                        with open(path, "w") as fp:
                            rows = (host.pairs() if hasattr(host, "pairs")
                                    else host.groups())
                            if printer is None:
                                for k, v in rows:
                                    fp.write(f"{k} {v}\n")
                            else:
                                for k, v in rows:
                                    printer(k, v, fp)
                        nbytes += os.path.getsize(path)
                else:
                    with open(d.path, "w") as fp:
                        if printer is None:
                            mr_dump(mr, fp)
                        else:
                            for k, v in _iter_pairs(mr):
                                printer(k, v, fp)
                    nbytes = os.path.getsize(d.path)
                sp.set(rows=(mr.kv.nkv if mr.kv is not None
                             else mr.kmv.nkmv if mr.kmv is not None else 0),
                       bytes=nbytes)
        if d.mr_name is not None:
            self.name_mr(d.mr_name, mr)


def _ensure_parent(path: str) -> None:
    """Create an -o path's parent directory: `set prepend sub` (and the
    serve/ session re-rooting built on it) names nested output paths
    whose directories the script never mkdir'd."""
    parent = os.path.dirname(path)
    if parent:
        try:
            os.makedirs(parent, exist_ok=True)
        except OSError:
            pass    # the open() that follows reports the real error


def _mesh_frame(mr: MapReduce):
    """The mr's single mesh-resident frame, or None (host/serial data,
    multi-frame datasets, or no data)."""
    from ..parallel.sharded import ShardedKMV, ShardedKV
    ds = mr.kv if mr.kv is not None else mr.kmv
    if ds is None or ds.nframes != 1:
        return None
    fr = next(iter(ds.frames()))
    return fr if isinstance(fr, (ShardedKV, ShardedKMV)) else None


def _iter_pairs(mr: MapReduce):
    """Yield (key, value) per KV pair, or (key, [values]) per KMV group when
    the MR holds a KMV (e.g. neighbor's adjacency lists)."""
    if mr.kv is not None:
        for fr in mr.kv.frames():
            yield from fr.pairs()
    elif mr.kmv is not None:
        for fr in mr.kmv.frames():
            yield from fr.groups()


def mr_dump(mr: MapReduce, fp):
    for k, v in _iter_pairs(mr):
        fp.write(f"{k} {v}\n")
