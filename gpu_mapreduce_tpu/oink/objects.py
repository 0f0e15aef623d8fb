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

import numpy as np

from .. import native
from ..core.column import row_columns, row_fields, write_rows
from ..core.frame import KMVFrame, KVFrame
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
        goldens address one file.

        Lines come from columns where the output can see that they may
        (:func:`_block_columns`): the printer declares its line
        (``kernels.row_template``: ``print_edge``, ``print_vertex``,
        ``print_vertex_value``, ``print_edge_value``,
        ``print_vertex_rank``, ``tri.print_tri``), the frame is a dense
        KV frame and its columns are of the kinds the template names.
        Such a frame becomes text a block at a time
        (``core/column.write_rows``: the blocks formatted on the MR's
        ingest pool and written in order; on a mesh one shard after
        another, as before), and no Python object is made for a row.  Every
        other frame (byte keys, KMV groups, a float under ``%d``, a
        printer with no template, no printer) is printed a row at a
        time, as ever; the files hold the same bytes either way.  The
        span says how it went: ``block_rows`` of its ``rows`` left as
        blocks, ``native`` 1 when the native formatter wrote them."""
        mr._flush_plan()   # a pending fused plan must land before we read
        if index > len(self.outputs):
            return
        d = self.outputs[index - 1]
        if d.path is not None:
            with get_tracer().span(names.OINK_OUTPUT, cat=names.HOST,
                                   path=d.path) as sp:
                _ensure_parent(d.path)
                pool = mr._ingest_pool()
                fr = _mesh_frame(mr)
                if fr is not None and fr.nprocs > 1:
                    if "%" in d.path:
                        paths = [d.path.replace("%", str(p), 1)
                                 for p in range(fr.nprocs)]
                    else:
                        paths = [f"{d.path}.{p}" for p in range(fr.nprocs)]
                    block_rows = 0
                    for p, path in enumerate(paths):    # a shard at a time
                        with open(path, "w") as fp:
                            block_rows += _write_frame(
                                fp, fr.shard_to_host(p), printer, pool)
                else:
                    paths, block_rows = [d.path], 0
                    with open(d.path, "w") as fp:
                        ds = mr.kv if mr.kv is not None else mr.kmv
                        for f in (ds.frames() if ds is not None else ()):
                            block_rows += _write_frame(fp, f, printer, pool)
                sp.set(rows=(mr.kv.nkv if mr.kv is not None
                             else mr.kmv.nkmv if mr.kmv is not None else 0),
                       bytes=sum(os.path.getsize(p) for p in paths),
                       **block_attrs(block_rows))
        if d.mr_name is not None:
            self.name_mr(d.mr_name, mr)


def block_attrs(block_rows: int) -> dict:
    """What a span that writes text says of how: ``block_rows``, the rows
    formatted from columns a block at a time, and ``native``, 1 when the
    native formatter wrote them."""
    return dict(block_rows=block_rows,
                native=int(block_rows > 0 and native.has_format_rows()))


def _block_columns(printer, fr) -> Optional[list]:
    """The columns of a host frame's lines, one a field of the printer's
    template, where the lines may be formatted from them: the printer
    declares a template, the frame is a dense KV frame, the key holds
    the template's first ``key_fields`` fields and the value the rest
    (none: the printer does not print it), a part of one field is
    ``[n]`` and one of several ``[n, w]`` (a row of ``[n, 1]`` prints as
    a tuple), and the kinds are the template's.  Else None."""
    template = getattr(printer, "template", None)
    if template is None or not isinstance(fr, KVFrame) or not fr.is_dense():
        return None
    nkey = printer.key_fields
    nvalue = len(row_fields(template)) - nkey
    parts = [(np.asarray(fr.key.data), nkey)]
    if nvalue:
        parts.append((np.asarray(fr.value.data), nvalue))
    if any(a.shape[1:] != ((w,) if w > 1 else ()) for a, w in parts):
        return None
    return row_columns(template, [a for a, _ in parts])


def _write_frame(fp, fr, printer, pool) -> int:
    """One frame's lines onto the text file ``fp``, from its columns
    where :func:`_block_columns` gives them, else a printer call a row;
    returns the rows that left as blocks."""
    if not isinstance(fr, (KVFrame, KMVFrame)):
        fr = fr.to_host()
    cols = _block_columns(printer, fr)
    if cols is not None:
        fp.flush()      # what the text layer holds goes first
        write_rows(fp.buffer, printer.template, cols, pool)
        return len(fr)
    rows = fr.pairs() if isinstance(fr, KVFrame) else fr.groups()
    if printer is None:
        for k, v in rows:
            fp.write(f"{k} {v}\n")
    else:
        for k, v in rows:
            printer(k, v, fp)
    return 0


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
