"""terasort command: ``apps/terasort.TeraSort`` from a script.

``terasort -i files -o outdir mr``: the files of input 1 (files of
100-byte records, never an MR object) are sorted by their 10-byte keys;
output 1's path is the DIRECTORY that takes one binary ``part-<shard>`` a
shard, its MR name the sorted dataset (key words, value words)."""

from __future__ import annotations

from ...apps.terasort import TeraSort
from ...core.runtime import MRError
from ..command import Command, command


@command("terasort")
class TeraSortCommand(Command):
    ninputs = 1
    noutputs = 1

    def params(self, args):
        if args:
            raise MRError("Illegal terasort command")

    def run(self):
        obj = self.obj
        if not obj.inputs or obj.inputs[0].mr_name is not None:
            raise MRError("terasort reads files of records, not an MR object")
        out = obj.outputs[0] if obj.outputs else None
        app = TeraSort(mr=obj.create_mr())
        self.nrecords = app.run(obj.inputs[0].paths,
                                outdir=out.path if out else None)
        self.parts = app.parts
        if out is not None and out.mr_name is not None:
            obj.name_mr(out.mr_name, app.mr)
        self.message(f"TeraSort: {self.nrecords} records, "
                     f"{len(app.parts)} part files, "
                     f"{len(app.splitters)} splitters")
        obj.cleanup()
