"""tpch_load / tpch_q3 / tpch_q1 commands: ``apps/tpch`` from a script.

``tpch_load -i customer_files orders_files lineitem_files -o NULL customer
-o NULL orders -o NULL lineitem``: the three inputs are files of the
tables' fixed-width records (never MR objects), in the schema's order;
each output's MR name takes that table, which stays where the record map
put it (on the mesh, under a mesh).

``tpch_q3 SEGMENT DATE -i customer orders lineitem -o q3.txt mrq3``:
Query 3 over three named tables; output 1's path takes the ten lines
``l_orderkey|revenue|o_orderdate|o_shippriority``, its MR name every
group of the pre-limit result.  The tables are left as they were.

``tpch_q1 DELTA -i lineitem -o q1.txt mrq1``: Query 1 over the named
``lineitem``, l_shipdate <= 1998-12-01 - DELTA days; output 1's path takes
a line a group (``l_returnflag|l_linestatus|sum_qty|sum_base_price|
sum_disc_price|sum_charge|avg_qty|avg_price|avg_disc|count_order``), its
MR name the groups.  The table is left as it was."""

from __future__ import annotations

from ...apps import tpch
from ...core.runtime import MRError
from ..command import Command, command


@command("tpch_load")
class TpchLoad(Command):
    ninputs = 3
    noutputs = 3

    def params(self, args):
        if args:
            raise MRError("Illegal tpch_load command")

    def run(self):
        obj = self.obj
        if len(obj.inputs) != 3 or any(d.mr_name is not None
                                       for d in obj.inputs):
            raise MRError("tpch_load reads three tables' files of records, "
                          "not MR objects")
        self.rows = {}
        for table, src, out in zip(tpch.TABLES, obj.inputs, obj.outputs):
            mr = obj.create_mr()
            self.rows[table] = tpch.load_table(mr, table, src.paths)
            if out.mr_name is not None:
                obj.name_mr(out.mr_name, mr)
        self.message("TPC-H: " + ", ".join(
            f"{n} {t} rows" for t, n in self.rows.items()))
        obj.cleanup()


@command("tpch_q3")
class TpchQ3(Command):
    ninputs = 3
    noutputs = 1

    def params(self, args):
        if len(args) != 2:
            raise MRError("Illegal tpch_q3 command")
        self.segment, self.date = args

    def run(self):
        obj = self.obj
        if len(obj.inputs) != 3 or any(d.mr_name is None
                                       for d in obj.inputs):
            raise MRError("tpch_q3 reads three named tables (tpch_load)")
        tables = [obj.input(i) for i in (1, 2, 3)]
        out = obj.outputs[0] if obj.outputs else None
        groups, self.lines, self.counts = tpch.q3(
            obj.create_mr, *tables, self.segment, self.date,
            path=out.path if out else None)
        if out is not None and out.mr_name is not None:
            obj.name_mr(out.mr_name, groups)
        self.message(tpch.message(self.segment, self.date, self.counts,
                                  len(self.lines)))
        obj.cleanup()


@command("tpch_q1")
class TpchQ1(Command):
    ninputs = 1
    noutputs = 1

    def params(self, args):
        if len(args) != 1 or not args[0].isdigit():
            raise MRError("Illegal tpch_q1 command")
        self.delta = int(args[0])

    def run(self):
        obj = self.obj
        if len(obj.inputs) != 1 or obj.inputs[0].mr_name is None:
            raise MRError("tpch_q1 reads the named lineitem table "
                          "(tpch_load)")
        out = obj.outputs[0] if obj.outputs else None
        groups, self.lines, self.counts = tpch.q1(
            obj.create_mr, obj.input(1), self.delta,
            path=out.path if out else None)
        if out is not None and out.mr_name is not None:
            obj.name_mr(out.mr_name, groups)
        self.message(tpch.q1_message(self.delta, self.counts,
                                     len(self.lines)))
        obj.cleanup()
