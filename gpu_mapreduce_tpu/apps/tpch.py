"""TPC-H on the op algebra: three tables resident as MR objects, and
Query 3 ("Shipping Priority") over them.

    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = :segment and c_custkey = o_custkey
      and l_orderkey = o_orderkey and o_orderdate < :date
      and l_shipdate > :date
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate  limit 10

A table is a KV of fixed-width rows read by the record map
(``utils/io.RecordFormat``, as TeraSort's records are): the key is the
primary key, 8 bytes big-endian in the file and two u32 words (high, low)
on the device, so both sides of a join are keyed alike; the value is one
u32 word a column (``COLUMNS``), a money column two (cents as int64, low
word first), a date days since 1992-01-01.  Variable-width text is not
held.

The query, from ``MapReduce`` operations only:

* three scans, ``map_mr`` over a resident table with a predicate that
  keeps the key and the few words the query needs (on a mesh
  ``parallel/devkernels.skv_scan``: the kept rows ordered by a sort of
  one operand and then taken);
  the ``lineitem`` scan multiplies ``extendedprice * (100 - discount)``
  into a 64-bit integer, so nothing downstream is a float;
* ``orders.join(customer)`` on the customer key, a ``map_mr`` that
  re-keys the open orders by their order key, ``lineitem.join(orders)``;
* ``collate`` + ``reduce(sum)`` of the int64 revenue by (l_orderkey,
  o_orderdate, o_shippriority);
* the top ten by (revenue desc, o_orderdate): ``gather(1)`` +
  ``sort_values(-1)`` of (revenue, -o_orderdate) and the first rows read.

The tables are read and never written: ``map_mr`` snapshots its source
and marks its frames shared, so no donation deletes a table's arrays.
"""

from __future__ import annotations

import datetime
import os
from typing import Callable, Optional, Sequence

import numpy as np

from ..core.frame import KVFrame
from ..core.mapreduce import MapReduce
from ..core.runtime import MRError
from ..obs import get_tracer, names
from ..ops.reduces import sum_values
from ..utils.io import RecordFormat, findfiles

EPOCH = datetime.date(1992, 1, 1)
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
KEY_BYTES = 8
LIMIT = 10
COLUMNS = {
    "customer": ("nationkey", "acctbal", "acctbal_hi", "mktsegment"),
    "orders": ("custkey_hi", "custkey", "orderstatus", "totalprice",
               "totalprice_hi", "orderdate", "orderpriority", "clerk",
               "shippriority"),
    "lineitem": ("partkey", "suppkey", "linenumber", "quantity",
                 "extendedprice", "extendedprice_hi", "discount", "tax",
                 "returnflag", "linestatus", "shipdate", "commitdate",
                 "receiptdate", "shipinstruct", "shipmode"),
}
TABLES = tuple(COLUMNS)
FORMATS = {t: RecordFormat(KEY_BYTES + 4 * len(c), KEY_BYTES)
           for t, c in COLUMNS.items()}

_C_SEGMENT = COLUMNS["customer"].index("mktsegment")
_O_CUSTKEY = COLUMNS["orders"].index("custkey_hi")
_O_DATE = COLUMNS["orders"].index("orderdate")
_O_PRIORITY = COLUMNS["orders"].index("shippriority")
_L_PRICE = COLUMNS["lineitem"].index("extendedprice")
_L_DISCOUNT = COLUMNS["lineitem"].index("discount")
_L_SHIPDATE = COLUMNS["lineitem"].index("shipdate")


def day(date: str) -> int:
    """Days since 1992-01-01 of ``YYYY-MM-DD``."""
    try:
        return (datetime.date.fromisoformat(date) - EPOCH).days
    except ValueError as e:
        raise MRError(f"tpch: {date!r} is no date (YYYY-MM-DD)") from e


def iso(days: int) -> str:
    return (EPOCH + datetime.timedelta(days=int(days))).isoformat()


def load_table(mr: MapReduce, table: str, files: Sequence[str]) -> int:
    """Read a table's files into ``mr`` (the record map); its rows."""
    paths = findfiles(list(files))
    with get_tracer().span(names.TPCH_LOAD, cat=names.HOST, table=table,
                           files=len(paths)) as sp:
        rows = mr.map_files(paths, FORMATS[table])
        sp.set(rows=int(rows),
               bytes=int(rows) * FORMATS[table].record_bytes)
    return int(rows)


# -- the maps -----------------------------------------------------------------
# ``rows(xp, key, value, *operands) -> (key, value, keep)`` over whole
# columns, written once for numpy (a host frame) and jax.numpy (a shard's
# block inside a program): indexing, comparisons and ``stack`` only.
# ``keep`` None: a map that keeps every row.

def _i64(xp, lo, hi):
    return lo.astype(xp.int64) | (hi.astype(xp.int64) << 32)


def _customer_rows(xp, k, v, segment):
    """c_mktsegment = :segment; keeps the segment's code."""
    return (k, v[:, _C_SEGMENT:_C_SEGMENT + 1],
            v[:, _C_SEGMENT] == segment)


def _orders_rows(xp, k, v, date):
    """o_orderdate < :date; re-keyed by o_custkey for the join with
    customer: (o_orderkey's two words, o_orderdate, o_shippriority)."""
    value = xp.stack([k[:, 0], k[:, 1], v[:, _O_DATE], v[:, _O_PRIORITY]], 1)
    return v[:, _O_CUSTKEY:_O_CUSTKEY + 2], value, v[:, _O_DATE] < date


def _lineitem_rows(xp, k, v, date):
    """l_shipdate > :date; l_extendedprice * (100 - l_discount) as the
    two words of an int64 (units of 10^-4)."""
    revenue = (_i64(xp, v[:, _L_PRICE], v[:, _L_PRICE + 1])
               * (100 - v[:, _L_DISCOUNT].astype(xp.int64)))
    value = xp.stack([(revenue & 0xFFFFFFFF).astype(xp.uint32),
                      (revenue >> 32).astype(xp.uint32)], 1)
    return k, value, v[:, _L_SHIPDATE] > date


def _by_orderkey_rows(xp, k, v):
    """An open order of the segment (orders ++ customer) keyed by its
    order key: (o_orderdate, o_shippriority)."""
    return v[:, 0:2], v[:, 2:4], None


def _groups_rows(xp, k, v):
    """A joined line (revenue words ++ o_orderdate, o_shippriority) keyed
    by the query's three group-by columns, its revenue the value."""
    key = xp.stack([k[:, 0], k[:, 1], v[:, 2], v[:, 3]], 1)
    return key, _i64(xp, v[:, 0], v[:, 1]), None


def _rank_rows(xp, k, v):
    """A group's place in the result: (revenue, -o_orderdate), descending."""
    return k, xp.stack([v, -k[:, 2].astype(xp.int64)], 1), None


def _mapper(rows: Callable, filters: bool = True) -> Callable:
    """The ``map_mr(batch=True)`` callback of a ``rows`` function; ``ptr``
    is the tuple of its operands (a segment code, a date).  On a mesh a
    map that filters is a scan (``skv_scan``: program
    ``jit_kv_scan_tpch_<rows>``), one that keeps every row a plain map of
    the rows where they lie (``skv_each``: ``jit_kv_map_tpch_<rows>``)."""
    import jax.numpy as jnp

    def dev(k, v, c, *operands):
        key, value, keep = rows(jnp, k, v, *operands)
        if not filters:
            return key, value
        return key, value, keep & (jnp.arange(k.shape[0]) < c)
    dev.__name__ = "tpch_" + rows.__name__.strip("_").removesuffix("_rows")

    def batch(fr, kv, ptr):
        operands = tuple(ptr or ())
        if not len(fr):         # an empty dataset's frame has no columns
            return
        if isinstance(fr, KVFrame):
            key, value, keep = rows(
                np, np.asarray(fr.key.to_host().data),
                np.asarray(fr.value.to_host().data),
                *(np.uint32(x) for x in operands))
            keep = slice(None) if keep is None else keep
            kv.add_batch(key[keep], value[keep])
        else:
            from ..parallel.devkernels import skv_each, skv_scan
            kv.add_frame((skv_scan if filters else skv_each)(
                fr, dev, extra=tuple(jnp.uint32(x) for x in operands)))
    return batch


SCAN = {"customer": _mapper(_customer_rows), "orders": _mapper(_orders_rows),
        "lineitem": _mapper(_lineitem_rows)}
_BY_ORDERKEY = _mapper(_by_orderkey_rows, filters=False)
_GROUPS = _mapper(_groups_rows, filters=False)
_RANK = _mapper(_rank_rows, filters=False)
# the scans' device programs, as a trace names them
SCAN_PROGRAMS = tuple(names.KV_SCAN_PREFIX + "tpch_" + t for t in TABLES)


def _scan(new_mr: Callable, table: str, source: MapReduce,
          operand: int, counts: dict) -> MapReduce:
    mr = new_mr()
    with get_tracer().span(names.TPCH_SCAN, cat=names.HOST,
                           table=table) as sp:
        rows_in = int(source.kv_stats(0)[0])
        rows_out = int(mr.map_mr(source, SCAN[table], ptr=(operand,),
                                 batch=True))
        sp.set(**{names.ATTR_ROWS_IN: rows_in,
                  names.ATTR_ROWS_OUT: rows_out,
                  names.ATTR_ROW_WORDS_IN: (
                      FORMATS[table].key_words
                      + FORMATS[table].value_words)})
    counts[table] = (rows_in, rows_out)
    return mr


def _free(*done: MapReduce) -> None:
    """Let go of steps whose rows have been read: a job's intermediates
    are otherwise all held until its command cleans up, beside the
    tables."""
    for mr in done:
        if mr.kv is not None:
            mr.kv.free()
            mr.kv = None


def line(key, revenue: int) -> str:
    """``l_orderkey|revenue|o_orderdate|o_shippriority``, the revenue with
    four decimals from the integer."""
    revenue = int(revenue)
    return (f"{(int(key[0]) << 32) | int(key[1])}|{revenue // 10000}."
            f"{revenue % 10000:04d}|{iso(key[2])}|{int(key[3])}")


def q3(new_mr: Callable, customer: MapReduce, orders: MapReduce,
       lineitem: MapReduce, segment: str, date: str,
       path: Optional[str] = None, limit: int = LIMIT):
    """Run the query over the three tables: ``(groups, lines, counts)``,
    the MR object of every group of the pre-limit result ((l_orderkey's
    two words, o_orderdate, o_shippriority) -> int64 revenue), the first
    ``limit`` lines, written to ``path`` (closed when this returns), and
    what the plan's steps counted: ``table -> (rows, rows kept)`` of the
    three scans, ``orders_joined`` and ``lines_joined`` of the two joins,
    ``groups``.  ``new_mr()`` makes the MR objects the plan needs; the
    tables are left as they were."""
    if segment not in SEGMENTS:
        raise MRError(f"tpch: no market segment {segment!r}")
    d = day(date)
    tracer = get_tracer()
    counts = {}
    with tracer.span(names.TPCH_Q3, cat=names.ENTRY, segment=segment,
                     date=date):
        building = _scan(new_mr, "customer", customer,
                         SEGMENTS.index(segment), counts)
        early = _scan(new_mr, "orders", orders, d, counts)
        counts["orders_joined"] = early.join(building)
        open_orders = new_mr()
        open_orders.map_mr(early, _BY_ORDERKEY, batch=True)
        _free(building, early)
        lines_ = _scan(new_mr, "lineitem", lineitem, d, counts)
        counts["lines_joined"] = lines_.join(open_orders)
        groups = new_mr()
        groups.map_mr(lines_, _GROUPS, batch=True)
        _free(open_orders, lines_)
        groups.collate()
        counts["groups"] = groups.reduce(sum_values, batch=True)
        with tracer.span(names.TPCH_TOPN, cat=names.HOST,
                         rows=counts["groups"]):
            ranked = new_mr()
            ranked.map_mr(groups, _RANK, batch=True)
            ranked.gather(1)
            ranked.sort_values(-1)
            fr = ranked.kv.one_frame()
            fr = (fr.slice(0, limit) if isinstance(fr, KVFrame)
                  else fr.shard_to_host(0, limit=limit))
            top = [line(k, v[0]) for k, v in zip(
                np.asarray(fr.key.data).tolist(),
                np.asarray(fr.value.data).tolist())]
        if path is not None:
            with tracer.span(names.TPCH_EMIT, cat=names.HOST,
                             rows=len(top)) as sp:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                with open(path, "w") as f:
                    f.write("".join(l + "\n" for l in top))
                sp.set(bytes=os.path.getsize(path))
    return groups, top, counts


def message(segment: str, date: str, counts: dict, lines: int) -> str:
    """What the command says of a run: every count of the plan."""
    kept = ", ".join(f"{t} {counts[t][1]} of {counts[t][0]}" for t in TABLES)
    return (f"TPC-H Q3 {segment} {date}: rows kept {kept}; "
            f"{counts['orders_joined']} orders and {counts['lines_joined']} "
            f"lines joined; {counts['groups']} groups, {lines} lines")
