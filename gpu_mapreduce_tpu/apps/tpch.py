"""TPC-H on the op algebra: three tables resident as MR objects, and
Query 3 ("Shipping Priority") and Query 1 ("Pricing Summary Report",
:func:`q1`, at the end) over them.

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
_L_QUANTITY = COLUMNS["lineitem"].index("quantity")
_L_TAX = COLUMNS["lineitem"].index("tax")
_L_RETURNFLAG = COLUMNS["lineitem"].index("returnflag")
_L_LINESTATUS = COLUMNS["lineitem"].index("linestatus")
# the letters of the two flag columns' codes; Query 1's key is the letters
# themselves, so the key's order is the ORDER BY's
RETURNFLAGS = "ARN"
LINESTATUSES = "FO"
Q1_ANCHOR = "1998-12-01"


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


def _q1_rows(xp, k, v, bound):
    """l_shipdate < :bound (the day after the query's last); keyed by the
    letters of (l_returnflag, l_linestatus); six int64 columns: quantity,
    extendedprice (cents), extendedprice * (100 - discount) (10^-4
    dollars), that * (100 + tax) (10^-6), discount (hundredths), 1."""
    i64 = lambda j: v[:, j].astype(xp.int64)
    price = _i64(xp, v[:, _L_PRICE], v[:, _L_PRICE + 1])
    net = price * (100 - i64(_L_DISCOUNT))
    a, r, n = (xp.uint32(ord(c)) for c in RETURNFLAGS)
    f, o = (xp.uint32(ord(c)) for c in LINESTATUSES)
    flag, status = v[:, _L_RETURNFLAG], v[:, _L_LINESTATUS]
    key = xp.stack([xp.where(flag == 0, a, xp.where(flag == 1, r, n)),
                    xp.where(status == 0, f, o)], 1)
    value = xp.stack([i64(_L_QUANTITY), price, net, net * (100 + i64(_L_TAX)),
                      i64(_L_DISCOUNT), xp.ones(k.shape[0], xp.int64)], 1)
    return key, value, v[:, _L_SHIPDATE] < bound


def _mapper(rows: Callable, filters: bool = True,
            folded: bool = False) -> Callable:
    """The ``map_mr(batch=True)`` callback of a ``rows`` function; ``ptr``
    is the tuple of its operands (a segment code, a date).  On a mesh a
    map that filters is a scan (``skv_scan``: program
    ``jit_kv_scan_tpch_<rows>``), one that keeps every row a plain map of
    the rows where they lie (``skv_each``: ``jit_kv_map_tpch_<rows>``);
    a scan whose rows ``compress`` folds next (``folded``) is counted and
    deferred (``skv_keep``): the combiner applies it where the source's
    rows lie, and nothing is written or packed."""
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
            from ..parallel.devkernels import skv_each, skv_keep, skv_scan
            run = skv_keep if folded else skv_scan if filters else skv_each
            kv.add_frame(run(
                fr, dev, extra=tuple(jnp.uint32(x) for x in operands)))
    return batch


SCAN = {"customer": _mapper(_customer_rows), "orders": _mapper(_orders_rows),
        "lineitem": _mapper(_lineitem_rows)}
_BY_ORDERKEY = _mapper(_by_orderkey_rows, filters=False)
_GROUPS = _mapper(_groups_rows, filters=False)
_RANK = _mapper(_rank_rows, filters=False)
_Q1_SCAN = _mapper(_q1_rows, folded=True)
# Query 1's two device programs, as a trace names them: the count of the
# rows the date keeps, and the combiner that applies the map where they lie
Q1_PROGRAMS = (names.KV_SCAN_PREFIX + "tpch_q1",
               names.COMBINE_PREFIX + "tpch_q1")
# the scans' device programs, as a trace names them
SCAN_PROGRAMS = tuple(names.KV_SCAN_PREFIX + "tpch_" + t for t in TABLES)


def _scan(new_mr: Callable, table: str, source: MapReduce,
          operand: int, counts: dict, scan: Callable = None) -> MapReduce:
    mr = new_mr()
    with get_tracer().span(names.TPCH_SCAN, cat=names.HOST,
                           table=table) as sp:
        rows_in = int(source.kv_stats(0)[0])
        rows_out = int(mr.map_mr(source, scan or SCAN[table],
                                 ptr=(operand,), batch=True))
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


# -- Query 1 ------------------------------------------------------------------
#     select l_returnflag, l_linestatus, sum(l_quantity),
#            sum(l_extendedprice), sum(l_extendedprice * (1 - l_discount)),
#            sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
#            avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
#     from lineitem where l_shipdate <= date '1998-12-01' - :delta days
#     group by l_returnflag, l_linestatus
#     order by l_returnflag, l_linestatus

def _decimal(x: int, places: int) -> str:
    """The integer ``x`` of 10^-places units, with its decimal point."""
    x, one = int(x), 10 ** places
    return f"{'-' if x < 0 else ''}{abs(x) // one}.{abs(x) % one:0{places}d}"


def _average(total: int, count: int, scale: int) -> int:
    """``total * scale / count`` rounded half up, in integers."""
    return (2 * int(total) * scale + int(count)) // (2 * int(count))


def q1_line(key, sums) -> str:
    """``l_returnflag|l_linestatus|sum_qty|sum_base_price|sum_disc_price|
    sum_charge|avg_qty|avg_price|avg_disc|count_order`` of a group: the
    sums from their integers (cents, 10^-4 and 10^-6 dollars), the
    averages of quantity and price with two decimals and of the discount
    with four, each the integer sum over the count rounded half up."""
    qty, price, net, charge, disc, count = (int(x) for x in sums)
    return "|".join((
        chr(int(key[0])), chr(int(key[1])), str(qty), _decimal(price, 2),
        _decimal(net, 4), _decimal(charge, 6),
        _decimal(_average(qty, count, 100), 2),
        _decimal(_average(price, count, 1), 2),
        _decimal(_average(disc, count, 100), 4), str(count)))


def q1(new_mr: Callable, lineitem: MapReduce, delta_days: int,
       path: Optional[str] = None):
    """Run Query 1 over the resident ``lineitem``: ``(groups, lines,
    counts)``, the MR object of the groups ((l_returnflag, l_linestatus)
    as their letters' codes -> six int64: the four sums, the discounts'
    sum, the count), their lines in the key's order, written to ``path``
    (closed when this returns), and ``lineitem -> (rows, rows kept)`` and
    ``groups``.  One map that keeps l_shipdate <= 1998-12-01 -
    ``delta_days`` (on a mesh counted now and run inside the combiner),
    ``compress(sum)`` (the combiner folds the table's rows where they
    lie), ``collate``,
    ``reduce(sum)``, ``gather(1)`` + ``sort_keys``.  The table is left as
    it was."""
    if isinstance(delta_days, bool) or not isinstance(delta_days, int) \
            or delta_days < 0:
        raise MRError(f"tpch: DELTA {delta_days!r} is no number of days "
                      f"(a whole number, 0 or more)")
    bound = max(0, day(Q1_ANCHOR) - delta_days + 1)
    tracer = get_tracer()
    counts = {}
    with tracer.span(names.TPCH_Q1, cat=names.ENTRY, delta=delta_days):
        groups = _scan(new_mr, "lineitem", lineitem, bound, counts,
                       scan=_Q1_SCAN)
        groups.compress(sum_values, batch=True)
        groups.collate()
        counts["groups"] = groups.reduce(sum_values, batch=True)
        groups.gather(1)
        groups.sort_keys(1)
        with tracer.span(names.TPCH_EMIT, cat=names.HOST,
                         rows=counts["groups"]) as sp:
            fr = groups.kv.one_frame()
            fr = fr if isinstance(fr, KVFrame) else fr.to_host()
            lines = [q1_line(k, v) for k, v in zip(
                np.asarray(fr.key.data).reshape(-1, 2).tolist(),
                np.asarray(fr.value.data).reshape(-1, 6).tolist())]
            if path is not None:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                with open(path, "w") as f:
                    f.write("".join(l + "\n" for l in lines))
                sp.set(bytes=os.path.getsize(path))
    return groups, lines, counts


def q1_message(delta_days: int, counts: dict, lines: int) -> str:
    """What the command says of a run: rows scanned, rows kept, groups."""
    rows, kept = counts["lineitem"]
    return (f"TPC-H Q1 DELTA {delta_days}: {rows} lineitem rows scanned, "
            f"{kept} kept; {counts['groups']} groups, {lines} lines")
