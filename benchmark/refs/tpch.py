"""Plain reference for TPC-H Query 3 ("Shipping Priority"), the table
files' layout, and a plain join for the op's own tests.  numpy only:
nothing of the program is used.

The three tables as the deployment holds them (``configs/tpch-1chip.json``
says which of this is quoted from memory of the specification): every
column of the schema that is a number, a date, a flag or a code, one u32
word each (a money column two: cents as a 64-bit integer, low word first);
a date is days since 1992-01-01; variable-width text is not held.  A table
file is fixed-width binary records: the 8-byte primary key big-endian (so
the key's bytes order as the number does), then the value words
little-endian.

    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = :segment and c_custkey = o_custkey
      and l_orderkey = o_orderkey and o_orderdate < :date
      and l_shipdate > :date
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate  limit 10

``revenue`` is exact: cents times (100 - hundredths of discount), an
integer in units of 10^-4 dollars, summed in int64 and printed with four
decimals.
"""

import datetime
import os

import numpy as np

from benchmark import check

EPOCH = datetime.date(1992, 1, 1)
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
KEY_BYTES = 8
LIMIT = 10

# value words of each table, in record order; a money column is two words
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


def words(table: str) -> int:
    return len(COLUMNS[table])


def record_bytes(table: str) -> int:
    return KEY_BYTES + 4 * words(table)


def col(table: str, name: str) -> int:
    return COLUMNS[table].index(name)


def day(date) -> int:
    """Days since 1992-01-01 of ``YYYY-MM-DD`` (or a ``datetime.date``)."""
    if isinstance(date, str):
        date = datetime.date.fromisoformat(date)
    return (date - EPOCH).days


def iso(days: int) -> str:
    return (EPOCH + datetime.timedelta(days=int(days))).isoformat()


def segment_code(segment: str) -> int:
    check(segment in SEGMENTS, f"no market segment {segment!r}")
    return SEGMENTS.index(segment)


def money(value: np.ndarray, table: str, name: str) -> np.ndarray:
    """A two-word money column as int64."""
    at = col(table, name)
    return (value[:, at].astype(np.int64)
            | (value[:, at + 1].astype(np.int64) << 32))


# -- table files --------------------------------------------------------------

def pack(table: str, key: np.ndarray, value: np.ndarray) -> np.ndarray:
    """``[n, record_bytes]`` bytes of the rows ``key`` (u64) -> ``value``
    (u32 ``[n, words]``)."""
    out = np.empty((len(key), record_bytes(table)), np.uint8)
    out[:, :KEY_BYTES] = key.astype(">u8").view(np.uint8).reshape(-1, 8)
    out[:, KEY_BYTES:] = np.ascontiguousarray(value, "<u4").view(
        np.uint8).reshape(len(key), -1)
    return out


def read_table(table: str, paths) -> tuple:
    """``(key u64[n], value u32[n, words])`` of a table's files; the value
    is a view of the records as they were read (one copy of the table in
    memory: at scale factor 10 ``lineitem`` is 4 GB)."""
    record = np.dtype([("key", ">u8"), ("value", "<u4", (words(table),))])
    sizes = [os.path.getsize(p) for p in paths]
    for p, size in zip(paths, sizes):
        check(size % record.itemsize == 0,
              f"{p}: no whole number of {table} records")
    rows = np.empty(sum(sizes) // record.itemsize, record)
    at = 0
    for p, size in zip(paths, sizes):
        n = size // record.itemsize
        with open(p, "rb") as f:
            f.readinto(rows[at:at + n].view(np.uint8))
        at += n
    return rows["key"].astype(np.uint64), rows["value"]


# -- the query ----------------------------------------------------------------

def q3(customer, orders, lineitem, segment: str, date: str) -> dict:
    """Every group of the pre-limit result, in order of (revenue desc,
    o_orderdate, l_orderkey): ``orderkey`` u64, ``revenue`` int64,
    ``orderdate``, ``shippriority``; and what the job's spans must say:
    the rows each predicate kept and the rows each join matched."""
    ck, cv = customer
    ok, ov = orders
    lk, lv = lineitem
    building = np.sort(ck[cv[:, col("customer", "mktsegment")]
                          == segment_code(segment)])
    check(len(np.unique(ck)) == len(ck) and len(np.unique(ok)) == len(ok),
          "a primary key occurs twice")
    d = day(date)
    custkey = (ov[:, col("orders", "custkey")].astype(np.uint64)
               | (ov[:, col("orders", "custkey_hi")].astype(np.uint64)
                  << np.uint64(32)))
    early = ov[:, col("orders", "orderdate")] < d
    open_ = early & np.isin(custkey, building)
    okeys = ok[open_]
    by = np.argsort(okeys)
    okeys, odate, oprio = (okeys[by],
                           ov[open_, col("orders", "orderdate")][by],
                           ov[open_, col("orders", "shippriority")][by])
    late = lv[:, col("lineitem", "shipdate")] > d
    lkeys = lk[late]
    at = np.searchsorted(okeys, lkeys)
    hit = okeys[np.minimum(at, max(len(okeys) - 1, 0))] == lkeys \
        if len(okeys) else np.zeros(len(lkeys), bool)
    price = money(lv, "lineitem", "extendedprice")[late][hit]
    disc = lv[late, col("lineitem", "discount")][hit].astype(np.int64)
    group, inverse = np.unique(at[hit], return_inverse=True)
    revenue = np.zeros(len(group), np.int64)
    np.add.at(revenue, inverse.reshape(-1), price * (100 - disc))
    order = np.lexsort((okeys[group], odate[group], -revenue))
    group, revenue = group[order], revenue[order]
    return {"orderkey": okeys[group], "revenue": revenue,
            "orderdate": odate[group].astype(np.int64),
            "shippriority": oprio[group].astype(np.int64),
            "scanned": {"customer": [len(ck), len(building)],
                        "orders": [len(ok), int(early.sum())],
                        "lineitem": [len(lk), int(late.sum())]},
            "matched": {"orders": [int(early.sum()), int(open_.sum())],
                        "lineitem": [int(late.sum()), int(hit.sum())]}}


def line(orderkey, revenue, orderdate, shippriority) -> str:
    revenue = int(revenue)
    return (f"{int(orderkey)}|{revenue // 10000}.{revenue % 10000:04d}|"
            f"{iso(orderdate)}|{int(shippriority)}")


def lines(result: dict, limit: int = LIMIT) -> list:
    """The first ``limit`` groups as the job prints them."""
    return [line(*row) for row in zip(
        result["orderkey"][:limit], result["revenue"][:limit],
        result["orderdate"][:limit], result["shippriority"][:limit])]


def tied(result: dict, limit: int = LIMIT) -> bool:
    """Whether two of the first ``limit + 1`` groups share (revenue,
    o_orderdate): their order, and which of them is the last line, is
    then the job's to choose."""
    pairs = list(zip(result["revenue"][:limit + 1].tolist(),
                     result["orderdate"][:limit + 1].tolist()))
    return len(set(pairs)) < len(pairs)


def check_q3(result: dict, groups: dict, printed: list,
             limit: int = LIMIT) -> dict:
    """Hold a job's result to the reference's: ``groups`` (``orderkey``,
    ``revenue``, ``orderdate``, ``shippriority``, in any order) equal to
    every group of ``result``, exactly; ``printed`` equal to the first
    ``limit`` lines, rows that tie in (revenue, o_orderdate) in any
    order of their keys."""
    want = np.stack([result["orderkey"].astype(np.int64), result["revenue"],
                     result["orderdate"], result["shippriority"]], 1)
    got = np.stack([np.asarray(groups[k]).astype(np.int64) for k in (
        "orderkey", "revenue", "orderdate", "shippriority")], 1)
    check(len(got) == len(want),
          f"{len(got)} groups where the reference has {len(want)}")
    want = want[np.lexsort(want.T[::-1])]
    got = got[np.lexsort(got.T[::-1])]
    bad = np.flatnonzero((got != want).any(axis=1))
    check(not len(bad),
          f"{len(bad)} groups differ from the reference; the first: got "
          f"{got[bad[:1]].tolist()}, reference {want[bad[:1]].tolist()}")
    ref = lines(result, limit)
    check(len(printed) == len(ref),
          f"{len(printed)} lines where the reference has {len(ref)}")
    rank = lambda l: (l.split("|")[1], l.split("|")[2])
    check([rank(l) for l in printed] == [rank(l) for l in ref],
          f"the printed lines are not in the reference's order: "
          f"{printed[:3]} ... against {ref[:3]}")
    if not tied(result, limit):
        check(printed == ref, f"the printed lines differ: {printed} "
                              f"against {ref}")
    else:
        every = {line(*row) for row in zip(
            result["orderkey"], result["revenue"], result["orderdate"],
            result["shippriority"])}
        check(set(printed) <= every and len(set(printed)) == len(printed),
              "a printed line is no group of the reference")
    return {"groups": len(want), "lines": len(printed),
            "tie_in_the_ten": tied(result, limit)}


# -- a plain join -------------------------------------------------------------

def join(probe_keys, probe_values, build_keys, build_values) -> list:
    """The inner join of two lists of rows by a Python dict: ``[(key,
    probe value + build value)]`` in the probe's order; keys and values
    are tuples.  A build key that occurs twice is a ``ValueError``."""
    table = {}
    for k, v in zip(build_keys, build_values):
        if k in table:
            raise ValueError(f"build key {k} occurs twice")
        table[k] = v
    return [(k, v + table[k]) for k, v in zip(probe_keys, probe_values)
            if k in table]
