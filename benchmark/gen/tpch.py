"""Seeded TPC-H tables: the input of the ``tpch-1chip`` deployment.

``dbgen``'s distributions, not its bit stream (``configs/tpch-1chip.json``
lists what is quoted from memory of the specification's clause 4.2 under
``assumed``): at scale factor SF, 150,000 x SF customers, 1,500,000 x SF
orders and 1 to 7 lines an order (about 6,000,000 x SF).  Order keys are
sparse, 8 of every 32 values used; an order's customer is never one whose
key is a multiple of three (a third of the customers have no orders);
o_orderdate is uniform over 1992-01-01 .. 1998-08-02, l_shipdate =
o_orderdate + 1..121 days, l_commitdate + 30..90, l_receiptdate =
l_shipdate + 1..30; quantity 1-50, discount 0.00-0.10, tax 0.00-0.08;
l_extendedprice = quantity x the part's retail price, (90000 + (partkey /
10) mod 20001 + 100 x (partkey mod 1000)) cents; five market segments,
five order priorities, four ship instructions and seven ship modes,
uniform; o_shippriority 0; l_returnflag R or A for a line received by
1995-06-17 and N after, l_linestatus F for one shipped by then and O
after, o_orderstatus F / O / P from its lines; o_totalprice the sum of
price x (1 + tax) x (1 - discount) over them.

The files are ``refs/tpch.pack``'s fixed-width records: ``customer.dat``,
and ``orders-<i>.dat`` / ``lineitem-<i>.dat`` in chunks of ``CHUNK``
orders, an order's lines in its chunk.  Everything is numpy in bulk, a
chunk at a time: the benchmark makes the tables anew for every seed, and
that time is set-up.
"""

import concurrent.futures
import os

import numpy as np

from benchmark.refs import tpch as ref

CUSTOMERS = 150_000         # x SF
ORDERS = 1_500_000          # x SF
PARTS = 200_000             # x SF
SUPPLIERS = 10_000          # x SF
CLERKS = 1_000              # x SF
CHUNK = 1_500_000           # orders a file
ORDER_DAYS = ref.day("1998-08-02") + 1      # o_orderdate: day 0 .. this - 1
CURRENT = ref.day("1995-06-17")


def counts(scale_factor: float) -> dict:
    return {"customer": max(3, round(CUSTOMERS * scale_factor)),
            "orders": max(1, round(ORDERS * scale_factor))}


def order_keys(first: int, n: int) -> np.ndarray:
    """The keys of orders ``first`` .. ``first + n - 1`` (0-based): the
    first 8 of every 32 values, from 1."""
    i = np.arange(first, first + n, dtype=np.uint64)
    return (i >> np.uint64(3) << np.uint64(5)) + (i & np.uint64(7)) \
        + np.uint64(1)


def _two(x: np.ndarray) -> tuple:
    """An int64 column as its two u32 words, low first."""
    x = x.astype(np.int64).view(np.uint64)
    return ((x & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            (x >> np.uint64(32)).astype(np.uint32))


def customer(scale_factor: float, seed: int) -> tuple:
    n = counts(scale_factor)["customer"]
    rng = np.random.default_rng([int(seed), 0])
    lo, hi = _two(rng.integers(-99999, 1000000, n))
    value = np.stack([rng.integers(0, 25, n).astype(np.uint32), lo, hi,
                      rng.integers(0, len(ref.SEGMENTS), n).astype(
                          np.uint32)], 1)
    return np.arange(1, n + 1, dtype=np.uint64), value


def orders_chunk(scale_factor: float, seed: int, index: int) -> tuple:
    """``((orders key, value), (lineitem key, value))`` of chunk
    ``index``."""
    total = counts(scale_factor)
    first = index * CHUNK
    n = min(CHUNK, total["orders"] - first)
    rng = np.random.default_rng([int(seed), 1, int(index)])
    u32 = lambda lo, hi, size: rng.integers(lo, hi, size).astype(np.uint32)
    okey = order_keys(first, n)
    # the customers with orders: keys 1, 2, 4, 5, 7, ... (not 0 mod 3)
    ncust = total["customer"]
    j = rng.integers(0, ncust - ncust // 3, n)
    custkey = (j + j // 2 + 1).astype(np.uint64)
    odate = u32(0, ORDER_DAYS, n)
    nlines = rng.integers(1, 8, n)
    m = int(nlines.sum())
    of = np.repeat(np.arange(n), nlines)            # a line's order
    starts = np.cumsum(nlines) - nlines
    linenumber = (np.arange(m) - starts[of] + 1).astype(np.uint32)
    sf = max(scale_factor, 0.001)
    partkey = u32(1, max(2, round(PARTS * sf)) + 1, m)
    quantity = u32(1, 51, m)
    retail = (90000 + (partkey.astype(np.int64) // 10) % 20001
              + 100 * (partkey.astype(np.int64) % 1000))
    price = quantity.astype(np.int64) * retail
    discount, tax = u32(0, 11, m), u32(0, 9, m)
    shipdate = odate[of] + u32(1, 122, m)
    commitdate = odate[of] + u32(30, 91, m)
    receiptdate = shipdate + u32(1, 31, m)
    received = receiptdate <= CURRENT
    returnflag = np.where(received, u32(0, 2, m), 2).astype(np.uint32)
    linestatus = (shipdate > CURRENT).astype(np.uint32)     # 0 F, 1 O
    plo, phi = _two(price)
    lvalue = np.stack([
        partkey, u32(1, max(2, round(SUPPLIERS * sf)) + 1, m), linenumber,
        quantity, plo, phi, discount, tax, returnflag, linestatus, shipdate,
        commitdate, receiptdate, u32(0, 4, m), u32(0, 7, m)], 1)
    # the order's own columns that come from its lines
    charged = price * (100 + tax.astype(np.int64)) \
        * (100 - discount.astype(np.int64)) // 10000
    totalprice = np.zeros(n, np.int64)
    np.add.at(totalprice, of, charged)
    open_lines = np.zeros(n, np.int64)
    np.add.at(open_lines, of, linestatus.astype(np.int64))
    status = np.where(open_lines == 0, 0,
                      np.where(open_lines == nlines, 1, 2)).astype(np.uint32)
    tlo, thi = _two(totalprice)
    chi, clo = (custkey >> np.uint64(32)).astype(np.uint32), \
        (custkey & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    ovalue = np.stack([
        chi, clo, status, tlo, thi, odate, u32(0, 5, n),
        u32(1, max(2, round(CLERKS * sf)) + 1, n),
        np.zeros(n, np.uint32)], 1)
    return (okey, ovalue), (okey[of], lvalue)


def nchunks(scale_factor: float) -> int:
    return -(-counts(scale_factor)["orders"] // CHUNK)


def make_tables(dirpath: str, scale_factor: float, seed: int) -> dict:
    """The three tables' files under ``dirpath``; returns table -> paths."""
    os.makedirs(dirpath, exist_ok=True)
    cpath = os.path.join(dirpath, "customer.dat")
    ref.pack("customer", *customer(scale_factor, seed)).tofile(cpath)

    def one(i: int) -> tuple:
        (ok, ov), (lk, lv) = orders_chunk(scale_factor, seed, i)
        paths = (os.path.join(dirpath, f"orders-{i:05d}.dat"),
                 os.path.join(dirpath, f"lineitem-{i:05d}.dat"))
        ref.pack("orders", ok, ov).tofile(paths[0])
        ref.pack("lineitem", lk, lv).tofile(paths[1])
        return paths
    # a chunk is its own stream of the seed, so the order they are made
    # in decides nothing; numpy's copies run beside one another
    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        made = list(pool.map(one, range(nchunks(scale_factor))))
    return {"customer": [cpath], "orders": [p for p, _ in made],
            "lineitem": [p for _, p in made]}
