"""Plain reference for TPC-H Query 1 ("Pricing Summary Report") over the
``lineitem`` table of ``refs/tpch.py``'s files.  numpy only: nothing of the
program is used.

    select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
           sum(l_extendedprice) as sum_base_price,
           sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem
    where l_shipdate <= date '1998-12-01' - interval ':delta' day
    group by l_returnflag, l_linestatus
    order by l_returnflag, l_linestatus

Every sum is exact, an int64 of integers: quantity; cents; cents x (100 -
hundredths of discount), in 10^-4 dollars; that x (100 + hundredths of
tax), in 10^-6 dollars; hundredths of discount.  A row's charge is at most
10,495,000 cents x 100 x 108 = 1.13 x 10^11, so 3 x 10^7 rows of a group
stay under 3.4 x 10^18 < 2^63 = 9.22 x 10^18: :func:`q1` sums the largest
group's charge once more in Python integers to show it.  The averages are
this repository's: the integer sum over the count, rounded half up, to two
decimals (quantity, price) and four (discount).
"""

import numpy as np

from benchmark import check
from benchmark.refs import tpch as ref

ANCHOR = "1998-12-01"
RETURNFLAGS = "ARN"         # the letters of l_returnflag's codes 0, 1, 2
LINESTATUSES = "FO"         # of l_linestatus' 0, 1
SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
        "sum_disc", "count")


def bound(delta: int) -> int:
    """The first day (since 1992-01-01) the predicate refuses."""
    return ref.day(ANCHOR) - int(delta) + 1


def q1(lineitem, delta: int) -> dict:
    """Every group in the order of (l_returnflag, l_linestatus) as letters:
    ``returnflag`` and ``linestatus`` (the letters' codes), the six int64
    columns of ``SUMS``; and ``scanned``: the table's rows and the rows the
    date kept."""
    _, lv = lineitem
    c = lambda name: lv[:, ref.col("lineitem", name)]
    keep = c("shipdate").astype(np.int64) < bound(delta)
    price = ref.money(lv, "lineitem", "extendedprice")[keep]
    disc = c("discount")[keep].astype(np.int64)
    net = price * (100 - disc)
    charge = net * (100 + c("tax")[keep].astype(np.int64))
    columns = (c("quantity")[keep].astype(np.int64), price, net, charge, disc,
               np.ones(len(price), np.int64))
    flag = np.frombuffer(RETURNFLAGS.encode(), np.uint8)[c("returnflag")[keep]]
    status = np.frombuffer(LINESTATUSES.encode(),
                           np.uint8)[c("linestatus")[keep]]
    code = flag.astype(np.int64) * 256 + status
    present = np.bincount(code, minlength=1 << 16) > 0
    groups = np.flatnonzero(present)
    inverse = (np.cumsum(present) - 1)[code]
    out = {"returnflag": groups // 256, "linestatus": groups % 256}
    for name, column in zip(SUMS, columns):
        out[name] = np.array([column[inverse == g].sum(dtype=np.int64)
                              for g in range(len(groups))], np.int64)
    if len(groups):
        # int64 did not wrap: the largest group's charge once more, pieces
        # of under 2^21 rows (2.4 x 10^17 at the most) added in Python
        g = int(np.argmax(out["count"]))
        rows = charge[inverse == g]
        exact = sum(int(x.sum(dtype=np.int64)) for x in np.array_split(
            rows, max(1, len(rows) >> 20)))
        check(exact == int(out["sum_charge"][g]) and exact < 2 ** 63,
              f"sum_charge of the largest group wraps in int64: {exact}")
    out["scanned"] = {"lineitem": [int(len(lv)), int(keep.sum())]}
    return out


def _decimal(x: int, places: int) -> str:
    one = 10 ** places
    return f"{x // one}.{x % one:0{places}d}"


def _average(total: int, count: int, scale: int) -> int:
    return (2 * total * scale + count) // (2 * count)


def lines(result: dict) -> list:
    """The groups as the job prints them."""
    out = []
    for i in range(len(result["count"])):
        qty, price, net, charge, disc, count = (
            int(result[name][i]) for name in SUMS)
        out.append("|".join((
            chr(int(result["returnflag"][i])),
            chr(int(result["linestatus"][i])), str(qty), _decimal(price, 2),
            _decimal(net, 4), _decimal(charge, 6),
            _decimal(_average(qty, count, 100), 2),
            _decimal(_average(price, count, 1), 2),
            _decimal(_average(disc, count, 100), 4), str(count))))
    return out


def check_q1(result: dict, groups: dict, printed: list) -> dict:
    """Hold a job's result to the reference's: ``groups`` (``returnflag``,
    ``linestatus`` and the six columns of ``SUMS``, in any order of the
    groups) equal to ``result`` in every sum and count of every group,
    exactly; ``printed`` equal to the reference's lines, in order."""
    cols = ("returnflag", "linestatus") + SUMS
    want = np.stack([np.asarray(result[k]).astype(np.int64) for k in cols], 1)
    got = np.stack([np.asarray(groups[k]).astype(np.int64).reshape(-1)
                    for k in cols], 1)
    check(len(got) == len(want),
          f"{len(got)} groups where the reference has {len(want)}")
    got = got[np.lexsort(got.T[1::-1])]
    bad = np.flatnonzero((got != want).any(axis=1))
    check(not len(bad),
          f"{len(bad)} groups differ from the reference; the first: got "
          f"{got[bad[:1]].tolist()}, reference {want[bad[:1]].tolist()}")
    ref_lines = lines(result)
    check(printed == ref_lines,
          f"the printed lines differ: {printed} against {ref_lines}")
    return {"groups": len(want), "lines": len(printed)}
