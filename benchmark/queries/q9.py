"""TPC-H Q9 (product type profit measure): its plain reference.

Parameter (TPC-H spec 2.4.9.3): COLOR, one of the 92 words P_NAME is made of.
A P_NAME is five of those words with blanks between them and a COLOR holds no
blank, so `p_name like '%COLOR%'` is true exactly where one of the five words
contains COLOR. Six tables, five joins, each done here as a sorted lookup on
the build side's key columns (PS_PARTKEY, PS_SUPPKEY is partsupp's primary
key, clause 1.4.2); lineitem by order blocks, sums as exact scaled integers
(scale 4: cents x hundredths), one accumulator a (nation, year).
"""
import numpy as np

from benchmark.harness import refkit, tpch_data
from benchmark.harness.compare import dec

SCANS = {"part": ["p_partkey", "p_name"],
         "supplier": ["s_suppkey", "s_nationkey"],
         "lineitem": ["l_suppkey", "l_partkey", "l_orderkey",
                      "l_extendedprice", "l_discount", "l_quantity"],
         "partsupp": ["ps_suppkey", "ps_partkey", "ps_supplycost"],
         "orders": ["o_orderkey", "o_orderdate"],
         "nation": ["n_nationkey", "n_name"]}

FIRST_YEAR = 1992    # of MIN_DATE; an order's year is at most 1998
YEARS = 8


def whole(table, sf):
    return tpch_data.rows(table, 0, tpch_data.row_count(table, sf), sf,
                          SCANS[table])


def lookup(build_key, probe_key):
    """-> (row of the build side with that key, found) for every probe key."""
    order = np.argsort(build_key, kind="stable")
    in_order = build_key[order]
    at = np.minimum(np.searchsorted(in_order, probe_key), len(build_key) - 1)
    return order[at], in_order[at] == probe_key


def year_of(day):
    return day.astype("datetime64[D]").astype("datetime64[Y]").astype(
        np.int64) + 1970


def reference(sf, params, lower=False):
    ar = refkit.Arith(lower)
    part, supp, ps, nation = (whole(t, sf) for t in (
        "part", "supplier", "partsupp", "nation"))
    word_has = np.asarray([params["color"] in w for w in tpch_data.COLORS])
    parts = part["p_partkey"][word_has[part["p_name"]].any(axis=1)]
    # both key columns in one: a suppkey is below the stride
    stride = int(supp["s_suppkey"].max()) + 1
    ps_key = ps["ps_partkey"] * stride + ps["ps_suppkey"]
    n_nations = len(nation["n_nationkey"])

    def block(order_lo, order_hi):
        od = tpch_data.orders(order_lo, order_hi, sf, SCANS["orders"])
        li = tpch_data.lineitem(order_lo, order_hi, sf, SCANS["lineitem"])
        keep = np.isin(li["l_partkey"], parts)
        li = {c: a[keep] for c, a in li.items()}
        s_row, s_ok = lookup(supp["s_suppkey"], li["l_suppkey"])
        n_row, n_ok = lookup(nation["n_nationkey"], supp["s_nationkey"][s_row])
        ps_row, ps_ok = lookup(ps_key, li["l_partkey"] * stride + li["l_suppkey"])
        o_row, o_ok = lookup(od["o_orderkey"], li["l_orderkey"])
        ok = s_ok & n_ok & ps_ok & o_ok
        amount = ar.num(li["l_extendedprice"]) * ar.num(100 - li["l_discount"]) \
            - ar.num(ps["ps_supplycost"][ps_row]) * ar.num(li["l_quantity"])
        group = nation["n_name"][n_row] * YEARS + \
            (year_of(od["o_orderdate"][o_row]) - FIRST_YEAR)
        total = np.zeros(n_nations * YEARS, dtype=ar.dtype)
        np.add.at(total, group[ok], amount[ok])
        return total, np.bincount(group[ok], minlength=n_nations * YEARS)

    blocks = refkit.map_blocks(block, tpch_data.order_blocks(sf))
    rows = []
    for g in np.flatnonzero(sum(count for _total, count in blocks)):
        profit = sum((total[g] for total, _count in blocks), ar.zero())
        rows.append((tpch_data.NATIONS[g // YEARS][0],
                     FIRST_YEAR + int(g % YEARS), dec(ar.scaled_int(profit), 4)))
    rows.sort(key=lambda r: (r[0], -r[1]))
    return rows
