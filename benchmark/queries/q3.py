"""TPC-H Q3 (shipping priority): its plain reference.

Parameters (TPC-H spec 2.4.3.3): one of the five market segments, a day of
March 1995. Lineitem is generated by order range, so every order's lines sit
in one block and the ten best of each block hold the ten best of all.
"""
import numpy as np

from benchmark.harness import refkit, tpch_data
from benchmark.harness.compare import dec

SCANS = {"customer": ["c_mktsegment", "c_custkey"],
         "orders": ["o_custkey", "o_orderkey", "o_orderdate",
                    "o_shippriority"],
         "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                      "l_shipdate"]}


def reference(sf, params, lower=False):
    ar = refkit.Arith(lower)
    cutoff = refkit.days(1995, 3, params["day"])
    cust = tpch_data.customer(0, tpch_data.row_count("customer", sf), sf,
                              SCANS["customer"])
    segment = tpch_data.SEGMENTS.index(params["segment"])
    custs = cust["c_custkey"][cust["c_mktsegment"] == segment]

    def block(order_lo, order_hi):
        od = tpch_data.orders(order_lo, order_hi, sf, SCANS["orders"])
        okeep = (od["o_orderdate"] < cutoff) & np.isin(od["o_custkey"], custs)
        li = tpch_data.lineitem(order_lo, order_hi, sf, SCANS["lineitem"])
        lkeep = (li["l_shipdate"] > cutoff) & \
            np.isin(li["l_orderkey"], od["o_orderkey"][okeep])
        keys, inv = np.unique(li["l_orderkey"][lkeep], return_inverse=True)
        revenue = np.zeros(len(keys), dtype=ar.dtype)
        np.add.at(revenue, inv, ar.num(li["l_extendedprice"][lkeep]) *
                  ar.num(100 - li["l_discount"][lkeep]))
        at = np.searchsorted(od["o_orderkey"], keys)
        odate = od["o_orderdate"][at]
        top = np.lexsort((odate, -revenue))[:10]
        return [(ar.scaled_int(revenue[i]), int(odate[i]), int(keys[i]),
                 int(od["o_shippriority"][at[i]])) for i in top]

    best = [r for part in refkit.map_blocks(block, tpch_data.order_blocks(sf))
            for r in part]
    best.sort(key=lambda r: (-r[0], r[1]))
    return [(key, dec(rev, 4), refkit.date_of(odate), prio)
            for rev, odate, key, prio in best[:10]]
