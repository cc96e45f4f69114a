"""TPC-H Q5 (local supplier volume): its plain reference.

Parameters (TPC-H spec 2.4.5.3): REGION, one of R_NAME's five values, and
DATE, the first of January of a year in 1993..1997. Six tables, and a join
graph with a cycle: a line counts where its order's customer and its supplier
are of the SAME nation, and that nation lies in the region. Each join is a
sorted lookup on the build side's key; `c_nationkey = s_nationkey` is a
comparison of the two looked-up columns. Lineitem by order blocks, revenue as
exact scaled integers (scale 4: cents x hundredths), one accumulator a nation.
"""
import numpy as np

from benchmark.harness import refkit, tpch_data
from benchmark.harness.compare import dec

SCANS = {"customer": ["c_custkey", "c_nationkey"],
         "orders": ["o_custkey", "o_orderkey", "o_orderdate"],
         "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice",
                      "l_discount"],
         "supplier": ["s_suppkey", "s_nationkey"],
         "nation": ["n_nationkey", "n_regionkey", "n_name"],
         "region": ["r_regionkey", "r_name"]}


def whole(table, sf):
    return tpch_data.rows(table, 0, tpch_data.row_count(table, sf), sf,
                          SCANS[table])


def lookup(build_key, probe_key):
    """-> (row of the build side with that key, found) for every probe key."""
    order = np.argsort(build_key, kind="stable")
    in_order = build_key[order]
    at = np.minimum(np.searchsorted(in_order, probe_key), len(build_key) - 1)
    return order[at], in_order[at] == probe_key


def reference(sf, params, lower=False):
    ar = refkit.Arith(lower)
    cust, supp, nation, region = (whole(t, sf) for t in (
        "customer", "supplier", "nation", "region"))
    first = refkit.days(params["year"], 1, 1)
    after = refkit.days(params["year"] + 1, 1, 1)
    regions = region["r_regionkey"][
        region["r_name"] == tpch_data.REGIONS.index(params["region"])]
    n_nations = len(nation["n_nationkey"])

    def block(order_lo, order_hi):
        od = tpch_data.orders(order_lo, order_hi, sf, SCANS["orders"])
        li = tpch_data.lineitem(order_lo, order_hi, sf, SCANS["lineitem"])
        o_row, o_ok = lookup(od["o_orderkey"], li["l_orderkey"])
        day = od["o_orderdate"][o_row]
        c_row, c_ok = lookup(cust["c_custkey"], od["o_custkey"][o_row])
        s_row, s_ok = lookup(supp["s_suppkey"], li["l_suppkey"])
        s_nation = supp["s_nationkey"][s_row]
        n_row, n_ok = lookup(nation["n_nationkey"], s_nation)
        r_row, r_ok = lookup(regions, nation["n_regionkey"][n_row])
        ok = o_ok & (day >= first) & (day < after) & c_ok & s_ok & n_ok & \
            r_ok & (cust["c_nationkey"][c_row] == s_nation)
        revenue = ar.num(li["l_extendedprice"]) * ar.num(100 - li["l_discount"])
        group = nation["n_name"][n_row]
        total = np.zeros(n_nations, dtype=ar.dtype)
        np.add.at(total, group[ok], revenue[ok])
        return total, np.bincount(group[ok], minlength=n_nations)

    blocks = refkit.map_blocks(block, tpch_data.order_blocks(sf))
    rows = []
    for g in np.flatnonzero(sum(count for _total, count in blocks)):
        revenue = sum((total[g] for total, _count in blocks), ar.zero())
        rows.append((tpch_data.NATIONS[g][0], ar.scaled_int(revenue)))
    rows.sort(key=lambda r: -r[1])
    return [(name, dec(revenue, 4)) for name, revenue in rows]
