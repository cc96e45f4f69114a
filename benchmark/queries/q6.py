"""TPC-H Q6 (forecasting revenue change): its plain reference.

Parameters (TPC-H spec 2.4.6.3): year 1993-1997, discount 0.02-0.09,
quantity 24-25. Decimals are scaled by 100, so price * discount has scale 4.
"""
from decimal import Decimal

from benchmark.harness import refkit, tpch_data
from benchmark.harness.compare import dec

SCANS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                      "l_extendedprice"]}


def reference(sf, params, lower=False):
    ar = refkit.Arith(lower)
    lo = refkit.days(params["year"], 1, 1)
    hi = refkit.days(params["year"] + 1, 1, 1)
    disc = int(Decimal(params["discount"]) * 100)
    qty = params["quantity"] * 100

    def block(order_lo, order_hi):
        li = tpch_data.lineitem(order_lo, order_hi, sf, SCANS["lineitem"])
        keep = (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi) & \
            (li["l_discount"] >= disc - 1) & (li["l_discount"] <= disc + 1) & \
            (li["l_quantity"] < qty)
        return ar.total(ar.num(li["l_extendedprice"][keep]) *
                        ar.num(li["l_discount"][keep])), int(keep.sum())

    parts = refkit.map_blocks(block, tpch_data.order_blocks(sf))
    if not sum(n for _t, n in parts):
        return [(None,)]
    total = sum((t for t, _n in parts), ar.zero())
    return [(dec(ar.scaled_int(total), 4),)]
