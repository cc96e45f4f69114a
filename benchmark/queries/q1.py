"""TPC-H Q1 (pricing summary report): its plain reference.

Parameter (TPC-H spec 2.4.1.3): delta 60-120 days. Sums of decimals are exact;
the three averages are doubles.
"""
from benchmark.harness import refkit, tpch_data
from benchmark.harness.compare import dec

SCANS = {"lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                      "l_extendedprice", "l_discount", "l_tax", "l_shipdate"]}

GROUPS = [(rf, ls) for rf in range(3) for ls in range(2)]
SUMS = ("qty", "price", "disc_price", "charge", "disc")


def reference(sf, params, lower=False):
    ar = refkit.Arith(lower)
    cutoff = refkit.days(1998, 12, 1) - params["delta"]

    def block(order_lo, order_hi):
        li = tpch_data.lineitem(order_lo, order_hi, sf, SCANS["lineitem"])
        keep = li["l_shipdate"] <= cutoff
        qty, price, disc, tax = (ar.num(li[c]) for c in (
            "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
        disc_price = price * (ar.num(100 - li["l_discount"]))
        cols = {"qty": qty, "price": price, "disc_price": disc_price,
                "charge": disc_price * ar.num(100 + li["l_tax"]), "disc": disc}
        out = {}
        for rf, ls in GROUPS:
            m = keep & (li["l_returnflag"] == rf) & (li["l_linestatus"] == ls)
            out[rf, ls] = ([ar.total(cols[c][m]) for c in SUMS], int(m.sum()))
        return out

    parts = refkit.map_blocks(block, tpch_data.order_blocks(sf))
    rows = []
    for rf, ls in GROUPS:
        n = sum(p[rf, ls][1] for p in parts)
        if not n:
            continue
        tot = {c: sum((p[rf, ls][0][i] for p in parts), ar.zero())
               for i, c in enumerate(SUMS)}
        rows.append((tpch_data.RETURNFLAGS[rf], tpch_data.LINESTATUSES[ls],
                     dec(ar.scaled_int(tot["qty"]), 2),
                     dec(ar.scaled_int(tot["price"]), 2),
                     dec(ar.scaled_int(tot["disc_price"]), 4),
                     dec(ar.scaled_int(tot["charge"]), 6),
                     ar.mean(tot["qty"], 100, n), ar.mean(tot["price"], 100, n),
                     ar.mean(tot["disc"], 100, n), n))
    return rows
