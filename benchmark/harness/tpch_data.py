"""The benchmark's own copy of the TPC-H column formulas the three queries read.

The plain reference computes over THESE columns and imports nothing of the
program: every value is a pure function of (table, column, row index) through a
splitmix64-style hash, copied from `presto_tpu/connectors/tpch/generator.py`
as it stood at PR 22 (spec-shape-validated, not dbgen-bit-compatible). If the
program's generator ever drifts from these formulas the served rows stop
matching the reference, which is the point of keeping the copy here.
`benchmark/tests/test_yardstick.py` holds the two equal at schema `tiny`.

Decimals are integers scaled by 100 (cents); dates are days since 1970-01-01;
dictionary columns are codes into the lists below.
"""
import numpy as np

RETURNFLAGS = ["A", "N", "R"]
LINESTATUSES = ["F", "O"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]

MIN_DATE = 8035          # 1992-01-01
MAX_ORDER_DATE = 10440   # 1998-08-02
CURRENT_DATE = 9298      # 1995-06-17

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def _mix(x):
    with np.errstate(over="ignore"):
        z = x + _GOLDEN
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


def _uniform(table_id, col_id, idx, lo, hi):
    """Uniform integers in [lo, hi] for rows `idx` of column (table, col)."""
    seed = np.uint64((table_id << 32) ^ (col_id << 16) ^ 0x5DEECE66D)
    with np.errstate(over="ignore"):
        h = _mix(np.asarray(idx, dtype=np.uint64) * _GOLDEN + seed)
    return (h % np.uint64(hi - lo + 1)).astype(np.int64) + lo


def _order_key(i):
    return (i // 8) * 32 + (i % 8) + 1


def _line_count(order_idx):
    return _uniform(7, 0, order_idx, 1, 7)


def _orderdate(order_idx):
    return _uniform(6, 4, order_idx, MIN_DATE, MAX_ORDER_DATE)


def orders_count(sf):
    return int(sf * 1_500_000)


def row_count(table, sf):
    """Rows of `table` at scale `sf` (lineitem: the exact sum of the lines)."""
    if table == "orders":
        return orders_count(sf)
    if table == "customer":
        return int(sf * 150_000)
    if table == "lineitem":
        total, step = 0, 4_000_000
        for lo in range(0, orders_count(sf), step):
            hi = min(lo + step, orders_count(sf))
            total += int(_line_count(np.arange(lo, hi, dtype=np.int64)).sum())
        return total
    raise KeyError(table)


def lineitem(order_lo, order_hi, sf, columns):
    """Lineitem rows of orders [order_lo, order_hi): {column: int64 array}."""
    order_idx = np.arange(order_lo, order_hi, dtype=np.int64)
    counts = _line_count(order_idx)
    o_rep = np.repeat(order_idx, counts)
    starts = np.cumsum(counts) - counts
    line_no = np.arange(len(o_rep), dtype=np.int64) - np.repeat(starts, counts) + 1
    lkey = o_rep * 8 + line_no
    shipdate = receipt = None
    if {"l_shipdate", "l_linestatus", "l_returnflag"} & set(columns):
        shipdate = _orderdate(o_rep) + _uniform(7, 10, lkey, 1, 121)
    if "l_returnflag" in columns:
        receipt = shipdate + _uniform(7, 9, lkey, 1, 30)
    out = {}
    for name in columns:
        if name == "l_orderkey":
            out[name] = _order_key(o_rep)
        elif name == "l_quantity":
            out[name] = _uniform(7, 4, lkey, 1, 50) * 100
        elif name == "l_extendedprice":
            pk = _uniform(7, 2, lkey, 1, int(sf * 200_000))
            retail = 90000 + ((pk // 10) % 20001) + 100 * (pk % 1000)
            out[name] = _uniform(7, 4, lkey, 1, 50) * retail
        elif name == "l_discount":
            out[name] = _uniform(7, 5, lkey, 0, 10)
        elif name == "l_tax":
            out[name] = _uniform(7, 6, lkey, 0, 8)
        elif name == "l_shipdate":
            out[name] = shipdate
        elif name == "l_linestatus":
            out[name] = (shipdate > CURRENT_DATE).astype(np.int64)
        elif name == "l_returnflag":
            r = _uniform(7, 7, lkey, 0, 1)
            out[name] = np.where(receipt <= CURRENT_DATE,
                                 np.where(r == 0, 0, 2), 1)
        else:
            raise KeyError(name)
    return out


def orders(lo, hi, sf, columns):
    i = np.arange(lo, hi, dtype=np.int64)
    out = {}
    for name in columns:
        if name == "o_orderkey":
            out[name] = _order_key(i)
        elif name == "o_custkey":
            c = int(sf * 150_000)
            k = _uniform(6, 1, i, 0, max(c - c // 3, 1) - 1)
            out[name] = k // 2 * 3 + k % 2 + 1
        elif name == "o_orderdate":
            out[name] = _orderdate(i)
        elif name == "o_shippriority":
            out[name] = np.zeros(len(i), dtype=np.int64)
        else:
            raise KeyError(name)
    return out


def customer(lo, hi, sf, columns):
    i = np.arange(lo, hi, dtype=np.int64)
    out = {}
    for name in columns:
        if name == "c_custkey":
            out[name] = i + 1
        elif name == "c_mktsegment":
            out[name] = _uniform(5, 6, i, 0, 4)
        else:
            raise KeyError(name)
    return out


def order_blocks(sf, block=500_000):
    """[(order_lo, order_hi)] covering every order, for block-wise work."""
    n = orders_count(sf)
    return [(lo, min(lo + block, n)) for lo in range(0, n, block)]
