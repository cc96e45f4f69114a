"""The benchmark's own copy of the TPC-H column formulas its queries read:
what Q1, Q3 and Q6 name, and what Q5 and Q9 will (all eight tables).

The plain reference computes over THESE columns and imports nothing of the
program: every value is a pure function of (table, column, row index) through a
splitmix64-style hash, copied from `presto_tpu/connectors/tpch/generator.py`
as it stood at PR 22 (spec-shape-validated, not dbgen-bit-compatible). If the
program's generator ever drifts from these formulas the served rows stop
matching the reference, which is the point of keeping the copy here.
`benchmark/tests/test_yardstick.py` holds the two equal at schema `tiny`.

Decimals are integers scaled by 100 (cents); dates are days since 1970-01-01;
dictionary columns are codes into the lists below (`n_name` and `r_name` are
the row's own key). `p_name` is five words of COLORS: an (n, 5) array of their
indices, so a reference decides `like '%green%'` from the words themselves.
"""
import numpy as np

RETURNFLAGS = ["A", "N", "R"]
LINESTATUSES = ["F", "O"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [  # (n_name, n_regionkey), clause 4.2.3
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("EGYPT", 4),
    ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2), ("INDONESIA", 2),
    ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0),
    ("MOROCCO", 0), ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1)]
COLORS = (  # the 92 words of P_NAME, clause 4.2.3
    "almond antique aquamarine azure beige bisque black blanched blue blush brown "
    "burlywood burnished chartreuse chiffon chocolate coral cornflower cornsilk cream "
    "cyan dark deep dim dodger drab firebrick floral forest frosted gainsboro ghost "
    "goldenrod green grey honeydew hot indian ivory khaki lace lavender lawn lemon "
    "light lime linen magenta maroon medium metallic midnight mint misty moccasin "
    "navajo navy olive orange orchid pale papaya peach peru pink plum powder puff "
    "purple red rose rosy royal saddle salmon sandy seashell sienna sky slate smoke "
    "snow spring steel tan thistle tomato turquoise violet wheat white yellow").split()
P_NAME_WORDS = 5

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
    return row_count("orders", sf)


# clause 4.2.5: rows at SF1 (partsupp is four suppliers a part); nation and
# region do not scale; lineitem is the sum of its orders' line counts
ROWS_AT_SF1 = {"part": 200_000, "supplier": 10_000, "customer": 150_000,
               "orders": 1_500_000}
FIXED_ROWS = {"nation": 25, "region": 5}


def row_count(table, sf):
    """Rows of `table` at scale `sf` (lineitem: the exact sum of the lines)."""
    if table in FIXED_ROWS:
        return FIXED_ROWS[table]
    if table in ROWS_AT_SF1:
        return int(sf * ROWS_AT_SF1[table])
    if table == "partsupp":
        return 4 * row_count("part", sf)
    if table == "lineitem":
        total, step = 0, 4_000_000
        for lo in range(0, orders_count(sf), step):
            hi = min(lo + step, orders_count(sf))
            total += int(_line_count(np.arange(lo, hi, dtype=np.int64)).sum())
        return total
    raise KeyError(table)


def _supplier_for(partkey, j, sf):
    """The j-th (0-3) of a part's four suppliers, clause 4.2.3."""
    s = row_count("supplier", sf)
    return (partkey + j * (s // 4 + (partkey - 1) // s)) % s + 1


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
    pk = None
    if {"l_partkey", "l_suppkey", "l_extendedprice"} & set(columns):
        pk = _uniform(7, 2, lkey, 1, row_count("part", sf))
    out = {}
    for name in columns:
        if name == "l_orderkey":
            out[name] = _order_key(o_rep)
        elif name == "l_partkey":
            out[name] = pk
        elif name == "l_suppkey":
            out[name] = _supplier_for(pk, _uniform(7, 3, lkey, 0, 3), sf)
        elif name == "l_quantity":
            out[name] = _uniform(7, 4, lkey, 1, 50) * 100
        elif name == "l_extendedprice":
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


def _o_custkey(i, sf):
    c = row_count("customer", sf)
    k = _uniform(6, 1, i, 0, max(c - c // 3, 1) - 1)
    return k // 2 * 3 + k % 2 + 1


def _p_name(i, sf):
    return np.stack([_uniform(3, 16 + f, i, 0, len(COLORS) - 1)
                     for f in range(P_NAME_WORDS)], axis=1)


_NATION_REGION = np.asarray([r for _n, r in NATIONS], dtype=np.int64)

# {table: {column: (row index i from 0, sf) -> int64 array}} of every table
# whose rows are a function of their index alone (lineitem hangs on its order)
COLUMNS = {
    "orders": {
        "o_orderkey": lambda i, sf: _order_key(i),
        "o_custkey": _o_custkey,
        "o_orderdate": lambda i, sf: _orderdate(i),
        "o_shippriority": lambda i, sf: np.zeros(len(i), dtype=np.int64)},
    "customer": {
        "c_custkey": lambda i, sf: i + 1,
        "c_nationkey": lambda i, sf: _uniform(5, 3, i, 0, 24),
        "c_mktsegment": lambda i, sf: _uniform(5, 6, i, 0, 4)},
    "part": {
        "p_partkey": lambda i, sf: i + 1,
        "p_name": _p_name},
    "supplier": {
        "s_suppkey": lambda i, sf: i + 1,
        "s_nationkey": lambda i, sf: _uniform(2, 3, i, 0, 24)},
    "partsupp": {
        "ps_partkey": lambda i, sf: i // 4 + 1,
        "ps_suppkey": lambda i, sf: _supplier_for(i // 4 + 1, i % 4, sf),
        "ps_supplycost": lambda i, sf: _uniform(4, 3, i, 100, 100000)},
    "nation": {
        "n_nationkey": lambda i, sf: i,
        "n_name": lambda i, sf: i,
        "n_regionkey": lambda i, sf: _NATION_REGION[i]},
    "region": {
        "r_regionkey": lambda i, sf: i,
        "r_name": lambda i, sf: i},
}


def rows(table, lo, hi, sf, columns):
    """Rows [lo, hi) of a table of COLUMNS: {column: int64 array}. A column
    with no formula here is a KeyError: copy it from the generator first."""
    i = np.arange(lo, hi, dtype=np.int64)
    return {name: COLUMNS[table][name](i, sf) for name in columns}


def orders(lo, hi, sf, columns):
    return rows("orders", lo, hi, sf, columns)


def customer(lo, hi, sf, columns):
    return rows("customer", lo, hi, sf, columns)


def order_blocks(sf, block=500_000):
    """[(order_lo, order_hi)] covering every order, for block-wise work."""
    n = orders_count(sf)
    return [(lo, min(lo + block, n)) for lo in range(0, n, block)]
