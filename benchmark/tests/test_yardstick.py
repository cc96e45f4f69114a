"""The yardstick's own parts, on the CPU: the copied column formulas against
the program's generator, the comparison, the traffic generator, the trace
reduction on hand-built intervals and on the recorded fixture.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import datetime
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, compare, tpch_data, trace_reduce  # noqa: E402
from benchmark.harness.traffic import Plan  # noqa: E402


TINY_SF = 0.01
COPIED = {   # every column the data copy holds, a table
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
                 "l_extendedprice", "l_discount", "l_tax", "l_returnflag",
                 "l_linestatus", "l_shipdate"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "customer": ["c_custkey", "c_nationkey", "c_mktsegment"],
    "part": ["p_partkey", "p_name"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
    "nation": ["n_nationkey", "n_name", "n_regionkey"],
    "region": ["r_regionkey", "r_name"],
}


@pytest.mark.parametrize("table", sorted(COPIED))
def test_copied_columns_equal_the_programs_generator_at_tiny(table):
    from presto_tpu.connectors.tpch import generator as g

    cols = COPIED[table]
    if table == "lineitem":
        n = g.table_row_count("orders", TINY_SF)
        mine = tpch_data.lineitem(0, n, TINY_SF, cols)
        theirs = g.lineitem_for_orders(0, n, TINY_SF, cols)
    else:
        assert sorted(cols) == sorted(tpch_data.COLUMNS[table])
        n = g.table_row_count(table, TINY_SF)
        mine = tpch_data.rows(table, 0, n, TINY_SF, cols)
        theirs = g.generate_rows(table, 0, n, TINY_SF, cols)
    assert n > 0
    for c in cols:
        if c == "p_name":     # the words themselves, not the program's packing
            words = np.asarray(tpch_data.COLORS, dtype=object)[mine[c]]
            assert [" ".join(w) for w in words] == \
                list(g.DICT_P_NAME.lookup(theirs[c]))
            continue
        assert mine[c].shape == (len(theirs[c]),), c
        assert (mine[c] == theirs[c].astype(np.int64)).all(), c
    for s in (TINY_SF, 1.0):
        assert tpch_data.row_count(table, s) == g.table_row_count(table, s)


def test_copied_word_lists_equal_the_programs_dictionaries():
    from presto_tpu.connectors.tpch import generator as g

    assert tpch_data.SEGMENTS == list(g.DICT_SEGMENT.lookup(np.arange(5)))
    assert tpch_data.RETURNFLAGS == list(g.DICT_RETURNFLAG.lookup(np.arange(3)))
    assert tpch_data.LINESTATUSES == list(g.DICT_LINESTATUS.lookup(np.arange(2)))
    assert tpch_data.REGIONS == list(g.DICT_REGION_NAME.lookup(np.arange(5)))
    assert [n for n, _r in tpch_data.NATIONS] == \
        list(g.DICT_NATION_NAME.lookup(np.arange(25)))
    assert len(tpch_data.COLORS) == 92 and "green" in tpch_data.COLORS
    with pytest.raises(KeyError):
        tpch_data.row_count("lineitems", 1.0)
    with pytest.raises(KeyError):      # a column nobody copied yet says so
        tpch_data.rows("part", 0, 4, 1.0, ["p_type"])


def test_every_line_names_one_row_of_partsupp():
    """Q9 joins lineitem to partsupp on both keys: the pair has to be a key
    there, and to be unique there."""
    sf = TINY_SF
    ps = tpch_data.rows("partsupp", 0, tpch_data.row_count("partsupp", sf), sf,
                        ["ps_partkey", "ps_suppkey"])
    suppliers = tpch_data.row_count("supplier", sf)
    keys = ps["ps_partkey"] * (suppliers + 1) + ps["ps_suppkey"]
    assert len(np.unique(keys)) == len(keys) == 4 * tpch_data.row_count("part", sf)
    assert ps["ps_suppkey"].min() >= 1 and ps["ps_suppkey"].max() <= suppliers
    li = tpch_data.lineitem(0, tpch_data.row_count("orders", sf), sf,
                            ["l_partkey", "l_suppkey"])
    pairs = li["l_partkey"] * (suppliers + 1) + li["l_suppkey"]
    assert len(pairs) == tpch_data.row_count("lineitem", sf)
    assert np.isin(pairs, keys).all()
    # all four of a part's suppliers are used by some line
    assert len(np.unique(pairs)) > 3 * tpch_data.row_count("part", sf)


def test_logical_widths_name_every_column_of_the_schema():
    from presto_tpu.connectors.tpch import generator as g

    widths = cells.load_json(cells.BENCH_DIR, "harness", "logical_widths.json")
    theirs = {t.name: [c.name for c in t.columns]
              for t in g.TPCH_TABLES.values()}
    theirs["lineitem"] = [name for name, _t, _d in g.LINEITEM_COLUMNS]
    assert {t: list(c) for t, c in widths["columns"].items()} == theirs
    for table, columns in widths["columns"].items():
        for column, kind in columns.items():
            assert widths["types"][kind] > 0, (table, column)
    for kind, nbytes in widths["types"].items():   # a text type says its length
        if kind.startswith(("char", "varchar")):
            assert kind.endswith(str(nbytes)), kind
    # a whole row by clause 1.4, added up by hand: lineitem 3 identifiers, an
    # integer, 4 decimals, 2 flags, 3 dates = 74 and 25 + 10 + 44 of text
    row = {t: sum(widths["types"][k] for k in c.values())
           for t, c in widths["columns"].items()}
    assert row == {"lineitem": 153, "orders": 142, "customer": 231,
                   "part": 168, "supplier": 205, "partsupp": 227,
                   "nation": 193, "region": 185}


# (rows, logical bytes) a query must read once: what `scanned()` gave before
# the width table held the whole schema (PR 33 pins them; rows_per_s and
# scan_roofline of every line of the ledger were taken with these)
SCANNED = {
    1.0: {"q1": (6001112, 228042256), "q3": (7651112, 206731136),
          "q6": (6001112, 168031136)},
    10.0: {"q1": (60012192, 2280463296), "q3": (76512192, 2067341376),
           "q6": (60012192, 1680341376)},
}


@pytest.mark.parametrize("sf", sorted(SCANNED))
def test_scanned_rows_and_bytes_of_the_three_queries_are_pinned(sf):
    from benchmark.run import scanned

    assert scanned({q: cells.Query(q) for q in SCANNED[sf]}, sf) == SCANNED[sf]


def test_scanned_answers_for_a_query_over_the_other_five_tables():
    """What the next query file (Q9) brings: a SCANS over tables no present
    query names resolves with no edit to the harness."""
    from types import SimpleNamespace

    from benchmark.run import scanned

    q9 = SimpleNamespace(scans={
        "part": ["p_partkey", "p_name"], "supplier": ["s_suppkey", "s_nationkey"],
        "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
        "nation": ["n_nationkey", "n_name"], "region": ["r_regionkey", "r_name"],
        "lineitem": ["l_partkey", "l_suppkey"]})
    rows = 200_000 + 10_000 + 800_000 + 25 + 5 + 6_001_112
    nbytes = 200_000 * 63 + 10_000 * 16 + 800_000 * 24 + 25 * 33 + 5 * 33 + \
        6_001_112 * 16
    assert scanned({"q9": q9}, 1.0) == {"q9": (rows, nbytes)}
    with pytest.raises(KeyError):
        scanned({"q": SimpleNamespace(scans={"part": ["p_colour"]})}, 1.0)


def test_compare_counts_cells_and_gaps():
    want = [("A", "12.5", 3, 1.0, datetime.date(1995, 3, 1))]
    assert compare.compare_rows(list(want), want) == (0, 0.0)
    got = [("A", "12.50001", 3, 1.0 + 1e-12, datetime.date(1995, 3, 1))]
    wrong, gap = compare.compare_rows(got, want)
    assert wrong == 1 and 0.5e-12 < gap < 2e-12
    assert compare.compare_rows([], want)[0] >= 1
    assert compare.compare_rows([("A", "12.5", 3.0, 1.0, None)], want)[0] == 2
    numbers, failed = compare.judge(
        [("q", list(want)), ("q", got), ("q", None)], {"q": want}, 1e-9)
    assert failed == 2 and not compare.within(numbers)
    numbers, failed = compare.judge([("q", list(want))], {"q": want}, 1e-9)
    assert failed == 0 and compare.within(numbers)
    assert not compare.within(compare.judge([], {"q": want}, 1e-9)[0])


def test_every_seed_sends_the_same_work_and_large_seeds_draw():
    cell = cells.Cell("q6_sf1")
    a = Plan(cell.traffic, cell.queries, 2**31 + 7)
    b = Plan(cell.traffic, cell.queries, 2**31 + 7)
    assert a.params == b.params and a.sql == b.sql
    seen = {json.dumps(Plan(cell.traffic, cell.queries, s).params)
            for s in range(40)}
    assert len(seen) > 10
    p = a.params["q6"]
    assert 1993 <= p["year"] <= 1997 and p["quantity"] in (24, 25)
    assert p["discount"] in {f"0.0{d}" for d in range(2, 10)}
    mix = {"loop": "open", "clients": 2, "rate_per_s": 5.0, "queries": [
        {"query": "q6", "weight": 3, "parameters": {}},
        {"query": "q1", "weight": 1, "parameters": {
            "delta": {"type": "int", "lo": 60, "hi": 120}}}]}
    both = {"q6": cells.Query("q6"), "q1": cells.Query("q1")}
    with pytest.raises(KeyError):
        Plan(mix, both, 1)      # q6's template needs its parameters
    mix["queries"][0]["parameters"] = cell.traffic["queries"][0]["parameters"]
    for seed in (1, 2, 3):
        plan = Plan(mix, both, seed)
        assert sorted(plan.block) == ["q1", "q6", "q6", "q6"]


def test_reduction_of_hand_built_intervals():
    ms = 1_000_000
    trace = {"spans": [(0, 100 * ms), (100 * ms, 200 * ms)],
             "devices": {"/device:TPU:0": [
                 (10 * ms, 30 * ms, "fusion.1"), (20 * ms, 25 * ms, "nested"),
                 (40 * ms, 50 * ms, "fusion.2"), (150 * ms, 160 * ms, "fusion.1"),
                 (500 * ms, 600 * ms, "after the window")]}}
    r = trace_reduce.reduce(trace)
    assert r["window_s"] == pytest.approx(0.2)
    assert r["busy_s"] == pytest.approx(0.040)      # nested op counted once
    assert r["queries"] == 2 and r["programs"] == 0
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.030)]
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["in_query.before_first_op"] == pytest.approx(0.010 + 0.050)
    assert gaps["in_query.between_ops"] == pytest.approx(0.010)
    assert gaps["in_query.after_last_op"] == pytest.approx(0.050 + 0.040)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    assert trace_reduce.reduce({"spans": [], "devices": {}}) is None
    assert trace_reduce.reduce({"spans": [(0, 10)], "devices": {"d": []}}) is None


def test_reduction_of_the_recorded_trace_gives_the_recorded_numbers():
    from benchmark.check_trace_reduction import differences

    assert differences() == []
