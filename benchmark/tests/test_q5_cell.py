"""The cell `q5_sf1` (PR 36) on the CPU: what its name resolves to, what its
SQL must read at SF1, its three per-layer metrics and their readers, the
sixteen columns Q5 names held equal between the benchmark's copy and the
program's generator, and `correct` seen to hold and to fail: a sound run at
schema `tiny`, an answer altered in its last decimal place, two rows swapped
in order, and the control (the reference in float32) on three seeds at a
tenth of SF1.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys
from decimal import Decimal

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control, run  # noqa: E402
from benchmark.harness import cells, compare, tpch_data  # noqa: E402
from benchmark.rehearse import TINY  # noqa: E402
from benchmark.run import run_cell  # noqa: E402
from benchmark.tests.test_mesh_cell import SHARED  # noqa: E402

CELL = "q5_sf1"
OWN = {"expanded_probe_pages_per_query", "probe_pages_per_query",
       "join_reorder_s_per_query"}
SEED = 2**31 + 36


def test_the_cell_resolves_to_q5s_six_tables_on_one_chip():
    cell, pair = cells.Cell(CELL), cells.Cell("q3_sf1")
    assert cell.chips == 1 and cell.config["chips"] == 1
    assert cell.config["name"] == "tpch-sf1-q5-1chip"
    assert cell.config["runner"] == "local" and cell.config["schema"] == "sf1"
    assert cell.config["scale_factor"] == 1.0
    assert cell.config["guarantees"] == pair.config["guarantees"]
    assert cell.config["reduced"] == ["scale_factor"]
    assert "2.4.5.3" in cell.config["source"]
    assert set(cell.queries) == {"q5"}
    assert set(cell.config["tables"]) == set(cell.queries["q5"].scans)
    for table, rows in cell.config["tables"].items():
        assert rows == tpch_data.row_count(table, 1.0), table
    # the parameters are qgen's: one of R_NAME's five, a year of 1993..1997
    spec = cell.traffic["queries"][0]["parameters"]
    assert list(spec) == ["region", "year"]
    assert spec["region"]["values"] == list(tpch_data.REGIONS)
    assert (spec["year"]["lo"], spec["year"]["hi"]) == (1993, 1997)
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 1
    entry = [c for c in cell.bench["configs"]
             if c["name"] == "tpch-sf1-q5-1chip"][0]
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200 and entry["reduced"] == ["scale_factor"]


def test_the_cell_reads_the_shared_metrics_and_its_own_three():
    cell = cells.Cell(CELL)
    assert sorted(m["name"] for m in cell.metrics("end_to_end")) == \
        ["rows_per_s", "setup_s"]
    assert {m["name"] for m in cell.metrics("per_layer")} == SHARED | OWN
    layers = {"expanded_probe_pages_per_query": "operators",
              "probe_pages_per_query": "operators",
              "join_reorder_s_per_query": "parse / plan / optimize"}
    for m in cell.bench["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "rows_per_s"
            assert m["layer"] == layers[m["name"]] and m["better"] == "lower"
            assert m["source"] == "program_counter"
    # no other cell's entry names this one
    assert all(CELL not in m.get("workloads", []) or m["name"] in OWN
               for m in cell.bench["per_layer"] + cell.bench["end_to_end"])


def test_what_q5_must_read_once_at_sf1():
    per_query = run.scanned(cells.Cell(CELL).queries, 1.0)
    assert per_query == {"q5": (7_661_142, 224_596_774)}


def test_the_sixteen_columns_q5_names_equal_the_programs_generator_at_tiny():
    from presto_tpu.connectors.tpch import generator as g

    scans = cells.Query("q5").scans
    assert sum(len(columns) for columns in scans.values()) == 16
    sf = TINY["scale_factor"]
    for table, columns in scans.items():
        if table == "lineitem":
            n = g.table_row_count("orders", sf)
            mine = tpch_data.lineitem(0, n, sf, columns)
            theirs = g.lineitem_for_orders(0, n, sf, columns)
        else:
            n = g.table_row_count(table, sf)
            mine = tpch_data.rows(table, 0, n, sf, columns)
            theirs = g.generate_rows(table, 0, n, sf, columns)
        for c in columns:
            assert len(mine[c]) > 0, c
            assert (mine[c] == np.asarray(theirs[c]).astype(np.int64)).all(), c
    assert list(g.DICT_REGION_NAME.lookup(np.arange(5))) == tpch_data.REGIONS
    assert list(g.DICT_NATION_NAME.lookup(np.arange(25))) == \
        [name for name, _region in tpch_data.NATIONS]


@pytest.mark.parametrize("region", tpch_data.REGIONS)
def test_every_region_and_year_leaves_the_reference_rows_at_tiny(region):
    q5 = cells.Query("q5")
    names = {name for name, r in tpch_data.NATIONS
             if r == tpch_data.REGIONS.index(region)}
    for year in range(1993, 1998):
        rows = q5.reference(TINY["scale_factor"],
                            {"region": region, "year": year})
        assert rows and {name for name, _revenue in rows} <= names
        revenues = [Decimal(revenue) for _name, revenue in rows]
        assert revenues == sorted(revenues, reverse=True), (region, year)


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 77])
def test_the_control_comes_out_not_correct(seed):
    # a tenth of SF1: large enough that float32 loses digits in every sum
    _params, ctl, sound = control.control_numbers(cells.Cell(CELL), seed, 0.1,
                                                  1e-9)
    assert compare.within(sound)
    assert not compare.within(ctl), ctl
    assert ctl["cells_unequal"]["value"] >= 1


def _window(before, after, completed=2):
    return {"completed": completed,
            "before": {"counters": {}, "histograms": before},
            "after": {"counters": {}, "histograms": after}}


def test_join_reorder_seconds_are_the_histograms_gain_over_the_queries():
    read = cells.load_module(os.path.join(
        cells.BENCH_DIR, "layer_metrics", "join_reorder_s_per_query.py"),
        "test_metric_join_reorder_s").read
    spec = cells.load_json(cells.BENCH_DIR, "layer_metrics",
                           "join_reorder_s_per_query.json")
    assert spec["reader"] == "file"
    assert spec["histogram"] == "planner.reorder_joins_s"
    name = spec["histogram"]
    grown = {name: {"n": 5, "total": 0.009}}
    # two queries of the window spent 6 ms ordering their joins: 3 ms a query
    assert read(spec, _window({name: {"n": 3, "total": 0.003}},
                              grown)) == pytest.approx(0.003)
    assert read(spec, _window({}, grown)) == pytest.approx(0.0045)
    # a program without the histogram (the commit before this cell), a window
    # in which nothing was planned, one with no query: nothing, and no raise
    assert read(spec, _window({}, {})) is None
    assert read(spec, _window(grown, grown)) is None
    assert read(spec, _window({}, grown, completed=0)) is None


def test_a_sound_run_is_correct_and_prints_the_planners_and_the_joins_metrics():
    r = run_cell(CELL, SEED, 0.5, True, need_chips=False, scale=TINY)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    m = {k: v["value"] for k, v in r["metrics"].items()}
    on_chip_only = {"scan_roofline", "device_programs_per_query",
                    "peak_hbm_bytes"}
    assert (SHARED - on_chip_only) | OWN <= set(m)
    assert m["window_compiles"] == 0
    assert m["expanded_probe_pages_per_query"] == 0.0
    # lineitem's pages through four fused probes, a packed page through one
    # (a whole number of pages a query; tests/test_q5_served.py pins which)
    assert m["probe_pages_per_query"] >= 5.0
    assert m["probe_pages_per_query"] % 1 == 0
    assert 0 < m["join_reorder_s_per_query"] < m["plan_s"]
    e2e = run_cell(CELL, SEED, 0.5, False, need_chips=False, scale=TINY)
    assert e2e["correct"] and set(e2e["metrics"]) == {"rows_per_s", "setup_s"}
    assert e2e["run"]["warm_up"][-1]["built"] == 0
    assert e2e["compared"]["answers_compared"]["value"] == e2e["attempted"]


def _altered_runs(monkeypatch, alter):
    from presto_tpu.runner import LocalQueryRunner

    execute = LocalQueryRunner.execute

    def altered(self, sql, *a, **kw):
        """The answer is altered where it is produced, before the wire."""
        result = execute(self, sql, *a, **kw)
        alter(result.rows)
        return result

    monkeypatch.setattr(LocalQueryRunner, "execute", altered)
    return run_cell(CELL, SEED, 0.5, False, need_chips=False, scale=TINY)


def test_an_answer_altered_in_its_last_decimal_place_is_not_correct(monkeypatch):
    def last_place(rows):
        row = list(rows[-1])
        assert isinstance(row[1], Decimal)
        row[1] = row[1] + Decimal(1).scaleb(row[1].as_tuple().exponent)
        rows[-1] = tuple(row)

    broken = _altered_runs(monkeypatch, last_place)
    assert not broken["correct"]
    assert broken["failed"] == broken["attempted"] >= 1
    assert broken["compared"]["cells_unequal"]["value"] == broken["attempted"]


def test_two_rows_swapped_in_order_are_not_correct(monkeypatch):
    def swapped(rows):
        assert len(rows) >= 2 and rows[0][1] > rows[1][1]
        rows[0], rows[1] = rows[1], rows[0]

    broken = _altered_runs(monkeypatch, swapped)
    assert not broken["correct"]
    assert broken["failed"] == broken["attempted"] >= 1
    assert broken["compared"]["cells_unequal"]["value"] >= broken["attempted"]
