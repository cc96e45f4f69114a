"""The cell `q9_sf1` (PR 34) on the CPU: what its name resolves to, what its
SQL must read at SF1, its three per-layer metrics and their readers, and
`correct` seen to hold and to fail: a sound run at schema `tiny`, an answer
altered in its last decimal place, a scan that drops a split, and the control
(the reference in float32) on three seeds at a tenth of SF1.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys
from decimal import Decimal

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control, run  # noqa: E402
from benchmark.harness import cells, compare, tpch_data  # noqa: E402
from benchmark.rehearse import TINY  # noqa: E402
from benchmark.run import run_cell  # noqa: E402
from benchmark.tests.test_mesh_cell import SHARED  # noqa: E402

CELL = "q9_sf1"
OWN = {"sorted_join_builds_per_query", "sorted_probe_pages_per_query",
       "join_build_s_per_query"}
SEED = 2**31 + 99


def test_the_cell_resolves_to_the_six_table_deployment_on_one_chip():
    cell, pair = cells.Cell(CELL), cells.Cell("q3_sf1")
    assert cell.chips == 1 and cell.config["chips"] == 1
    assert cell.config["name"] == "tpch-sf1-6table-1chip"
    assert cell.config["runner"] == "local" and cell.config["schema"] == "sf1"
    assert cell.config["scale_factor"] == 1.0
    assert cell.config["guarantees"] == pair.config["guarantees"]
    assert cell.config["reduced"] == ["scale_factor"]
    assert "2.4.9.3" in cell.config["source"]
    assert set(cell.queries) == {"q9"}
    assert set(cell.config["tables"]) == set(cell.queries["q9"].scans)
    for table, rows in cell.config["tables"].items():
        assert rows == tpch_data.row_count(table, 1.0), table
    # the parameter is qgen's: one of the 92 words P_NAME is made of
    spec = cell.traffic["queries"][0]["parameters"]
    assert list(spec) == ["color"]
    assert spec["color"]["values"] == list(tpch_data.COLORS)
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 1


def test_the_cell_reads_the_shared_metrics_and_its_own_three():
    cell = cells.Cell(CELL)
    assert sorted(m["name"] for m in cell.metrics("end_to_end")) == \
        ["rows_per_s", "setup_s"]
    assert {m["name"] for m in cell.metrics("per_layer")} == SHARED | OWN
    bench = cells.load_json(cells.ROOT, "BENCHMARK.json")
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "rows_per_s"
            assert m["layer"] == "operators" and m["better"] == "lower"
            assert m["source"] == "program_counter"


def test_what_q9_must_read_once_at_sf1():
    per_query = run.scanned(cells.Cell(CELL).queries, 1.0)
    assert per_query == {"q9": (8_511_137, 338_014_201)}


def test_every_colour_leaves_the_reference_rows_at_tiny():
    q9 = cells.Query("q9")
    for colour in tpch_data.COLORS:
        rows = q9.reference(TINY["scale_factor"], {"color": colour})
        assert rows, colour
        assert rows == sorted(rows, key=lambda r: (r[0], -r[1])), colour
        assert all(1992 <= year <= 1998 for _n, year, _p in rows), colour


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


def test_join_build_seconds_are_the_histograms_gain_over_the_queries():
    read = cells.load_module(os.path.join(
        cells.BENCH_DIR, "layer_metrics", "join_build_s_per_query.py"),
        "test_metric_join_build_s").read
    spec = cells.load_json(cells.BENCH_DIR, "layer_metrics",
                           "join_build_s_per_query.json")
    assert spec["reader"] == "file" and spec["histogram"] == "join.build_s"
    grown = {"join.build_s": {"n": 15, "total": 4.5}}
    # ten builds of two queries took 3.0 s: 1.5 s a query
    assert read(spec, _window({"join.build_s": {"n": 5, "total": 1.5}},
                              grown)) == pytest.approx(1.5)
    assert read(spec, _window({}, grown)) == pytest.approx(2.25)
    # a program without the histogram (the commit before this cell), a window
    # in which nothing was built, one with no query: nothing, and no raise
    assert read(spec, _window({}, {})) is None
    assert read(spec, _window(grown, grown)) is None
    assert read(spec, _window({}, grown, completed=0)) is None


def test_a_sound_run_is_correct_and_prints_the_join_layers_metrics():
    r = run_cell(CELL, SEED, 0.5, True, need_chips=False, scale=TINY)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    m = {k: v["value"] for k, v in r["metrics"].items()}
    on_chip_only = {"scan_roofline", "device_programs_per_query",
                    "peak_hbm_bytes"}
    assert (SHARED - on_chip_only) | OWN <= set(m)
    assert m["window_compiles"] == 0
    assert m["sorted_join_builds_per_query"] == 1.0
    # every lineitem page goes through the partsupp probe's search once
    # (a whole number of pages a query; tests/test_q9_served.py pins which)
    assert m["sorted_probe_pages_per_query"] >= 1.0
    assert m["sorted_probe_pages_per_query"] % 1 == 0
    assert m["join_build_s_per_query"] > 0
    e2e = run_cell(CELL, SEED, 0.5, False, need_chips=False, scale=TINY)
    assert e2e["correct"] and set(e2e["metrics"]) == {"rows_per_s", "setup_s"}
    assert e2e["run"]["warm_up"][-1]["built"] == 0
    assert e2e["compared"]["answers_compared"]["value"] == e2e["attempted"]


def test_an_answer_altered_in_its_last_decimal_place_is_not_correct(monkeypatch):
    from presto_tpu.runner import LocalQueryRunner

    execute = LocalQueryRunner.execute

    def altered(self, sql, *a, **kw):
        """The profit of the LAST row off by one unit in its last place: the
        answer is altered where it is produced, before the wire."""
        result = execute(self, sql, *a, **kw)
        row = list(result.rows[-1])
        assert isinstance(row[2], Decimal)
        row[2] = row[2] + Decimal(1).scaleb(row[2].as_tuple().exponent)
        result.rows[-1] = tuple(row)
        return result

    monkeypatch.setattr(LocalQueryRunner, "execute", altered)
    broken = run_cell(CELL, SEED, 0.5, False, need_chips=False, scale=TINY)
    assert not broken["correct"]
    assert broken["failed"] == broken["attempted"] >= 1
    assert broken["compared"]["cells_unequal"]["value"] == broken["attempted"]


def test_a_scan_that_drops_a_split_is_not_correct(monkeypatch):
    from presto_tpu.connectors.tpch import connector

    get_splits = connector.TpchSplitManager.get_splits

    def fewer(self, table, constraint, desired_splits):
        splits = get_splits(self, table, constraint, desired_splits)
        return splits[:-1] if len(splits) > 1 else splits

    monkeypatch.setattr(connector.TpchSplitManager, "get_splits", fewer)
    r = run_cell(CELL, SEED, 0.5, False, need_chips=False, scale=TINY)
    assert not r["correct"] and r["failed"] == r["attempted"] >= 1
    assert r["compared"]["cells_unequal"]["value"] >= 1
