"""The cell `q1_sf10_files` (PR 40) on the CPU: what its name resolves to
(the catalog `tpch_files`, schema `sf10`, one chip, `traffic/q1.json` as
`q1_sf10` has it), its five per-layer metrics and their readers, and
`correct` seen to hold and to fail: a sound run at schema `tiny` through the
stored catalog, with and without the trace, and the control (the reference
in float32) on three seeds at a tenth of SF1.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control, run  # noqa: E402
from benchmark.harness import cells, compare, readers, tpch_data  # noqa: E402
from benchmark.rehearse import TINY  # noqa: E402
from benchmark.run import run_cell  # noqa: E402
from benchmark.tests.test_mesh_cell import SHARED  # noqa: E402

CELL, TWIN = "q1_sf10_files", "q1_sf10"
OWN = {"scan_upload_bytes_per_query": "lower", "scan_compute_stall_s": "lower",
       "scan_read_busy_s": "lower", "scan_upload_busy_s": "lower",
       "scan_upload_gbytes_per_s": "higher"}
SEED = 2**31 + 40


def test_the_cell_resolves_to_the_stored_catalog_at_sf10_on_one_chip():
    cell, twin = cells.Cell(CELL), cells.Cell(TWIN)
    assert cell.chips == 1 and cell.config["chips"] == 1
    assert cell.config["name"] == "tpch-sf10-files-1chip"
    assert cell.config["catalog"] == "tpch_files"
    assert cell.config["runner"] == "local" and cell.config["schema"] == "sf10"
    assert cell.config["scale_factor"] == 10.0 and cell.config["reduced"] == []
    assert cell.config["residency"] == "none: every query reads the files"
    assert cell.config["guarantees"]["answers"] == \
        twin.config["guarantees"]["answers"]
    assert cell.config["guarantees"]["double_rel_tol"] == 1e-9
    assert cell.config["tables"] == \
        {"lineitem": tpch_data.row_count("lineitem", 10.0)}
    # the pair differs by where the pages come from and by nothing else
    assert cell.traffic == twin.traffic
    assert set(cell.queries) == set(twin.queries) == {"q1"}
    assert cell.queries["q1"].template == twin.queries["q1"].template
    assert twin.config["catalog"] == "tpch"
    entry = [c for c in cell.bench["configs"]
             if c["name"] == "tpch-sf10-files-1chip"][0]
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200 and entry["reduced"] == []
    assert "generate-tpch.py" in entry["source"]
    assert run.scanned(cell.queries, 10.0) == run.scanned(twin.queries, 10.0)


def test_the_cell_reads_the_shared_metrics_and_its_own_five():
    cell = cells.Cell(CELL)
    assert sorted(m["name"] for m in cell.metrics("end_to_end")) == \
        ["rows_per_s", "setup_s"]
    assert {m["name"] for m in cell.metrics("per_layer")} == SHARED | set(OWN)
    for m in cell.bench["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "rows_per_s"
            assert m["layer"] == "scan pipeline"
            assert m["better"] == OWN[m["name"]]
            assert m["source"] == "program_counter"
    # no other cell's entry names this one, and its five name no other cell
    assert all(CELL not in m.get("workloads", []) or m["name"] in OWN
               for m in cell.bench["per_layer"] + cell.bench["end_to_end"])


def _window(before, after, completed=2):
    return {"completed": completed,
            "before": {"counters": before}, "after": {"counters": after}}


def test_the_five_read_the_scan_pipelines_counters():
    spec = {name: cells.load_json(cells.BENCH_DIR, "layer_metrics",
                                  name + ".json") for name in OWN}
    p = "scan.pipeline."
    before = {p + "bytes": 100, p + "upload_busy_s": 1.0}
    after = {p + "bytes": 100 + 4_000_000_000, p + "upload_busy_s": 3.0,
             p + "compute_stall_s": 1.0, p + "read_busy_s": 3.0,
             p + "decode_busy_s": 1.0}
    w = _window(before, after)
    per_query = readers.READERS["counter_per_query"]
    for name in ("scan_upload_bytes_per_query", "scan_compute_stall_s",
                 "scan_read_busy_s", "scan_upload_busy_s"):
        assert spec[name]["reader"] == "counter_per_query", name
    assert per_query(spec["scan_upload_bytes_per_query"], w) == 2e9
    assert per_query(spec["scan_compute_stall_s"], w) == 0.5
    assert per_query(spec["scan_read_busy_s"], w) == 2.0
    assert per_query(spec["scan_upload_busy_s"], w) == 1.0
    assert spec["scan_upload_gbytes_per_s"]["reader"] == "file"
    rate = cells.load_module(os.path.join(
        cells.BENCH_DIR, "layer_metrics", "scan_upload_gbytes_per_s.py"),
        "test_metric_scan_upload_gbytes_per_s").read
    assert rate(spec["scan_upload_gbytes_per_s"], w) == pytest.approx(2.0)
    # a program without the counters (the commit before this cell), a window
    # that uploaded nothing (every scan replayed): nothing, and no raise
    assert rate(spec["scan_upload_gbytes_per_s"], _window({}, {})) is None
    assert rate(spec["scan_upload_gbytes_per_s"], _window(after, after)) is None
    assert per_query(spec["scan_compute_stall_s"], _window({}, {})) == 0.0


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 77])
def test_the_control_comes_out_not_correct(seed):
    # a tenth of SF1: large enough that float32 loses digits in every sum
    _params, ctl, sound = control.control_numbers(cells.Cell(CELL), seed, 0.1,
                                                  1e-9)
    assert compare.within(sound)
    assert not compare.within(ctl), ctl
    assert ctl["cells_unequal"]["value"] >= 1


def test_a_sound_run_is_correct_and_reports_the_scans_five():
    from presto_tpu.utils.metrics import METRICS

    def resident():
        return METRICS.raw_snapshot("scan.")["gauges"][
            "scan.resident_cache_streams"]

    kept = resident()
    r = run_cell(CELL, SEED, 0.5, True, need_chips=False, scale=TINY)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    m = {k: v["value"] for k, v in r["metrics"].items()}
    on_chip_only = {"scan_roofline", "device_programs_per_query",
                    "peak_hbm_bytes"}
    assert (SHARED - on_chip_only) | set(OWN) <= set(m)
    assert m["window_compiles"] == 0
    # every query uploads the same pages anew: Q1's seven narrow columns and
    # the row mask, 13 bytes a slot, a whole number of slots a query
    assert m["scan_upload_bytes_per_query"] > 0
    assert m["scan_upload_bytes_per_query"] % 13 == 0
    assert m["scan_upload_busy_s"] > 0 and m["scan_read_busy_s"] > 0
    assert m["scan_compute_stall_s"] > 0 and m["scan_upload_gbytes_per_s"] > 0
    assert m["scan_upload_gbytes_per_s"] == pytest.approx(
        m["scan_upload_bytes_per_query"] / m["scan_upload_busy_s"] / 1e9)
    # nothing of the table stays on the device between two queries
    assert resident() == kept
    e2e = run_cell(CELL, SEED, 0.5, False, need_chips=False, scale=TINY)
    assert e2e["correct"] and set(e2e["metrics"]) == {"rows_per_s", "setup_s"}
    assert e2e["run"]["warm_up"][-1]["built"] == 0
    assert e2e["compared"]["answers_compared"]["value"] == e2e["attempted"]
