"""The cell `q5_sf1_mesh4` and its readers, on the CPU: what its name resolves
to, the three per-layer metrics of the layer "mesh exchange" it brings (looked
up by name: an entry appended later moves no position this file reads), the
all-gathers' roofline share against hand-built numbers, and whole runs of the
harness at schema `tiny` on the four virtual devices conftest.py asks for.
At `tiny` the cost model replicates the lineitem side as well (a fourth
BROADCAST, no repartition on the order key): the rehearsal proves the paths
and the comparison, `tests/test_q5_mesh_served.py` the cell's own plan.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_q5_mesh_cell.py -q
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, collectives  # noqa: E402
from benchmark.rehearse import TINY  # noqa: E402
from benchmark.run import run_cell  # noqa: E402

CELL = "q5_sf1_mesh4"
NEW = ("broadcast_bytes_per_query", "allgather_ici_roofline",
       "merge_exchange_s")


def _entry(group, name):
    bench = cells.load_json(cells.ROOT, "BENCHMARK.json")
    (found,) = [e for e in bench[group] if e["name"] == name]
    return found


def _spec(name):
    return cells.load_json(cells.BENCH_DIR, "layer_metrics", name + ".json")


def test_the_cell_resolves_to_the_distributed_runner_on_four_chips():
    cell, local = cells.Cell(CELL), cells.Cell("q5_sf1")
    assert cell.chips == 4 and cell.config["chips"] == 4
    assert cell.config["runner"] == "distributed"
    assert cell.config["schema"] == "sf1.0" and \
        cell.config["catalog"] == "tpch"
    from presto_tpu.connectors.tpch.connector import schema_scale_factor
    assert schema_scale_factor(cell.config["schema"]) == \
        cell.config["scale_factor"] == local.config["scale_factor"] == 1.0
    # the same traffic, SQL, reference, tables and guarantees as the one-chip
    # cell: the two differ by runner and chips alone
    assert cell.traffic == local.traffic and set(cell.queries) == {"q5"}
    assert cell.config["tables"] == local.config["tables"]
    assert cell.config["guarantees"] == local.config["guarantees"]
    # the plan is the cost model's: the deployment names no session property
    assert "properties" not in cell.config
    assert "AUTOMATIC" in cell.config["assumed"]["join_distribution_type"]
    config = _entry("configs", cell.config["name"])
    assert config["file"] == "benchmark/configs/tpch-sf1-q5-mesh4.json"
    assert config["reduced"] == cell.config["reduced"] == ["scale_factor"]
    assert config["source"] == cell.config["source"]
    assert len(config["source"]) <= 200 and "2.4.5.3" in config["source"]


def test_the_cell_reads_the_shared_metrics_and_its_own_three():
    cell = cells.Cell(CELL)
    bench = cells.load_json(cells.ROOT, "BENCHMARK.json")
    names = {m["name"] for m in cell.metrics("per_layer")}
    shared = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert shared <= names
    assert {"rows_per_s", "setup_s"} <= \
        {m["name"] for m in cell.metrics("end_to_end")}
    for name in NEW:
        assert name in names, name
        entry = _entry("per_layer", name)
        assert entry["workloads"] == [CELL], name
        assert (entry["layer"], entry["moves"]) == \
            ("mesh exchange", "rows_per_s"), name
    assert _entry("per_layer", "allgather_ici_roofline")["unit"] == "%"
    assert _spec("broadcast_bytes_per_query")["counters"] == \
        ["exchange.broadcast.live_bytes"]
    assert _spec("merge_exchange_s")["counters"] == ["exchange.merge.pump_s"]


def test_the_allgather_roofline_of_hand_built_numbers(monkeypatch):
    spec = _spec("allgather_ici_roofline")
    read = cells.load_module(
        os.path.join(cells.BENCH_DIR, "layer_metrics",
                     "allgather_ici_roofline.py"), "test_allgather").read
    assert spec["ici_bytes_per_s"] == 2.0e11 and \
        "1,600 Gbit/s" in spec["source"]
    # two queries: each delivered 4 MB of broadcast rows (1 MB of rows that
    # reached four workers) and gathered 1 MB to worker 0
    window = {"trace": None, "completed": 2,
              "before": {"counters": {spec["broadcast"]: 1e6,
                                      spec["gather"]: 0}},
              "after": {"counters": {spec["broadcast"]: 1e6 + 8e6,
                                     spec["gather"]: 2e6}}}
    assert read(spec, window) is None          # an untraced run
    window["trace"] = {"queries": 2}
    monkeypatch.setattr(collectives, "of_window", lambda w: {
        "total": 0.01, "all-to-all": 0.009, "all-gather": 0.001})
    assert read(spec, window) is None          # a CPU has no ICI
    import jax

    class Chip:
        device_kind = spec["device_kind"]

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()] * 4)
    monkeypatch.setattr(jax, "device_count", lambda *a: 4)
    # 2 MB of rows a query: a chip holds a quarter and sends it to its three
    # peers, 1.5 MB at 2e11 B/s = 7.5 us, over 1 ms of all-gathers
    assert read(spec, window) == pytest.approx(0.75)
    # a tree without the counters, or a trace without an all-gather: nothing
    window["after"]["counters"] = dict(window["before"]["counters"])
    assert read(spec, window) is None
    window["after"]["counters"] = {spec["broadcast"]: 9e6, spec["gather"]: 2e6}
    monkeypatch.setattr(collectives, "of_window", lambda w: {
        "total": 0.009, "all-to-all": 0.009})
    assert read(spec, window) is None


def test_a_cpu_rehearsal_of_the_cell_is_correct_and_prints_its_metrics():
    import jax

    assert jax.device_count() >= 4
    r = run_cell(CELL, 2**31 + 43, 0.5, True, need_chips=False, scale=TINY)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert r["compared"]["answers_wrong"]["value"] == 0
    assert r["compared"]["cells_unequal"]["value"] == 0
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert m["broadcast_bytes_per_query"] > 0
    assert m["merge_exchange_s"] > 0
    assert "allgather_ici_roofline" not in m    # no ICI on a CPU
    assert m["window_compiles"] == 0
    e2e = run_cell(CELL, 2**31 + 43, 0.5, False, need_chips=False,
                   scale=TINY)
    assert e2e["correct"] and set(e2e["metrics"]) == {"rows_per_s", "setup_s"}
    assert e2e["run"]["warm_up"][-1]["built"] == 0
