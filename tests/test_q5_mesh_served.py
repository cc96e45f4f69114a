"""TPC-H Q5 served by the mesh runner on four virtual devices: the deployment
`tpch-sf1-q5-mesh4` of the benchmark, at schema `tiny` on the CPU.

At `sf1.0` the cost model replicates supplier, nation and the filtered region
and repartitions the rest (`tests/golden_plans/q5_sf1_mesh4.txt`). At `tiny`
it would replicate the lineitem side too, so the session here lowers
`broadcast_join_threshold_rows` until `tiny` plans the cell's exchanges: three
BROADCASTs, five REPARTITIONs (two of them on two columns, the cyclic join
graph's closing edge), one MERGE into the ORDER BY and one GATHER. A
`PrestoTpuServer` over that `DistributedQueryRunner`, as `python -m
presto_tpu.server --distributed` builds it, answers Q5 from
`benchmark/queries/q5.sql` through `client.dbapi`; every answer is held to the
benchmark's plain numpy reference (`benchmark/queries/q5.py`, which imports
nothing of the program). Beside it: a warm query builds nothing, the counters
by exchange kind add up to the totals, and the merge's splitter sample is a
span inside a `sync` of its pump.
"""
import json
import os
import sys
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from presto_tpu.metadata import Session  # noqa: E402
from presto_tpu.utils.metrics import METRICS  # noqa: E402

WORKERS = 4
TINY_SF = 0.01
# above supplier's 100 rows at `tiny`, under what the lineitem side and the
# year's orders are estimated at: the plan of `sf1.0` under the default
THRESHOLD = 500
PAIRS = [("ASIA", 1994), ("AMERICA", 1993), ("EUROPE", 1997),
         ("AFRICA", 1995), ("MIDDLE EAST", 1996)]
KINDS = {"broadcast": 3, "repartition": 5, "merge": 1, "gather": 1}
# the merge's fragment: the final sums, ranged into the ORDER BY's workers
MERGE_FRAGMENT = 8


@pytest.fixture(scope="module")
def q5():
    from benchmark.harness import cells

    return cells.Query("q5")


def _runner(devices, **properties):
    from presto_tpu.parallel.mesh import MeshContext
    from presto_tpu.parallel.runner import DistributedQueryRunner

    return DistributedQueryRunner(
        MeshContext(devices[:WORKERS], n_workers=WORKERS),
        session=Session(catalog="tpch", schema="tiny", properties=dict(
            broadcast_join_threshold_rows=THRESHOLD, **properties)))


@pytest.fixture(scope="module")
def runner(eight_devices):
    return _runner(eight_devices)


@pytest.fixture(scope="module")
def server(runner):
    from presto_tpu.server import PrestoTpuServer

    srv = PrestoTpuServer(runner, port=0)
    srv.start()
    yield srv
    srv.stop()


def _sql(q5, region, year):
    return q5.template.format(region=region, year=year)


def _ask(server, sql):
    """-> the answer as the benchmark types it (decimals exact text)."""
    import presto_tpu.client.dbapi as dbapi
    from benchmark.harness.compare import typed

    with dbapi.connect(host="127.0.0.1", port=server.port, user="t",
                       catalog="tpch", schema="tiny") as conn:
        cur = conn.cursor()
        cur.execute(sql)
        return typed(cur.fetchall(), cur.description)


def _counters():
    return {k: v for k, v in METRICS.raw_snapshot()["counters"].items()
            if k.startswith(("exchange.", "kernel_cache."))}


def test_the_lowered_threshold_plans_the_cells_exchanges(runner):
    """At `tiny` under the lowered threshold the plan is the cell's at
    `sf1.0` under the default, letter for letter but the schema."""
    from presto_tpu.models.tpch_sql import QUERIES

    text = runner.explain(QUERIES[5])
    with open(os.path.join(ROOT, "tests", "golden_plans",
                           "q5_sf1_mesh4.txt")) as f:
        assert text.replace("tiny.", "sf1.0.") + "\n" == f.read()
    heads = [line for line in text.splitlines() if " output=" in line]
    assert Counter(h.split(" output=")[1].split(" ")[0] for h in heads) == \
        KINDS
    two_column = [h for h in heads if "repartition" in h and
                  h.split(" keys=")[1].count(",") == 1]
    assert [h.split(" keys=")[1] for h in two_column] == [
        "['s_nationkey', 'o_custkey']", "['c_nationkey', 'c_custkey']"]


@pytest.mark.parametrize("region,year", PAIRS)
def test_served_mesh_q5_equals_the_plain_reference(server, q5, region, year):
    got = _ask(server, _sql(q5, region, year))
    assert got, "Q5 at tiny returns rows for every region and year"
    assert got == q5.reference(TINY_SF, {"region": region, "year": year})


def test_a_second_run_builds_nothing_and_uploads_nothing(runner, q5):
    """What the cell's warm-up relies on: the second run of a parameter set
    compiles no kernel and no collective, and no page crosses host->device
    inside an exchange."""
    sql = _sql(q5, *PAIRS[0])
    first = runner.execute(sql)
    before = _counters()
    again = runner.execute(sql)
    after = _counters()

    def gained(name):
        return after.get(name, 0) - before.get(name, 0)

    assert again.rows == first.rows
    assert gained("kernel_cache.misses") == 0
    assert gained("exchange.collective_compiles") == 0
    assert gained("exchange.exchanges") == sum(KINDS.values())
    assert after.get("exchange.host_uploads", 0) == 0


def test_the_counters_by_kind_add_up_to_the_totals(runner, q5):
    """`exchange.<kind>.live_bytes|rows|chunks|pump_s` are each exchange's
    entry summed by its kind, in the query's stats and on /v1/metrics; the
    kinds together are the totals, and the pumps' lives the seconds of
    their states."""
    from presto_tpu.parallel.streaming_exchange import PUMP_STATES

    before = _counters()
    ex = runner.execute(_sql(q5, *PAIRS[1])).stats["exchange"]
    after = _counters()
    per = ex["per_exchange"]
    assert Counter(e["kind"] for e in per) == KINDS
    for kind in KINDS:
        mine = [e for e in per if e["kind"] == kind]
        for name, field in (("live_bytes", "live_bytes"),
                            ("rows", "rows_out"), ("chunks", "chunks"),
                            ("pump_s", "pump_s")):
            key = f"{kind}.{name}"
            assert ex[key] == pytest.approx(sum(e[field] for e in mine),
                                            abs=1e-5), key
            assert after["exchange." + key] - before.get(
                "exchange." + key, 0) == pytest.approx(ex[key], abs=1e-5), key
    for name in ("live_bytes", "rows", "chunks"):
        assert sum(ex[f"{kind}.{name}"] for kind in KINDS) == ex[name], name
    states = sum(ex["stall_s" if s == "starved" else f"{s}_s"]
                 for s in PUMP_STATES)
    assert sum(ex[f"{kind}.pump_s"] for kind in KINDS) == \
        pytest.approx(states, abs=1e-4)
    # every worker receives every replicated row: supplier's 100, nation's
    # 25 and the one region, at 16, 20 and 12 bytes a row
    assert ex["broadcast.rows"] == WORKERS * (100 + 25 + 1)
    assert ex["broadcast.live_bytes"] == WORKERS * (100 * 16 + 25 * 20 + 12)


def test_the_merge_splitters_span_lies_inside_a_sync_state(eight_devices, q5):
    """The merge freezes its range splitters once, from its first chunk: one
    host read, the span `merge_splitters f<fragment>` with the rows it
    sampled and the splitters it made, inside the pump's `pump_sync` of
    `of` = splitters. It adds no state: the pump's states still add up to
    its life."""
    result = _runner(eight_devices, query_trace=True).execute(
        _sql(q5, *PAIRS[2]))
    with open(result.trace_path) as f:
        events = [e for e in json.load(f)["traceEvents"]
                  if e.get("cat") == "exchange"]
    (froze,) = [e for e in events
                if e["name"] == f"merge_splitters f{MERGE_FRAGMENT}"]
    assert froze["args"]["splitters"] == WORKERS - 1
    assert 1 <= froze["args"]["rows"] <= 128 * WORKERS
    (sync,) = [e for e in events
               if e["name"] == f"pump_sync f{MERGE_FRAGMENT}"
               and e["args"]["of"] == "splitters"]
    assert sync["ts"] <= froze["ts"] and \
        froze["ts"] + froze["dur"] <= sync["ts"] + sync["dur"] + 1e-3
    (merge,) = [e for e in result.stats["exchange"]["per_exchange"]
                if e["kind"] == "merge"]
    assert merge["fragment"] == MERGE_FRAGMENT
    assert sum(merge["state_s"].values()) == pytest.approx(
        merge["pump_s"], abs=max(0.02 * merge["pump_s"], 0.002))
