"""chip_smoke.py — proof that the SQL path starts and answers on the chip.

One process, one TPU (the default), or four behind ``--chips 4``:

- default: starts the server the way ``python -m presto_tpu.server --schema
  sf1`` does (LocalQueryRunner -> PrestoTpuServer on a free port), sends
  TPC-H Q6, Q1 and Q3 at SF1 over ``/v1/statement`` through
  ``presto_tpu.client.dbapi``, each twice, and compares every row with a
  plain-numpy evaluation over the generator's columns (decimals and keys
  exactly, doubles to the tests' 1e-9). Second runs must build no kernel,
  and the device must hold bytes afterwards.
- ``--chips 4``: only the mesh path — ``DistributedQueryRunner`` over a
  four-device ``MeshContext`` on Q1 and Q3 at SF1, compared with
  ``LocalQueryRunner`` on one device in the same process; every device must
  hold bytes and the exchange must have run collectives.

Every line printed before the last is an OBSERVATION (one JSON object each),
not a metric: nothing here is a median or a steady-state window. The last
line is the verdict the driver reads. Any failed phase raises, so it reaches
the exit code; without a TPU the script says why and exits 1.

Run: python chip_smoke.py [--chips 4]
"""
import argparse
import datetime
import json
import os
import sys
import time
from decimal import Decimal

SCHEMA = "sf1"
EPOCH = datetime.date(1970, 1, 1)


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - EPOCH).days


def dec(scaled: int, scale: int) -> str:
    """Exact decimal text of scaled / 10^scale, canonical (no exponent, no
    trailing zeros) — compared as text, so decimals must match exactly."""
    return canon(Decimal(int(scaled)).scaleb(-scale))


def canon(d: Decimal) -> str:
    return format(d.normalize(), "f")


# ---------------------------------------------------------------------------
# the reference: plain numpy over the generator's columns, no engine operator
# ---------------------------------------------------------------------------

def reference_rows(sf: float) -> dict:
    import numpy as np

    from presto_tpu.connectors.tpch import generator as g

    n_orders = g.table_row_count("orders", sf)
    li = g.lineitem_for_orders(0, n_orders, sf, [
        "l_orderkey", "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate"])
    orders = g.generate_rows("orders", 0, n_orders, sf, [
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"])
    cust = g.generate_rows("customer", 0, g.table_row_count("customer", sf),
                           sf, ["c_custkey", "c_mktsegment"])
    qty, ep, disc, tax = (li[c].astype(np.int64) for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    ship = li["l_shipdate"]
    out = {}

    # Q6: decimals are scaled by 100, so price * discount has scale 4
    keep = (ship >= days(1994, 1, 1)) & (ship < days(1995, 1, 1)) & \
        (disc >= 5) & (disc <= 7) & (qty < 2400)
    out[6] = [(dec((ep[keep] * disc[keep]).sum(), 4),)]

    # Q1
    keep = ship <= days(1998, 12, 1) - 90
    flags = g.DICT_RETURNFLAG.lookup(np.arange(3))
    stati = g.DICT_LINESTATUS.lookup(np.arange(2))
    disc_price = ep * (100 - disc)
    charge = disc_price * (100 + tax)
    rows = []
    for rf in range(3):
        for ls in range(2):
            m = keep & (li["l_returnflag"] == rf) & (li["l_linestatus"] == ls)
            n = int(m.sum())
            if n == 0:
                continue
            rows.append((str(flags[rf]), str(stati[ls]),
                         dec(qty[m].sum(), 2), dec(ep[m].sum(), 2),
                         dec(disc_price[m].sum(), 4), dec(charge[m].sum(), 6),
                         int(qty[m].sum()) / 100 / n,
                         int(ep[m].sum()) / 100 / n,
                         int(disc[m].sum()) / 100 / n, n))
    out[1] = rows

    # Q3
    cutoff = days(1995, 3, 15)
    building = list(g.DICT_SEGMENT.lookup(np.arange(5))).index("BUILDING")
    custs = cust["c_custkey"][cust["c_mktsegment"] == building]
    okeep = (orders["o_orderdate"] < cutoff) & \
        np.isin(orders["o_custkey"], custs)
    okeys = orders["o_orderkey"][okeep]
    lkeep = (ship > cutoff) & np.isin(li["l_orderkey"], okeys)
    keys, inv = np.unique(li["l_orderkey"][lkeep], return_inverse=True)
    revenue = np.zeros(len(keys), dtype=np.int64)
    np.add.at(revenue, inv, disc_price[lkeep])
    at = np.searchsorted(orders["o_orderkey"], keys)
    assert (orders["o_orderkey"][at] == keys).all()
    odate = orders["o_orderdate"][at]
    top = np.lexsort((odate, -revenue))[:10]
    out[3] = [(int(keys[i]), dec(revenue[i], 4),
               EPOCH + datetime.timedelta(days=int(odate[i])),
               int(orders["o_shippriority"][at[i]])) for i in top]
    return out


def exact_decimals(rows):
    """Engine rows with Decimal values as exact canonical text."""
    return [tuple(canon(v) if isinstance(v, Decimal) else v for v in row)
            for row in rows]


def typed(rows, description):
    """Wire rows -> comparable values: decimals stay exact canonical text,
    dates become dates."""
    kinds = [d[1] for d in description]
    out = []
    for row in rows:
        vals = []
        for v, kind in zip(row, kinds):
            if kind == "decimal":
                v = canon(Decimal(v))
            elif kind == "date":
                v = datetime.date.fromisoformat(v)
            vals.append(v)
        out.append(tuple(vals))
    return out


# ---------------------------------------------------------------------------
# what the process can observe about itself
# ---------------------------------------------------------------------------

class CompileWatch:
    """Counts XLA backend compiles and persistent-cache hits through
    jax.monitoring, so a query's compile cost is observed where it happens."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def counters(self) -> dict:
        """XLA's counters beside the engine's own, for before/after deltas."""
        from presto_tpu.utils.metrics import METRICS

        return {"segments.compiles":
                METRICS.counter_value("segments.compiles"),
                "kernel_cache.misses":
                METRICS.counter_value("kernel_cache.misses"),
                "xla_compiles": self.compiles,
                "xla_compile_s": self.compile_s,
                "persistent_cache_hits": self.cache_hits}

    def timed(self, run):
        """-> (run(), wall seconds, what was compiled meanwhile)."""
        before, t0 = self.counters(), time.perf_counter()
        out = run()
        wall = time.perf_counter() - t0
        after = self.counters()
        return out, round(wall, 3), {k: round(after[k] - before[k], 3)
                                     for k in after}


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def device_bytes(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {"in_use": int(stats.get("bytes_in_use", 0)),
            "peak": int(stats.get("peak_bytes_in_use", 0))}


def require_tpu(count: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: jax reports platform {devs[0].platform!r} "
                 f"({devs[0].device_kind}), not a TPU — nothing was run")
    if len(devs) < count:
        sys.exit(f"chip_smoke: {count} TPU devices needed, jax sees "
                 f"{len(devs)}")
    return devs[:count]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def served_phase(devs, schema: str, watch: CompileWatch) -> None:
    """Q6, Q1, Q3 twice each over /v1/statement, checked against numpy."""
    from presto_tpu.client import dbapi
    from presto_tpu.connectors.tpch.connector import SCHEMAS
    from presto_tpu.metadata import Session
    from presto_tpu.models.tpch_sql import QUERIES
    from presto_tpu.runner import LocalQueryRunner
    from presto_tpu.server.http_server import PrestoTpuServer
    from presto_tpu.utils.metrics import METRICS
    from presto_tpu.utils.testing import assert_rows_equal

    t0 = time.perf_counter()
    expected = reference_rows(SCHEMAS[schema])
    emit(observation="reference", schema=schema,
         host_datagen_and_numpy_s=round(time.perf_counter() - t0, 3))

    runner = LocalQueryRunner(session=Session(catalog="tpch", schema=schema))
    server = PrestoTpuServer(runner, port=0)
    thread = server.start()
    try:
        conn = dbapi.connect(host="127.0.0.1", port=server.port,
                             catalog="tpch", schema=schema, user="chip_smoke")
        segment_compiles = 0

        def ask(sql):
            cur = conn.cursor()
            cur.execute(sql)
            return typed(cur.fetchall(), cur.description)

        for qid in (6, 1, 3):
            walls, built = [], []
            for _run in range(2):
                rows, wall, compiled = watch.timed(
                    lambda: ask(QUERIES[qid]))
                assert_rows_equal(rows, expected[qid], ordered=True,
                                  rel_tol=1e-9)
                walls.append(wall)
                built.append(compiled)
            emit(observation="query", query=f"q{qid}", schema=schema,
                 rows=len(rows), first_wall_s=walls[0],
                 second_wall_s=walls[1], first=built[0], second=built[1],
                 matches_reference=True)
            if built[0]["kernel_cache.misses"] <= 0:
                raise RuntimeError(f"q{qid}: the first run built no kernel")
            if built[1]["kernel_cache.misses"] or \
                    built[1]["segments.compiles"] or \
                    built[1]["xla_compiles"]:
                raise RuntimeError(
                    f"q{qid}: the second run compiled: {built[1]}")
            segment_compiles += built[0]["segments.compiles"]
        if segment_compiles <= 0:
            raise RuntimeError("no fused segment was compiled by any query")
        conn.close()
    finally:
        server.stop()
        thread.join(timeout=10.0)
    if thread.is_alive():
        raise RuntimeError("the server thread did not stop")

    resident = METRICS.snapshot("scan.resident_cache_bytes").get(
        "scan.resident_cache_bytes", 0)
    mem = device_bytes(devs[0])
    emit(observation="device", resident_scan_cache_bytes=int(resident),
         device_bytes_in_use=mem["in_use"], device_peak_bytes=mem["peak"])
    if resident <= 0:
        raise RuntimeError("the resident scan cache holds no bytes")
    if mem["in_use"] <= 0:
        raise RuntimeError("the device reports no bytes in use")


def mesh_phase(devs, schema: str, watch: CompileWatch) -> None:
    """Q1 and Q3 through the four-device mesh runner against one device."""
    from presto_tpu.metadata import Session
    from presto_tpu.models.tpch_sql import QUERIES
    from presto_tpu.parallel.mesh import MeshContext
    from presto_tpu.parallel.runner import DistributedQueryRunner
    from presto_tpu.runner import LocalQueryRunner
    from presto_tpu.utils.testing import assert_rows_equal

    session = Session(catalog="tpch", schema=schema)
    mesh_runner = DistributedQueryRunner(MeshContext(list(devs)),
                                         session=session)
    local = LocalQueryRunner(session=session)
    collectives = 0
    for qid in (1, 3):
        on_mesh, mesh_wall, mesh_built = watch.timed(
            lambda: mesh_runner.execute(QUERIES[qid]))
        on_one, one_wall, one_built = watch.timed(
            lambda: local.execute(QUERIES[qid]))
        assert_rows_equal(exact_decimals(on_mesh.rows),
                          exact_decimals(on_one.rows), ordered=True,
                          rel_tol=1e-9)
        ex = (on_mesh.stats or {}).get("exchange", {})
        emit(observation="mesh_query", query=f"q{qid}", schema=schema,
             rows=len(on_one.rows),
             mesh={"wall_s": mesh_wall,
                   "xla_compile_s": mesh_built["xla_compile_s"]},
             one_device={"wall_s": one_wall,
                         "xla_compile_s": one_built["xla_compile_s"]},
             matches_one_device=True,
             exchange={k: ex.get(k) for k in (
                 "mode", "exchanges", "chunks", "fills", "refills",
                 "collective_compiles", "carry_rows")})
        collectives += int(ex.get("chunks") or 0)
        if not ex.get("exchanges"):
            raise RuntimeError(f"q{qid}: no exchange ran on the mesh: {ex}")
    if collectives <= 0:
        raise RuntimeError("the exchanges dispatched no collective")
    per_device = [device_bytes(d) for d in devs]
    emit(observation="devices", bytes=per_device)
    idle = [i for i, m in enumerate(per_device) if m["peak"] <= 0]
    if idle:
        raise RuntimeError(f"devices {idle} never held a byte")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh path against one device")
    args = ap.parse_args()

    devs = require_tpu(args.chips)
    import jax
    import jaxlib

    import presto_tpu  # noqa: F401 - configures x64 and the compile cache

    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "unknown"
    cache_dir = jax.config.jax_compilation_cache_dir
    entries_before = cache_entries(cache_dir)
    emit(observation="versions", jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu,
         device_kind=devs[0].device_kind, devices=len(jax.devices()))
    emit(observation="compile_cache", dir=cache_dir,
         entries_before=entries_before)

    watch = CompileWatch()
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(devs, SCHEMA, watch)
    else:
        served_phase(devs, SCHEMA, watch)
    emit(observation="compile_cache", dir=cache_dir,
         entries_before=entries_before,
         entries_after=cache_entries(cache_dir),
         xla_compiles=watch.compiles,
         xla_compile_s=round(watch.compile_s, 3),
         persistent_cache_hits=watch.cache_hits,
         total_s=round(time.perf_counter() - t0, 3))

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
