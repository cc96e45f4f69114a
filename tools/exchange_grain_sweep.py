"""The sweep behind the grain of the mesh's data plane
(`parallel/streaming_exchange.MESH_PAGE_ROWS`).

Runs the mesh cell's Q3 (`benchmark/queries/q3.sql` over schema `sf1.0`, the
deployment `tpch-sf1-mesh4`) on a `DistributedQueryRunner` over the host's
chips at each grain: the session's `page_capacity` is the grain, and every
exchange derives its send chunk from it
(`streaming_exchange.derive_chunk_rows`). Run it on four chips:
`chiprun --chips 4 --timeout 1800 -- python3 -m tools.exchange_grain_sweep`.

One JSON line a grain: the first query's seconds from an EMPTY compile cache
(the persistent cache is pointed at a fresh directory and every in-process
cache is dropped, so each grain compiles all it runs), the warm walls, the
collective chunks, fill programs and refills of the last warm query, the
chunk each exchange derived, the pump seconds of that query by state (summed
over its exchanges, and each exchange's own beside its `pump_s`: where the
pumps' time went is WHY a warm wall moves with the grain), and from a profile of one more warm query the
device programs over all chips, a chip's mean busy seconds and the fullest
chip's peak HBM. The grain the mesh runs at is the one with the shortest warm
wall whose cell still ends its cold run inside the check's limit (PR 37: the
warm wall rose with the grain over 2^16, 2^18 and 2^20, the three grains
that were run, on a tree before the fills moved rows by gathers; a grain is
about 11 chip-minutes on four chips, so the grains under 2^16 wait for a PR
with the budget: PERF.md section 7); a CPU run says nothing about it.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import jax

import presto_tpu  # noqa: F401  (enables 64-bit types, sets the compile cache)
from presto_tpu.metadata import Session
from presto_tpu.ops.scan import RESIDENT_CACHE
from presto_tpu.parallel import streaming_exchange
from presto_tpu.parallel.mesh import MeshContext
from presto_tpu.parallel.runner import DistributedQueryRunner
from presto_tpu.utils import kernel_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRAINS = (14, 15, 16, 17, 18, 19, 20)
WARM_RUNS = 2
SEGMENT, DAY = "BUILDING", 15


def _q3_sql():
    with open(os.path.join(ROOT, "benchmark", "queries", "q3.sql")) as f:
        return f.read().format(segment=SEGMENT, day=DAY)


def _empty_caches(cache_dir):
    """Nothing compiled survives: the persistent cache moves to an empty
    directory, jit's and the engine's in-process caches are dropped, and the
    resident tables go (their pages are cut at the grain)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    cc.reset_cache()
    jax.clear_caches()
    kernel_cache.clear()
    streaming_exchange._fill_chunk_jit.cache_clear()
    streaming_exchange._append_chunk_jit.cache_clear()
    RESIDENT_CACHE.clear()


def _profiled(run, trace_dir):
    """One query under the profiler -> (device programs over all chips,
    a chip's mean busy seconds), by the benchmark's own reduction."""
    from benchmark.harness import trace_reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    trace = trace_reduce.read(trace_reduce.newest_xplane(trace_dir))
    busy = [sum(e - s for s, e in trace_reduce.union(
        [(s, e) for s, e, _name in ops])) / 1e9
        for ops in trace["devices"].values()]
    return len(trace["programs"]), (sum(busy) / len(busy) if busy else None)


def sweep(grains, schema, chips, scratch):
    devices = jax.devices()[:chips]
    sql = _q3_sql()
    want = None
    for g in grains:
        _empty_caches(os.path.join(scratch, f"cache-{g}"))
        runner = DistributedQueryRunner(
            MeshContext(devices, n_workers=chips),
            session=Session(catalog="tpch", schema=schema,
                            properties={"page_capacity": 1 << g}))
        walls = []
        for _ in range(1 + WARM_RUNS):
            t0 = time.perf_counter()
            result = runner.execute(sql)
            walls.append(time.perf_counter() - t0)
        rows = [tuple(r) for r in result.rows]
        want = want or rows
        ex = result.stats["exchange"]
        programs, busy_s = _profiled(lambda: runner.execute(sql),
                                     os.path.join(scratch, f"trace-{g}"))
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devices]
        print(json.dumps({
            "device": devices[0].device_kind,
            "platform": devices[0].platform, "chips": chips,
            "schema": schema, "grain_rows": 1 << g,
            "cold_s": walls[0], "warm_s": walls[1:],
            "chunks": ex.get("chunks"), "fills": ex.get("fills"),
            "refills": ex.get("refills"),
            "chunk_rows": {e["fragment"]: e["chunk_rows"]
                           for e in ex.get("per_exchange", [])},
            "state_s": {k: ex[k] for k in sorted(ex)
                        if k.endswith("_s") and k != "overlap_s"},
            "per_exchange": {e["fragment"]: dict(e["state_s"],
                                                 lock_wait=e["lock_wait_s"],
                                                 pump_s=e["pump_s"])
                             for e in ex.get("per_exchange", [])},
            "device_programs": programs, "busy_s_a_chip": busy_s,
            # the process's peak so far: read the grains in rising order
            "peak_hbm_bytes": max((p for p in peaks if p), default=None),
            "same_rows": rows == want}), flush=True)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--grains", default=",".join(map(str, GRAINS)),
                        help="log2 of the page rows, comma-separated")
    parser.add_argument("--schema", default="sf1.0")
    parser.add_argument("--chips", type=int, default=4)
    args = parser.parse_args(argv)
    grains = [int(g) for g in args.grains.split(",")]
    scratch = tempfile.mkdtemp(prefix="exchange-grain-sweep-")
    try:
        return sweep(grains, args.schema, args.chips, scratch)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
