"""Compile-ahead: populate the kernel + XLA caches for the north-star set.

The engine bounds per-query compiled-program count (shape-bucketed pages,
shared operator kernels via the global kernel cache), but the first process
on a machine still compiles every kernel. This tool runs the
measurement-ladder queries once so every kernel lands in the process kernel
cache AND the persistent XLA compilation cache (`JAX_COMPILATION_CACHE_DIR`
where set, else `<checkout>/.jax_cache`; presto_tpu/__init__.py), from which
a later process on the same machine replays the compiles.

:func:`warm` is importable; a serving process does the same through its own
runner at start (``python -m presto_tpu.server --compile-ahead``) so the
first tenants of a fresh worker never pay compile walls, and the
single-flight kernel cache means a concurrent thundering herd arriving
mid-warm shares the same builds instead of duplicating them.

Usage: python tools/compile_ahead.py [--schemas tiny,sf1] [--queries 1,3,5,6,9]
"""
import argparse
import time


def warm(schemas=("tiny",), queries=(1, 3, 6),
         verbose: bool = True) -> dict:
    """Run the given TPC-H queries once per schema through a fresh
    LocalQueryRunner, filling the process kernel cache (and, transitively,
    the persistent XLA cache). Returns {"queries", "seconds",
    "kernel_cache_entries"}; a query that fails raises."""
    from presto_tpu.metadata import Session
    from presto_tpu.models.tpch_sql import QUERIES
    from presto_tpu.runner import LocalQueryRunner
    from presto_tpu.utils import kernel_cache

    t_start = time.perf_counter()
    ran = 0
    for schema in schemas:
        runner = LocalQueryRunner(
            session=Session(catalog="tpch", schema=schema))
        for qid in queries:
            t0 = time.perf_counter()
            out = runner.execute(QUERIES[int(qid)])
            ran += 1
            if verbose:
                print(f"compile-ahead {schema} q{qid}: "
                      f"{time.perf_counter() - t0:.1f}s, "
                      f"{len(out.rows)} rows", flush=True)
    return {"queries": ran,
            "seconds": round(time.perf_counter() - t_start, 2),
            "kernel_cache_entries": kernel_cache.stats()["entries"]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--schemas", default="tiny,sf1")
    ap.add_argument("--queries", default="1,3,5,6,9")
    args = ap.parse_args()

    qids = [int(x) for x in args.queries.split(",") if x]
    schemas = [s for s in args.schemas.split(",") if s]
    summary = warm(schemas=schemas, queries=qids)
    print(f"compile-ahead: {summary['queries']} queries warmed "
          f"in {summary['seconds']}s, "
          f"{summary['kernel_cache_entries']} kernel-cache entries",
          flush=True)


if __name__ == "__main__":
    main()
