"""Every program run of one warm query, and the engine line that dispatched it.

`device_programs_per_query` (the benchmark's trace reduction) counts a
query's XLA program runs on the chip; this tool puts each of them down to
the line of `presto_tpu` that dispatched it. It works on whatever device JAX
gives: here on the CPU (`JAX_PLATFORMS=cpu python -m tools.program_census
--query 1`), on the chip through `chiprun -- python3 -m tools.program_census
--query 1 --schema sf1`. The count of a CPU run is the chip's for the same
plan and page count; no time is taken.

How: JAX runs every compiled program, a jitted kernel's and an eager
operation's alike, through `pxla.ExecuteReplicated.__call__`, unless the
C++ fast path of a `jit` object has cached the call. `with Census()` turns
that fast path off (`pjit._get_fastpath_data` returns None, and the C++
caches that already hold fast-path data are cleared) and wraps `__call__`;
`Census.counting()` records each run's program name and the innermost
`presto_tpu` frame on the stack. The query runs once to compile and fill the
engine's caches, and once more counted.
"""
import argparse
import collections
import contextlib
import os
import sys
import traceback
from typing import List, Optional, Tuple

import jax
from jax._src import pjit
from jax._src.interpreters import pxla

_PKG = os.sep + "presto_tpu" + os.sep


def _engine_line() -> str:
    """`path:line function` of the innermost frame inside presto_tpu."""
    for fs in reversed(traceback.extract_stack()):
        i = fs.filename.rfind(_PKG)
        if i >= 0:
            return f"{fs.filename[i + 1:]}:{fs.lineno} {fs.name}"
    return "?"


class Census:
    """Counts program runs while `counting()` is open, inside
    `with Census() as census:`. While the block is open every jit call
    takes JAX's Python path (slower); on exit JAX is as it was, its caches
    cleared once more so the fast path fills again."""

    def __init__(self):
        self.runs: List[Tuple[str, str]] = []
        self._on = False
        self._saved = None

    def __enter__(self) -> "Census":
        self._saved = (pjit._get_fastpath_data,
                       pxla.ExecuteReplicated.__call__)
        orig = self._saved[1]
        census = self

        def call(self_, *args):
            if census._on:
                census.runs.append((self_.name, _engine_line()))
            return orig(self_, *args)

        pjit._get_fastpath_data = lambda *a, **k: None
        pxla.ExecuteReplicated.__call__ = call
        jax.clear_caches()  # drop fast-path data cached before the patch
        return self

    def __exit__(self, *exc) -> None:
        pjit._get_fastpath_data, pxla.ExecuteReplicated.__call__ = self._saved
        jax.clear_caches()  # entries made without fast-path data

    @contextlib.contextmanager
    def counting(self):
        """Yields the list this block's program runs are appended to."""
        self.runs = []
        self._on = True
        try:
            yield self.runs
        finally:
            self._on = False


def report(runs, out=sys.stdout) -> None:
    """Each run in order, then the runs by engine line."""
    for i, (name, line) in enumerate(runs):
        print(f"{i:4d}  {name:40s} {line}", file=out)
    print(f"\n{len(runs)} program runs; by engine line:", file=out)
    by_line = collections.Counter(line for _n, line in runs)
    for line, n in by_line.most_common():
        print(f"{n:5d}  {line}", file=out)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--query", type=int, default=1,
                    help="TPC-H query number (presto_tpu.models.tpch_sql)")
    ap.add_argument("--sql", help="SQL text in place of --query")
    ap.add_argument("--schema", default="tiny")
    ap.add_argument("--catalog", default="tpch")
    ap.add_argument("--page-capacity", type=int, default=None,
                    help="session page_capacity (default: the platform's)")
    args = ap.parse_args(argv)

    from presto_tpu.metadata import Session
    from presto_tpu.models.tpch_sql import QUERIES
    from presto_tpu.runner import LocalQueryRunner
    props = {}
    if args.page_capacity:
        props["page_capacity"] = args.page_capacity
    runner = LocalQueryRunner(session=Session(
        catalog=args.catalog, schema=args.schema, properties=props))
    sql = args.sql or QUERIES[args.query]
    with Census() as census:
        runner.execute(sql)  # compiles, fills the engine's caches
        with census.counting() as runs:
            runner.execute(sql)
    print(f"device: {jax.devices()[0].platform} "
          f"{jax.devices()[0].device_kind}")
    report(runs)
    return 0


if __name__ == "__main__":
    sys.exit(main())
