"""Testing utilities: page sinks, operator drivers, and the SQL oracle.

Analogue of the reference testing kit: OperatorAssertion.java, PageConsumerOperator,
NullOutputOperator (presto-main testing/), and the H2 oracle pattern of
QueryAssertions.assertQuery (presto-tests/.../QueryAssertions.java:97-119,
H2QueryRunner.java:88) — here the oracle is sqlite3 over the same generated data.
"""
from __future__ import annotations

import contextlib
import math
import sqlite3
import threading
from typing import Any, Iterable, List, Optional, Sequence

import numpy as np

from ..block import Page
from ..ops.operator import Operator, OperatorContext, OperatorFactory
from ..types import Type


class PageConsumerOperator(Operator):
    """Sink that materializes pages (testing/PageConsumerOperator analogue)."""

    def __init__(self, context: OperatorContext, types: List[Type]):
        super().__init__(context)
        self._types = types
        self.pages: List[Page] = []

    @property
    def output_types(self) -> List[Type]:
        return self._types

    def add_input(self, page: Page) -> None:
        self.context.record_input(page, page.capacity)
        self.pages.append(page)

    def get_output(self) -> Optional[Page]:
        return None

    def rows(self) -> List[list]:
        out = []
        for p in self.pages:
            out.extend(p.to_pylists())
        return out


class PageConsumerFactory(OperatorFactory):
    def __init__(self, operator_id: int = 999, types: Optional[List[Type]] = None):
        super().__init__(operator_id, "PageConsumer")
        self.types = types or []
        self.consumers: List[PageConsumerOperator] = []
        self.consumers_by_worker: dict = {}

    def create_operator(self, worker: int = 0) -> PageConsumerOperator:
        op = PageConsumerOperator(
            OperatorContext(self.operator_id, self.name, worker=worker), self.types)
        self.consumers.append(op)
        self.consumers_by_worker.setdefault(worker, []).append(op)
        return op

    def rows(self) -> List[list]:
        out = []
        for c in self.consumers:
            out.extend(c.rows())
        return out

    def pages_for(self, worker: int) -> List[Page]:
        return [p for c in self.consumers_by_worker.get(worker, [])
                for p in c.pages]


def drive_operators(operators: List[Operator]) -> None:
    """Run an operator chain to completion (OperatorAssertion.toPages analogue)."""
    from ..exec.driver import Driver

    Driver(operators).run_to_completion()


def assert_no_residue(pool, query_id: Optional[str] = None) -> None:
    """Shared zero-residue gate (replaces the tests' hand-rolled ledger
    asserts): with `query_id`, that query must hold zero RAM and zero
    spill bytes in `pool`; without, the pool's whole spill ledger must be
    empty (RAM is deliberately NOT asserted pool-wide — the shared pool
    outlives any one test, and a concurrent tenant's live reservation is
    not this test's residue). When the runtime leak sanitizer is
    installed, its findings must be empty too — a leak the ledger math
    happens to cancel out still fails, with the allocation stack."""
    if query_id is not None:
        held = pool.query_bytes(query_id)
        assert held == 0, \
            f"query {query_id!r} left {held} reserved byte(s) in the pool"
        spilled = pool.spill_bytes(query_id)
        assert spilled == 0, \
            f"query {query_id!r} left {spilled} spill byte(s) charged"
    else:
        ledger = pool.spill_by_query()
        assert ledger == {}, f"spill ledger residue: {ledger}"
    from . import leaksan

    if leaksan.enabled():
        leaksan.SANITIZER.assert_clean()


# ---------------------------------------------------------------------------
# program recorder
# ---------------------------------------------------------------------------

def _abstract(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if isinstance(x, (jax.Array, np.ndarray)) else x, tree)


class ProgramRecorder:
    """Wraps jitted callables so that each top-level call leaves (callable,
    args, kwargs) behind: their shapes, or with `keep_values` the arrays
    themselves. `recording()` wraps every kernel the engine builds through
    `kernel_cache.get_or_build`."""

    def __init__(self, keep_values: bool = False):
        self.keep_values = keep_values
        self.calls = []
        self._lock = threading.Lock()

    def wrap(self, fn):
        import jax

        def recorded(*args, **kwargs):
            leaves = jax.tree_util.tree_leaves((args, kwargs))
            if not any(isinstance(x, jax.core.Tracer) for x in leaves):
                kept = (args, kwargs) if self.keep_values \
                    else _abstract((args, kwargs))
                with self._lock:
                    self.calls.append((fn, kept))
            return fn(*args, **kwargs)
        return recorded

    @contextlib.contextmanager
    def recording(self):
        from . import kernel_cache

        real_build = kernel_cache.get_or_build

        def recording_build(key, make):
            fn, built = real_build(key, make)
            return (self.wrap(fn) if callable(fn) else fn), built

        kernel_cache.get_or_build = recording_build
        try:
            yield self
        finally:
            kernel_cache.get_or_build = real_build

    def named(self, part: str):
        """Distinct recorded programs whose function name contains `part`,
        as (fn, args, kwargs)."""
        import jax

        seen, out = set(), []
        for fn, (args, kwargs) in self.calls:
            name = getattr(fn, "__qualname__", "") or repr(fn)
            shapes = _abstract((args, kwargs))
            sig = (name, str(jax.tree_util.tree_structure(shapes)),
                   tuple(str(x) for x in jax.tree_util.tree_leaves(shapes)))
            if part in name and sig not in seen:
                seen.add(sig)
                out.append((fn, args, kwargs))
        return out


# ---------------------------------------------------------------------------
# sqlite oracle
# ---------------------------------------------------------------------------

TPCH_KEY_SUFFIXES = ("_orderkey", "_partkey", "_suppkey", "_custkey",
                     "_nationkey", "_regionkey")


class SqliteOracle:
    """Loads generated TPC-H data into sqlite and runs reference SQL.

    Decimal columns are loaded as REAL (sqlite has no decimals) — comparisons use
    tolerances for floating results and exactness for integers/strings.
    """

    def __init__(self):
        self.conn = sqlite3.connect(":memory:")

    def load_tpch(self, schema_sf: float, tables: Sequence[str],
                  max_rows: Optional[int] = None) -> None:
        from ..connectors.tpch import generator as g

        for t in tables:
            if t == "lineitem":
                cols = list(g.LINEITEM_COLUMNS)
                n_orders = g.TPCH_TABLES["orders"].row_count(schema_sf)
                data = g.lineitem_for_orders(0, n_orders, schema_sf,
                                             [c[0] for c in cols])
            else:
                cols = [(c.name, c.type, c.dictionary)
                        for c in g.TPCH_TABLES[t].columns]
                n = g.table_row_count(t, schema_sf)
                if max_rows:
                    n = min(n, max_rows)
                data = g.generate_rows(t, 0, n, schema_sf,
                                       [c[0] for c in cols])
            self._load_table(t, cols, data)
            # without them sqlite plans a correlated subquery (Q21's
            # EXISTS / NOT EXISTS over lineitem) as a scan per outer row
            for name, _, _ in cols:
                if name.endswith(TPCH_KEY_SUFFIXES):
                    self.conn.execute(f"CREATE INDEX IF NOT EXISTS "
                                      f"{name}_idx ON {t} ({name})")
        self.conn.commit()

    def load_tpcds(self, schema_sf: float, tables: Sequence[str]) -> None:
        from ..connectors.tpcds import generator as g

        for t in tables:
            cols = [(c.name, c.type, c.dictionary)
                    for c in g.TPCDS_TABLES[t].columns]
            n = g.table_row_count(t, schema_sf)
            data = g.generate_rows(t, 0, n, schema_sf, [c[0] for c in cols])
            self._load_table(t, cols, data)
        self.conn.commit()

    def _load_table(self, table: str, cols, data) -> None:
        """Decode dictionary codes / rescale decimals and bulk-insert."""
        cur = self.conn.cursor()
        names = [c[0] for c in cols]
        cur.execute(f"CREATE TABLE IF NOT EXISTS {table} ({', '.join(names)})")
        pycols = []
        for (cname, ctype, cdict) in cols:
            arr = data[cname]
            if cdict is not None:
                pycols.append(cdict.lookup(arr.astype(np.int64)))
            elif ctype.name == "decimal":
                pycols.append(arr.astype(np.float64) / (10 ** ctype.scale))
            else:
                pycols.append(arr)
        rows = list(zip(*[list(c) for c in pycols]))
        rows = [tuple(x.item() if isinstance(x, np.generic) else x for x in r)
                for r in rows]
        cur.executemany(
            f"INSERT INTO {table} VALUES ({', '.join('?' * len(names))})",
            rows)

    def query(self, sql: str, params: tuple = ()) -> List[tuple]:
        return self.conn.execute(sql, params).fetchall()


def normalize_value(v: Any) -> Any:
    """Python value -> comparable canonical form."""
    import datetime
    from decimal import Decimal

    if isinstance(v, Decimal):
        return float(v)
    if isinstance(v, datetime.date):
        return (v - datetime.date(1970, 1, 1)).days
    if isinstance(v, np.generic):
        return v.item()
    return v


def assert_rows_equal(actual: Iterable[Sequence], expected: Iterable[Sequence],
                      ordered: bool = False, rel_tol: float = 1e-6) -> None:
    """QueryAssertions.assertEqualsIgnoreOrder analogue with float tolerance."""
    a = [tuple(normalize_value(x) for x in row) for row in actual]
    e = [tuple(normalize_value(x) for x in row) for row in expected]
    if not ordered:
        a = sorted(a, key=_row_key)
        e = sorted(e, key=_row_key)
    assert len(a) == len(e), f"row count mismatch: {len(a)} != {len(e)}\n" \
                             f"actual[:5]={a[:5]}\nexpected[:5]={e[:5]}"
    for i, (ra, re_) in enumerate(zip(a, e)):
        assert len(ra) == len(re_), f"row {i} arity: {ra} vs {re_}"
        for j, (va, ve) in enumerate(zip(ra, re_)):
            if isinstance(va, float) or isinstance(ve, float):
                if va is None or ve is None:
                    assert va is ve is None, f"row {i} col {j}: {va} != {ve}"
                    continue
                ok = math.isclose(float(va), float(ve), rel_tol=rel_tol, abs_tol=1e-9)
                assert ok, f"row {i} col {j}: {va} != {ve}\nrow actual={ra}\nrow expected={re_}"
            else:
                assert va == ve, f"row {i} col {j}: {va!r} != {ve!r}\n" \
                                 f"row actual={ra}\nrow expected={re_}"


def _row_key(row):
    return tuple((x is None, str(type(x)), str(x)) for x in row)
