"""LocalQueryRunner: parse -> analyze/plan -> optimize -> execute, in-process.

Analogue of presto-main testing/LocalQueryRunner.java:210 (executeInternal :620,
createDrivers :679): the single-process full-engine path used by ring-2 tests and
benchmarks — no HTTP, real operators. The distributed runner
(parallel/distributed.py) layers the mesh exchange on the same plans.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional

from .connectors.tpch.connector import StoredTpchConnector, TpchConnector
from .exec.local_planner import LocalExecutionPlanner
from .exec.task_executor import TaskExecutor
from .metadata import CatalogManager, MetadataManager, Session
from .sql import tree as t
from .sql.parser import SqlParser
from .sql.planner.optimizer import optimize
from .sql.planner.plan import OutputNode, plan_to_text
from .types import BIGINT
from .sql.planner.planner import LogicalPlanner
from .utils import trace


def _virtual_remap(source_dict, target_dict):
    """-> callable(codes, live) -> int32 codes in target_dict's space,
    decoding the virtual source per batch and extending the target for unseen
    values. Only LIVE lanes decode: masked/null lanes carry stale codes that
    must never pollute the dictionary. One lock: writer drivers may run
    concurrently under the task executor."""
    import threading

    import numpy as np

    lock = threading.Lock()

    def remap(codes: "np.ndarray", live: "np.ndarray") -> "np.ndarray":
        codes = np.asarray(codes, dtype=np.int64)
        out = np.zeros(len(codes), dtype=np.int32)
        sel = np.flatnonzero(np.asarray(live))
        if len(sel) == 0:
            return out
        uniq, inverse = np.unique(codes[sel], return_inverse=True)
        strings = [str(v) for v in source_dict.lookup(uniq)]
        with lock:
            mapped = np.asarray(target_dict.extend(strings), dtype=np.int32)
        out[sel] = mapped[inverse]
        return out
    return remap


@dataclasses.dataclass
class QueryResult:
    rows: List[list]
    column_names: List[str]
    types: Optional[List] = None  # output Type objects when the engine knows them
    # execution stats: cluster tier adds query/task attempts, retries, faults
    # injected, backoff time; the local tier adds the streaming scan
    # pipeline's per-stage busy/stall breakdown under "scan_pipeline".
    # None when there is nothing to report.
    stats: Optional[dict] = None
    # Chrome trace-event JSON export of the query's flight recorder
    # (utils/trace.py), set when the `query_trace` session knob is on;
    # loads directly in Perfetto / chrome://tracing
    trace_path: Optional[str] = None
    # forensic export of the always-on black-box ring (utils/trace.py):
    # set when the query survived through retries after a failed attempt —
    # a query that failed outright carries the same path on its exception's
    # `failure_trace_path` attribute instead (there is no result then)
    failure_trace_path: Optional[str] = None


# unique per-query ids in the process-shared memory pool (itertools.count
# is atomic under the GIL, so concurrent submits never collide)
_QUERY_MEM_SEQ = itertools.count(1)


def _pool_steps(pool_key: Optional[str]) -> int:
    """Live shared-pool step count of this query's fairness slots (racy
    plain-int read by design: live progress, not an invariant)."""
    if not pool_key:
        return 0
    from .exec.shared_pools import EXCHANGE_POOL, SCAN_POOL

    total = 0
    for pool in (SCAN_POOL, EXCHANGE_POOL):
        client = pool._clients.get(pool_key)
        if client is not None:
            total += client.steps
    return total


def _scan_pipeline_stats(drivers) -> Optional[dict]:
    """Roll every scan's pipeline stage breakdown (ops/scan_pipeline.py) up
    to one query-level dict — the wall-clock attribution bench rounds read."""
    agg: Dict[str, float] = {}
    for d in drivers:
        for op in d.operators:
            fn = getattr(op, "pipeline_stats", None)
            s = fn() if fn is not None else None
            if not s:
                continue
            for k, v in s.items():
                agg[k] = round(agg.get(k, 0) + v, 6)
    return agg or None


def _segment_stats(exec_plan) -> Optional[dict]:
    """Fused-segment observability (ops/fused_segment.py): the compiler's
    fusion decisions plus per-segment dispatch/compile counts, rolled into
    QueryResult.stats["segments"]."""
    from .ops.fused_segment import FusedSegmentOperatorFactory

    segs = []
    dispatches = compiles = 0
    for pi, chain in enumerate(exec_plan.pipelines):
        for fac in chain:
            if isinstance(fac, FusedSegmentOperatorFactory):
                d = fac.describe()
                d["pipeline"] = pi
                segs.append(d)
                dispatches += d["dispatches"]
                compiles += d["compiles"]
    if not segs and not exec_plan.segment_decisions:
        return None
    return {"count": len(segs), "dispatches": dispatches,
            "compiles": compiles, "segments": segs,
            "decisions": exec_plan.segment_decisions}


class LocalQueryRunner:
    """In-process engine instance bound to a catalog registry."""

    def __init__(self, session: Optional[Session] = None,
                 catalogs: Optional[CatalogManager] = None,
                 page_capacity: Optional[int] = None):
        # page_capacity None = platform default, resolved LAZILY at local
        # planning (metadata.default_page_capacity) — the constructor must
        # not touch the jax backend: metadata/DDL-only callers never need
        # the device (and a process that has touched it holds the chip)
        if catalogs is None:
            catalogs = CatalogManager()
            catalogs.register("tpch", TpchConnector("tpch"))
            # the same tables kept as columnar files and read on every
            # query (a directory of its own, made on the first write)
            catalogs.register("tpch_files",
                              StoredTpchConnector("tpch_files"))
            from .connectors.tpcds import TpcdsConnector
            catalogs.register("tpcds", TpcdsConnector("tpcds"))
            from .connectors.memory import MemoryConnector
            catalogs.register("memory", MemoryConnector("memory"))
            from .connectors.blackhole import BlackholeConnector
            catalogs.register("blackhole", BlackholeConnector("blackhole"))
        self.catalogs = catalogs
        self.metadata = MetadataManager(catalogs)
        self.session = session or Session(catalog="tpch", schema="tiny")
        if page_capacity is not None and \
                "page_capacity" not in self.session.properties:
            self.session = self.session.with_properties(
                page_capacity=page_capacity)
        self.parser = SqlParser()
        # bucket count of the last grouped (lifespan) execution, None if the
        # last query ran ungrouped — observability for tests and EXPLAIN
        self.last_grouped: Optional[int] = None

    # ------------------------------------------------------------------ api

    def plan_sql(self, sql: str) -> OutputNode:
        stmt = self.parser.parse(sql)
        if not isinstance(stmt, t.Query):
            raise ValueError(f"cannot plan {type(stmt).__name__}")
        return self.plan_statement(stmt)

    def plan_statement(self, stmt: t.Query) -> OutputNode:
        planner = LogicalPlanner(self.metadata, self.session)
        plan = planner.plan(stmt)
        return optimize(plan, self.metadata, self.session)

    def explain(self, sql: str) -> str:
        return plan_to_text(self.plan_sql(sql))

    # access control: optional AccessControl attached by the server layer
    # (security/AccessControlManager.java checkCanSelectFromColumns analogue —
    # every referenced table is checked before planning; DDL/DML check their
    # write privilege)
    access_control = None

    def _check_access(self, stmt, user: "Optional[str]" = None) -> None:
        ac = self.access_control
        if ac is None:
            return
        from .sql.analyzer import _ast_children

        user = user if user is not None else self.session.user

        def resolve(name_parts):
            qname = self.metadata.resolve_table_name(
                self.session, tuple(p.lower() for p in name_parts))
            return qname

        def walk(node, cte_names=frozenset()):
            if isinstance(node, t.Query) and node.with_ is not None:
                names = set(cte_names)
                for cte_name, cte_query in node.with_.queries:
                    walk(cte_query, frozenset(names))  # body checked too
                    names.add(cte_name.lower())
                walk(node.body, frozenset(names))
                for c in _ast_children(node):
                    if c is not node.body and c is not node.with_:
                        walk(c, frozenset(names))
                return
            if isinstance(node, t.Table):
                # single-part names matching an in-scope CTE are not tables
                if len(node.name) == 1 and node.name[0].lower() in cte_names:
                    return
                q = resolve(node.name)
                ac.check_can_select(user, q.catalog, q.schema, q.table)
                return
            for c in _ast_children(node):
                walk(c, cte_names)

        if isinstance(stmt, t.CreateTableAsSelect):
            q = resolve(stmt.name)
            ac.check_can_write(user, q.catalog, q.schema, q.table, "create")
            walk(stmt.query)
        elif isinstance(stmt, t.Insert):
            q = resolve(stmt.name)
            ac.check_can_write(user, q.catalog, q.schema, q.table, "insert")
            if stmt.query is not None:
                walk(stmt.query)
        elif isinstance(stmt, t.DropTable):
            q = resolve(stmt.name)
            ac.check_can_write(user, q.catalog, q.schema, q.table, "drop")
        else:
            walk(stmt)

    def execute(self, sql: str, user: Optional[str] = None) -> QueryResult:
        """Public entry: runs the statement under the per-query flight
        recorder — a FULL one when `query_trace` is on or a profile is being
        taken, else the always-on coarse black-box ring — and histograms
        the wall and the four phases either way (`query.wall_s`,
        `query.parse_s` ... p50/p95/p99 at /v1/metrics). A failing statement
        dumps the ring as a forensic trace pinned to the exception."""
        with trace.QueryScope(self.session) as scope:
            result = self._execute_statement(sql, user)
        return scope.finish(result)

    def _execute_statement(self, sql: str,
                           user: Optional[str] = None) -> QueryResult:
        self.last_grouped = None  # set again on the grouped query path
        with trace.phase("parse"):
            stmt = self.parser.parse(sql)
        self._check_access(stmt, user)
        if isinstance(stmt, t.Explain):
            inner = stmt.statement
            if not isinstance(inner, t.Query):
                raise ValueError("EXPLAIN requires a query")
            if stmt.analyze:
                text = self._explain_analyze(inner)
            else:
                text = plan_to_text(self.plan_statement(inner))
            return QueryResult([[line] for line in text.split("\n")],
                               ["Query Plan"])
        if isinstance(stmt, t.ShowTables):
            catalog, schema = self.session.catalog, self.session.schema
            if stmt.schema:  # FROM [catalog.]schema
                parts = tuple(stmt.schema)
                if len(parts) == 2:
                    catalog, schema = parts
                elif len(parts) == 1:
                    schema = parts[0]
                else:
                    raise ValueError("SHOW TABLES FROM takes [catalog.]schema")
            conn = self.metadata.connector(catalog)
            tables = conn.metadata().list_tables(schema)
            return QueryResult([[st.table] for st in tables], ["Table"])
        if isinstance(stmt, t.ShowSchemas):
            conn = self.metadata.connector(self.session.catalog)
            return QueryResult([[s] for s in conn.metadata().list_schemas()],
                               ["Schema"])
        if isinstance(stmt, t.ShowColumns):
            qname = self.metadata.resolve_table_name(
                self.session, tuple(p.lower() for p in stmt.table))
            handle = self.metadata.get_table_handle(self.session, qname)
            if handle is None:
                raise ValueError(f"table {qname} does not exist")
            meta = self.metadata.get_table_metadata(handle)
            return QueryResult([[c.name, c.type.name] for c in meta.columns],
                               ["Column", "Type"])
        if isinstance(stmt, (t.CreateTableAsSelect, t.Insert, t.DropTable)):
            return self._execute_write(stmt)
        if not isinstance(stmt, t.Query):
            raise ValueError(f"unsupported statement {type(stmt).__name__}")

        with trace.phase("plan"):
            plan = self.plan_statement(stmt)

        # grouped (lifespan) execution: co-bucketed scans run one bucket at
        # a time so join/agg device state is bounded by a single bucket
        from .exec.grouped import analyze_grouped, merge_rows
        g = analyze_grouped(plan, self.metadata, self.session)
        if g is not None:
            self.last_grouped = g.bucket_count
            results, names, types = [], None, None
            scan_stats: Dict[str, float] = {}
            seg_stats: Optional[dict] = None
            for b in range(g.bucket_count):
                exec_plan, drivers, _w = self._run_plan(plan, bucket_filter=b)
                results.append(exec_plan.sink.rows())
                names = exec_plan.output_names
                types = exec_plan.output_types
                for k, v in (_scan_pipeline_stats(drivers) or {}).items():
                    scan_stats[k] = round(scan_stats.get(k, 0) + v, 6)
                s = _segment_stats(exec_plan)
                if s is not None:
                    if seg_stats is None:
                        seg_stats = s
                    else:  # sum counters across buckets, keep one decision set
                        for k in ("count", "dispatches", "compiles"):
                            seg_stats[k] += s[k]
                        seg_stats["segments"].extend(s["segments"])
            stats = {}
            if scan_stats:
                stats["scan_pipeline"] = scan_stats
            if seg_stats is not None:
                stats["segments"] = seg_stats
            return QueryResult(merge_rows(results, g), names, types,
                               stats=stats or None)

        exec_plan, drivers, _wall = self._run_plan(plan)
        scan = _scan_pipeline_stats(drivers)
        seg = _segment_stats(exec_plan)
        stats = {}
        if scan:
            stats["scan_pipeline"] = scan
        if seg is not None:
            stats["segments"] = seg
        # the answer's pages fetched from the device and turned into rows:
        # one blocking copy a block (0.4-0.7 ms each on a v5e, PERF.md)
        with trace.span(trace.LIFECYCLE, "result"):
            rows = exec_plan.sink.rows()
        return QueryResult(rows, exec_plan.output_names,
                           exec_plan.output_types, stats=stats or None)

    def _execute_write(self, stmt) -> QueryResult:
        """CTAS / INSERT / DROP: plan the source query, swap the result sink
        for TableWriter operators feeding the connector's page sink, commit
        the written fragments (TableWriterOperator + TableFinishOperator
        flow, with the commit in the coordinator as the reference does)."""
        from .ops.writer import TableWriterOperatorFactory
        from .spi.connector import ColumnMetadata, SchemaTableName, TableMetadata
        from .utils.testing import PageConsumerFactory

        qname = self.metadata.resolve_table_name(
            self.session, tuple(p.lower() for p in stmt.name))
        conn = self.metadata.connector(qname.catalog)
        meta = conn.metadata()
        name = SchemaTableName(qname.schema, qname.table)
        handle = meta.get_table_handle(name)

        if isinstance(stmt, t.DropTable):
            if handle is None:
                if stmt.exists_ok:
                    return QueryResult([[0]], ["rows"], [BIGINT])
                raise ValueError(f"table {qname} does not exist")
            meta.drop_table(handle)
            return QueryResult([[0]], ["rows"], [BIGINT])

        # source plan first: its physical output schema defines/validates the
        # target columns
        plan = self.plan_statement(stmt.query)
        local = LocalExecutionPlanner(self.metadata, self.session)
        mem, over_target, mem_release = self._query_memory()
        local.attach_memory(mem, over_target)
        exec_plan = local.plan(plan)

        from .types import ArrayType, MapType
        for n, tt in zip(exec_plan.output_names, exec_plan.output_types):
            if isinstance(tt, (ArrayType, MapType)):
                # handles index a query-lifetime host store; persisting
                # them would write dangling int32s (no file format here
                # serializes ragged values yet)
                raise ValueError(
                    f"column {n}: {tt.name} values cannot be persisted "
                    f"(array_agg/map_agg outputs are query-scoped)")

        created = False
        if isinstance(stmt, t.CreateTableAsSelect):
            if handle is not None:
                if stmt.not_exists:
                    return QueryResult([[0]], ["rows"], [BIGINT])
                raise ValueError(f"table {qname} already exists")
            if len(set(exec_plan.output_names)) != len(exec_plan.output_names):
                raise ValueError(
                    f"CTAS output has duplicate column names: "
                    f"{exec_plan.output_names}")
            # materialized dictionaries are COPIED so the table owns them:
            # later INSERTs can extend a private dictionary but must never
            # mutate one shared with a source connector
            from .block import Dictionary as _Dict
            cols = tuple(
                ColumnMetadata(n, tt, dictionary=(
                    _Dict(list(d.values)) if d is not None and
                    hasattr(d, "values") else d))
                for n, tt, d in zip(exec_plan.output_names,
                                    exec_plan.output_types,
                                    exec_plan.output_dicts))
            props = dict(stmt.properties)
            if props:
                meta.create_table(TableMetadata(name, cols), properties=props)
            else:
                meta.create_table(TableMetadata(name, cols))
            handle = meta.get_table_handle(name)
            created = True
        else:  # INSERT
            if handle is None:
                raise ValueError(f"table {qname} does not exist")
            target = meta.get_table_metadata(handle)
            tcols = [c for c in target.columns]
            if stmt.columns and list(stmt.columns) != [c.name for c in tcols]:
                raise ValueError("INSERT column list must match the table "
                                 "schema (partial inserts not supported)")
            if len(tcols) != len(exec_plan.output_types):
                raise ValueError(
                    f"INSERT has {len(exec_plan.output_types)} columns, "
                    f"table {qname} has {len(tcols)}")
            remaps: List[Optional[object]] = []
            casts: List[Optional[object]] = []
            from .types import UNKNOWN as _UNKNOWN
            for c, st, sd in zip(tcols, exec_plan.output_types,
                                 exec_plan.output_dicts):
                if st is _UNKNOWN or st.name == "unknown":
                    # typeless NULL literal column: retype to the table column
                    # at write time (writer cast), nulls ride along
                    casts.append(c.type)
                    remaps.append(None)
                    continue
                casts.append(None)
                if c.type.name != st.name:
                    raise ValueError(
                        f"INSERT type mismatch on {c.name}: {st.name} vs "
                        f"{c.type.name}")
                if c.dictionary is None or sd is c.dictionary:
                    remaps.append(None)
                    continue
                # re-encode source codes into the table's private dictionary,
                # extending it for values it has not seen
                if sd is None or not hasattr(c.dictionary, "values"):
                    raise ValueError(
                        f"INSERT into dictionary column {c.name} requires a "
                        "materialized target dictionary")
                import numpy as _np
                tgt = c.dictionary
                if not hasattr(sd, "values"):
                    # virtual source (formatted/packed): value-level re-encode
                    remaps.append(_virtual_remap(sd, tgt))
                    continue
                remaps.append(_np.asarray(
                    tgt.extend([str(v) for v in sd.values]), dtype=_np.int32))

        sink_provider = conn.page_sink_provider()
        if sink_provider is None:
            raise ValueError(f"catalog {qname.catalog} is not writable")
        insert_handle = meta.begin_insert(handle)
        is_insert = isinstance(stmt, t.Insert)
        if is_insert and any(r is not None for r in remaps):
            # INSERT re-encodes into the table's dictionaries; CTAS pages keep
            # their source dictionaries (codes match the copies by construction,
            # and file sinks materialize virtual dictionaries from the blocks)
            target_meta = meta.get_table_metadata(handle)
            column_dicts = [c.dictionary for c in target_meta.columns]
            writer_fac = TableWriterOperatorFactory(
                9000, sink_provider, insert_handle,
                remaps=remaps, column_dicts=column_dicts, casts=casts)
        elif is_insert and any(c is not None for c in casts):
            writer_fac = TableWriterOperatorFactory(
                9000, sink_provider, insert_handle, casts=casts)
        else:
            writer_fac = TableWriterOperatorFactory(9000, sink_provider,
                                                    insert_handle)
        count_sink = PageConsumerFactory(9001, [BIGINT])
        # scaled writers (reference parallelism axis #9,
        # execution/scheduler/ScaledWriterScheduler.java narrowed to the
        # local tier): a large source fans out over K parallel writer
        # drivers behind a local exchange, each with its own sink file —
        # small writes keep ONE writer so they don't shatter into K files
        n_writers = self._scaled_writer_count(plan)
        if n_writers > 1:
            from .ops.local_exchange import (LocalExchangeFactory,
                                             LocalExchangeSinkFactory,
                                             LocalExchangeSourceFactory)
            # pages are DEALT round-robin over the writers: every writer
            # must get a share (and write a file) no matter how fast the
            # scan pipeline bursts pages into the buffer
            lx = LocalExchangeFactory(n_producers=1,
                                      max_pages=2 * n_writers + 2,
                                      deal_slots=n_writers)
            exec_plan.pipelines[-1] = exec_plan.pipelines[-1][:-1] + \
                [LocalExchangeSinkFactory(9002, lx, [])]
            for _ in range(n_writers):
                exec_plan.pipelines.append(
                    [LocalExchangeSourceFactory(9003, lx, []),
                     writer_fac, count_sink])
        else:
            # swap the result consumer for writer -> row-count consumer
            exec_plan.pipelines[-1] = exec_plan.pipelines[-1][:-1] + \
                [writer_fac, count_sink]
        drivers = exec_plan.create_drivers()
        try:
            TaskExecutor(
                int(self.session.get("task_concurrency"))).execute(drivers)
        except BaseException:
            for d in drivers:
                try:
                    d.close()
                except Exception:  # noqa: BLE001 - teardown best effort
                    pass
            for s in writer_fac.sinks:
                s.abort()
            if created:  # CTAS is atomic: roll the metadata back on failure
                meta.drop_table(handle)
            raise
        finally:
            mem_release()
        fragments = [p for s in writer_fac.sinks for p in s.finish()]
        meta.finish_insert(insert_handle, fragments)
        total = sum(r[0] for r in count_sink.rows())
        return QueryResult([[total]], ["rows"], [BIGINT])

    def _scaled_writer_count(self, plan: OutputNode) -> int:
        """K parallel writer drivers when the write is big enough that K
        sink files each stay above writer_min_rows_per_driver."""
        if not self.session.get("scaled_writers"):
            return 1
        try:
            from .sql.planner.optimizer import estimate_rows
            est = estimate_rows(plan.source, self.metadata)
        except Exception:
            return 1
        per_driver = int(self.session.get("writer_min_rows_per_driver"))
        cap = int(self.session.get("task_concurrency"))
        return max(1, min(cap, int(est // max(per_driver, 1))))

    def _run_plan(self, plan: OutputNode, bucket_filter=None):
        """Shared execution recipe: local planning + memory wiring + task
        executor. Both execute() and EXPLAIN ANALYZE go through here so the
        profile always measures the pipeline the query actually runs."""
        import time as _time

        mem, over_target, release = self._query_memory()
        unregister = lambda: None  # noqa: E731 - rebound below
        try:
            with trace.phase("local_plan"):
                local = LocalExecutionPlanner(self.metadata, self.session,
                                              bucket_filter=bucket_filter)
                local.attach_memory(mem, over_target)
                exec_plan = local.plan(plan)
                drivers = exec_plan.create_drivers()
            # live progress (exec/progress.py): while the drivers run, the
            # protocol layer can serve their per-operator counters at
            # GET /v1/query/{id} — registration is a no-op outside a
            # query_scope (engine used directly, no HTTP)
            from .exec import progress as _progress
            from .exec.explain import driver_stats as _dstats

            def _live() -> dict:
                return {"operators": _dstats(drivers),
                        "memory_reserved_bytes": mem.total_bytes(),
                        "pool_steps": _pool_steps(local.pool_key)}
            unregister = _progress.register(_live)
            t0 = _time.perf_counter()
            # task executor: build/probe pipelines overlap on runner threads
            # (blocked probes park until their lookup slot resolves)
            try:
                with trace.phase("execute"):
                    TaskExecutor(
                        int(self.session.get("task_concurrency"))
                    ).execute(drivers)
            except BaseException:
                # abandoned drivers' pipelines must tear down BEFORE the
                # query's reservations are cleared from the shared pool, or
                # a still-running stage would re-reserve phantom bytes that
                # outlive the query (the pool is process-shared now)
                for d in drivers:
                    try:
                        d.close()
                    except Exception:  # noqa: BLE001 - teardown best effort
                        pass
                raise
            return exec_plan, drivers, _time.perf_counter() - t0
        finally:
            unregister()
            release()

    def _explain_analyze(self, stmt: t.Query) -> str:
        """EXPLAIN ANALYZE: execute, then render the plan with per-operator
        rows/time/blocked/memory (ExplainAnalyzeOperator.java analogue —
        the stats roll up from each driver's OperatorContext after the run;
        the mesh and cluster runners render the same table per fragment via
        exec/explain.py). Prints the stats the engine tracks but never
        showed before: per-operator blocked time and the fused-segment
        compile/dispatch breakdown."""
        from .exec.explain import driver_stats, table

        plan = self.plan_statement(stmt)
        exec_plan, drivers, wall = self._run_plan(plan)
        lines = [f"Query: {wall * 1000:.0f}ms wall, "
                 f"{len(drivers)} drivers, "
                 f"{sum(len(d.operators) for d in drivers)} operators", ""]
        lines += table(driver_stats(drivers), pipelines=True)
        seg = _segment_stats(exec_plan)
        if seg:
            lines += ["", f"fused segments: {seg['count']} fused, "
                          f"{seg['dispatches']} dispatches, "
                          f"{seg['compiles']} compiles"]
            for s in seg["segments"]:
                lines.append(
                    f"  pipeline {s['pipeline']}: "
                    f"{'+'.join(s['operators'])} "
                    f"({s['dispatches']} dispatches, "
                    f"{s['compiles']} compiles)")
        scan = _scan_pipeline_stats(drivers)
        if scan:
            lines += ["", "scan pipeline: " +
                      ", ".join(f"{k}={scan[k]}" for k in sorted(scan))]
        lines += ["", plan_to_text(plan)]
        return "\n".join(lines)

    def _query_memory(self):
        """Per-query memory root drawing on the process-SHARED general pool
        (memory.shared_general_pool): concurrent tenants' operator state,
        scan prefetch and exchange in-flight bytes all compete in one
        accounting surface. Returns (memory, over_target, release): the
        probe fires when the POOL (all tenants) crosses the revoke target —
        OR when this query alone crosses the target fraction of its
        session's `memory_pool_bytes`, since the shared pool is grow-only
        and a tenant configuring a small budget must still get pressure
        revocation even while the process pool has room; `release` clears
        this query's reservations at end of query so failed teardowns never
        leak phantom pressure into later tenants.

        The query's disk tier rides along as `memory.spill` (a
        SpillManager, or None when `spill_to_disk` is off): attach_memory
        lifts it into the factories, and `release` closes it — spill files
        are deleted and their ledger bytes freed in the same ``finally``
        that clears the RAM reservations."""
        from .exec.spill import SpillManager
        from .memory import QueryContextMemory, shared_general_pool

        session_bytes = int(self.session.get("memory_pool_bytes"))
        pool = shared_general_pool(session_bytes)
        qid = f"query-{next(_QUERY_MEM_SEQ)}"
        qmem = QueryContextMemory(
            qid, pool, int(self.session.get("query_max_memory_bytes")))
        target = float(self.session.get("revoke_target_fraction"))
        spill = None
        if bool(self.session.get("spill_to_disk")):
            spill = SpillManager(
                qid, pool, spill_dir=str(self.session.get("spill_dir") or ""),
                max_bytes=int(self.session.get("spill_max_bytes") or 0))
        qmem.memory.spill = spill

        def over_target() -> bool:
            return (pool.reserved_bytes() > pool.max_bytes * target
                    or pool.query_bytes(qid) > session_bytes * target)

        def release() -> None:
            if spill is not None:
                spill.close()
            pool.clear_query(qid)
        return qmem.memory, over_target, release
