"""Engine metadata layer: sessions, catalogs, and the metadata manager.

Analogue of presto-main's metadata/MetadataManager.java (fronting per-catalog
connector metadata), metadata/CatalogManager, and Session.java:56. Narrowed to what
the analyzer/planner need: qualified-name resolution to table handles, column
enumeration, and statistics for the cost-based join ordering.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from .spi.connector import (ColumnHandle, Connector, Constraint, SchemaTableName,
                            TableHandle, TableMetadata, TableStatistics)


@dataclasses.dataclass
class Session:
    """Session.java:56 — per-query context (user, catalog/schema defaults,
    system + per-catalog session properties, SystemSessionProperties.java:54)."""

    user: str = "user"
    catalog: Optional[str] = None
    schema: Optional[str] = None
    properties: Dict[str, object] = dataclasses.field(default_factory=dict)

    # engine defaults (the SystemSessionProperties subset that matters here)
    DEFAULTS = {
        # None = platform default (default_page_capacity), resolved only
        # when execution actually needs the backend; the mesh runner holds
        # the same default to streaming_exchange.MESH_PAGE_ROWS
        "page_capacity": None,
        "task_concurrency": 4,
        # intra-pipeline driver parallelism: AUTO = task_concurrency on
        # accelerators, 1 on the CPU backend (XLA-CPU already uses all cores);
        # an integer forces that many drivers per eligible pipeline
        "driver_parallelism": "AUTO",
        "join_distribution_type": "AUTOMATIC",   # BROADCAST | PARTITIONED | AUTOMATIC
        # AUTOMATIC broadcasts a build side whose estimated row count is below
        # this (join-distribution CBO; the reference bounds replicated size via
        # join_max_broadcast_table_size)
        "broadcast_join_threshold_rows": 1 << 15,
        "max_groups": 1 << 20,
        # memory/spill (advisory accounting over XLA's allocator). Under
        # pressure, revocation walks the full ladder: device HBM -> host RAM
        # -> disk (exec/spill.py writes PCOL runs; the reference's
        # FileSingleStreamSpiller). OOM kill is the LAST rung, after the
        # ladder has been attempted.
        "memory_pool_bytes": 8 << 30,
        "query_max_memory_bytes": 4 << 30,
        "revoke_target_fraction": 0.9,
        # disk tier: on by default; spill_dir "" = <tempdir>/presto-tpu-spill;
        # spill_max_bytes 0 = unlimited on-disk bytes per query
        "spill_to_disk": True,
        "spill_dir": "",
        "spill_max_bytes": 0,
        # grouped (lifespan) execution over co-bucketed tables: run the plan
        # once per bucket so join/agg state is bounded by one bucket's data
        # (execution/Lifespan.java + StageExecutionDescriptor analogue)
        "grouped_execution": True,
        # scaled writers: INSERT/CTAS fan out over K parallel writer drivers
        # (one sink file each) when the source is at least K * this many rows
        "scaled_writers": True,
        "writer_min_rows_per_driver": 1 << 20,
        # fuse maximal runs of page-local operators (filter/project -> join
        # probe -> partial hash-agg / TopN contribution) into ONE jitted
        # dispatch per page (ops/fused_segment.py). False = per-operator
        # dispatches — the differential-testing oracle
        "segment_fusion": True,
        # --- streaming scan pipeline (ops/scan_pipeline.py) ---
        # staged host->HBM ingest: split-parallel readers -> ordered
        # re-batch into device-shaped pages -> async upload. False =
        # single-reader passthrough (pages keep their source shapes)
        "scan_pipeline": True,
        # reader pool size per scan driver; 0 = engine default
        # (scan_pipeline.DEFAULT_READER_THREADS: min(8, host cores))
        "scan_reader_threads": 0,
        # re-batched page rows; 0 = the session page_capacity (canonical
        # device shape: kernels see ONE large static shape per schema)
        "scan_target_page_rows": 0,
        # in-flight byte bound per scan, applied to BOTH the decoded host
        # staging and the uploaded-but-unconsumed device pages — bounding
        # bytes (not page count) lets prefetch depth adapt to page size;
        # 0 = engine default (scan_pipeline.DEFAULT_PREFETCH_BYTES, 256MB)
        "scan_prefetch_bytes": 0,
        # --- streaming mesh exchange (parallel/streaming_exchange.py) ---
        # fixed-capacity chunks stream through the inter-fragment collectives
        # while producer drivers still run (producer/consumer fragments share
        # one task executor)
        # per-worker chunk capacity in rows (pow2-rounded); 0 = DERIVED by
        # each exchange, when it is built, from the page capacity of its
        # producing fragment and the in-flight bound
        # (streaming_exchange.derive_chunk_rows: the page's pow2, two chunks
        # a side inside exchange_inflight_bytes, at least 4096), so a page
        # that fits its chunk is never split. A value is the tests'
        # override (pages longer than the chunk: the leftover path). The
        # chunk shape is FIXED per stream, so each exchange compiles ONE
        # collective program per shape instead of one per pow2 volume
        "exchange_chunk_rows": 0,
        # in-flight byte bound per exchange: producer sinks park (BLOCKED)
        # while staged + undelivered bytes exceed it — no stage ever holds a
        # full intermediate result; 0 = engine default
        # (streaming_exchange.DEFAULT_INFLIGHT_BYTES, 256MB)
        "exchange_inflight_bytes": 0,
        # skew-aware repartitioning for partitioned INNER joins: the
        # build-side exchange samples its first chunk for heavy-hitter
        # keys, SPLITS hot build rows round-robin across partitions
        # and the probe-side exchange REPLICATES matching probe rows to all
        # partitions — a 99%-one-key join spreads across the mesh instead of
        # landing on one chip (carry-over already made it *correct*; this
        # makes it *parallel*). Per-partition delivered-row counts surface
        # in QueryResult.stats["exchange"]. False = hash-only routing.
        "skew_aware_exchange": True,
        # --- multi-tenant serving (exec/shared_pools.py) ---
        # run scan-pipeline stages and exchange pumps on the process-wide
        # shared worker pools with per-query round-robin fairness, so N
        # concurrent queries cost O(pool) threads instead of O(N * stages).
        # Pool sizes are fixed once per process (PRESTO_TPU_SCAN_POOL_THREADS
        # / PRESTO_TPU_EXCHANGE_POOL_THREADS env knobs). False = per-query
        # dedicated stage threads — the differential-testing oracle
        "shared_pools": True,
        # --- observability: per-query flight recorder (utils/trace.py) ---
        # record spans across every engine layer (lifecycle, driver quanta,
        # operators, fused segments, scan stages, exchange chunks, cluster
        # HTTP) and export Chrome trace-event JSON readable in Perfetto /
        # chrome://tracing; the path lands in QueryResult.trace_path and is
        # served at GET /v1/query/{id}/trace. Near-zero cost when False.
        "query_trace": False,
        # export directory for trace files; "" = the platform tempdir
        "query_trace_dir": "",
        # always-on black-box recorder: every query keeps a small COARSE
        # span ring (driver quanta, exchange chunks, scan stage stalls,
        # pool steps, kernel builds, cluster HTTP — per-page operator spans
        # dropped at the source) so a FAILED / OOM-killed / retry-exhausted
        # query dumps a forensic Chrome trace it never opted into
        # (QueryInfo.failure_trace_path, GET /v1/query/{id}/trace). False =
        # recorder compiled out
        "query_blackbox": True,
        # --- cluster fault tolerance (cluster/retry.py) ---
        # NONE fails fast; QUERY re-plans + re-runs the whole query on
        # retryable failures (failed nodes excluded from placement); TASK
        # additionally re-places failed task creates and recovers failed
        # leaf tasks in place
        "retry_policy": "NONE",
        "query_retry_attempts": 2,      # extra attempts after the first
        "task_retry_attempts": 2,       # in-place recoveries per task (TASK)
        "retry_initial_delay_s": 0.1,   # jittered-exponential backoff floor
        "retry_max_delay_s": 2.0,       # ... and ceiling
        # transient-failure budget for one remote-task create
        "remote_task_error_budget_s": 10.0,
        # transient-failure budget before an exchange source is declared dead
        "exchange_error_budget_s": 60.0,
        # deterministic fault-injection spec (cluster/faults.py); "" = off
        "fault_injection": "",
        "fault_seed": 0,
        # per-task bound on the acked-frame replay spool (cluster/buffers.py);
        # spooled bytes are reserved in the shared pool under the query id.
        # 0 disables spooling — mid-stream TASK recovery then escalates
        # loudly to a query-level retry (ReplayWindowLost / HTTP 410)
        "exchange_spool_bytes": 64 << 20,
        # rows a sink accumulates per partition before flushing one exchange
        # frame (= one replayable chunk); None = the 16k built-in. Small
        # values force many-chunk streams (chaos tests, latency-sensitive
        # pipelines), large values amortize serialization
        "exchange_flush_rows": None,
        # --- straggler speculation (cluster/scheduler.py) ---
        # launch a duplicate of a straggling task on another node; the first
        # copy to FINISH wins (its consumers rewire from their chunk
        # cursors), the loser is aborted and journaled `task.speculated`
        "speculative_execution": False,
        "speculation_min_wall_s": 5.0,   # never speculate younger tasks
        # straggler = running wall > multiplier x median FINISHED sibling wall
        "speculation_multiplier": 2.0,
    }

    def get(self, name: str, default=None):
        if name in self.properties:
            return self.properties[name]
        if name in self.DEFAULTS:
            return self.DEFAULTS[name]
        return default

    def with_properties(self, **kw) -> "Session":
        props = dict(self.properties)
        props.update(kw)
        return dataclasses.replace(self, properties=props)


def default_page_capacity() -> int:
    """Platform default page size, resolved at execution time. Pages are the
    unit of dispatch: on an accelerator every page costs host-side dispatch
    work per operator, so pages are sized to make the page COUNT small (a
    scan's pages are further bounded by its splits: SF1 lineitem arrives as
    8 x 1M-row pages, not 23 x 256k). XLA-CPU prefers cache-sized batches
    (256k)."""
    import jax

    return (1 << 22) if jax.default_backend() != "cpu" else (1 << 18)


@dataclasses.dataclass(frozen=True)
class QualifiedObjectName:
    catalog: str
    schema: str
    table: str

    def __str__(self):
        return f"{self.catalog}.{self.schema}.{self.table}"


class CatalogManager:
    """metadata/CatalogManager — registered connectors by catalog name."""

    def __init__(self):
        self._catalogs: Dict[str, Connector] = {}

    def register(self, name: str, connector: Connector) -> None:
        self._catalogs[name] = connector

    def get(self, name: str) -> Optional[Connector]:
        return self._catalogs.get(name)

    def names(self) -> List[str]:
        return list(self._catalogs)


class MetadataManager:
    """metadata/MetadataManager.java — engine-facing metadata fronting connectors."""

    def __init__(self, catalogs: CatalogManager):
        self.catalogs = catalogs

    def resolve_table_name(self, session: Session,
                           parts: Sequence[str]) -> QualifiedObjectName:
        """tree.Table name -> fully qualified, filling session defaults
        (metadata/MetadataUtil.createQualifiedObjectName analogue)."""
        parts = list(parts)
        if len(parts) == 1:
            if not session.catalog or not session.schema:
                raise ValueError(f"table '{parts[0]}' requires session catalog/schema")
            return QualifiedObjectName(session.catalog, session.schema, parts[0])
        if len(parts) == 2:
            if not session.catalog:
                raise ValueError(f"table '{'.'.join(parts)}' requires session catalog")
            return QualifiedObjectName(session.catalog, parts[0], parts[1])
        if len(parts) == 3:
            return QualifiedObjectName(*parts)
        raise ValueError(f"invalid table name {'.'.join(parts)}")

    def get_table_handle(self, session: Session,
                         name: QualifiedObjectName) -> Optional[TableHandle]:
        conn = self.catalogs.get(name.catalog)
        if conn is None:
            return None
        return conn.metadata().get_table_handle(SchemaTableName(name.schema, name.table))

    def get_table_metadata(self, table: TableHandle) -> TableMetadata:
        return self._connector(table).metadata().get_table_metadata(table)

    def get_column_handles(self, table: TableHandle) -> Dict[str, ColumnHandle]:
        return self._connector(table).metadata().get_column_handles(table)

    def get_table_statistics(self, table: TableHandle,
                             constraint: Constraint = Constraint.all()) -> TableStatistics:
        return self._connector(table).metadata().get_table_statistics(table, constraint)

    def connector(self, catalog: str) -> Connector:
        conn = self.catalogs.get(catalog)
        if conn is None:
            raise KeyError(f"unknown catalog {catalog}")
        return conn

    def _connector(self, table: TableHandle) -> Connector:
        return self.connector(table.connector_id)
