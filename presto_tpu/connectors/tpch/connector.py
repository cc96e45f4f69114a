"""TPC-H connector: SPI implementation over the deterministic generator.

Analogue of presto-tpch (tpch/TpchConnectorFactory.java:32, TpchMetadata,
TpchSplitManager.java:45, TpchRecordSet). Schemas are scale factors: `tiny` (0.01),
`sf1`, `sf10`, `sf100`, ... and, as TpchMetadata.schemaNameToScaleFactor has it, any
`sf<number>` (`sf4`, `sf0.5`, `sf1.0`). Splits are contiguous row ranges (order ranges for
lineitem) so every worker/chip generates its shard locally — the TPU analogue of
split-at-the-data scheduling (SOURCE_DISTRIBUTION).

Supports pushed-down partitioning on the primary key like the reference's
TpchNodePartitioningProvider, which lets co-partitioned scans skip the mesh exchange.

A STORED catalog (`tpch.storage-dir`, `StoredTpchConnector`): the reference's benchmark
suite runs over tables that generate_schemas/generate-tpch.py wrote once from this
connector as columnar files (tpch_sf300_orc); here that is one catalog. It answers
metadata and statistics as `tpch` does, writes a table's PCOL files through the file
connector's page sink on the first lookup of its handle, and reads them through the file
connector's split manager and page source on every scan: no `cache_token`, nothing of
the table resident between two queries.
"""
from __future__ import annotations

import atexit
import json
import math
import os
import re
import shutil
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

from ...utils.batching import clamp_capacity, take_rows

from ...block import Block, Page
from ...spi.connector import (ColumnHandle, ColumnMetadata, ColumnStatistics, Connector,
                              ConnectorFactory, ConnectorMetadata,
                              ConnectorNodePartitioningProvider, ConnectorPageSource,
                              ConnectorPageSourceProvider, ConnectorSplitManager,
                              Constraint, SchemaTableName, Split, TableHandle,
                              TableMetadata, TableStatistics)
from ...types import BIGINT
from ...utils import trace
from ...utils.metrics import METRICS
from ..file import FileConnector
from . import generator as g

SCHEMAS = {"tiny": 0.01, "sf1": 1.0, "sf10": 10.0, "sf100": 100.0, "sf300": 300.0,
           "sf1000": 1000.0}
_SF_SCHEMA = re.compile(r"sf(\d+(?:\.\d+)?)")


def schema_scale_factor(schema: str) -> Optional[float]:
    """The scale factor a schema name stands for, None where it names none: the listed
    schemas, and any `sf<number>` with a positive finite number (listed or not, as
    presto-tpch's schemaNameToScaleFactor reads them)."""
    if schema in SCHEMAS:
        return SCHEMAS[schema]
    number = _SF_SCHEMA.fullmatch(schema)
    sf = float(number.group(1)) if number else 0.0
    return sf if 0.0 < sf < math.inf else None


_TABLE_NAMES = ["region", "nation", "supplier", "part", "partsupp", "customer",
                "orders", "lineitem"]


def _columns_of(table: str):
    if table == "lineitem":
        return [(n, t, d) for (n, t, d) in g.LINEITEM_COLUMNS]
    t = g.TPCH_TABLES[table]
    return [(c.name, c.type, c.dictionary) for c in t.columns]


class TpchMetadata(ConnectorMetadata):
    def __init__(self, connector_id: str):
        self.connector_id = connector_id

    def list_schemas(self) -> List[str]:
        return list(SCHEMAS)

    def list_tables(self, schema: Optional[str] = None) -> List[SchemaTableName]:
        schemas = [schema] if schema else list(SCHEMAS)
        return [SchemaTableName(s, t) for s in schemas for t in _TABLE_NAMES]

    def get_table_handle(self, name: SchemaTableName) -> Optional[TableHandle]:
        sf = schema_scale_factor(name.schema)
        if sf is not None and name.table in _TABLE_NAMES:
            return TableHandle(self.connector_id, name, extra=(sf,))
        return None

    def get_table_metadata(self, table: TableHandle) -> TableMetadata:
        cols = tuple(ColumnMetadata(n, t, dictionary=d)
                     for (n, t, d) in _columns_of(table.schema_table.table))
        return TableMetadata(table.schema_table, cols)

    _UNIQUE_KEYS = {
        "region": [("r_regionkey",)],
        "nation": [("n_nationkey",)],
        "supplier": [("s_suppkey",)],
        "part": [("p_partkey",)],
        "partsupp": [("ps_partkey", "ps_suppkey")],
        "customer": [("c_custkey",)],
        "orders": [("o_orderkey",)],
        "lineitem": [("l_orderkey", "l_linenumber")],
    }

    def get_unique_column_sets(self, table: TableHandle):
        return list(self._UNIQUE_KEYS.get(table.schema_table.table, []))

    # clause 1.4.2's key relations: a foreign key holds values of the table
    # it references, so that table's row count is its distinct count.
    # l_orderkey (-> orders) is NOT here and keeps lineitem's own rows:
    # add_exchanges' broadcast choice reads it through estimate_rows' JoinNode
    # arm, and with orders' count the mesh cell's Q3 replicates customer
    # (PERF.md §7: the perf_opt that measures q3_sf1_mesh4 adds the line)
    _FOREIGN_KEYS = {
        "n_regionkey": "region",
        "s_nationkey": "nation", "c_nationkey": "nation",
        "ps_partkey": "part", "l_partkey": "part",
        "ps_suppkey": "supplier", "l_suppkey": "supplier",
        "o_custkey": "customer",
    }

    def get_table_statistics(self, table: TableHandle, constraint: Constraint) -> TableStatistics:
        name = table.schema_table.table
        sf = table.extra[0]
        rows = float(g.table_row_count(name, sf))
        stats = TableStatistics(row_count=rows)
        for (cname, ctype, cdict) in _columns_of(name):
            cs = ColumnStatistics(null_fraction=0.0)
            if cdict is not None and type(cdict).__name__ == "Dictionary":
                cs.distinct_count = float(len(cdict))
            elif cname.endswith("key"):
                cs.distinct_count = float(g.table_row_count(
                    self._FOREIGN_KEYS.get(cname, name), sf))
            stats.columns[cname] = cs
        return stats


class TpchSplitManager(ConnectorSplitManager):
    """Row-range splits; lineitem is split by order range (see generator docstring)."""

    def __init__(self, connector_id: str, splits_per_table: int = 8):
        self.connector_id = connector_id
        self.splits_per_table = splits_per_table

    def get_splits(self, table: TableHandle, constraint: Constraint,
                   desired_splits: int) -> List[Split]:
        name = table.schema_table.table
        sf = table.extra[0]
        if name == "lineitem":
            units = g.TPCH_TABLES["orders"].row_count(sf)  # split the order keyspace
        else:
            units = g.table_row_count(name, sf)
        n_splits = max(1, min(desired_splits or self.splits_per_table, units))
        step = math.ceil(units / n_splits)
        splits = []
        for b, lo in enumerate(range(0, units, step)):
            hi = min(lo + step, units)
            splits.append(Split(self.connector_id, payload=(name, sf, lo, hi), bucket=b))
        return splits


def _narrow_columns(table: str, sf: float, data: Dict[str, np.ndarray],
                    dicts: Dict[str, object]) -> Dict[str, np.ndarray]:
    """Downcast columns to their STATIC wire dtypes (generator.narrow_dtype).

    The scan widens back to the declared type ON DEVICE (ops/scan.py), so the
    narrow form only exists on the host→HBM wire — host→device bandwidth is the
    streaming-scan wall, and TPC-H's value domains shrink most int64 columns to
    1-4 bytes (discount/tax int8, dates/quantity int16, prices int32). The
    dtype is a function of (column, sf) only — never of observed chunk values —
    so every page of a scan shares one dtype signature (one XLA trace)."""
    out = {}
    for name, arr in data.items():
        dt = g.narrow_dtype(table, name, sf, dicts.get(name))
        if dt is None or arr.dtype.kind != "i" or arr.dtype.itemsize <= dt.itemsize:
            out[name] = arr
            continue
        narrowed = arr.astype(dt)
        # the static bounds are formula-derived; a violation is a generator or
        # bounds bug and must fail loudly, not silently corrupt query results
        if len(arr) and not np.array_equal(narrowed.astype(arr.dtype), arr):
            raise AssertionError(
                f"narrow bounds violated for {table}.{name} (sf={sf}): "
                f"values outside {dt}")
        out[name] = narrowed
    return out


class _GenCache:
    """Bounded, thread-safe LRU over generated (and narrowed) column chunks.

    The reference's benchmark harness scans in-memory pages (LocalQueryRunner);
    here warm scans re-slice cached host arrays instead of re-hashing the
    generator, which is ~10x slower than the device consuming its output.
    Generation runs OUTSIDE the lock (concurrent misses may generate the same
    chunk twice; last insert wins — correct either way)."""

    def __init__(self, max_bytes: int = 4 << 30):
        self.max_bytes = max_bytes
        self._data: "Dict[tuple, Dict[str, np.ndarray]]" = {}
        self._order: List[tuple] = []
        self._bytes = 0
        self._lock = threading.Lock()

    def get_or_generate(self, key: tuple, generate) -> Dict[str, np.ndarray]:
        with self._lock:
            hit = self._data.get(key)
            if hit is not None:
                self._order.remove(key)
                self._order.append(key)
                return hit
        data = generate()
        size = sum(a.nbytes for a in data.values())
        if size <= self.max_bytes:
            with self._lock:
                if key not in self._data:
                    while self._bytes + size > self.max_bytes and self._order:
                        old = self._order.pop(0)
                        self._bytes -= sum(
                            a.nbytes for a in self._data.pop(old).values())
                    self._data[key] = data
                    self._order.append(key)
                    self._bytes += size
        return data

    def clear(self):
        with self._lock:
            self._data.clear()
            self._order.clear()
            self._bytes = 0


GEN_CACHE = _GenCache()


class TpchPageSource(ConnectorPageSource):
    """Generates, narrows, caches, and re-batches column chunks into FULL pages
    (exactly `capacity` live rows except the last) — page fill drives both the
    upload efficiency and the per-page Python dispatch amortization.
    `wire=False` is the stored catalog's one pass over a table: the columns as
    the generator gives them (the sink writes declared widths), nothing cached."""

    def __init__(self, split: Split, columns: Sequence[ColumnHandle], page_capacity: int,
                 wire: bool = True):
        self.split = split
        self.columns = list(columns)
        name, _sf, lo, hi = split.payload
        est = (hi - lo) * 4 if name == "lineitem" else (hi - lo)
        self.capacity = clamp_capacity(est, page_capacity)
        self._wire = wire
        self._bytes = 0

    def _chunk(self, key: tuple, generate, dicts) -> Dict[str, np.ndarray]:
        if not self._wire:
            return generate()
        name, sf = key[0], key[1]
        return GEN_CACHE.get_or_generate(
            key, lambda: _narrow_columns(name, sf, generate(), dicts))

    def _chunks(self, names, dicts) -> Iterator[Dict[str, np.ndarray]]:
        name, sf, lo, hi = self.split.payload
        key_cols = tuple(sorted(names))
        if name == "lineitem":
            order_step = max(1, self.capacity // 4)  # ~capacity rows per chunk
            for olo in range(lo, hi, order_step):
                ohi = min(olo + order_step, hi)
                yield self._chunk(
                    ("lineitem", sf, olo, ohi, key_cols),
                    lambda: g.lineitem_for_orders(olo, ohi, sf, names), dicts)
        else:
            for rlo in range(lo, hi, self.capacity):
                rhi = min(rlo + self.capacity, hi)
                yield self._chunk(
                    (name, sf, rlo, rhi, key_cols),
                    lambda: g.generate_rows(name, rlo, rhi, sf, names), dicts)

    def __iter__(self) -> Iterator[Page]:
        name, sf, _lo, _hi = self.split.payload
        names = [c.name for c in self.columns]
        col_info = {n: (t, d) for (n, t, d) in _columns_of(name)}
        dicts = {n: d for n, (_t, d) in col_info.items()}
        wire_dtypes = {
            n: (self._wire and g.narrow_dtype(name, n, sf, dicts.get(n))
                or col_info[n][0].np_dtype) for n in names}
        pend: List[List[np.ndarray]] = []
        pend_rows = 0
        empty = True
        for chunk in self._chunks(names, dicts):
            n = len(next(iter(chunk.values()))) if chunk else 0
            if n == 0:
                continue
            pend.append([chunk[c] for c in names])
            pend_rows += n
            while pend_rows >= self.capacity:
                yield self._assemble(pend, self.capacity, names, col_info,
                                     wire_dtypes)
                pend_rows -= self.capacity
                empty = False
        if pend_rows > 0 or empty:
            yield self._assemble(pend, pend_rows, names, col_info, wire_dtypes)

    def _assemble(self, pend: List[List[np.ndarray]], count: int,
                  names, col_info, wire_dtypes) -> Page:
        """Take exactly `count` rows off the front of `pend` into one page."""
        cols = take_rows(pend, count)
        blocks = []
        for i, cname in enumerate(names):
            ctype, cdict = col_info[cname]
            arr = cols[i] if cols else np.zeros(0, dtype=wire_dtypes[cname])
            if len(arr) < self.capacity:
                arr = np.concatenate(
                    [arr, np.zeros(self.capacity - len(arr), dtype=arr.dtype)])
            self._bytes += arr.nbytes
            blocks.append(Block(ctype, arr, None, cdict))
        mask = np.arange(self.capacity) < count
        return Page(tuple(blocks), mask)

    def completed_bytes(self) -> int:
        return self._bytes

    @property
    def cache_token(self):
        # the generated stream is a pure function of (table, sf, row range,
        # columns, capacity) — safe to keep device-resident across queries
        return ("tpch", self.split.payload, tuple(c.name for c in self.columns),
                self.capacity)


class TpchPageSourceProvider(ConnectorPageSourceProvider):
    def create_page_source(self, split: Split, columns: Sequence[ColumnHandle],
                           page_capacity: int,
                           constraint: Constraint = Constraint.all()) -> ConnectorPageSource:
        return TpchPageSource(split, columns, page_capacity)


class TpchNodePartitioningProvider(ConnectorNodePartitioningProvider):
    """Primary-key range bucketing (reference TpchNodePartitioningProvider analogue)."""

    def bucket_count(self, table: TableHandle) -> Optional[int]:
        return None  # engine chooses; splits already carry bucket ids


class TpchConnector(Connector):
    def __init__(self, connector_id: str, splits_per_table: int = 8):
        self._metadata = TpchMetadata(connector_id)
        self._splits = TpchSplitManager(connector_id, splits_per_table)
        self._sources = TpchPageSourceProvider()
        self._partitioning = TpchNodePartitioningProvider()

    def metadata(self) -> ConnectorMetadata:
        return self._metadata

    def split_manager(self) -> ConnectorSplitManager:
        return self._splits

    def page_source_provider(self) -> ConnectorPageSourceProvider:
        return self._sources

    def node_partitioning_provider(self) -> ConnectorNodePartitioningProvider:
        return self._partitioning


# rows a stored file holds at the most: the TPU's page (2^22 rows), so a scan's
# range readers of one page come from one or two files
STORE_FILE_ROWS = 1 << 22
# rows a page of the store's one pass holds: host pages, never uploaded
STORE_PAGE_ROWS = 1 << 20
STORED_MARK = "_STORED.json"   # written last: a table without it is written anew


class _TableStore:
    """The files of a stored tpch catalog: `<dir>/<schema>/<table>/*.pcol` and
    `_STORED.json`, all columns at their declared widths, written through a
    `FileConnector`'s page sink and read through its split manager and page
    source. `storage_dir` None: a directory of its own, made with
    `tempfile.mkdtemp` on the first write and removed when the process ends (no
    run reads what another wrote, nothing lands in the checkout); a named
    directory is the user's and is kept, its stored tables found again."""

    def __init__(self, connector_id: str, storage_dir: Optional[str],
                 metadata: "TpchMetadata"):
        self.connector_id = connector_id
        self._dir = storage_dir
        self._own: Optional[str] = None   # the directory made here, if any
        self._tpch = metadata
        self._files: Optional[FileConnector] = None
        self._lock = threading.Lock()     # the two maps below, never held over a write
        self._table_locks: Dict[SchemaTableName, threading.Lock] = {}
        self._stored: set = set()

    def files(self) -> FileConnector:
        with self._lock:
            if self._files is None:
                base = self._dir
                if base is None:
                    base = self._own = tempfile.mkdtemp(
                        prefix="presto-tpu-tpch-")
                    atexit.register(self.close)
                self._files = FileConnector(self.connector_id, base,
                                            declared=self._declared)
            return self._files

    def close(self) -> None:
        """Remove the directory this store made for itself (never a named
        one); the process's end calls it."""
        with self._lock:
            own, self._own = self._own, None
        if own is not None:
            shutil.rmtree(own, ignore_errors=True)

    def _declared(self, name: SchemaTableName) -> Optional[TableMetadata]:
        handle = self._tpch.get_table_handle(name)
        return None if handle is None else self._tpch.get_table_metadata(handle)

    def ensure(self, handle: TableHandle) -> None:
        """Write the table's files unless they are there: ONCE a table, the
        first lookup of its handle; a second caller waits for the first. The
        caller holds no lock of the server's (planning runs in the query's own
        thread, outside `QueryManager._lock`)."""
        name = handle.schema_table
        with self._lock:
            if name in self._stored:
                return
            lock = self._table_locks.setdefault(name, threading.Lock())
        with lock:
            with self._lock:
                if name in self._stored:
                    return
            mark = os.path.join(
                self.files().metadata()._table_dir(name), STORED_MARK)
            if not os.path.isfile(mark):
                self._store(handle, mark)
            with self._lock:
                self._stored.add(name)

    def _store(self, handle: TableHandle, mark: str) -> None:
        name, sf = handle.schema_table, handle.extra[0]
        files = self.files().metadata()
        t0 = time.perf_counter()
        with trace.span(trace.TPCH, "store", table=str(name)) as stored:
            partial = files.get_table_handle(name)
            if partial is not None:     # a write that never reached its mark
                files.drop_table(partial)
            files.create_table(self._tpch.get_table_metadata(handle))
            target = files.begin_insert(files.get_table_handle(name))
            rows = g.table_row_count(name.table, sf)
            splits = TpchSplitManager(self.connector_id).get_splits(
                handle, Constraint.all(), -(-rows // STORE_FILE_ROWS))
            columns = list(self._tpch.get_column_handles(handle).values())
            sinks = self.files().page_sink_provider()

            def write(split: Split) -> tuple:
                sink = sinks.create_page_sink(target)
                for page in TpchPageSource(split, columns, STORE_PAGE_ROWS,
                                           wire=False):
                    sink.append_page(page)
                return sink.rows_written, sink.finish()

            with ThreadPoolExecutor(
                    max_workers=min(len(splits), os.cpu_count() or 1, 8),
                    thread_name_prefix="tpch-store") as pool:
                done = list(pool.map(write, splits))
            files.finish_insert(target, None)
            paths = [p for _rows, written in done for p in written]
            counts = {"tables": 1, "rows": sum(r for r, _w in done),
                      "bytes": sum(os.path.getsize(p) for p in paths)}
            with open(mark, "w") as f:
                json.dump(dict(counts, files=len(paths)), f)
            stored.note(rows=counts["rows"], bytes=counts["bytes"],
                        files=len(paths))
        METRICS.count_many(counts, prefix="tpch.store.")
        METRICS.histogram("tpch.store_s", time.perf_counter() - t0)


class StoredTpchMetadata(TpchMetadata):
    """`tpch`'s metadata and statistics (so every plan is `tpch`'s), over
    tables whose files are written on the first lookup of their handle."""

    def __init__(self, connector_id: str, storage_dir: Optional[str]):
        super().__init__(connector_id)
        self.store = _TableStore(connector_id, storage_dir,
                                 TpchMetadata(connector_id))

    def get_table_handle(self, name: SchemaTableName) -> Optional[TableHandle]:
        handle = super().get_table_handle(name)
        if handle is not None:
            self.store.ensure(handle)
        return handle

    def close(self) -> None:
        self.store.close()


class StoredTpchConnector(Connector):
    """Composition: `tpch` for what a table IS, a `FileConnector` for where its
    rows are. Splits and page sources are the file connector's own."""

    def __init__(self, connector_id: str, storage_dir: Optional[str] = None):
        self._metadata = StoredTpchMetadata(connector_id, storage_dir)
        self._partitioning = TpchNodePartitioningProvider()

    def metadata(self) -> ConnectorMetadata:
        return self._metadata

    def split_manager(self) -> ConnectorSplitManager:
        return self._metadata.store.files().split_manager()

    def page_source_provider(self) -> ConnectorPageSourceProvider:
        return self._metadata.store.files().page_source_provider()

    def node_partitioning_provider(self) -> ConnectorNodePartitioningProvider:
        return self._partitioning

    def shutdown(self) -> None:
        self._metadata.close()


class TpchConnectorFactory(ConnectorFactory):
    @property
    def name(self) -> str:
        return "tpch"

    def create(self, catalog_name: str, config: Dict[str, str]) -> Connector:
        storage = config.get("tpch.storage-dir")
        if storage:
            return StoredTpchConnector(catalog_name, storage)
        return TpchConnector(catalog_name,
                             int(config.get("tpch.splits-per-node", "8")))
