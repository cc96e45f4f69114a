"""File connector: directory-backed tables in PCOL, PARQUET or ORC.

The engine's presto-hive analogue, radically narrowed: a catalog roots at a
directory, `<base>/<schema>/<table>/*.pcol` (or `*.parquet` / `*.orc`) are
the table's files. PCOL reads are native-mmap scans with header-stats SPLIT
PRUNING (the ORC stripe-skipping pattern) plus libpcol range pre-filters;
PARQUET and ORC reads go through the engine's own readers
(formats/parquet.py, formats/orc.py — the presto-parquet / presto-orc
analogues) with one split per row group / stripe, pruned by chunk
statistics. ORC is ingest-only; parquet is read-write.
Writes (CTAS/INSERT) produce new immutable files — one per writer sink, the
classic append-only layout — in the connector's configured write format:
PCOL (default, the native mmap format) or PARQUET via the engine's own
writer (formats/parquet_writer.py), making parquet tables fully
read-write when the catalog opts in (`file.format=parquet`).

Dictionary handling: each table exposes ONE unioned dictionary per varchar
column (built from all files' persisted dictionaries); per-file codes remap
to it at scan time, so files written before a dictionary grew stay valid.
Virtual dictionaries (formatted/packed source columns) are materialized for
the codes actually written, unless the catalog DECLARES the table's schema
(`FileMetadata(declared=...)`, the hive metastore's role: the stored tpch
catalog does) and declares that very dictionary: the file then holds the bare
codes and no dictionary, and the declared one decodes them.

The narrow wire form: a PCOL header holds each integer column's min and max,
and a pcol page source emits, PER TABLE COLUMN (the union of its files'
statistics, so two files never give two shapes), the narrowest signed dtype
that holds the range (`_wire_dtypes`); `ops/scan._widen_page` widens it back
to the declared type on the device. Files keep the declared widths. A file
page source declares no `cache_token`: files can change, so nothing of a
table stays in `ops/scan.RESIDENT_CACHE` and every scan reads the files.
"""
from __future__ import annotations

import ctypes
import json
import os
import threading
import uuid
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ...block import Block, Dictionary, Page
from ...formats.parquet import ParquetFile
from ...formats.pcol import PcolFile, write_pcol
from ...types import is_string
from ...utils.batching import narrowest_int_dtype
from ...spi.connector import (ColumnHandle, ColumnMetadata, ColumnStatistics,
                              Connector, ConnectorMetadata,
                              ConnectorPageSink, ConnectorPageSinkProvider,
                              ConnectorPageSource, ConnectorPageSourceProvider,
                              ConnectorSplitManager, Constraint,
                              SchemaTableName, Split, TableHandle,
                              TableMetadata, TableStatistics)


# plan-time bound on a varchar column's materialized distinct-value set
# (the PLAIN-encoded parquet fallback decodes whole columns to build it)
MAX_VARCHAR_DICTIONARY = 1 << 21


class _ExternalFile:
    """Uniform chunked view over the two external formats: parquet files
    read per ROW GROUP, ORC files per STRIPE. Each chunk becomes one split,
    pruned by that chunk's column statistics (the OrcPredicate pattern)."""

    def __init__(self, path: str):
        self.path = path
        if path.endswith(".rc"):
            from ...formats.rcfile import RcTableFile
            self._f = RcTableFile(path)
            self.n_chunks = self._f.n_chunks
            self.chunk_rows = self._f.chunk_rows
            self.read_chunk = self._f.read_chunk
            self.chunk_stats = self._f.chunk_stats
        elif path.endswith(".orc"):
            from ...formats.orc import OrcFile
            self._f = OrcFile(path)
            self.n_chunks = self._f.n_stripes
            self.chunk_rows = self._f.stripe_rows
            self.read_chunk = self._f.read_stripe
            self.chunk_stats = self._f.stripe_col_stats
        else:
            self._f = ParquetFile(path)
            self.n_chunks = self._f.n_row_groups
            self.chunk_rows = self._f.row_group_rows
            self.read_chunk = self._f.read_row_group
            self.chunk_stats = self._f.row_group_stats
        self.num_rows = self._f.num_rows
        self.schema = self._f.schema

    def column_distinct_strings(self, name: str):
        return self._f.column_distinct_strings(name)

    def close(self):
        self._f.close()


class _TableInfo:
    def __init__(self, metadata: TableMetadata, files: List[str],
                 rows: int, signature, pcol_headers: Optional[Dict] = None):
        self.metadata = metadata
        self.files = files
        self.rows = rows
        self.signature = signature
        # path -> parsed pcol header (the _load pass already parsed every
        # header for schema/rows/dict-union): split readers reuse these so
        # pipeline construction re-opens and re-parses NOTHING
        self.pcol_headers = pcol_headers or {}
        # column -> the narrow dtype its pages leave the host in
        self.wire_dtypes = _wire_dtypes(metadata, self.pcol_headers.values())


def _wire_dtypes(metadata: TableMetadata, headers) -> Dict[str, np.dtype]:
    """{column: narrow wire dtype} of a pcol table, from the UNION of its
    files' header statistics: one dtype a table column whatever file a page
    comes from, so a scan's pages share one shape and a warm query compiles
    nothing. Codes of a materialized dictionary are bounded by its size (a
    file's own codes are remapped into it); any other integer column needs a
    min and max in every file that has rows. A column with no entry keeps its
    stored dtype."""
    headers = [h for h in headers if h["rows"] > 0]
    out: Dict[str, np.dtype] = {}
    for col in metadata.columns if headers else ():
        entries = [next((e for e in h["columns"] if e["name"] == col.name),
                        None) for h in headers]
        if any(e is None or np.dtype(e["dtype"]).kind != "i" for e in entries):
            continue
        if col.dictionary is not None and hasattr(col.dictionary, "values"):
            lo, hi = 0, max(len(col.dictionary) - 1, 0)
        elif all("min" in e for e in entries):
            lo = min(e["min"] for e in entries)
            hi = max(e["max"] for e in entries)
        else:
            continue
        narrow = narrowest_int_dtype(lo, hi)
        if narrow is not None and narrow.itemsize < max(
                np.dtype(e["dtype"]).itemsize for e in entries):
            out[col.name] = narrow
    return out


class FileMetadata(ConnectorMetadata):
    def __init__(self, connector_id: str, base_dir: str,
                 write_format: str = "pcol", declared=None):
        if write_format not in ("pcol", "parquet", "orc"):
            raise ValueError(f"unknown file write format {write_format!r}")
        self.connector_id = connector_id
        self.base = base_dir
        self.write_format = write_format
        # SchemaTableName -> TableMetadata or None: the schema a catalog
        # DECLARES for a pcol table (types and dictionaries, the metastore's
        # part). The files then hold data alone: a varchar column may be
        # stored as the bare codes of the declared dictionary.
        self._declared = declared
        self._cache: Dict[SchemaTableName, _TableInfo] = {}
        self._lock = threading.Lock()

    # --------------------------------------------------------------- layout

    def _table_dir(self, name: SchemaTableName) -> str:
        return os.path.join(self.base, name.schema, name.table)

    def list_schemas(self) -> List[str]:
        if not os.path.isdir(self.base):
            return []
        return sorted(d for d in os.listdir(self.base)
                      if os.path.isdir(os.path.join(self.base, d)))

    def list_tables(self, schema: Optional[str] = None) -> List[SchemaTableName]:
        out = []
        for s in ([schema] if schema else self.list_schemas()):
            sdir = os.path.join(self.base, s)
            if not os.path.isdir(sdir):
                continue
            for t in sorted(os.listdir(sdir)):
                if os.path.isdir(os.path.join(sdir, t)):
                    out.append(SchemaTableName(s, t))
        return out

    def _files_of(self, name: SchemaTableName) -> List[str]:
        d = self._table_dir(name)
        if not os.path.isdir(d):
            return []
        return sorted(os.path.join(d, f) for f in os.listdir(d)
                      if f.endswith((".pcol", ".parquet", ".orc", ".rc")))

    def _load(self, name: SchemaTableName) -> Optional[_TableInfo]:
        files = self._files_of(name)
        if not files:
            return None
        sig = tuple((f, os.path.getmtime(f)) for f in files)
        with self._lock:
            cached = self._cache.get(name)
            if cached is not None and cached.signature == sig:
                return cached
        exts = {f.rsplit(".", 1)[-1] for f in files}
        if len(exts) > 1:
            raise RuntimeError(
                f"table {name} mixes {'/'.join(sorted(exts))} files — "
                f"unsupported (write every file through one catalog "
                f"with a consistent file.format)")
        if exts in ({"parquet"}, {"orc"}, {"rc"}):
            return self._load_external(name, files, sig)
        headers = []
        by_path = {}
        rows = 0
        for f in files:
            pf = PcolFile(f)
            try:
                headers.append(pf.header)
                by_path[f] = pf.header
                rows += pf.rows
            finally:
                pf.close()
        # schema from the first file; dictionaries UNION across files so
        # every file's codes can remap into one table-wide dictionary
        from ...formats.pcol import _type_from_tag
        cols = []
        for e in headers[0]["columns"]:
            d = None
            if "dict" in e:
                seen = {}
                values: List[str] = []
                for h in headers:
                    he = next(c for c in h["columns"] if c["name"] == e["name"])
                    for v in he.get("dict", []):
                        if v not in seen:
                            seen[v] = len(values)
                            values.append(v)
                d = Dictionary(values)
            cols.append(ColumnMetadata(
                e["name"], _type_from_tag(e["type"], e["scale"]),
                dictionary=d))
        declared = self._declared(name) if self._declared else None
        if declared is not None:
            cols = self._declared_columns(name, declared, cols)
        info = _TableInfo(TableMetadata(name, tuple(cols)), files, rows, sig,
                          pcol_headers=by_path)
        with self._lock:
            self._cache[name] = info
        return info

    @staticmethod
    def _declared_columns(name, declared: TableMetadata, stored) -> list:
        """The declared columns in the place of what the files' headers
        gave: they must name the stored columns, in order and of one type;
        a stored dictionary must be the declared one's values (a file that
        holds no dictionary holds the declared one's codes)."""
        if [(c.name, c.type.name) for c in declared.columns] != \
                [(c.name, c.type.name) for c in stored]:
            raise RuntimeError(
                f"table {name}: the files' columns are not the declared ones")
        for want, have in zip(declared.columns, stored):
            if have.dictionary is not None and (
                    not hasattr(want.dictionary, "values")
                    or list(have.dictionary.values)
                    != list(want.dictionary.values)):
                raise RuntimeError(
                    f"table {name}: column {have.name}'s stored dictionary "
                    "is not the declared one")
        return list(declared.columns)

    def _load_external(self, name: SchemaTableName, files: List[str],
                      sig) -> _TableInfo:
        """Parquet/ORC tables: schema from the first file. Varchar columns
        get ONE table-wide SORTED Dictionary built at load by decoding every
        file's string values once (dictionary-encoded pages/streams make
        this a near-metadata read) — plan-time string predicates need the
        complete code space (reference: hive table dictionaries from ORC
        metadata)."""
        rows = 0
        schema = None
        string_values: Dict[str, set] = {}
        for f in files:
            pf = _ExternalFile(f)
            try:
                if schema is None:
                    schema = pf.schema
                rows += pf.num_rows
                str_cols = [n for n, t in pf.schema if is_string(t)]
                for n in str_cols:
                    vals_set = string_values.setdefault(n, set())
                    # cheap path: union the files' own dictionary
                    # pages/streams
                    distinct = pf.column_distinct_strings(n)
                    if distinct is not None:
                        vals_set.update(distinct)
                        continue
                    # direct-encoded fallback: decode the column once, with
                    # a hard cardinality bound — an unbounded
                    # high-cardinality column would materialize every
                    # distinct string in memory at PLAN time; fail with a
                    # clear message instead of an OOM
                    for gi in range(pf.n_chunks):
                        if pf.chunk_rows(gi) == 0:
                            continue
                        vals, nulls = pf.read_chunk(gi, [n])[n]
                        if nulls is not None:
                            vals = vals[~nulls]
                        vals_set.update(
                            np.unique(vals.astype(str)).tolist())
                        if len(vals_set) > MAX_VARCHAR_DICTIONARY:
                            raise ValueError(
                                f"varchar column {n!r} of {name} exceeds "
                                f"{MAX_VARCHAR_DICTIONARY} distinct "
                                "values; re-encode the files with "
                                "dictionary encoding (or drop the column "
                                "from the table)")
            finally:
                pf.close()
        cols = tuple(
            ColumnMetadata(
                n, t,
                dictionary=Dictionary(sorted(string_values.get(n, ())))
                if is_string(t) else None)
            for n, t in schema)
        info = _TableInfo(TableMetadata(name, cols), files, rows, sig)
        with self._lock:
            self._cache[name] = info
        return info

    # ------------------------------------------------------------------ spi

    def get_table_handle(self, name: SchemaTableName) -> Optional[TableHandle]:
        if self._files_of(name):
            return TableHandle(self.connector_id, name)
        return None

    def get_table_metadata(self, table: TableHandle) -> TableMetadata:
        return self._load(table.schema_table).metadata

    def get_table_statistics(self, table: TableHandle,
                             constraint: Constraint) -> TableStatistics:
        info = self._load(table.schema_table)
        return TableStatistics(row_count=float(info.rows) if info else 0.0)

    def table_info(self, table: TableHandle) -> _TableInfo:
        return self._load(table.schema_table)

    # ---------------------------------------------------------------- writes

    def create_table(self, metadata: TableMetadata, properties=None) -> None:
        if properties:
            raise ValueError(
                "file connector tables take no properties (partitioning "
                "lives in the hive connector; format is per-catalog)")
        d = self._table_dir(metadata.name)
        if self._files_of(metadata.name):
            raise ValueError(f"table {metadata.name} already exists")
        os.makedirs(d, exist_ok=True)
        # an empty seed file pins the schema on disk; virtual dictionaries
        # seed empty (data files carry their own materialized dictionaries,
        # unioned at load)
        names = [c.name for c in metadata.columns]
        types = [c.type for c in metadata.columns]
        virtual = None if self._declared else Dictionary([])
        dicts = [c.dictionary if c.dictionary is None or
                 hasattr(c.dictionary, "values") else virtual
                 for c in metadata.columns]
        if self.write_format == "parquet":
            from ...formats.parquet_writer import write_parquet
            write_parquet(os.path.join(d, "00000000.parquet"),
                          names, types, dicts, [])
        elif self.write_format == "orc":
            from ...formats.orc_writer import write_orc
            write_orc(os.path.join(d, "00000000.orc"),
                      names, types, dicts, [])
        else:
            write_pcol(os.path.join(d, "00000000.pcol"),
                       names, types, dicts, [])

    def begin_insert(self, table: TableHandle):
        files = self._files_of(table.schema_table)
        if any(f.endswith(".rc") for f in files):
            raise RuntimeError(
                f"table {table.schema_table} is RCFile-backed and "
                f"read-only (RCFile is ingest-only)")
        exts = {os.path.splitext(f)[1].lstrip(".") for f in files}
        if exts and exts != {self.write_format}:
            have = "/".join(sorted(exts))
            raise RuntimeError(
                f"table {table.schema_table} is {have}-backed and this "
                f"catalog writes {self.write_format} — formats cannot mix "
                f"(set file.format={have} in the catalog properties)")
        return table

    def finish_insert(self, handle, fragments) -> None:
        with self._lock:
            self._cache.pop(handle.schema_table, None)

    def drop_table(self, table: TableHandle) -> None:
        d = self._table_dir(table.schema_table)
        for f in self._files_of(table.schema_table):
            os.unlink(f)
            if f.endswith(".rc") and os.path.isfile(f + ".schema"):
                os.unlink(f + ".schema")  # rcfile's sidecar type descriptor
        try:
            os.rmdir(d)
        except OSError:
            pass
        with self._lock:
            self._cache.pop(table.schema_table, None)




def iter_pcol_pages(path: str, names, type_of, table_dicts, capacity: int,
                    prefilter_fn=None, wire_dtypes=None):
    """One pcol file -> fixed-capacity masked pages, remapping per-file
    varchar codes into the TABLE's unioned dictionaries. Shared by the file
    and raptor connectors (one implementation of the chunk loop: the file
    is opened ONCE, columns are read once and sliced per chunk).
    `prefilter_fn(pf) -> bool mask | None` runs on the open file and ANDs
    into the row mask (the native libpcol range scan). `wire_dtypes`
    ({column: dtype}, the file connector's `_wire_dtypes`) names the columns
    that leave in a narrower dtype than they are stored in."""
    wire_dtypes = wire_dtypes or {}
    pf = PcolFile(path)
    try:
        if pf.rows == 0:
            return
        prefilter = prefilter_fn(pf) if prefilter_fn is not None else None
        cols = {}
        for n in names:
            data, nulls, _d = pf.read_column(n)
            cols[n] = (data, nulls)
        # one remap implementation for the serial and split-parallel paths —
        # they must stay row-identical by construction
        remap = pcol_dict_remaps(pf.columns, names, table_dicts)
        for lo in range(0, pf.rows, capacity):
            hi = min(lo + capacity, pf.rows)
            n_rows = hi - lo
            blocks = []
            for cname in names:
                data, nulls = cols[cname]
                seg = data[lo:hi]
                if cname in remap:
                    seg = remap[cname][np.clip(seg.astype(np.int32), 0,
                                               len(remap[cname]) - 1)]
                # a copy, off the mapping, in the wire dtype where one is named
                seg = seg.astype(wire_dtypes.get(cname, seg.dtype))
                if n_rows < capacity:
                    seg = np.concatenate(
                        [seg, np.zeros(capacity - n_rows, dtype=seg.dtype)])
                nseg = None
                if nulls is not None:
                    nseg = np.zeros(capacity, dtype=bool)
                    nseg[:n_rows] = nulls[lo:hi]
                blocks.append(Block(type_of[cname], seg, nseg,
                                    table_dicts.get(cname)))
            mask = np.arange(capacity) < n_rows
            if prefilter is not None:
                mask = mask & np.pad(prefilter[lo:hi],
                                     (0, capacity - n_rows))
            yield Page(tuple(blocks), mask)
    finally:
        pf.close()


# a range reader compacts its pre-filter's survivors only where they are at
# most this share of the range (ops/coalesce.py's PASSTHROUGH_SELECTIVITY asks
# the same of a page on the device)
PREFILTER_COMPACT_BELOW = 0.5

# CAP on rows per parallel pcol range split: binds only when the target
# page is larger (the 4M-row accelerator capacity -> 4 ranges per page, so
# the byte budget has granularity and the reader pool has work items);
# smaller targets make each range exactly one page
_RANGE_ROWS = 1 << 20


def pcol_dict_remaps(columns, names, table_dicts):
    """{column: int32 remap array} for columns whose FILE dictionary differs
    from the TABLE's unioned one. O(dict size) — computed once per file and
    shared by every range reader of that file. `columns` is the header's
    column-entry mapping (``PcolFile.columns`` or the metadata cache's
    parsed header) — no open file needed."""
    remaps = {}
    for cname in names:
        e = columns.get(cname)
        td = table_dicts.get(cname)
        if e is None or "dict" not in e or td is None or \
                list(e["dict"]) == list(td.values):
            continue
        pos = {v: i for i, v in enumerate(td.values)}
        remaps[cname] = np.asarray([pos[v] for v in e["dict"]],
                                   dtype=np.int32)
    return remaps


def read_pcol_range_chunk(path: str, names, type_of, table_dicts,
                          lo: int, hi: int, prefilter_fn=None, remaps=None,
                          header=None, wire_dtypes=None):
    """Decode rows [lo, hi) of one pcol file into a compacted HostChunk —
    the read+decode step of the streaming scan pipeline. Opens its own
    mapping so ranges of one file are readable concurrently; all returned
    arrays are detached from the mapping before it closes. `prefilter_fn(pf,
    lo, hi) -> bool mask | None` compacts non-surviving rows away HERE, so
    they never cost host->HBM bytes, where at most PREFILTER_COMPACT_BELOW of
    the range survives; a range that keeps more leaves whole. `remaps` (pcol_dict_remaps) carries the
    per-file dictionary re-encodings, precomputed by the caller; None =
    derive them here (the self-contained path). `header` likewise shares one
    parsed file header across the ranges (each range still opens its own
    mapping so reads stay concurrent). `wire_dtypes` as in iter_pcol_pages:
    the narrowing IS the copy off the mapping, so a narrowed column costs one
    pass that reads the stored width and writes the narrow one."""
    from ...ops.scan_pipeline import HostChunk

    wire_dtypes = wire_dtypes or {}
    pf = PcolFile(path, header=header)
    try:
        if remaps is None:
            remaps = pcol_dict_remaps(pf.columns, names, table_dicts)
        keep = None
        if prefilter_fn is not None:
            pre = prefilter_fn(pf, lo, hi)
            # compacting is a gather a column: it pays in upload bytes only
            # where it drops much of the range (TPC-H Q1's date bound keeps
            # 98%, and compacting it tripled the readers' seconds: PERF.md
            # section 6, PR 40). The device's filter sees every row that is
            # left, so a range kept whole stays exact.
            if pre is not None and np.count_nonzero(pre) <= \
                    (hi - lo) * PREFILTER_COMPACT_BELOW:
                keep = np.flatnonzero(pre)
        cols = []
        nulls = []
        for cname in names:
            data, nl, _d = pf.read_column_range(cname, lo, hi)
            seg = np.asarray(data)
            rm = remaps.get(cname)
            if rm is not None:
                seg = rm[np.clip(seg.astype(np.int32), 0, len(rm) - 1)]
            # the copy off the mapping, into the wire dtype where one is
            # named: survivors are then gathered from the narrow array
            seg = seg.astype(wire_dtypes.get(cname, seg.dtype),
                             copy=rm is None)
            if keep is not None:
                seg = seg[keep]
            cols.append(np.ascontiguousarray(seg))
            if nl is None:
                nulls.append(None)
            else:  # read_column_range already copied (astype) off the map
                nulls.append(nl[keep] if keep is not None else nl)
        rows = int(len(keep)) if keep is not None else hi - lo
        return HostChunk.build(cols, nulls,
                               [type_of[c] for c in names],
                               [table_dicts.get(c) for c in names], rows)
    finally:
        pf.close()


class _LazyRemaps:
    """Once-per-file dictionary remaps, computed by the first range reader
    that runs (on the scan pipeline's pool) instead of serially at pipeline
    construction — the lazy split-reader setup."""

    def __init__(self, columns, names, table_dicts):
        self._columns = columns
        self._names = names
        self._table_dicts = table_dicts
        self._lock = threading.Lock()
        self._val = None
        self._done = False

    def get(self):
        with self._lock:
            if not self._done:
                self._val = pcol_dict_remaps(self._columns, self._names,
                                             self._table_dicts)
                self._done = True
            return self._val


class FileSplitManager(ConnectorSplitManager):
    """One split per file, pruned by header min/max vs the pushed-down
    constraint (the ORC stripe-statistics skip)."""

    def __init__(self, connector_id: str, metadata: FileMetadata):
        self.connector_id = connector_id
        self._metadata = metadata

    def get_splits(self, table: TableHandle, constraint: Constraint,
                   desired_splits: int) -> List[Split]:
        info = self._metadata.table_info(table)
        if info.files and info.files[0].endswith((".parquet", ".orc", ".rc")):
            return self._external_splits(table, info, constraint)
        splits = []
        for b, f in enumerate(info.files):
            pf = PcolFile(f)
            try:
                keep = pf.rows > 0
                if keep and constraint.domains:
                    for col, dom in constraint.domains.items():
                        if col not in pf.columns:
                            continue
                        lo, hi = dom if isinstance(dom, tuple) \
                            else (None, None)
                        mn, mx = pf.column_stats(col)
                        if mn is None:
                            continue
                        if (hi is not None and mn > hi) or \
                                (lo is not None and mx < lo):
                            keep = False
                            break
            finally:
                pf.close()
            if keep:
                splits.append(Split(self.connector_id,
                                    payload=(table.schema_table, f),
                                    bucket=b))
        return splits  # [] = every file pruned: the scan yields no pages

    def _external_splits(self, table: TableHandle, info: _TableInfo,
                         constraint: Constraint) -> List[Split]:
        """One split per row group (parquet) / stripe (ORC), pruned by that
        chunk's min/max statistics (the reference's OrcPredicate
        stripe/row-group skipping)."""
        splits = []
        b = 0
        for f in info.files:
            pf = _ExternalFile(f)
            try:
                for g in range(pf.n_chunks):
                    keep = pf.chunk_rows(g) > 0
                    if keep and constraint.domains:
                        for col, dom in constraint.domains.items():
                            lo, hi = dom if isinstance(dom, tuple) else (None, None)
                            stats = pf.chunk_stats(g, col)
                            if stats is None or stats[0] is None or \
                                    isinstance(stats[0], str):
                                continue
                            mn, mx = stats
                            if (hi is not None and mn > hi) or \
                                    (lo is not None and mx < lo):
                                keep = False
                                break
                    if keep:
                        splits.append(Split(self.connector_id,
                                            payload=(table.schema_table, f, g),
                                            bucket=b))
                    b += 1
            finally:
                pf.close()
        return splits


class FilePageSource(ConnectorPageSource):
    def __init__(self, metadata: FileMetadata, split: Split,
                 columns: Sequence[ColumnHandle], page_capacity: int,
                 constraint: Constraint):
        self._metadata = metadata
        self.split = split
        self.columns = list(columns)
        self.capacity = page_capacity
        self.constraint = constraint

    def __iter__(self) -> Iterator[Page]:
        if len(self.split.payload) == 3:
            yield from self._iter_external()
            return
        name, path = self.split.payload
        info = self._metadata._load(name)
        table_dicts = {c.name: c.dictionary for c in info.metadata.columns}
        names = [c.name for c in self.columns]
        type_of = {c.name: info.metadata.column(c.name).type
                   for c in self.columns}
        yield from iter_pcol_pages(path, names, type_of, table_dicts,
                                   self.capacity, self._native_prefilter,
                                   self._wire_of(info))

    @staticmethod
    def _wire_of(info) -> Dict[str, np.dtype]:
        """The table's narrow wire dtypes; a provider that names none (the
        hive connector's per-snapshot shim) keeps the stored ones."""
        return getattr(info, "wire_dtypes", None) or {}

    def split_readers(self, target_rows: int):
        """Row-range split readers (the scan-pipeline SPI): a pcol split
        decomposes into independently-decodable row ranges read by the
        shared reader pool. External formats (parquet/orc/rc) decode whole
        chunks and stay on the serial path (None)."""
        if len(self.split.payload) != 2:
            return None
        try:
            from ...native import native_available
            if not native_available():
                # no native mmap: PcolFile's fallback reads the WHOLE file
                # (np.fromfile) per open, so per-range readers would each
                # re-read it — the serial one-open path wins there
                return None
        except Exception:
            return None
        name, path = self.split.payload
        info = self._metadata._load(name)
        table_dicts = {c.name: c.dictionary for c in info.metadata.columns}
        names = [c.name for c in self.columns]
        type_of = {c.name: info.metadata.column(c.name).type
                   for c in self.columns}
        # LAZY per-file setup: the header was already parsed (and cached)
        # by the metadata load, so pipeline construction opens NO files —
        # a 1000-file table fans out instantly. The dictionary remaps
        # (O(dict size) host work per file) are deferred into a shared
        # once-holder that the FIRST scheduled range reader computes on a
        # pool thread; sibling ranges reuse it.
        header = info.pcol_headers.get(path)
        if header is None:  # stale cache entry (file swapped in place)
            pf = PcolFile(path)
            header = pf.header
            pf.close()
        rows = header["rows"]
        columns = {e["name"]: e for e in header["columns"]}
        lazy = _LazyRemaps(columns, names, table_dicts)
        wire = self._wire_of(info)
        from ...formats.pcol import row_ranges
        step = max(1, min(int(target_rows), _RANGE_ROWS))

        def reader(lo: int, hi: int):
            def read():
                yield read_pcol_range_chunk(path, names, type_of,
                                            table_dicts, lo, hi,
                                            self._native_prefilter,
                                            lazy.get(), header, wire)
            return read

        return [reader(lo, hi) for lo, hi in row_ranges(rows, step)]

    def _iter_external(self) -> Iterator[Page]:
        name, path, group = self.split.payload
        info = self._metadata._load(name)
        table_dicts = {c.name: c.dictionary for c in info.metadata.columns}
        types = {c.name: c.type for c in info.metadata.columns}
        names = [c.name for c in self.columns]
        pf = _ExternalFile(path)
        try:
            data = pf.read_chunk(group, names)
        finally:
            pf.close()
        n = pf.chunk_rows(group)
        from ...utils.batching import clamp_capacity
        cap = clamp_capacity(n, self.capacity)
        cols = {}
        for cname in names:
            vals, nulls = data[cname]
            d = table_dicts.get(cname)
            if d is not None:
                # re-encode into the table dictionary built at load; python
                # work is per-DISTINCT value, not per row. Null slots carry a
                # placeholder code 0 under their null flag.
                strs = np.asarray([u"" if v is None else v for v in vals],
                                  dtype=object)
                uniq, inv = np.unique(strs.astype(str), return_inverse=True)
                index = d.index()
                nl = data[cname][1]
                umap = np.empty(len(uniq), dtype=np.int32)
                for ui, u in enumerate(uniq):
                    code = index.get(u)
                    if code is None:
                        if nl is not None and u == "":
                            # null placeholder under the null flag; -1 is the
                            # dictionary's absent sentinel (lookup -> None)
                            code = -1
                        else:
                            raise RuntimeError(
                                f"{path}: value {u!r} missing from the "
                                f"table dictionary of {cname} — stale "
                                f"metadata cache? (file changed in place)")
                    umap[ui] = code
                vals = umap[inv]
            cols[cname] = (vals, nulls)
        for lo in range(0, max(n, 1), cap):
            hi = min(lo + cap, n)
            n_rows = hi - lo
            blocks = []
            for cname in names:
                vals, nulls = cols[cname]
                tt = types[cname]
                seg = np.asarray(vals[lo:hi]).astype(tt.np_dtype, copy=False)
                if n_rows < cap:
                    seg = np.concatenate(
                        [seg, np.zeros(cap - n_rows, dtype=seg.dtype)])
                nseg = None
                if nulls is not None:
                    nseg = np.zeros(cap, dtype=bool)
                    nseg[:n_rows] = nulls[lo:hi]
                blocks.append(Block(tt, seg, nseg, table_dicts.get(cname)))
            mask = np.arange(cap) < n_rows
            yield Page(tuple(blocks), mask)
            if n == 0:
                break

    def _native_prefilter(self, pf: PcolFile, row_lo: int = 0,
                          row_hi: Optional[int] = None
                          ) -> Optional[np.ndarray]:
        """AND together pushed-down ranges via libpcol's native scan kernels
        (skips rows before they ever reach the device). `row_lo`/`row_hi`
        restrict the scan to one row range so split-parallel readers only
        touch their own slice of the mapping."""
        if not self.constraint.domains:
            return None
        try:
            from ...native import libpcol
            lib = libpcol()
        except Exception:
            return None
        row_hi = pf.rows if row_hi is None else row_hi
        n = row_hi - row_lo
        mask: Optional[np.ndarray] = None
        for col, dom in self.constraint.domains.items():
            if col not in pf.columns:
                continue
            lo, hi = dom if isinstance(dom, tuple) else (None, None)
            if lo is None and hi is None:
                continue
            data, nulls, _ = pf.read_column_range(col, row_lo, row_hi)
            if data.dtype == np.int64:
                fn = lib.pcol_filter_range_i64
            elif data.dtype == np.int32:
                fn = lib.pcol_filter_range_i32
            else:
                continue
            if mask is None:
                mask = np.ones(n, dtype=np.uint8)
            c = np.ascontiguousarray(data)
            fn(c.ctypes.data, len(c),
               np.iinfo(np.int64).min if lo is None else int(lo),
               np.iinfo(np.int64).max if hi is None else int(hi),
               mask.ctypes.data)
        return mask.astype(bool) if mask is not None else None


class FilePageSourceProvider(ConnectorPageSourceProvider):
    def __init__(self, metadata: FileMetadata):
        self._metadata = metadata

    def create_page_source(self, split: Split, columns: Sequence[ColumnHandle],
                           page_capacity: int,
                           constraint: Constraint = Constraint.all()
                           ) -> ConnectorPageSource:
        return FilePageSource(self._metadata, split, columns, page_capacity,
                              constraint)


class FilePageSink(ConnectorPageSink):
    """Buffers host pages; finish() writes ONE immutable file in the
    catalog's write format (pcol or parquet)."""

    def __init__(self, metadata: FileMetadata, table: TableHandle):
        self._metadata = metadata
        self._table = table
        self._pages: List[Page] = []
        self.rows_written = 0

    def append_page(self, page: Page) -> None:
        import jax

        host = jax.device_get(page)
        self._pages.append(host)
        self.rows_written += int(np.asarray(host.mask).sum())

    def finish(self):
        if not self._pages:
            return []
        info = self._metadata.table_info(self._table)
        names = [c.name for c in info.metadata.columns]
        types = [c.type for c in info.metadata.columns]
        dicts, pages = _materialize_dicts(
            self._pages, [c.dictionary for c in info.metadata.columns]
            if self._metadata._declared else None)
        d = self._metadata._table_dir(self._table.schema_table)
        if self._metadata.write_format == "parquet":
            from ...formats.parquet_writer import write_parquet
            path = os.path.join(d, f"{uuid.uuid4().hex[:12]}.parquet")
            write_parquet(path, names, types, dicts, pages)
        elif self._metadata.write_format == "orc":
            from ...formats.orc_writer import write_orc
            path = os.path.join(d, f"{uuid.uuid4().hex[:12]}.orc")
            write_orc(path, names, types, dicts, pages)
        else:
            path = os.path.join(d, f"{uuid.uuid4().hex[:12]}.pcol")
            write_pcol(path, names, types, dicts, pages)
        return [path]


def _materialize_dicts(pages, declared=None):
    """-> (per-column dictionaries, pages) ready to persist. Blocks carry
    their own dictionaries; virtual ones (formatted/packed) cannot persist,
    so the codes actually written decode to strings and re-encode through a
    real Dictionary, MAX_VARCHAR_DICTIONARY distinct values a sink at the
    most (the strings go into the file's header, and a reader unions them at
    plan time). `declared` (the catalog's own dictionaries, a column each)
    keeps a column whose virtual dictionary IS the declared one as it is:
    the file holds its codes and no dictionary (None here), which is how
    l_comment's 60M distinct values at SF10 are stored at all."""
    ncols = len(pages[0].blocks)
    out_dicts: List[Optional[Dictionary]] = []
    out_pages = list(pages)
    for ci in range(ncols):
        d = pages[0].blocks[ci].dictionary
        if d is None or hasattr(d, "values"):
            out_dicts.append(d)
            continue
        if declared is not None and declared[ci] is d:
            out_dicts.append(None)
            continue
        codes = np.concatenate(
            [np.asarray(p.blocks[ci].data)[np.asarray(p.mask)]
             for p in pages]).astype(np.int64)
        uniq = np.unique(codes)
        if len(uniq) > MAX_VARCHAR_DICTIONARY:
            raise ValueError(
                f"column {ci}: {len(uniq)} distinct values of a virtual "
                f"dictionary ({d!r}) in one sink, over the "
                f"{MAX_VARCHAR_DICTIONARY} a file's header may hold; write "
                "it through a catalog that declares the dictionary "
                "(tpch.storage-dir) or leave the column out")
        strings = d.lookup(uniq)
        new_d = Dictionary([str(s) for s in strings])
        new_pages = []
        for p in out_pages:
            b = p.blocks[ci]
            data = np.asarray(b.data).astype(np.int64)
            # a slot whose code was never live (padding) takes code 0
            at = np.minimum(np.searchsorted(uniq, data), max(len(uniq) - 1, 0))
            mapped = np.where(uniq[at] == data, at, 0).astype(np.int32) \
                if len(uniq) else np.zeros(len(data), dtype=np.int32)
            blocks = list(p.blocks)
            blocks[ci] = Block(b.type, mapped, b.nulls, new_d)
            new_pages.append(Page(tuple(blocks), p.mask))
        out_pages = new_pages
        out_dicts.append(new_d)
    return out_dicts, out_pages


class FilePageSinkProvider(ConnectorPageSinkProvider):
    def __init__(self, metadata: FileMetadata):
        self._metadata = metadata

    def create_page_sink(self, insert_handle) -> ConnectorPageSink:
        return FilePageSink(self._metadata, insert_handle)


class FileConnector(Connector):
    def __init__(self, connector_id: str, base_dir: str,
                 write_format: str = "pcol", declared=None):
        os.makedirs(base_dir, exist_ok=True)
        self._metadata = FileMetadata(connector_id, base_dir, write_format,
                                      declared)
        self._splits = FileSplitManager(connector_id, self._metadata)
        self._sources = FilePageSourceProvider(self._metadata)
        self._sinks = FilePageSinkProvider(self._metadata)

    def metadata(self) -> ConnectorMetadata:
        return self._metadata

    def split_manager(self) -> ConnectorSplitManager:
        return self._splits

    def page_source_provider(self) -> ConnectorPageSourceProvider:
        return self._sources

    def page_sink_provider(self) -> Optional[ConnectorPageSinkProvider]:
        return self._sinks
