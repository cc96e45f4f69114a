"""The stored tpch catalog (PR 40): `tpch_files`, the deployment
`tpch-sf10-files-1chip` of the benchmark, at schema `tiny` on the CPU.

A table of `tpch_files` is written ONCE, on the first lookup of its handle,
as PCOL files through the file connector's page sink, and read through the
file connector's page source on every scan. Held here: a stored table equals
the generated one, column for column, all eight; Q1 and Q6 over the files
equal the benchmark's plain numpy references and the same SQL over `tpch`;
the 22 TPC-H plans are `tpch`'s but for the catalog's name; a second scan
reads the files again (the pipeline's page counter grows by the same count,
the resident cache by nothing); the narrow wire form widens to the declared
values at the edges of each width, with negative decimals and nulls, and two
files of one table leave in ONE dtype; the store runs once under racing
lookups, is found again in a directory the user named, writes a table anew
where its mark is missing, and the default catalog's directory is gone when
the process ends. Nothing heavier than `tiny` is stored.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from presto_tpu.block import Block, Page  # noqa: E402
from presto_tpu.connectors.file import FileConnector  # noqa: E402
from presto_tpu.connectors.tpch import connector as tpch  # noqa: E402
from presto_tpu.formats.pcol import write_pcol  # noqa: E402
from presto_tpu.metadata import Session  # noqa: E402
from presto_tpu.models.tpch_sql import QUERIES  # noqa: E402
from presto_tpu.runner import LocalQueryRunner  # noqa: E402
from presto_tpu.spi.connector import Constraint, SchemaTableName  # noqa: E402
from presto_tpu.types import BIGINT, DATE, INTEGER, DecimalType  # noqa: E402
from presto_tpu.utils.metrics import METRICS  # noqa: E402

TINY_SF = 0.01
TABLES = ["region", "nation", "supplier", "part", "partsupp", "customer",
          "orders", "lineitem"]


@pytest.fixture(scope="module")
def runner():
    """One runner, default catalogs: `tpch` and `tpch_files` side by side."""
    return LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))


def _scan_numbers():
    snap = METRICS.raw_snapshot()
    numbers = {k: v for k, v in snap["counters"].items()
               if k.startswith(("scan.pipeline.", "tpch.store."))}
    numbers.update({k: v for k, v in snap["gauges"].items()
                    if k.startswith("scan.resident_cache")})
    return numbers


def _gained(before):
    return {k: v - before.get(k, 0) for k, v in _scan_numbers().items()}


# ------------------------------------------------------ the stored tables

@pytest.mark.parametrize("table", TABLES)
def test_a_stored_table_equals_the_generated_one(runner, table):
    stored = runner.execute(f"select * from tpch_files.tiny.{table}")
    generated = runner.execute(f"select * from tpch.tiny.{table}")
    assert stored.column_names == generated.column_names
    assert len(stored.rows) == len(generated.rows) > 0
    key = repr
    assert sorted(stored.rows, key=key) == sorted(generated.rows, key=key)
    # the catalog answers metadata and statistics as tpch does
    files = runner.metadata.connector("tpch_files").metadata()
    plain = runner.metadata.connector("tpch").metadata()
    name = SchemaTableName("tiny", table)
    mine, theirs = files.get_table_handle(name), plain.get_table_handle(name)
    assert mine.extra == theirs.extra and mine.connector_id == "tpch_files"
    assert files.get_table_metadata(mine).columns == \
        plain.get_table_metadata(theirs).columns
    got = files.get_table_statistics(mine, Constraint.all())
    want = plain.get_table_statistics(theirs, Constraint.all())
    assert got.row_count == want.row_count == len(stored.rows)
    assert {c: s.distinct_count for c, s in got.columns.items()} == \
        {c: s.distinct_count for c, s in want.columns.items()}
    assert files.get_unique_column_sets(mine) == \
        plain.get_unique_column_sets(theirs)


def test_the_files_hold_all_columns_at_declared_widths_and_a_mark(runner):
    runner.execute("select count(*) from tpch_files.tiny.lineitem")
    conn = runner.metadata.connector("tpch_files")
    store = conn.metadata().store
    info = store.files().metadata().table_info(
        conn.metadata().get_table_handle(SchemaTableName("tiny", "lineitem")))
    assert [c.name for c in info.metadata.columns] == \
        [n for n, _t, _d in tpch.g.LINEITEM_COLUMNS]
    data = [f for f in info.files if info.pcol_headers[f]["rows"]]
    assert len(data) == 1 and info.rows == 60032   # one file under 2^22 rows
    stored = {e["name"]: e for e in info.pcol_headers[data[0]]["columns"]}
    for name, type_, _dict in tpch.g.LINEITEM_COLUMNS:
        assert np.dtype(stored[name]["dtype"]) == type_.np_dtype, name
    # l_comment's virtual dictionary is the catalog's: the file holds codes
    assert "dict" not in stored["l_comment"]
    assert stored["l_returnflag"]["dict"] == ["A", "N", "R"]
    with open(os.path.join(os.path.dirname(data[0]), tpch.STORED_MARK)) as f:
        mark = json.load(f)
    assert mark["rows"] == 60032 and mark["files"] == 1
    assert mark["bytes"] == os.path.getsize(data[0])
    # and they leave the host narrow: Q1's seven columns in 12 bytes a row
    q1 = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
          "l_returnflag", "l_linestatus", "l_shipdate"]
    assert [info.wire_dtypes[c].itemsize for c in q1] == [2, 4, 1, 1, 1, 1, 2]
    assert "l_comment" not in info.wire_dtypes      # 42-bit codes stay 64


# ------------------------------------------- Q1 and Q6 against the reference

def _served_sql(name, seed):
    from benchmark.harness import cells
    from benchmark.harness.traffic import Plan

    cell = cells.Cell({"q1": "q1_sf10_files", "q6": "q6_sf1"}[name])
    plan = Plan(cell.traffic, cell.queries, seed)
    return cell.queries[name], plan.params[name], plan.sql[name]


@pytest.fixture(scope="module")
def server():
    from presto_tpu.server import PrestoTpuServer

    srv = PrestoTpuServer(LocalQueryRunner(
        session=Session(catalog="tpch_files", schema="tiny")), port=0)
    srv.start()
    yield srv
    srv.stop()


def _ask(server, sql, catalog):
    """-> the answer as the benchmark types it (decimals exact text)."""
    import presto_tpu.client.dbapi as dbapi
    from benchmark.harness.compare import typed

    with dbapi.connect(host="127.0.0.1", port=server.port, user="t",
                       catalog=catalog, schema="tiny") as conn:
        cur = conn.cursor()
        cur.execute(sql)
        return typed(cur.fetchall(), cur.description)


@pytest.mark.parametrize("seed", [40, 2**31 + 40])
@pytest.mark.parametrize("name", ["q1", "q6"])
def test_q1_and_q6_over_the_files_equal_the_reference_and_tpch(
        server, name, seed):
    from benchmark.harness.compare import compare_rows

    query, params, sql = _served_sql(name, seed)
    stored = _ask(server, sql, "tpch_files")
    generated = _ask(server, sql, "tpch")
    want = query.reference(TINY_SF, params)
    unequal, gap = compare_rows(stored, want)
    assert unequal == 0 and gap <= 1e-9
    assert compare_rows(stored, generated)[0] == 0
    assert len(want) == (4 if name == "q1" else 1)


@pytest.mark.parametrize("number", sorted(QUERIES))
def test_explain_is_tpchs_but_for_the_catalogs_name(number):
    sql = QUERIES[number]
    plans = {}
    for catalog in ("tpch_files", "tpch"):
        r = LocalQueryRunner(session=Session(catalog=catalog, schema="tiny"))
        plans[catalog] = r.explain(sql)
    # (a plan's text names schema and table, no catalog: they are EQUAL)
    assert plans["tpch_files"].replace("tpch_files", "tpch") == plans["tpch"]
    assert "TableScan tiny." in plans["tpch"]


# -------------------------------------------------- every scan reads the files

def test_a_second_scan_reads_the_files_again(runner):
    sql = "select sum(l_quantity), count(*) from tpch_files.tiny.lineitem"
    first = runner.execute(sql)          # the table is stored by now or here
    before = _scan_numbers()
    second = runner.execute(sql)
    one = _gained(before)
    third = runner.execute(sql)
    two = _gained(before)
    assert first.rows == second.rows == third.rows
    assert one["scan.pipeline.pages"] >= 1
    assert two["scan.pipeline.pages"] == 2 * one["scan.pipeline.pages"]
    assert two["scan.pipeline.rows"] == 2 * one["scan.pipeline.rows"] == 2 * 60032
    assert two["scan.pipeline.bytes"] == 2 * one["scan.pipeline.bytes"] > 0
    # nothing of the table is kept on the device between two queries
    assert two["scan.resident_cache_streams"] == 0
    assert two["scan.resident_cache_bytes"] == 0
    assert two.get("tpch.store.tables", 0) == 0
    # and the same SQL over `tpch` is resident after its first scan
    resident = sql.replace("tpch_files", "tpch")
    runner.execute(resident)
    before = _scan_numbers()
    runner.execute(resident)
    assert _gained(before).get("scan.pipeline.pages", 0) == 0


def test_a_file_page_source_declares_no_cache_token(runner):
    conn = runner.metadata.connector("tpch_files")
    meta = conn.metadata()
    table = meta.get_table_handle(SchemaTableName("tiny", "nation"))
    splits = conn.split_manager().get_splits(table, Constraint.all(), 8)
    assert splits and all(s.connector_id == "tpch_files" for s in splits)
    cols = list(meta.get_column_handles(table).values())
    source = conn.page_source_provider().create_page_source(
        splits[0], cols, 1 << 10, Constraint.all())
    assert getattr(source, "cache_token", None) is None


# ------------------------------------------------------- the narrow wire form

DEC = DecimalType(18, 2)


def _write(base, name, columns, rows_of):
    """One pcol file of table `s.<name>`: columns [(name, type)], rows_of a
    list of per-column (values, nulls or None)."""
    d = os.path.join(str(base), "s", name)
    os.makedirs(d, exist_ok=True)
    n = len(rows_of[0][0])
    blocks = tuple(
        Block(t, np.asarray(v, dtype=t.np_dtype),
              None if nl is None else np.asarray(nl, dtype=bool), None)
        for (_c, t), (v, nl) in zip(columns, rows_of))
    path = os.path.join(d, f"{len(os.listdir(d)):04d}.pcol")
    write_pcol(path, [c for c, _t in columns], [t for _c, t in columns],
               [None] * len(columns), [Page(blocks, np.ones(n, dtype=bool))])
    return path


def _pages(conn, name, capacity=1 << 6):
    meta = conn.metadata()
    table = meta.get_table_handle(SchemaTableName("s", name))
    cols = list(meta.get_column_handles(table).values())
    out = []
    for split in conn.split_manager().get_splits(table, Constraint.all(), 8):
        source = conn.page_source_provider().create_page_source(
            split, cols, capacity, Constraint.all())
        out.append((source, list(source)))
    return out


@pytest.mark.parametrize("top, nbytes", [(127, 1), (128, 2), (32767, 2),
                                         (32768, 4), (2**31 - 1, 4)])
def test_the_narrow_form_widens_to_the_declared_values_at_the_edges(
        tmp_path, top, nbytes):
    values = [0, 1, top, top - 1, 5]
    decimals = [-top - 1, -1, 0, 17, top]      # the signed range's both ends
    dates = [8035, 10591, 9000, 8035, 9298]
    nulls = [False, True, False, False, True]
    _write(tmp_path, "t", [("k", BIGINT), ("d", DEC), ("day", DATE),
                           ("n", INTEGER)],
           [(values, None), (decimals, None), (dates, None),
            (values, nulls)])
    conn = FileConnector("store", str(tmp_path))
    (source, pages), = _pages(conn, "t")
    page, = pages
    widths = [b.data.dtype.itemsize for b in page.blocks]
    assert widths == [nbytes, nbytes, 2, min(nbytes, 4)]
    live = np.asarray(page.mask)
    assert page.blocks[0].data[live].astype(np.int64).tolist() == values
    assert page.blocks[1].data[live].astype(np.int64).tolist() == decimals
    assert np.asarray(page.blocks[3].nulls)[live].tolist() == nulls
    # the range readers (the pipeline's path) leave in the same dtypes
    readers = source.split_readers(1 << 6)
    if readers is not None:
        chunk, = list(readers[0]())
        assert [c.dtype for c in chunk.cols] == \
            [b.data.dtype for b in page.blocks]
        assert chunk.cols[1].astype(np.int64).tolist() == decimals
    # and through the engine they are the declared values again
    r = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    r.catalogs.register("store", conn)
    got = r.execute("select k, d, day, n from store.s.t order by d")
    order = np.argsort(decimals)
    assert [row[0] for row in got.rows] == [values[i] for i in order]
    assert [int(row[1] * 100) for row in got.rows] == \
        [decimals[i] for i in order]
    assert [row[3] for row in got.rows] == \
        [None if nulls[i] else values[i] for i in order]
    total = r.execute("select sum(k), min(d), max(d), count(n) from store.s.t")
    assert total.rows[0][0] == sum(values)
    assert int(total.rows[0][1] * 100) == -top - 1
    assert int(total.rows[0][2] * 100) == top and total.rows[0][3] == 3


def test_two_files_of_one_table_leave_in_one_dtype(tmp_path):
    columns = [("k", BIGINT), ("d", DEC)]
    _write(tmp_path, "two", columns, [([1, 2, 100], None), ([-5, 0, 5], None)])
    _write(tmp_path, "two", columns,
           [([7, 40000, 9], None), ([1, 2, -40000], None)])
    conn = FileConnector("store", str(tmp_path))
    dtypes = {(b.data.dtype for b in page.blocks).__next__()
              for _s, pages in _pages(conn, "two") for page in pages}
    per_file = [[b.data.dtype for b in page.blocks]
                for _s, pages in _pages(conn, "two") for page in pages]
    assert len(per_file) == 2 and per_file[0] == per_file[1]
    assert per_file[0] == [np.dtype(np.int32), np.dtype(np.int32)]
    assert dtypes == {np.dtype(np.int32)}
    r = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    r.catalogs.register("store", conn)
    assert r.execute("select sum(k), sum(d) from store.s.two").rows[0][0] == \
        1 + 2 + 100 + 7 + 40000 + 9
    # a third file with a wider range widens the table's dtype, for all files
    _write(tmp_path, "two", columns, [([2**40], None), ([3], None)])
    per_file = [[b.data.dtype for b in page.blocks]
                for _s, pages in _pages(conn, "two") for page in pages]
    assert len(per_file) == 3
    assert all(f == [np.dtype(np.int64), np.dtype(np.int32)] for f in per_file)


@pytest.mark.parametrize("bound, compacted", [(980, False), (500, True),
                                              (100, True)])
def test_a_range_reader_compacts_only_a_selective_prefilter(
        tmp_path, bound, compacted):
    """A pushed-down range that keeps most of a range (Q1's date bound keeps
    98%) leaves the rows whole for the device's filter; one that keeps half
    or less is compacted on the host, so the rest is never uploaded."""
    from presto_tpu.native import native_available

    if not native_available():
        pytest.skip("no native pcol: no range pre-filter, no split readers")
    n = 1000
    _write(tmp_path, "pre", [("k", BIGINT)], [(list(range(n)), None)])
    conn = FileConnector("store", str(tmp_path))
    meta = conn.metadata()
    table = meta.get_table_handle(SchemaTableName("s", "pre"))
    cols = list(meta.get_column_handles(table).values())
    constraint = Constraint(domains={"k": (None, bound - 1)})
    split, = conn.split_manager().get_splits(table, constraint, 8)
    source = conn.page_source_provider().create_page_source(
        split, cols, 1 << 10, constraint)
    chunk, = list(source.split_readers(1 << 10)[0]())
    assert chunk.rows == (bound if compacted else n)
    r = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    r.catalogs.register("store", conn)
    assert r.execute(f"select count(*), max(k) from store.s.pre "
                     f"where k < {bound}").rows == [[bound, bound - 1]]


# ------------------------------------------------------------------ the store

def test_racing_lookups_store_a_table_once(tmp_path):
    conn = tpch.StoredTpchConnector("stored", str(tmp_path))
    before = _scan_numbers()
    handles, errors = [], []

    def look():
        try:
            handles.append(conn.metadata().get_table_handle(
                SchemaTableName("tiny", "supplier")))
        except BaseException as e:  # noqa: BLE001 - shown by the assert below
            errors.append(e)

    threads = [threading.Thread(target=look) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "a lookup never came back from the store"
    assert not errors and len(handles) == 4 and len(set(handles)) == 1
    gained = _gained(before)
    assert gained["tpch.store.tables"] == 1
    assert gained["tpch.store.rows"] == 100
    assert gained["tpch.store.bytes"] > 0
    assert METRICS.raw_snapshot("tpch.")["histograms"]["tpch.store_s"]["n"] >= 1


def test_a_named_directory_is_kept_and_found_again(tmp_path):
    from presto_tpu.server.config import FACTORIES

    config = {"tpch.storage-dir": str(tmp_path)}
    first = FACTORIES["tpch"]("warehouse", config)
    assert isinstance(first, tpch.StoredTpchConnector)
    assert isinstance(FACTORIES["tpch"]("plain", {}), tpch.TpchConnector)
    name = SchemaTableName("tiny", "nation")
    before = _scan_numbers()
    first.metadata().get_table_handle(name)
    assert _gained(before)["tpch.store.tables"] == 1
    mark = os.path.join(str(tmp_path), "tiny", "nation", tpch.STORED_MARK)
    assert os.path.isfile(mark)
    # another process's connector over the same directory writes nothing
    before = _scan_numbers()
    second = FACTORIES["tpch"]("warehouse", config)
    second.metadata().get_table_handle(name)
    assert _gained(before).get("tpch.store.tables", 0) == 0
    r = LocalQueryRunner(session=Session(catalog="warehouse", schema="tiny"))
    r.catalogs.register("warehouse", second)
    assert r.execute("select count(*), max(n_nationkey) from nation").rows \
        == [[25, 24]]
    # a write that never reached its mark is made anew
    os.unlink(mark)
    before = _scan_numbers()
    third = FACTORIES["tpch"]("warehouse", config)
    third.metadata().get_table_handle(name)
    assert _gained(before)["tpch.store.tables"] == 1
    files = [f for f in os.listdir(os.path.dirname(mark))
             if f.endswith(".pcol")]
    assert len(files) == 2 and os.path.isfile(mark)   # the seed and the data


def test_an_unknown_schema_or_table_stores_nothing(tmp_path):
    conn = tpch.StoredTpchConnector("stored", str(tmp_path))
    assert conn.metadata().get_table_handle(
        SchemaTableName("nosuch", "nation")) is None
    assert conn.metadata().get_table_handle(
        SchemaTableName("tiny", "nosuch")) is None
    assert os.listdir(str(tmp_path)) == []


def test_the_default_catalogs_directory_goes_with_the_process():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from presto_tpu.runner import LocalQueryRunner\n"
        "from presto_tpu.metadata import Session\n"
        "r = LocalQueryRunner(session=Session(catalog='tpch_files', "
        "schema='tiny'))\n"
        "store = r.metadata.connector('tpch_files').metadata().store\n"
        "assert store._files is None\n"       # nothing is made before a write
        "assert r.execute('select count(*) from region').rows == [[5]]\n"
        "print(store.files().metadata().base)\n" % ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    base = out.stdout.strip().splitlines()[-1]
    assert os.path.basename(base).startswith("presto-tpu-tpch-")
    assert not base.startswith(ROOT)          # nothing lands in the checkout
    assert not os.path.exists(base)           # and nothing outlives the run


def test_a_long_store_parks_no_other_clients_query(monkeypatch):
    """The write runs in the query's own thread, outside every lock of the
    `QueryManager`: while one client's first query stores its table for
    seconds, another client's query is planned, run and answered, and the
    first client's GETs come back every `MAX_WAIT_S` with the query RUNNING."""
    import time

    import presto_tpu.client.dbapi as dbapi
    from presto_tpu.server import PrestoTpuServer, protocol

    hold = threading.Event()
    real = tpch._TableStore._store

    def slow(self, handle, mark):
        hold.wait(20.0)
        return real(self, handle, mark)

    monkeypatch.setattr(tpch._TableStore, "_store", slow)
    srv = PrestoTpuServer(LocalQueryRunner(
        session=Session(catalog="tpch_files", schema="tiny")), port=0)
    srv.start()
    first = {}

    def store_and_count():
        with dbapi.connect(host="127.0.0.1", port=srv.port, user="a",
                           catalog="tpch_files", schema="tiny") as conn:
            cur = conn.cursor()
            t0 = time.perf_counter()
            cur.execute("select count(*) from region")
            first["rows"] = cur.fetchall()
            first["wall"] = time.perf_counter() - t0

    try:
        t = threading.Thread(target=store_and_count)
        t.start()
        time.sleep(0.3)                      # the first query is in its store
        parked = METRICS.counter_value("protocol.long_poll.parked")
        with dbapi.connect(host="127.0.0.1", port=srv.port, user="b",
                           catalog="tpch", schema="tiny") as conn:
            cur = conn.cursor()
            t0 = time.perf_counter()
            cur.execute("select count(*) from nation")
            assert [tuple(r) for r in cur.fetchall()] == [(25,)]
            other = time.perf_counter() - t0
        assert other < protocol.MAX_WAIT_S * 3   # not behind the store
        time.sleep(protocol.MAX_WAIT_S * 2.2)
        # the storing query's GETs keep coming back, a second each
        assert METRICS.counter_value("protocol.long_poll.parked") >= parked + 2
        assert "rows" not in first
        hold.set()
        t.join(60.0)
        assert [tuple(r) for r in first["rows"]] == [(5,)]
        assert first["wall"] > 2.0
    finally:
        hold.set()
        srv.stop()


@pytest.mark.parametrize("users, installed", [(None, True), ("65536", False)])
def test_page_sized_arrays_stay_on_the_heap_unless_the_user_said(
        monkeypatch, users, installed):
    """`import presto_tpu` sets glibc's mmap threshold to a page's size once
    (utils/hostmem.py); a `MALLOC_MMAP_THRESHOLD_` of the user's holds."""
    import ctypes.util

    from presto_tpu.utils import hostmem

    if not ctypes.util.find_library("c"):
        pytest.skip("no libc to ask")
    if users is None:
        monkeypatch.delenv("MALLOC_MMAP_THRESHOLD_", raising=False)
    else:
        monkeypatch.setenv("MALLOC_MMAP_THRESHOLD_", users)
    assert hostmem.install() is installed
    assert hostmem.MMAP_THRESHOLD >= (1 << 22) * 8   # a page's int64 column
