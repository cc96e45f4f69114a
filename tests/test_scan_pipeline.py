"""Streaming scan pipeline: re-batching, byte-bounded prefetch, ordering.

Covers the host->HBM ingest overhaul (ops/scan_pipeline.py): take_rows
partial-chunk semantics, pow2 re-batch capacities with correct tail masks,
reader-pool error propagation and close-while-blocked races, and the
split-parallel pcol read returning rows identical to the serial reader.
"""
import time

import numpy as np
import pytest

from presto_tpu.block import Block, Page
from presto_tpu.connectors.file import FileConnector
from presto_tpu.metadata import Session
from presto_tpu.ops.scan_pipeline import HostChunk, Rebatcher, ScanPipeline
from presto_tpu.runner import LocalQueryRunner
from presto_tpu.types import BIGINT
from presto_tpu.utils.batching import clamp_capacity, take_rows


# ------------------------------------------------------------- take_rows

def test_take_rows_partial_chunk_consumes_exact_prefix():
    a = np.arange(10, dtype=np.int64)
    b = np.arange(10, dtype=np.float64) * 0.5
    pend = [[a, b]]
    first = take_rows(pend, 3)
    assert first[0].tolist() == [0, 1, 2]
    # the consumed prefix must be GONE from pend: the next take starts at 3
    second = take_rows(pend, 4)
    assert second[0].tolist() == [3, 4, 5, 6]
    assert second[1].tolist() == [1.5, 2.0, 2.5, 3.0]
    third = take_rows(pend, 3)
    assert third[0].tolist() == [7, 8, 9]
    assert pend == []


def test_take_rows_partial_views_are_disjoint():
    """The returned prefix and the retained remainder are views over
    disjoint row ranges: writing into one must never leak into the other."""
    a = np.arange(8, dtype=np.int64)
    pend = [[a]]
    first = take_rows(pend, 5)
    first[0][:] = -1  # caller scribbles over its take
    rest = take_rows(pend, 3)
    assert rest[0].tolist() == [5, 6, 7]


def test_take_rows_spans_chunks():
    pend = [[np.arange(3, dtype=np.int64)],
            [np.arange(3, 7, dtype=np.int64)]]
    out = take_rows(pend, 5)
    assert out[0].tolist() == [0, 1, 2, 3, 4]
    assert take_rows(pend, 2)[0].tolist() == [5, 6]


# ------------------------------------------------------------- re-batcher

def _chunk(vals, nulls=None):
    data = np.asarray(vals, dtype=np.int64)
    return HostChunk.build([data], [None if nulls is None
                                    else np.asarray(nulls, dtype=bool)],
                           [BIGINT], [None])


def test_rebatcher_emits_exact_target_pages_then_pow2_tail():
    rb = Rebatcher(256)
    out = []
    out += rb.add(_chunk(range(0, 200)))
    out += rb.add(_chunk(range(200, 400)))   # 400 pending -> one full page
    out += rb.add(_chunk(range(400, 612)))   # 356 pending -> one more full
    assert [rows for _p, _b, rows in out] == [256, 256]
    for page, _b, rows in out:
        assert page.capacity == 256
        assert np.asarray(page.mask).all()
    assert np.asarray(out[0][0].blocks[0].data).tolist() == list(range(256))
    assert np.asarray(out[1][0].blocks[0].data).tolist() == \
        list(range(256, 512))
    tail = rb.flush()
    assert tail is not None
    page, _b, rows = tail
    assert rows == 100
    assert page.capacity == clamp_capacity(100, 256) == 128  # pow2 bucket
    mask = np.asarray(page.mask)
    assert mask[:100].all() and not mask[100:].any()
    assert np.asarray(page.blocks[0].data)[:100].tolist() == \
        list(range(512, 612))
    assert rb.flush() is None


def test_rebatcher_null_masks_cross_chunks():
    rb = Rebatcher(4)
    out = rb.add(_chunk([1, 2], nulls=[True, False]))
    assert out == []
    out = rb.add(_chunk([3, 4, 5]))  # second chunk declares no nulls
    (page, _b, rows), = out
    assert rows == 4
    nulls = np.asarray(page.blocks[0].nulls)
    assert nulls.tolist() == [True, False, False, False]
    tail_page, _b, tail_rows = rb.flush()
    assert tail_rows == 1
    assert not np.asarray(tail_page.blocks[0].nulls).any()


def test_rebatcher_without_nulls_emits_none_mask():
    rb = Rebatcher(4)
    (page, _b, _r), = rb.add(_chunk([1, 2, 3, 4]))
    assert page.blocks[0].nulls is None


# ------------------------------------------------ pipeline: fake sources

class _ChunkSource:
    """split_readers-only source: `specs` is a list of per-reader chunk
    lists; optional per-reader delay exercises out-of-order completion."""

    def __init__(self, specs, delays=None, fail_reader=None):
        self._specs = specs
        self._delays = delays or [0.0] * len(specs)
        self._fail = fail_reader

    def __iter__(self):  # serial fallback unused in these tests
        raise AssertionError("pipeline should use split_readers")

    def close(self):
        pass

    def split_readers(self, target_rows):
        def reader(i):
            def read():
                if self._delays[i]:
                    time.sleep(self._delays[i])
                if self._fail == i:
                    raise RuntimeError(f"reader {i} exploded")
                for c in self._specs[i]:
                    yield c
            return read
        return [reader(i) for i in range(len(self._specs))]


def _drain(pipe):
    pages = []
    deadline = time.monotonic() + 60
    while True:
        p = pipe.next()
        if p is None:
            return pages
        pages.append(p)
        assert time.monotonic() < deadline, "the pipeline never ended"


def test_pipeline_preserves_split_order_under_racing_readers():
    # reader 0 is SLOW and reader 1 fast: output must still be split order
    specs = [[_chunk(range(0, 6))], [_chunk(range(6, 10))]]
    src = _ChunkSource(specs, delays=[0.2, 0.0])
    pipe = ScanPipeline(src, reader_threads=2, target_rows=4,
                        prefetch_bytes=1 << 20)
    pages = _drain(pipe)
    got = np.concatenate(
        [np.asarray(p.blocks[0].data)[np.asarray(p.mask)] for p in pages])
    assert got.tolist() == list(range(10))
    pipe.close()
    stats = pipe.stats()
    assert stats["rows"] == 10 and stats["pages"] == len(pages)


def test_pipeline_byte_budget_backpressure_still_correct():
    # a budget far smaller than the stream forces staged, blocking flow
    specs = [[_chunk(range(i * 5, i * 5 + 5))] for i in range(8)]
    pipe = ScanPipeline(_ChunkSource(specs), reader_threads=4, target_rows=8,
                        prefetch_bytes=64)  # ~one chunk at a time
    pages = _drain(pipe)
    got = np.concatenate(
        [np.asarray(p.blocks[0].data)[np.asarray(p.mask)] for p in pages])
    assert got.tolist() == list(range(40))
    assert [p.capacity for p in pages] == [8, 8, 8, 8, 8]
    pipe.close()


def test_pipeline_reader_error_propagates_to_consumer():
    specs = [[_chunk(range(0, 4))], [_chunk(range(4, 8))]]
    pipe = ScanPipeline(_ChunkSource(specs, fail_reader=1), reader_threads=2,
                        target_rows=4, prefetch_bytes=1 << 20)
    with pytest.raises(RuntimeError, match="reader 1 exploded"):
        _drain(pipe)
    # sticky: later calls keep raising instead of hanging
    with pytest.raises(RuntimeError):
        pipe.next()
    pipe.close()


def test_pipeline_close_while_blocked_joins_threads():
    # tiny budget + a consumer that stops after one page: producers are
    # parked on the byte budget when close() fires; it must stop and JOIN
    # them (the old _Prefetcher.close never joined its daemon thread)
    specs = [[_chunk(range(i * 8, i * 8 + 8))] for i in range(6)]
    pipe = ScanPipeline(_ChunkSource(specs), reader_threads=3, target_rows=8,
                        prefetch_bytes=64)
    assert pipe.next() is not None
    threads = list(pipe._threads)
    assert threads
    pipe.close()
    assert pipe._threads == []  # every stage thread joined
    assert all(not t.is_alive() for t in threads)


def test_pipeline_close_before_start_is_safe():
    pipe = ScanPipeline(_ChunkSource([[_chunk([1])]]), target_rows=4)
    pipe.close()
    assert pipe.stats()["pages"] == 0


class _PageSource:
    """Plain iterable source (no split support): passthrough mode."""

    def __init__(self, pages, fail_after=None):
        self._pages = pages
        self._fail_after = fail_after

    def __iter__(self):
        for i, p in enumerate(self._pages):
            if self._fail_after is not None and i == self._fail_after:
                raise ValueError("source died mid-stream")
            yield p

    def close(self):
        pass


def _page(vals):
    data = np.asarray(vals, dtype=np.int64)
    return Page((Block(BIGINT, data),), np.ones(len(data), dtype=bool))


def test_pipeline_passthrough_preserves_pages():
    pages = [_page([1, 2, 3]), _page([4, 5])]
    pipe = ScanPipeline(_PageSource(pages), reader_threads=4)
    out = _drain(pipe)
    assert [np.asarray(p.blocks[0].data).tolist() for p in out] == \
        [[1, 2, 3], [4, 5]]  # shapes untouched: no split support, no rebatch
    pipe.close()


def test_pipeline_passthrough_error_propagates():
    pipe = ScanPipeline(_PageSource([_page([1])] * 4, fail_after=2))
    with pytest.raises(ValueError, match="died mid-stream"):
        _drain(pipe)
    pipe.close()


# ------------------------------------ split-parallel pcol == serial reader

@pytest.fixture()
def pcol_runner(tmp_path):
    def make(**props):
        r = LocalQueryRunner(session=Session(
            catalog="tpch", schema="tiny",
            properties=dict(page_capacity=1 << 10, **props)))
        r.catalogs.register("store", FileConnector("store", str(tmp_path)))
        return r
    return make


def test_split_parallel_pcol_rows_identical_to_serial(pcol_runner):
    writer = pcol_runner()
    writer.execute("create table store.w.li as select l_orderkey, "
                   "l_quantity, l_shipdate, l_comment from lineitem")
    # several inserts -> several files: re-batching crosses file boundaries
    writer.execute("insert into store.w.li select l_orderkey, l_quantity, "
                   "l_shipdate, l_comment from lineitem where l_orderkey < 500")
    q = ("select l_orderkey, l_quantity, l_comment from store.w.li "
         "where l_quantity < 30")
    pipelined = pcol_runner(scan_pipeline=True).execute(q)
    serial = pcol_runner(scan_pipeline=False).execute(q)
    # identical rows IN ORDER: the reorder buffer makes the parallel read
    # indistinguishable from the serial one
    assert pipelined.rows == serial.rows
    assert len(pipelined.rows) > 0
    assert pipelined.stats and "scan_pipeline" in pipelined.stats


def test_split_reader_setup_is_lazy(pcol_runner, monkeypatch):
    """split_readers must open NO files at pipeline construction: headers
    come from the metadata cache and dictionary remaps defer to the first
    scheduled reader — 1000-file tables must not pay serial per-file setup
    before the first page can flow."""
    import presto_tpu.connectors.file as filemod
    from presto_tpu.spi.connector import Constraint

    r = pcol_runner()
    r.execute("create table store.w.lazy as select l_orderkey, l_comment "
              "from lineitem where l_orderkey < 400")
    conn = r.metadata.connector("store")
    table = conn.metadata().get_table_handle(
        filemod.SchemaTableName("w", "lazy"))
    splits = conn.split_manager().get_splits(table, Constraint.all(), 8)
    cols = list(conn.metadata().get_table_metadata(table).columns)
    src = conn.page_source_provider().create_page_source(
        splits[0], cols, 1 << 10, Constraint.all())
    if src.split_readers(1 << 10) is None:
        pytest.skip("no native pcol: serial path has no split readers")

    opens = []
    real = filemod.PcolFile

    def counting(path, *a, **kw):
        opens.append(path)
        return real(path, *a, **kw)

    monkeypatch.setattr(filemod, "PcolFile", counting)
    readers = src.split_readers(1 << 10)
    assert readers, "expected at least one range reader"
    assert opens == []  # construction touched no files
    chunk = next(iter(readers[0]()))
    assert chunk.rows > 0
    assert opens  # the scheduled reader did the (deferred) open


def test_query_stats_carry_stage_breakdown(pcol_runner):
    r = pcol_runner()
    r.execute("create table store.w.t as select * from nation")
    res = r.execute("select count(*) from store.w.t")
    assert res.rows == [[25]]
    s = res.stats["scan_pipeline"]
    for key in ("read_busy_s", "read_stall_s", "decode_busy_s",
                "decode_stall_s", "upload_busy_s", "upload_stall_s",
                "compute_stall_s", "pages", "rows", "bytes"):
        assert key in s
    assert s["rows"] == 25
