"""Staged host->HBM streaming scan pipeline.

The streaming-scan wall, rebuilt as a pipeline of independent stages (the
host side, not the chip, bounds an out-of-core stream; the cell
`q1_sf10_files` of benchmark/ streams 60M rows a query through it and reads
the stage counters below, PERF.md section 3):

    split readers (pool) -> ordered staging -> re-batch -> upload -> compute
    mmap + slice + remap    bytes-bounded      take_rows    async     driver
    N workers               reorder buffer     pow2 pages   device_put

- READ: a source that can decompose itself into row-range splits
  (``ConnectorPageSource.split_readers``) is read by a pool of reader
  workers concurrently — pcol chunk slicing is embarrassingly parallel
  (the header carries per-chunk offsets). Sources without split support
  run as ONE reader streaming their pages through the same machinery;
  either way this replaces the old one-thread-per-source ``_Prefetcher``.
- ORDER: decoded chunks enter a reorder buffer keyed ``(reader, seq)``;
  the decode stage consumes them in split order, so the pipeline's output
  rows are identical to the serial reader's. Backpressure is by in-flight
  BYTES, not item count, so prefetch depth adapts to chunk size. The chunk
  the decode stage needs next always bypasses a full budget — readers
  completing out of order can therefore never deadlock the pipeline.
- RE-BATCH: chunks accumulate through ``utils/batching.take_rows`` and
  leave as fixed target-row pages (pow2-clamped tail, masked), so device
  kernels see a handful of large static shapes — device occupancy stays
  high regardless of source file layout, and the XLA shape set (hence
  first-run compile count) stays small.
- UPLOAD: a dedicated stage issues a page's ``jax.device_put``s ahead of
  the consumer and waits for them there (one page on the link at a time),
  bounded by the same byte budget applied to uploaded pages the driver has
  not consumed yet.

Scheduling: every stage is written as a GENERATOR whose each step performs
one bounded unit of work (one chunk read / one re-batch / one upload) and
whose blocking points wait at most ``shared_pools.STEP_WAIT_S`` before
yielding. Under the default ``shared_pools`` session knob the generators run
on the process-wide :data:`~presto_tpu.exec.shared_pools.SCAN_POOL` —
N concurrent queries share O(pool) threads with per-query round-robin
fairness; with ``shared_pools=False`` the same generators run on per-query
dedicated threads (the differential-testing oracle, and the pre-serving
behavior bit-for-bit).

Memory: when the planner hands the pipeline a per-query memory context, the
staged + uploaded-unconsumed bytes are accounted as user memory — prefetch
competes with operator state in the query's pool, the cluster OOM killer
sees the whole footprint, and a query whose prefetch blows its budget FAILS
(the limit exception propagates to the consumer) instead of wedging.

Every stage accounts busy/stall seconds into ``utils/metrics.METRICS``
(``scan.pipeline.*``) and into a per-pipeline ``stats()`` dict that the
runner surfaces through ``QueryResult.stats`` — bench rounds attribute the
wall clock to a stage instead of guessing.
"""
from __future__ import annotations

import dataclasses
import os
import queue
import threading
import time
from typing import Callable, List, Optional, Sequence

import jax
import numpy as np

from ..block import Block, Page
from ..exec.shared_pools import AGAIN, SCAN_POOL, STEP_WAIT_S, WAIT
from ..utils import trace
from ..utils.batching import clamp_capacity, take_rows
from ..utils.metrics import METRICS

_DONE = object()   # per-reader end-of-stream marker in the reorder buffer
_EOS = object()    # pipeline end-of-stream on the output queue
_ERR = object()    # error marker on the output queue: (_ERR, exception)

# engine defaults, the single source of truth for every construction path
# (session properties 0/None mean "use these")
DEFAULT_PREFETCH_BYTES = 256 << 20
DEFAULT_READER_THREADS = min(8, os.cpu_count() or 4)
# close()/stat waiters re-check at this cadence while parked
_WAIT_S = 0.1

_STAGE_KEYS = ("read_busy_s", "read_stall_s", "decode_busy_s",
               "decode_stall_s", "upload_busy_s", "upload_stall_s",
               "compute_stall_s")
_COUNT_KEYS = ("chunks", "pages", "rows", "bytes")

# flight-recorder noise floor for STALL spans: sub-100us waits are scheduler
# jitter, not attribution-worthy events (busy spans always record)
_TRACE_STALL_NS = 100_000


def page_nbytes(page: Page) -> int:
    n = page.mask.nbytes
    for b in page.blocks:
        n += b.data.nbytes + (b.nulls.nbytes if b.nulls is not None else 0)
    return n


@dataclasses.dataclass
class HostChunk:
    """Decoded rows of one split: compacted (live-only) host column arrays.

    The unit flowing reader -> re-batcher. ``nulls[i]`` is None when the
    contributing range declared no null mask for column i; the re-batcher
    materializes all-false masks only while a null-bearing chunk is pending.
    """

    cols: List[np.ndarray]
    nulls: List[Optional[np.ndarray]]
    types: Sequence
    dicts: Sequence
    rows: int
    nbytes: int

    @staticmethod
    def build(cols, nulls, types, dicts, rows: Optional[int] = None
              ) -> "HostChunk":
        if rows is None:
            rows = len(cols[0]) if cols else 0
        nbytes = sum(int(c.nbytes) for c in cols) + \
            sum(int(n.nbytes) for n in nulls if n is not None)
        return HostChunk(list(cols), list(nulls), list(types), list(dicts),
                         int(rows), nbytes)


class Rebatcher:
    """Accumulate decoded chunks; emit canonical device-shaped host pages.

    Full pages are exactly ``target_rows`` (all-true mask); the stream tail
    is clamped to its pow2 capacity bucket (utils/batching.clamp_capacity)
    with the usual padding mask. Chunk schema (types/dicts) is pinned by
    the first chunk — split readers of one table share table-wide
    dictionaries by construction.
    """

    def __init__(self, target_rows: int):
        assert target_rows > 0
        self.target = int(target_rows)
        self._pend: List[List[np.ndarray]] = []
        self._rows = 0
        self._ncols: Optional[int] = None
        self._types: Optional[list] = None
        self._dicts: Optional[list] = None
        self._has_nulls: List[bool] = []
        # null masks are materialized LAZILY: a null-free stream (the common
        # TPC-H case) never allocates or concatenates them; the first
        # null-bearing chunk switches the layout on and backfills zeros
        self._nulls_on = False

    @property
    def pending_rows(self) -> int:
        return self._rows

    def add(self, chunk: HostChunk) -> List[tuple]:
        """-> [(host Page, nbytes, rows)] full batches ready to upload."""
        if chunk.rows == 0:
            return []
        if self._ncols is None:
            self._ncols = len(chunk.cols)
            self._types = list(chunk.types)
            self._dicts = list(chunk.dicts)
            self._has_nulls = [False] * self._ncols
        for i, nl in enumerate(chunk.nulls):
            if nl is not None:
                self._has_nulls[i] = True
        if not self._nulls_on and any(nl is not None for nl in chunk.nulls):
            self._nulls_on = True
            for entry in self._pend:  # backfill pending null-free chunks
                n = len(entry[0])
                entry.extend(np.zeros(n, dtype=bool)
                             for _ in range(self._ncols))
        if self._ncols:
            # one pend entry = cols (then null masks once any column went
            # nullable), so a single take_rows consumes them in lockstep
            entry = [np.asarray(c) for c in chunk.cols]
            if self._nulls_on:
                for nl in chunk.nulls:
                    entry.append(np.asarray(nl) if nl is not None
                                 else np.zeros(chunk.rows, dtype=bool))
            self._pend.append(entry)
        self._rows += chunk.rows
        out = []
        while self._rows >= self.target:
            out.append(self._take(self.target, self.target))
        return out

    def flush(self) -> Optional[tuple]:
        """Emit the stream tail (pow2-clamped capacity), or None if empty."""
        if self._rows == 0:
            return None
        return self._take(self._rows, clamp_capacity(self._rows, self.target))

    def _take(self, rows: int, cap: int) -> tuple:
        if self._ncols:
            arrays = take_rows(self._pend, rows)
        else:  # zero-column scan (count(*) pruned projections): mask only
            arrays = []
        self._rows -= rows
        blocks = []
        for i in range(self._ncols or 0):
            data = arrays[i]
            if len(data) < cap:
                data = np.concatenate(
                    [data, np.zeros(cap - len(data), dtype=data.dtype)])
            nl = None
            if self._nulls_on and self._has_nulls[i]:
                nl = arrays[(self._ncols or 0) + i]
                if len(nl) < cap:
                    nl = np.concatenate(
                        [nl, np.zeros(cap - len(nl), dtype=bool)])
            blocks.append(Block(self._types[i], data, nl, self._dicts[i]))
        mask = np.ones(cap, dtype=bool) if rows == cap \
            else np.arange(cap) < rows
        page = Page(tuple(blocks), mask)
        return page, page_nbytes(page), rows


class ScanPipeline:
    """One page source driven through the staged read->re-batch->upload
    pipeline. ``next()`` is the consumer API (None = exhausted); ``close()``
    stops the stages and waits for every stage step to retire (bounded) so a
    producer mid ``jax.device_put`` can never race interpreter teardown."""

    def __init__(self, source, device=None, *,
                 reader_threads: Optional[int] = None,
                 target_rows: Optional[int] = None,
                 prefetch_bytes: Optional[int] = None,
                 rebatch: bool = True,
                 pool_key: Optional[str] = None,
                 memory=None):
        self._source = source
        self._device = device
        self._target = int(target_rows) if target_rows else 0
        self._max_bytes = max(int(prefetch_bytes or DEFAULT_PREFETCH_BYTES),
                              1)
        # pool_key set: stages run on the process-wide SCAN_POOL under the
        # query's fairness slot; None: per-query dedicated threads (oracle).
        # Sources whose reads block indefinitely on EXTERNAL progress
        # (remote exchange streams, another coordinator) cannot honor the
        # pool's bounded-step contract — one would wedge a pool worker and
        # starve every other query's stages, circularly including the very
        # upstream producers the read waits for — so they always run on
        # dedicated threads regardless of the session knob.
        if getattr(source, "external_wait", False):
            pool_key = None
        self._pool = SCAN_POOL.client(pool_key) if pool_key else None
        # per-query memory context (LocalMemoryContext): staged + uploaded
        # bytes are accounted as user memory so prefetch competes with
        # operator state and the OOM killer sees it; None = unaccounted
        self._memory = memory
        # owning query's flight recorder: dedicated stage threads re-bind it
        # (pool steps re-bind what was captured at submit)
        self._traced = trace.capture()
        readers = None
        if rebatch and self._target > 0:
            split = getattr(source, "split_readers", None)
            if split is not None:
                readers = split(self._target)
        if readers is None:
            # no split support: ONE reader streams the source's own pages
            # through the same staged machinery (passthrough, no re-batch)
            self._rebatch = False
            self._readers: List[Callable] = [lambda: iter(source)]
        else:
            self._rebatch = True
            self._readers = list(readers)
        self._n_threads = max(1, min(
            int(reader_threads or DEFAULT_READER_THREADS),
            len(self._readers) or 1))
        self._stop = threading.Event()
        self._cv = threading.Condition()   # reorder buffer + staging budget
        self._buf: dict = {}
        self._staged_bytes = 0
        self._needed = (0, 0)
        self._next_reader = 0
        self._upq: queue.Queue = queue.Queue()  # decode -> upload hand-off
        self._out: queue.Queue = queue.Queue()
        self._ocv = threading.Condition()  # uploaded-but-unconsumed budget
        self._out_bytes = 0
        self._error: Optional[BaseException] = None
        self._stats_lock = threading.Lock()
        self._stats = {k: 0.0 for k in _STAGE_KEYS}
        self._stats.update({k: 0 for k in _COUNT_KEYS})
        self._flushed = False
        self._started = False
        self._live_gens = 0
        self._threads: List[threading.Thread] = []

    # ------------------------------------------------------------- consumer

    def next(self) -> Optional[Page]:
        """Next uploaded page, or None at end of stream. Blocks (accounted
        as compute_stall_s — the device had nothing to chew on)."""
        if not self._started:
            self._start()
        t0 = time.perf_counter_ns()
        with trace.span(trace.SCAN, "compute_stall", min_ns=_TRACE_STALL_NS):
            item = self._out.get()
        self._add("compute_stall_s", (time.perf_counter_ns() - t0) / 1e9)
        if item is _EOS:
            self._out.put(_EOS)  # keep later next() calls returning None
            self._flush_metrics()
            return None
        if isinstance(item, tuple) and item[0] is _ERR:
            self._out.put(item)  # sticky: re-raise on every later call
            self._flush_metrics()
            raise item[1]
        page, nbytes = item
        with self._ocv:
            self._out_bytes -= nbytes
            self._ocv.notify_all()
        self._account()  # releasing bytes never trips the limit
        return page

    def close(self, timeout_s: float = 2.0) -> None:
        """Stop all stages, drain, and wait for every stage generator to
        retire (bounded wait): a stage blocked on a budget observes the stop
        flag within STEP_WAIT_S and exits; anything wedged in a backend call
        is abandoned (daemon threads / dropped pool steps) rather than
        hanging teardown."""
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        with self._ocv:
            self._ocv.notify_all()
        self._upq.put(_EOS)  # wake an upload stage parked on its queue
        try:  # drain so nothing keeps device pages (HBM) alive
            while True:
                self._out.get_nowait()
        except queue.Empty:
            pass
        deadline = time.perf_counter() + timeout_s  # bound on the WHOLE wait
        with self._cv:
            while self._live_gens > 0:
                left = deadline - time.perf_counter()
                if left <= 0:
                    break
                self._cv.wait(min(left, _WAIT_S))
        for t in self._threads:
            t.join(timeout=max(0.0, deadline - time.perf_counter()))
        self._threads = [t for t in self._threads if t.is_alive()]
        if self._pool is not None:
            self._pool.release()
            self._pool = None
        if self._memory is not None:
            self._memory.close()  # reservation drops with the prefetch
        self._flush_metrics()

    def stats(self) -> dict:
        with self._stats_lock:
            return {k: (round(v, 6) if isinstance(v, float) else v)
                    for k, v in self._stats.items()}

    # --------------------------------------------------------------- stages

    def _start(self) -> None:
        self._started = True
        if not self._readers:
            self._out.put(_EOS)
            return
        gens = [self._reader_gen() for _ in range(self._n_threads)]
        gens.append(self._decode_gen())
        gens.append(self._upload_gen())
        with self._cv:
            self._live_gens = len(gens)
        if self._pool is not None:
            for g in gens:
                self._pool.submit(self._guard(g))
            return
        names = [f"scan-read-{i}" for i in range(self._n_threads)]
        names += ["scan-decode", "scan-upload"]
        for g, name in zip(gens, names):
            t = threading.Thread(target=self._drive,
                                 args=(self._guard(g),), name=name,
                                 daemon=True)
            t.start()
            self._threads.append(t)

    def _drive(self, gen) -> None:
        """Dedicated-thread scheduler: the generator's internal bounded
        waits provide the blocking cadence, so draining it step-by-step is
        behaviorally the old thread loop."""
        with trace.bound(*self._traced):
            for _ in gen:
                pass

    def _guard(self, gen):
        """Wrap a stage generator: surface its failure to the consumer and
        retire it from the live count (what close() waits on)."""
        try:
            yield from gen
        except BaseException as e:  # noqa: BLE001 - surfaced to the consumer
            self._fail(e)
        finally:
            with self._cv:
                self._live_gens -= 1
                self._cv.notify_all()

    def _add(self, key: str, value) -> None:
        with self._stats_lock:
            self._stats[key] += value

    def _account(self) -> None:
        """Publish staged + uploaded-unconsumed bytes into the query memory
        context. Raises the pool's limit exception when over budget — the
        stage guard routes it to the consumer, so an over-prefetching query
        dies loudly instead of wedging."""
        m = self._memory
        if m is None:
            return
        with self._stats_lock:
            m.set_bytes(self._staged_bytes + self._out_bytes)

    def _reader_gen(self):
        """Reader stage: claim split readers one at a time, decode their
        chunks, admit them to the reorder buffer under the byte budget."""
        while not self._stop.is_set():
            with self._cv:
                ri = self._next_reader
                if ri >= len(self._readers):
                    return
                self._next_reader = ri + 1
            it = iter(self._readers[ri]())
            seq = 0
            while True:
                t0 = time.perf_counter_ns()
                try:
                    with trace.span(trace.SCAN, "read", reader=ri, seq=seq):
                        item = next(it)
                except StopIteration:
                    break
                self._add("read_busy_s", (time.perf_counter_ns() - t0) / 1e9)
                nbytes = item.nbytes if isinstance(item, HostChunk) \
                    else page_nbytes(item)
                ok = yield from self._stage_put_gen(ri, seq, item, nbytes)
                if not ok:
                    return
                seq += 1
                yield AGAIN  # fairness checkpoint between chunks
            ok = yield from self._stage_put_gen(ri, seq, _DONE, 0)
            if not ok:
                return

    def _stage_put_gen(self, ri: int, seq: int, item, nbytes: int):
        """Admit one decoded item into the reorder buffer under the byte
        budget. The item the decode stage needs NEXT bypasses a full budget
        (deadlock freedom); returns False when the pipeline stopped."""
        key = (ri, seq)
        t0 = time.perf_counter_ns()
        stall = trace.span(trace.SCAN, "read_stall",
                           min_ns=_TRACE_STALL_NS).__enter__()
        while True:
            with self._cv:
                if self._stop.is_set():
                    return False
                if not (self._staged_bytes > 0
                        and self._staged_bytes + nbytes > self._max_bytes
                        and key != self._needed):
                    self._buf[key] = (item, nbytes)
                    self._staged_bytes += nbytes
                    self._cv.notify_all()
                    break
                self._cv.wait(STEP_WAIT_S)
            yield WAIT
        self._account()
        stall.__exit__(None, None, None)
        self._add("read_stall_s", (time.perf_counter_ns() - t0) / 1e9)
        return True

    def _stage_take_gen(self, ri: int, seq: int):
        """In-order take from the reorder buffer; returns None when the
        pipeline stopped."""
        key = (ri, seq)
        t0 = time.perf_counter_ns()
        stall = trace.span(trace.SCAN, "decode_stall",
                           min_ns=_TRACE_STALL_NS).__enter__()
        while True:
            with self._cv:
                self._needed = key
                self._cv.notify_all()
                if key in self._buf:
                    item, nbytes = self._buf.pop(key)
                    self._staged_bytes -= nbytes
                    self._cv.notify_all()
                    break
                if self._stop.is_set():
                    return None
                self._cv.wait(STEP_WAIT_S)
            yield WAIT
        self._account()
        stall.__exit__(None, None, None)
        self._add("decode_stall_s", (time.perf_counter_ns() - t0) / 1e9)
        return item

    def _decode_gen(self):
        """Decode stage: consume the reorder buffer in split order and
        re-batch into device-shaped host pages, handing them to the
        (separate) upload stage so device_put overlaps re-batching."""
        rb = Rebatcher(self._target) if self._rebatch else None
        for ri in range(len(self._readers)):
            seq = 0
            while True:
                item = yield from self._stage_take_gen(ri, seq)
                if item is None:
                    return  # stopped
                if item is _DONE:
                    break
                seq += 1
                if rb is not None:
                    t0 = time.perf_counter_ns()
                    with trace.span(trace.SCAN, "rebatch"):
                        batches = rb.add(item)
                    self._add("decode_busy_s",
                              (time.perf_counter_ns() - t0) / 1e9)
                    self._add("chunks", 1)
                    for page, nbytes, rows in batches:
                        ok = yield from self._emit_gen(page, nbytes, rows)
                        if not ok:
                            return
                else:
                    # live rows from the mask when it is host-side; a
                    # replayed device page would cost a sync to count,
                    # so its capacity stands in
                    rows = int(item.mask.sum()) \
                        if isinstance(item.mask, np.ndarray) \
                        else item.capacity
                    ok = yield from self._emit_gen(item, page_nbytes(item),
                                                   rows)
                    if not ok:
                        return
                yield AGAIN  # fairness checkpoint between chunks
        if rb is not None:
            tail = rb.flush()
            if tail is not None:
                ok = yield from self._emit_gen(*tail)
                if not ok:
                    return
        self._upq.put(_EOS)

    def _emit_gen(self, page: Page, nbytes: int, rows: int):
        """Admit a decoded page to the upload stage under the byte budget
        on uploaded-but-unconsumed pages (the stall here means the CONSUMER
        is the bottleneck — the healthy state)."""
        t0 = time.perf_counter_ns()
        stall = trace.span(trace.SCAN, "upload_stall",
                           min_ns=_TRACE_STALL_NS).__enter__()
        while True:
            with self._ocv:
                if self._stop.is_set():
                    return False
                if not (self._out_bytes > 0
                        and self._out_bytes + nbytes > self._max_bytes):
                    self._out_bytes += nbytes
                    break
                self._ocv.wait(STEP_WAIT_S)
            yield WAIT
        self._account()
        stall.__exit__(None, None, None)
        self._add("upload_stall_s", (time.perf_counter_ns() - t0) / 1e9)
        self._upq.put((page, nbytes, rows))
        return True

    def _upload_gen(self):
        """Upload stage: issue the (async) device_puts, decoupled from
        re-batching so host concatenation and host->device transfer
        overlap."""
        while True:
            try:
                item = self._upq.get(timeout=STEP_WAIT_S)
            except queue.Empty:
                if self._stop.is_set():
                    return
                yield WAIT
                continue
            if item is _EOS or self._stop.is_set():
                if self._error is None:  # a _fail already queued _ERR
                    self._out.put(_EOS)
                return
            page, nbytes, rows = item
            t0 = time.perf_counter_ns()
            with trace.span(trace.SCAN, "upload", rows=rows, bytes=nbytes):
                # issued together, waited for here: upload_busy_s and the
                # span are the seconds the page took to REACH the chip (its
                # reader divides bytes by them), and the bytes the budget
                # releases to the consumer are bytes that have arrived
                dev = jax.block_until_ready(jax.tree.map(
                    lambda a: jax.device_put(a, self._device), page))
            self._add("upload_busy_s", (time.perf_counter_ns() - t0) / 1e9)
            with self._stats_lock:
                self._stats["pages"] += 1
                self._stats["rows"] += rows
                self._stats["bytes"] += nbytes
            self._out.put((dev, nbytes))
            yield AGAIN  # fairness checkpoint between uploads

    def _fail(self, e: BaseException) -> None:
        self._error = e
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        with self._ocv:
            self._ocv.notify_all()
        self._upq.put(_EOS)  # wake an upload stage parked on its queue
        self._out.put((_ERR, e))

    def _flush_metrics(self) -> None:
        with self._stats_lock:
            if self._flushed:
                return
            self._flushed = True
            snap = dict(self._stats)
        METRICS.count_many(snap, prefix="scan.pipeline.")
