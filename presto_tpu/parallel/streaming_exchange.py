"""Streaming mesh exchange: chunked, overlapped inter-fragment collectives.

The mesh runner's ONE data plane between fragments (parallel/runner.py). No
fragment drains before its consumer starts: the reference's ExchangeClient
pulls pages over HTTP while producers still run
(operator/ExchangeClient.java), and OutputBuffer backpressure bounds what is
in flight. This module is that data plane, TPU-shaped:

- producer drivers of fragment F end in an :class:`ExchangeSinkOperator`
  feeding per-worker CHUNK buffers (fixed pow2 capacity) instead of
  accumulating pages. The capacity is DERIVED, not chosen
  (:func:`derive_chunk_rows`), when the exchange is built: the pow2 of the
  page capacity its producing fragment was planned with (a scan's pages
  and an upstream exchange's receive pages are no longer), no larger than
  what `exchange_inflight_bytes` holds twice over a side, no smaller than
  MIN_CHUNK_ROWS, so the data plane runs at the grain of the pages the
  engine runs at on this platform, and the same query builds the same
  shapes in every run. A page that fits a chunk is never split: it is
  appended by one prefix sum, one index scatter and a gather a column, and
  when what is left of a chunk cannot take the next page whole the chunk
  goes out partly filled (padding on the wire) and the page opens a fresh
  one. Only a page LONGER than its chunk (the tests' override
  `exchange_chunk_rows`, a chunk the bound capped, a carry) is filled to
  the brim with its leftover re-queued (`exchange.refills` counts those,
  `exchange.fills` every fill program of either side);
- an exchange pump thread dispatches ONE compiled shard_map collective per
  chunk; the shape is static per query, so the repartition/broadcast/merge
  program compiles once per (kind, shape) and is reused for every chunk;
- dispatch is double-buffered: the collective for chunk k is issued async
  (XLA dispatch returns futures) and its delivery sync is deferred until
  chunk k+1 has been absorbed and dispatched, so host-side compaction of the
  next chunk overlaps the in-flight collective;
- REPARTITION/MERGE overflow rows (what `repartition_by_pid` would drop)
  come back as same-shape CARRY buffers, re-fed into the next chunk — skewed
  keys are correct by construction, not by worst-case capacity sizing;
- received rows are PACKED per consumer into pages of the shard's length,
  handed over when full (the last one at the stream's end; a stream that
  fits ONE page hands it cut to the pow2 of its rows): a consumer sees
  ceil(rows / length) pages however the pumps' timing cut the chunks, so
  the fragments above an exchange trace the same shapes in every run;
- in-flight bytes are bounded on both sides: producers park (BLOCKED, the
  task executor's poll-able future) when staged + undelivered bytes exceed
  `exchange_inflight_bytes`, mirroring the scan pipeline's byte budget; no
  stage ever holds a full intermediate result.

MERGE exchanges fix their range splitters at the first dispatch and route
every chunk through the same ranges, so worker shards stay globally
disjoint; the consumer fragment's per-worker sort (the bounded re-order the
mesh plan already carries downstream of every MERGE) restores within-worker
order regardless of chunk arrival interleaving.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..block import Block, Dictionary, Page
from ..exec.shared_pools import AGAIN, EXCHANGE_POOL, STEP_WAIT_S, WAIT
from ..ops.local_exchange import LocalExchangeBuffer, LocalExchangeSource
from ..ops.operator import Operator, OperatorContext, OperatorFactory, timed
from ..ops.scan_pipeline import page_nbytes
from ..sql.planner.plan import BROADCAST, GATHER, MERGE, REPARTITION
from ..types import Type
from ..utils import trace
from ..utils.metrics import METRICS
from .mesh import MeshContext, WORKER_AXIS

# ---------------------------------------------------------------------------
# exchange observability + device helpers
# ---------------------------------------------------------------------------

# process-wide aggregate for the multichip dryrun's "no host copies between
# fragments" check: host_uploads counts PAGE DATA crossing host->device in
# the exchange (must stay zero — fragment chains are device-resident);
# zero_backfills counts constant all-zero shards, cached and uploaded at
# most once per (device, dtype, length). Mutate via record_exchange_stat.
EXCHANGE_STATS = {"host_uploads": 0, "zero_backfills": 0, "exchanges": 0}

_STATS_LOCK = threading.Lock()


class ExchangeStatsBook:
    """Per-query exchange counters (rolled into QueryResult.stats["exchange"]
    and flushed to /v1/metrics as `exchange.*`). Thread-safe: producer
    drivers, the pump threads and the runner all write concurrently."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, float] = {}
        self.per_exchange: List[dict] = []

    def bump(self, name: str, delta: float = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + delta

    def add_exchange(self, entry: dict) -> None:
        with self._lock:
            self.per_exchange.append(entry)

    def snapshot(self) -> dict:
        with self._lock:
            out = {k: (round(v, 6) if isinstance(v, float) else v)
                   for k, v in self.counters.items()}
            if self.per_exchange:
                out["per_exchange"] = [dict(e) for e in self.per_exchange]
            return out


def exchange_row_bytes(types: Sequence[Type], has_nulls=()) -> int:
    """Logical bytes one live row of an exchange carries: every column's
    value at its type's declared width (not the padded chunk, not a widened
    device dtype) plus one byte of null mask where the column carries one.
    `exchange.live_bytes` counts delivered rows times this, so it reads the
    same whatever implements the collective."""
    return sum(np.dtype(t.np_dtype).itemsize for t in types) + \
        sum(1 for h in has_nulls if h)


def record_exchange_stat(name: str, delta: int = 1,
                         book: Optional[ExchangeStatsBook] = None) -> None:
    """Bump the process-wide EXCHANGE_STATS counter (under its lock — pump
    threads and the runner mutate concurrently) and, when given, the active
    query's book."""
    with _STATS_LOCK:
        if name in EXCHANGE_STATS:
            EXCHANGE_STATS[name] += delta
    if book is not None:
        book.bump(name, delta)


# cached constant all-zero device shards. LRU-bounded: every distinct
# (device, dtype, length) is a resident device allocation — the pow2 shape
# discipline keeps the key set tiny, and evicting the COLDEST entry (not
# clearing wholesale) keeps the hot chunk templates every _fresh_chunk
# needs resident even when a shape-churning workload cycles past the bound.
_ZEROS_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_ZEROS_CACHE_MAX = 256
_ZEROS_LOCK = threading.Lock()


def _zeros_shard(dev, dtype, L: int, book: Optional[ExchangeStatsBook] = None):
    """Cached all-zero device array (immutable, safely shared as a read-only
    collective input). Pump threads hit this concurrently — LRU
    bookkeeping is not atomic, hence the lock."""
    import jax

    key = (dev, np.dtype(dtype).str, L)
    with _ZEROS_LOCK:
        z = _ZEROS_CACHE.get(key)
        if z is not None:
            _ZEROS_CACHE.move_to_end(key)
            return z
    record_exchange_stat("zero_backfills", 1, book)
    z = jax.device_put(np.zeros(L, dtype=dtype), dev)
    with _ZEROS_LOCK:
        cur = _ZEROS_CACHE.get(key)
        if cur is not None:
            return cur
        while len(_ZEROS_CACHE) >= _ZEROS_CACHE_MAX:
            _ZEROS_CACHE.popitem(last=False)
        _ZEROS_CACHE[key] = z
    return z


def _range_key_for(data, nulls, type_, dictionary, descending: bool,
                   nulls_first: bool):
    """One worker's MERGE routing key (device, eager): the primary ORDER BY
    column mapped to a monotone int64/float64 code — mirrors the local sort's
    transform (ops/topn.py _sort_key_arrays) so range routing and the
    per-worker sort can never disagree on order."""
    import jax.numpy as jnp

    from ..types import is_string

    x = data
    if is_string(type_) and dictionary is not None:
        if hasattr(dictionary, "values"):
            x = jnp.asarray(dictionary.sort_keys())[x]
        elif not getattr(dictionary, "monotonic", False):
            raise NotImplementedError(
                f"distributed ORDER BY over non-monotonic virtual "
                f"dictionary {dictionary!r}")
    if jnp.issubdtype(x.dtype, jnp.floating):
        key = x.astype(jnp.float64)
        lo, hi = -jnp.inf, jnp.inf
    else:
        key = x.astype(jnp.int64)
        info = np.iinfo(np.int64)
        lo, hi = info.min + 1, info.max
    if descending:
        key = -key
    if nulls is not None:
        key = jnp.where(nulls, lo if nulls_first else hi, key)
    return key


def _pow2(n: int, floor: int = 1) -> int:
    return max(1 << (max(int(n), 1) - 1).bit_length(), floor)


# ---------------------------------------------------------------------------
# chunk fill kernel: append a page's live rows to a fixed-capacity chunk
# ---------------------------------------------------------------------------

# the floor of a derived send chunk (rows a worker: the fixed grain the
# exchange ran at before it read one from its input) and the default
# in-flight byte budget (session knob exchange_inflight_bytes overrides)
MIN_CHUNK_ROWS = 1 << 12
DEFAULT_INFLIGHT_BYTES = 1 << 28

# The grain of the mesh's data plane: the longest page (rows) the mesh runner
# asks for where the session names none (parallel/runner.py), and so the
# largest send chunk an exchange derives. Under the platform's page (2^22 on
# an accelerator) because of what four v5e chips gave
# (tools/exchange_grain_sweep.py, PERF.md section 6, PR 37). The sweep ran
# 2^16, 2^18 and 2^20 on a tree whose fills still scattered a column and whose
# collective still sorted: a server's first Q3 from an empty compile cache
# took 277 / 351 / 494 s (the check gives a whole run 360 s) and a warm one
# 3.9 / 4.5 / 5.1 s, RISING with the grain: a page that does not fit a chunk
# is not split, so chunks leave half filled and every padded slot is moved by
# the collective's program and the receive side's pack. 2^16 was the smallest
# grain run, so the sweep does not bracket its best: 2^14, 2^15 and 2^17 are
# not measured, on that tree or this one (PERF.md section 7). On this tree
# 2^16 alone is measured: a first Q3 162 s cold, 2.13 s warm. A session's
# `page_capacity` still wins.
MESH_PAGE_ROWS = 1 << 16


def derive_chunk_rows(page_rows: int, row_bytes: int, n_workers: int,
                      inflight_bytes: int, override: int = 0) -> int:
    """Rows a worker's send chunk holds: the pow2 of `page_rows`, the longest
    page the producing fragment hands over (a page that fits its chunk is
    appended whole, never split), no larger than what `inflight_bytes` holds
    twice over a side (two chunks are in flight for the double buffering,
    each `row_bytes` x rows x `n_workers`), no smaller than MIN_CHUNK_ROWS.
    `override` (the session's `exchange_chunk_rows`, the tests' knob) wins
    over all of it."""
    if override:
        return _pow2(override, floor=64)
    held = int(inflight_bytes) // (2 * max(int(row_bytes), 1) * n_workers)
    bound = 1 << max(held.bit_length() - 1, 0)   # the largest pow2 <= held
    return max(min(_pow2(page_rows), bound), MIN_CHUNK_ROWS)

# per-peer receive floor for the repartition: small, because the chunk shape
# is FIXED per query anyway (no compile-diversity concern) and carry-over
# makes small capacities correct; tiny floors only cost extra dispatches
# under skew
_MIN_STREAM_OUT_CAP = 1 << 6

# ---------------------------------------------------------------------------
# skew-aware repartitioning (the `skew_aware_exchange` session knob)
# ---------------------------------------------------------------------------
#
# PR 5's carry-over made a 99%-one-key partitioned join CORRECT — but every
# hot-key row still hashes to one partition, so one chip does the join while
# the rest idle. The fix is the JSPIM/PRPD shape (PAPERS.md): detect heavy
# hitters, then treat them specially on BOTH sides of the join boundary.
# Each side of an INNER join's REPARTITION pair samples its OWN first chunk
# for heavy-hitter combined keys and freezes the result (exactly like MERGE
# splitters freeze at first dispatch; freeze-before-wait, so the handshake
# can never deadlock). A key hot on one side is then
#
# - SPLIT round-robin across all partitions on the side where it is hot
#   (that side's rows are the volume to spread), and
# - REPLICATED to every partition on the PEER side via an extra all_gather
#   lane in the same collective (its own capacity + carry),
#
# so every (probe row, build row) pair of a hot key meets on exactly one
# partition while the heavy side's rows — and the join work — spread across
# the mesh. A key hot on BOTH sides splits on the build side only (both
# sides derive the same resolution from the frozen sets). Correct for INNER
# joins only — a replicated row would emit spurious unmatched rows under
# LEFT/FULL/semi semantics — which is why the runner wires roles only onto
# REPARTITION pairs feeding an INNER join (parallel/runner._wire_skew).

# hot = a key holding at least this fraction of the first chunk's sampled
# rows; at 0.4 at most two keys can qualify organically — this is a heavy-
# hitter detector, not a frequency histogram
SKEW_HOT_FRACTION = 0.4
# below this many sampled rows the first chunk says nothing about skew
SKEW_MIN_SAMPLE = 64
# static hot-set capacity per side (the membership compare is
# rows x SKEW_MAX_HOT); sets pad with a repeated real key, so membership
# stays exact
SKEW_MAX_HOT = 8

BUILD_SIDE, PROBE_SIDE = "build", "probe"


class SkewCoordinator:
    """The frozen-hot-set handshake between one INNER join's build-side and
    probe-side exchanges. Each side freezes its OWN sample once (at its
    first dispatch, or empty at pump end/teardown so the peer can never
    hang), then waits for the peer before routing anything — the routing
    treatment of every key must be identical across the whole stream."""

    def __init__(self):
        self._freeze_lock = threading.Lock()
        self._events = {BUILD_SIDE: threading.Event(),
                        PROBE_SIDE: threading.Event()}
        self._hot = {BUILD_SIDE: None, PROBE_SIDE: None}

    def freeze(self, side: str, hot_keys) -> None:
        # locked check-then-act: the pump's sample freeze and teardown's
        # empty freeze race on different threads, and a LATER write would
        # flip plan() mid-stream (the one invariant this class exists for)
        with self._freeze_lock:
            if self._events[side].is_set():
                return
            self._hot[side] = np.asarray(hot_keys, dtype=np.int64)
            self._events[side].set()

    def frozen(self, side: str) -> bool:
        return self._events[side].is_set()

    def wait_peer(self, side: str, timeout: float) -> bool:
        peer = PROBE_SIDE if side == BUILD_SIDE else BUILD_SIDE
        return self._events[peer].wait(timeout)

    def plan(self, side: str):
        """-> (spray_keys, replicate_keys) for `side`, both frozen sets
        resolved consistently: build-hot keys split on the build side and
        replicate on the probe side; probe-hot keys (minus any also hot on
        the build side) the other way around."""
        hb, hp = self._hot[BUILD_SIDE], self._hot[PROBE_SIDE]
        hp = np.setdiff1d(hp, hb)
        return (hb, hp) if side == BUILD_SIDE else (hp, hb)


@functools.lru_cache(maxsize=1)
def _append_chunk_jit():
    """(chunk state, page) -> new chunk state, for a page whose live rows
    FIT what is left of the chunk (the pump knows both counts on the host):
    live rows append densely at positions count..count+live-1. One prefix
    sum, one index scatter and a gather a column (exchange.move_rows);
    nothing is left over, so no second pass."""
    import jax
    import jax.numpy as jnp

    from .exchange import move_rows

    def fn(ch_d, ch_n, ch_m, count, pd, pn, pm):
        C, ncols = ch_m.shape[0], len(pd)
        pos = count + jnp.cumsum(pm.astype(jnp.int32)) - 1
        cols, new = move_rows(pd + pn, jnp.where(pm, pos, C), C,
                              into=ch_d + ch_n)
        return tuple(cols[:ncols]), tuple(cols[ncols:]), ch_m | new
    return jax.jit(fn)


@functools.lru_cache(maxsize=128)
def _fill_chunk_jit(ncols: int, C: int):
    """(chunk state, page) -> (new chunk state, leftover page), for a page
    that does NOT fit: longer than its chunk (the `exchange_chunk_rows`
    override, a chunk the in-flight bound capped) or past the end of a
    receive page being packed.

    Live page rows append densely at chunk positions count..count+live-1;
    rows past capacity C compact to the front of same-shape leftover buffers
    (the pump dispatches the full chunk and re-feeds the leftover). The
    chunk buffers never round-trip the host."""
    import jax
    import jax.numpy as jnp

    from .exchange import move_rows

    def fn(ch_d, ch_n, ch_m, count, pd, pn, pm):
        P = pm.shape[0]
        pos = count + jnp.cumsum(pm.astype(jnp.int32)) - 1
        cols, new = move_rows(pd + pn, jnp.where(pm & (pos < C), pos, C), C,
                              into=ch_d + ch_n)
        # a row past the chunk's end is the leftover's row pos - C
        left, left_m = move_rows(
            pd + pn, jnp.where(pm & (pos >= C), pos - C, P), P)
        return (tuple(cols[:ncols]), tuple(cols[ncols:]), ch_m | new,
                tuple(left[:ncols]), tuple(left[ncols:]), left_m)
    return jax.jit(fn)


@functools.lru_cache(maxsize=1)
def _shard_facts_jit():
    """(mask, null masks) -> (live rows, which columns hold a null): what the
    pump reads back of a page or a received shard, as ONE program (taken
    eagerly it was a program a column and two for the count)."""
    import jax
    import jax.numpy as jnp

    def fn(mask, nulls):
        has_nulls = jnp.stack([jnp.any(x) for x in nulls]) if nulls else \
            jnp.zeros(0, dtype=jnp.bool_)
        return jnp.sum(mask.astype(jnp.int32)), has_nulls
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# the per-chunk collective program (compiled once per (kind, shape), reused
# for every chunk of every exchange with that signature)
# ---------------------------------------------------------------------------

# Collective LAUNCH order must be identical on every device: two pump
# threads each dispatching an SPMD program could otherwise enqueue their
# collectives in different orders on different devices — the classic
# concurrent-collective deadlock. Dispatch is async (returns futures), so
# serializing the launch keeps all the overlap while guaranteeing one global
# enqueue order.
COLLECTIVE_DISPATCH_LOCK = threading.Lock()


def _streaming_program(mesh, kind: str, key_idx: Optional[Tuple[int, ...]],
                       ncols: int, W: int, C: int, out_cap: int,
                       range_dtype: Optional[str],
                       skew: Optional[str] = None):
    """-> (program, compiled_now). REPARTITION/MERGE return
    (out_arrays, out_mask, carry_arrays, carry_mask); BROADCAST/GATHER
    return (out_arrays, out_mask) — an all_gather has full capacity, so
    nothing can ever overflow. `skew` selects the REPARTITION heavy-hitter
    variants: "split" sprays hot rows round-robin, "replicate" routes them
    through an all_gather lane (extra hot outputs + a second carry).
    Programs live in the global LRU kernel cache (one compile per
    (mesh, kind, keys, shape, skew), ever)."""
    from ..utils import kernel_cache as kc

    key = ("exchange-stream", mesh, kind, key_idx, ncols, W, C, out_cap,
           range_dtype, skew)
    return kc.get_or_build(
        key, lambda: _build_streaming_program(mesh, kind, key_idx, ncols, W,
                                              C, out_cap, skew))


def _build_streaming_program(mesh, kind: str,
                             key_idx: Optional[Tuple[int, ...]],
                             ncols: int, W: int, C: int, out_cap: int,
                             skew: Optional[str] = None):
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from ..ops.hash_join import combined_key
    from .exchange import (broadcast_gather, gather_to_single, partition_ids,
                           range_partition_ids, repartition_by_pid_with_carry)

    n_arrays = 2 * ncols
    sharded = tuple(P(WORKER_AXIS) for _ in range(n_arrays))

    def _combined(arrays, mask):
        keys = [jnp.where(arrays[ncols + i], 0,
                          arrays[i]).astype(jnp.int64) for i in key_idx]
        return combined_key(keys)

    if kind == REPARTITION and skew == "skew":
        hot_cap = out_cap

        def skew_stage(arrays, mask, spray_keys, spray_n, repl_keys, repl_n,
                       offset):
            n = mask.shape[0]
            ck = _combined(arrays, mask)
            # membership against a FIXED-width key set; empty sets are
            # disabled by their count (padding repeats a real key)
            spray_hot = mask & (spray_n[0] > 0) & jnp.any(
                ck[:, None] == spray_keys[None, :], axis=1)
            repl_hot = mask & (repl_n[0] > 0) & ~spray_hot & jnp.any(
                ck[:, None] == repl_keys[None, :], axis=1)
            # spray: the i-th hot row of this worker's chunk k goes to
            # partition (k + worker + i) mod W — deterministic, balanced,
            # different workers start offset apart
            pid = partition_ids(ck, W)
            hidx = jnp.cumsum(spray_hot.astype(jnp.int32)) - 1
            spray = (offset[0] + lax.axis_index(WORKER_AXIS) + hidx) % W
            pid = jnp.where(spray_hot, spray.astype(jnp.int32), pid)
            base = mask & ~repl_hot
            pid = jnp.where(base, pid, W)
            out, m, carry, cm = repartition_by_pid_with_carry(
                list(arrays), base, pid, W, out_cap)
            # replicate: compact hot rows into a fixed lane and all_gather
            # it — every partition sees every replicated row (each meets
            # the sprayed peer rows its partition holds exactly once)
            hpos = jnp.cumsum(repl_hot.astype(jnp.int32)) - 1
            into = repl_hot & (hpos < hot_cap)
            htgt = jnp.where(into, hpos, hot_cap)
            hmask = jnp.zeros(hot_cap, dtype=jnp.bool_).at[htgt].set(
                into, mode="drop")
            hbufs = [jnp.zeros(hot_cap, dtype=a.dtype).at[htgt].set(
                a, mode="drop") for a in arrays]
            hout, hm = broadcast_gather(hbufs, hmask)
            # replicate-lane overflow: its own carry (same re-feed protocol
            # as the base carry; membership re-resolves at the next chunk)
            hover = repl_hot & ~into
            hcpos = jnp.cumsum(hover.astype(jnp.int32)) - 1
            hct = jnp.where(hover, hcpos, n)
            hcm = jnp.zeros(n, dtype=jnp.bool_).at[hct].set(hover,
                                                            mode="drop")
            hcarry = tuple(jnp.zeros(n, dtype=a.dtype).at[hct].set(
                a, mode="drop") for a in arrays)
            return (tuple(out), m, tuple(hout), hm, tuple(carry), cm,
                    hcarry, hcm)

        smapped = shard_map(
            skew_stage, mesh=mesh,
            in_specs=(sharded, P(WORKER_AXIS), P(), P(), P(), P(), P()),
            out_specs=(sharded, P(WORKER_AXIS), sharded, P(WORKER_AXIS),
                       sharded, P(WORKER_AXIS), sharded, P(WORKER_AXIS)))
        return jax.jit(smapped)

    if kind == MERGE:
        def merge_stage(arrays, mask, range_key, splitters):
            pid = range_partition_ids(range_key, splitters, mask, W)
            out, m, carry, cm = repartition_by_pid_with_carry(
                list(arrays) + [range_key], mask, pid, W, out_cap)
            # the carried range_key is dropped: the pump recomputes it when
            # the carry refills the next chunk (same transform, same answer)
            return tuple(out[:-1]), m, tuple(carry[:-1]), cm

        smapped = shard_map(
            merge_stage, mesh=mesh,
            in_specs=(sharded, P(WORKER_AXIS), P(WORKER_AXIS), P()),
            out_specs=(sharded, P(WORKER_AXIS), sharded, P(WORKER_AXIS)))
        prog = jax.jit(smapped)
    elif kind == REPARTITION:
        def repart_stage(arrays, mask):
            keys = [jnp.where(arrays[ncols + i], 0,
                              arrays[i]).astype(jnp.int64) for i in key_idx]
            pid = jnp.where(mask, partition_ids(combined_key(keys), W), W)
            out, m, carry, cm = repartition_by_pid_with_carry(
                list(arrays), mask, pid, W, out_cap)
            return tuple(out), m, tuple(carry), cm

        smapped = shard_map(
            repart_stage, mesh=mesh,
            in_specs=(sharded, P(WORKER_AXIS)),
            out_specs=(sharded, P(WORKER_AXIS), sharded, P(WORKER_AXIS)))
        prog = jax.jit(smapped)
    else:
        def gather_stage(arrays, mask):
            if kind == BROADCAST:
                out, m = broadcast_gather(list(arrays), mask)
            elif kind == GATHER:
                out, m = gather_to_single(list(arrays), mask)
            else:
                raise AssertionError(kind)
            return tuple(out), m

        smapped = shard_map(
            gather_stage, mesh=mesh,
            in_specs=(sharded, P(WORKER_AXIS)),
            out_specs=(sharded, P(WORKER_AXIS)))
        prog = jax.jit(smapped)
    return prog


class _Closed(Exception):
    """Internal pump-unwind signal for close-while-running teardown."""


# The states of a pump. From start() to the pump's end exactly ONE is open at
# any moment (StreamingExchange._enter), so their seconds add up to the pump's
# life (`pump_s`) and a trace shows, for every moment a chip sat idle inside a
# mesh query, what each exchange's host side was doing:
#   starved       the bounded wait for producer pages
#   sync          blocked in jax.device_get (live counts, the collective's
#                 output, the skew sample): the pump waits for a chip
#   fill          host Python of the send side: page intake, fill programs
#                 enqueued, and the pump loop's own bookkeeping
#   dispatch      _dispatch: assemble, the collective lock, the program call
#   deliver       host Python of the receive side: packs and page cuts
#   backpressure  parked on a consumer queue's byte bound
#   skew_wait     waiting for the peer side's frozen hot-key set
#   queued        yielded and runnable, waiting for a pool worker (no span:
#                 the step that resumes the pump may run on another thread)
# Each but `queued` is a span `presto.exchange.<name> f<fragment>`.
PUMP_STATES = ("starved", "sync", "fill", "dispatch", "deliver",
               "backpressure", "skew_wait", "queued")
(STARVED, SYNC, FILL, DISPATCH, DELIVER, BACKPRESSURE, SKEW_WAIT,
 QUEUED) = PUMP_STATES
# a part OF dispatch, not a state beside it: the wait for
# COLLECTIVE_DISPATCH_LOCK (an arg of the chunk_dispatch span, no span)
LOCK_WAIT = "lock_wait"
_STATE_SPANS = {STARVED: "pump_stall", SYNC: "pump_sync", FILL: "pump_fill",
                DISPATCH: "chunk_dispatch", DELIVER: "chunk_deliver",
                BACKPRESSURE: "pump_backpressure",
                SKEW_WAIT: "pump_skew_wait"}
# a starved pump wakes every STEP_WAIT_S: its shorter stalls stay out of
# the ring (the profiler's trace holds them all)
_STALL_RING_FLOOR_NS = 1_000_000


# ---------------------------------------------------------------------------
# the exchange itself
# ---------------------------------------------------------------------------

class _ChunkState:
    """One worker's in-progress send chunk: fixed-capacity device buffers
    plus the host-tracked fill count (rows are packed densely at the front,
    so `count` fully describes the live prefix)."""

    __slots__ = ("datas", "nulls", "mask", "count", "has_nulls")

    def __init__(self, datas, nulls, mask, count=0, has_nulls=None):
        self.datas = datas
        self.nulls = nulls
        self.mask = mask
        self.count = count
        # receive side only: which columns carried a null in any chunk
        # packed into these buffers
        self.has_nulls = has_nulls


class _QueuedPage:
    """A column batch awaiting absorption into a chunk.

    `live` is None until the batched device_get resolves it. `is_carry`
    marks a re-queued overflow buffer (counted as carry, not input rows);
    `charged_bytes` is EXACTLY what add_page charged against the in-flight
    budget for this batch's source page (0 for leftovers and carry, whose
    backing page was already released or never charged) — releasing the
    same figure keeps the accounting symmetric no matter how widening or
    null-mask materialization changed the device footprint."""

    __slots__ = ("datas", "nulls", "mask", "live", "is_carry",
                 "charged_bytes")

    def __init__(self, datas, nulls, mask, live=None, is_carry=False,
                 charged_bytes=0):
        self.datas = datas
        self.nulls = nulls
        self.mask = mask
        self.live = live
        self.is_carry = is_carry
        self.charged_bytes = charged_bytes


class StreamingExchange:
    """Producer chunk buffers -> per-chunk collective -> consumer queues.

    One instance per fragment boundary. Producer sinks call
    :meth:`add_page` / :meth:`producer_finished`; consumers read the
    per-worker :class:`LocalExchangeBuffer` from :meth:`out_buffer`. The
    pump thread owns all device work between the two."""

    def __init__(self, mesh: MeshContext, fragment_id: int, kind: str,
                 key_idx: Optional[List[int]], types: Sequence[Type],
                 dicts: Sequence[Optional[Dictionary]],
                 orderings=None, chunk_rows: int = 0,
                 inflight_bytes: int = 0, page_capacity: int = 0,
                 book: Optional[ExchangeStatsBook] = None,
                 pool_key: Optional[str] = None, memory=None):
        self.mesh = mesh
        self.fragment_id = fragment_id
        self.kind = kind
        self.key_idx = tuple(key_idx) if key_idx is not None else None
        self.types = list(types)
        self.dicts = list(dicts)
        self.orderings = orderings
        self.book = book
        W = mesh.n_workers
        self.W = W
        self.inflight_bytes = int(inflight_bytes or DEFAULT_INFLIGHT_BYTES)
        if not page_capacity:
            from ..metadata import default_page_capacity
            page_capacity = default_page_capacity()
        self.page_capacity = page_capacity
        # the send chunk (rows a worker), fixed HERE from what the planner
        # knows: the page capacity the producing fragment was planned with
        # bounds a scan's pages and an upstream exchange's receive pages
        # (_emit_gen); a page past it takes the leftover path. Not from the
        # first page that arrives: which worker's page that is, is timing,
        # and another C is another set of programs. `chunk_rows` given = the
        # tests' override
        self.chunk_rows = derive_chunk_rows(
            page_capacity, exchange_row_bytes(self.types), W,
            self.inflight_bytes, int(chunk_rows or 0))
        if kind in (REPARTITION, MERGE):
            # per-peer receive slice: 2x the balanced share, floored low —
            # overflow carries over, so this only trades dispatch count
            # against padding bandwidth, never correctness
            self.out_cap = min(self.chunk_rows,
                               _pow2(-(-2 * self.chunk_rows // W),
                                     floor=_MIN_STREAM_OUT_CAP))
        else:
            self.out_cap = self.chunk_rows
        self._cv = threading.Condition()
        self._inbox: List[List[Page]] = [[] for _ in range(W)]
        self._inbox_bytes = 0
        self._open_producers: Optional[int] = None
        self._error: Optional[BaseException] = None
        self._closed = False
        # consumer queues: byte-bounded so a slow consumer backpressures the
        # pump (and through it the producers) instead of buffering the world
        per_worker_bytes = max(self.inflight_bytes // (2 * W), 1 << 16)
        self._out = [LocalExchangeBuffer(n_producers=1,
                                         max_bytes=per_worker_bytes)
                     for _ in range(W)]
        # receive side: per shard length, each consumer's page being packed
        # (pump-thread private, see _deliver_part)
        self._recv: Dict[int, List[Optional[_ChunkState]]] = {}
        self._pages_to = [0] * W   # pages handed to each consumer so far
        self._pump: Optional[threading.Thread] = None
        # pool_key set: the pump runs as generator steps on the process-wide
        # EXCHANGE_POOL under the query's fairness slot; None = a dedicated
        # pump thread (the shared_pools=False oracle)
        self._pool_key = pool_key
        self._pool = None
        self._pump_started = False
        self._pump_done = threading.Event()
        # per-query memory context: in-flight bytes (staged producer pages +
        # delivered-unconsumed consumer queues) reserve as user memory so
        # exchange buffering competes with operator state in the query pool
        self._memory = memory
        self._mem_lock = threading.Lock()
        # owning query's flight recorder (re-bound by the pump thread; pool
        # steps re-bind what was captured at submit)
        self._traced = trace.capture()
        self._finished_ok = False
        # skew-aware routing (wired by parallel/runner._wire_skew onto the
        # REPARTITION pair feeding an INNER join): "detect" samples + splits
        # hot build keys, "replicate" fans hot probe rows to all partitions
        self._skew: Optional[SkewCoordinator] = None
        self._skew_role: Optional[str] = None
        # stats (pump-thread private until publish). partition_rows counts
        # DELIVERED live rows per consumer partition — the observable proof
        # that a skewed key spread instead of landing on one worker
        self.stats = {"fragment": fragment_id, "kind": kind,
                      "chunk_rows": self.chunk_rows, "out_cap": self.out_cap,
                      "chunks": 0, "overlap_chunks": 0, "rows_in": 0,
                      "rows_out": 0, "pages_out": 0, "live_bytes": 0,
                      "carry_rows": 0, "compiles": 0,
                      # fill programs the pump ran (pages appended to send
                      # chunks + received shards packed) and, of the send
                      # side's, those that left a leftover to feed again
                      "fills": 0, "refills": 0, "overlap_s": 0.0,
                      "partition_rows": [0] * W, "hot_keys": 0,
                      "replicated_rows": 0}
        # the pump's state clock (_enter): nanoseconds a state, the state
        # open now and since when, and its span where it has one
        self._state_ns = dict.fromkeys(PUMP_STATES + (LOCK_WAIT,), 0)
        self._state: Optional[str] = None
        self._state_at = 0
        self._started_at = 0
        self._span = None
        self._span_names = {st: f"{name} f{fragment_id}"
                            for st, name in _STATE_SPANS.items()}

    # ------------------------------------------------------------- lifecycle

    def set_skew(self, role: str, coordinator: SkewCoordinator) -> None:
        """Attach a skew side BEFORE start(): "build" or "probe" of the
        INNER join this REPARTITION pair feeds. Both sides sample + freeze
        their own first chunk and handle the peer's hot keys."""
        assert role in (BUILD_SIDE, PROBE_SIDE), role
        self._skew_role = role
        self._skew = coordinator

    def start(self, n_producers: int) -> None:
        """Called once all producer sinks are created (driver instantiation
        precedes execution, so the count is exact before any page flows)."""
        with self._cv:
            self._open_producers = n_producers
            self._cv.notify_all()
        record_exchange_stat("exchanges", 1, self.book)
        self._pump_started = True
        self._started_at = self._enter(QUEUED)
        if self._pool_key:
            self._pool = EXCHANGE_POOL.client(self._pool_key)
            self._pool.submit(self._pump_steps())
            return
        self._pump = threading.Thread(
            target=self._pump_loop, daemon=True,
            name=f"exchange-pump-f{self.fragment_id}")
        self._pump.start()

    def close(self, error: Optional[BaseException] = None) -> None:
        """Tear down: wake every blocked party, poison the consumer queues
        (so a consumer blocked mid-stream raises instead of silently seeing
        a truncated input) and wait for the pump to retire. Idempotent; a
        no-op after a clean pump finish except for the bounded wait."""
        with self._cv:
            self._closed = True
            if error is not None and self._error is None:
                self._error = error
            self._cv.notify_all()
        if self._skew is not None:
            # the peer must never park forever on a torn-down exchange: an
            # empty freeze keeps it on plain hash routing
            self._skew.freeze(self._skew_role, np.zeros(0, dtype=np.int64))
        # poison BEFORE joining: a pump blocked on a full consumer queue (or
        # a consumer blocked on an empty one) wakes through the buffer's own
        # condition, not the exchange's
        if error is not None or not self._finished_ok:
            exc = error or RuntimeError(
                f"streaming exchange (fragment {self.fragment_id}) closed "
                "before its stream completed")
            for b in self._out:
                b.poison(exc)
        if self._pump is not None:
            self._pump.join(timeout=10.0)
        elif self._pump_started:
            self._pump_done.wait(timeout=10.0)
        if self._pool is not None:
            self._pool.release()
            self._pool = None
        if self._memory is not None:
            with self._mem_lock:
                self._memory.close()  # reservation dies with the exchange

    # ---------------------------------------------------------- producer api

    def add_page(self, worker: int, page: Page) -> None:
        with self._cv:
            if self._error is not None:
                raise RuntimeError(
                    f"streaming exchange (fragment {self.fragment_id}) "
                    f"failed") from self._error
            if self._closed:
                raise RuntimeError(
                    f"streaming exchange (fragment {self.fragment_id}) "
                    "is closed")
            self._inbox[worker].append(page)
            self._inbox_bytes += page_nbytes(page)
            self._cv.notify_all()
        # over-budget raises HERE, on the producer driver: the query dies
        # with the memory-limit error instead of buffering past its pool
        self._charge_memory()

    def has_capacity(self) -> bool:
        """Producer backpressure poll. True also on error/close so parked
        sinks wake and surface the failure from add_input."""
        if self._error is not None or self._closed:
            return True
        out_bytes = sum(b.buffered_bytes() for b in self._out)
        with self._cv:
            return self._inbox_bytes + out_bytes < self.inflight_bytes

    def producer_finished(self) -> None:
        with self._cv:
            if self._open_producers is not None:
                self._open_producers -= 1
            self._cv.notify_all()

    # ---------------------------------------------------------- consumer api

    def out_buffer(self, worker: int) -> LocalExchangeBuffer:
        return self._out[worker]

    # -------------------------------------------------------------- the pump

    def _enter(self, state: Optional[str], **args) -> Optional[int]:
        """Close the pump's open state and open `state` (None: the pump has
        ended). The ONE place the pump reads the clock: the nanoseconds since
        the last change go to the state that was open, so the states add up
        to the pump's life exactly. -> that clock reading (None where the
        state is open already: a run of fills is one `fill`).

        A state with a span opens it HERE, on the thread that runs the step,
        and every `yield` is preceded by QUEUED (_yield), which has none: no
        span stays open on a pool thread. With no recorder bound
        `trace.span` hands out the shared no-op span. The wait for the
        collective lock lies INSIDE dispatch: it leaves the chunk_dispatch
        span open and becomes its `lock_wait_us`."""
        was = self._state
        if state == was:
            return None
        now = time.perf_counter_ns()
        ns = now - self._state_at
        if was is not None:
            self._state_ns[was] += ns
        if was == LOCK_WAIT:
            self._span.note(lock_wait_us=ns // 1000)
        self._state, self._state_at = state, now
        if {was, state} == {DISPATCH, LOCK_WAIT}:
            return now
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        name = self._span_names.get(state)
        if name is not None:
            self._span = trace.span(
                trace.EXCHANGE, name,
                min_ns=_STALL_RING_FLOOR_NS if state == STARVED else 0,
                **args)
            self._span.__enter__()
        return now

    def _yield(self, status: str, resume: str, **args):
        """One `yield` of the pump to its scheduler: QUEUED from the yield
        to the statement after it, then `resume`."""
        self._enter(QUEUED)
        yield status
        self._enter(resume, **args)

    def _pump_loop(self) -> None:
        """Dedicated-thread scheduler (shared_pools=False): drain the pump
        generator; its internal bounded waits provide the blocking cadence."""
        with trace.bound(*self._traced):
            for _ in self._pump_steps():
                pass

    def _pump_steps(self):
        """The pump's outer guard as a generator: one logic, two schedulers
        (a dedicated thread, or steps on the shared EXCHANGE_POOL under the
        query's fairness slot)."""
        try:
            self._enter(FILL)
            yield from self._pump_gen()
        except _Closed:
            pass  # close() already poisoned the consumer side
        except BaseException as e:  # noqa: BLE001 - relayed to both sides
            with self._cv:
                if self._error is None:
                    self._error = e
                self._cv.notify_all()
            for b in self._out:
                b.poison(e)
        else:
            self._finished_ok = True
            for b in self._out:
                b.producer_finished()
        finally:
            if self._skew is not None:
                # a stream that ended without dispatching a single chunk
                # (zero rows) has no skew to report — freeze empty so the
                # peer proceeds on plain hash routing
                self._skew.freeze(self._skew_role,
                                  np.zeros(0, dtype=np.int64))
            # even an interrupted pump (close mid-flush, producer error)
            # publishes what it measured — chunk counts bumped at dispatch
            # must never appear without their overlap/stall attribution
            self.stats["pump_s"] = \
                (self._enter(None) - self._started_at) / 1e9
            self._publish_stats()
            self._pump_done.set()

    def _check_live(self) -> None:
        with self._cv:
            if self._error is not None:
                raise self._error
            if self._closed:
                raise _Closed()

    def _charge_memory(self) -> None:
        """Publish staged + delivered-unconsumed bytes into the query memory
        context (producer drivers and the pump both call this — hence the
        dedicated lock). Raises the pool's limit exception when over
        budget; callers let it propagate so the query fails loudly."""
        m = self._memory
        if m is None:
            return
        out_bytes = sum(b.buffered_bytes() for b in self._out)
        with self._cv:
            inbox = self._inbox_bytes
        with self._mem_lock:
            m.set_bytes(inbox + out_bytes)

    def _pump_gen(self):
        W = self.W
        devices = self.mesh.devices
        state = [self._fresh_chunk(w) for w in range(W)]
        # (datas, nulls, mask, live_or_None) pages awaiting absorption; a
        # None live count is resolved in the next batched device_get
        queue: List[List[list]] = [[] for _ in range(W)]
        pending_delivery = None
        self._splitters = None
        self._range_dtype = None

        while True:
            # ---- wait for pages / completion ------------------------------
            with self._cv:
                idle = not any(self._inbox)
            if pending_delivery is not None and idle:
                # the pump is about to park: hand the in-flight chunk to the
                # consumers now instead of letting it ride until the next
                # dispatch (double buffering must never become starvation)
                yield from self._deliver_gen(pending_delivery)
                pending_delivery = None
            with self._cv:
                waited = False
                if not any(self._inbox) and \
                        (self._open_producers is None or
                         self._open_producers > 0) and \
                        self._error is None and not self._closed:
                    # ONE bounded wait per step, not wait-until-work: a
                    # starved pump frees its pool worker every STEP_WAIT_S
                    self._enter(STARVED)
                    self._cv.wait(timeout=STEP_WAIT_S)
                    waited = True
                drained = self._inbox
                self._inbox = [[] for _ in range(W)]
                producers_done = (self._open_producers is not None and
                                  self._open_producers <= 0)
            self._check_live()
            if waited and not any(drained) and not producers_done:
                # still starved: park, other queries' pumps run (a wake
                # with nothing to do stays ONE pump_stall, no fill)
                yield from self._yield(WAIT, STARVED)
                continue
            self._enter(FILL)

            # ---- ingest drained pages into the absorb queues --------------
            for w in range(W):
                for p in drained[w]:
                    queue[w].append(self._page_columns(p, devices[w]))

            # ---- absorb, dispatching whenever a chunk fills ---------------
            pending_delivery = yield from self._absorb_gen(
                state, queue, pending_delivery)

            if producers_done and not any(queue) and \
                    not any(s.count for s in state):
                break
            if producers_done and not any(self._inbox):
                # flush: drain partial chunks (and any carry they generate)
                while any(queue) or any(s.count for s in state):
                    self._check_live()
                    pending_delivery = yield from self._absorb_gen(
                        state, queue, pending_delivery, flush=True)
                break
        if pending_delivery is not None:
            yield from self._deliver_gen(pending_delivery)
        # the stream is complete: hand over each consumer's partial page
        for L, packing in self._recv.items():
            for w, acc in enumerate(packing):
                if acc is not None and acc.count:
                    self._enter(DELIVER, chunk=self.stats["chunks"])
                    yield from self._emit_gen(w, acc, L,
                                              self.stats["chunks"], last=True)
                packing[w] = None

    # ------------------------------------------------------------ page intake

    def _page_columns(self, page: Page, dev) -> list:
        """Page -> [datas tuple, nulls tuple, mask, live_count(None=unknown)]
        on the worker's device, widened to the exchange's declared types.
        Host-sourced (numpy) pages are uploads the multichip dryrun's
        device-residency assertion exists to catch, so they are counted."""
        import jax
        import jax.numpy as jnp

        if isinstance(page.mask, np.ndarray) or \
                any(isinstance(b.data, np.ndarray) for b in page.blocks):
            record_exchange_stat("host_uploads", 1, self.book)
        datas, nulls = [], []
        for c in range(len(self.types)):
            dt = np.dtype(self.types[c].np_dtype)
            b = page.blocks[c]
            datas.append(jax.device_put(jnp.asarray(b.data).astype(dt), dev))
            nraw = b.nulls if b.nulls is not None else \
                _zeros_shard(dev, bool, page.capacity, self.book)
            nulls.append(jax.device_put(jnp.asarray(nraw), dev))
        mask = jax.device_put(jnp.asarray(page.mask), dev)
        return _QueuedPage(tuple(datas), tuple(nulls), mask,
                           charged_bytes=page_nbytes(page))

    def _resolve_lives(self, queue, include_carry: bool = True) -> None:
        """Fill in unknown live counts with ONE batched device_get.

        ``include_carry=False`` defers the carry buffers: their counts are
        OUTPUTS of the in-flight collective, so syncing them immediately
        would stall chunk k+1's host-side fill behind collective k — the
        absorb loop resolves them only when a carry entry is actually
        reached (by which point the collective has usually drained)."""
        import jax

        unknown = [entry for q in queue for entry in q
                   if entry.live is None and
                   (include_carry or not entry.is_carry)]
        if not unknown:
            return
        facts = _shard_facts_jit()
        counts = [facts(e.mask, ())[0] for e in unknown]
        self._enter(SYNC, of="carry" if include_carry else "live")
        counts = jax.device_get(counts)
        self._enter(FILL)
        for e, n in zip(unknown, counts):
            e.live = int(n)
            if e.is_carry:  # a re-queued carry buffer, not a producer page
                self.stats["carry_rows"] += int(n)

    def _fresh_chunk(self, w: int, C: int = 0) -> _ChunkState:
        dev = self.mesh.devices[w]
        C = C or self.chunk_rows
        datas = tuple(_zeros_shard(dev, t.np_dtype, C, self.book)
                      for t in self.types)
        nulls = tuple(_zeros_shard(dev, bool, C, self.book)
                      for _ in self.types)
        return _ChunkState(datas, nulls, _zeros_shard(dev, bool, C, self.book))

    # ---------------------------------------------------------------- absorb

    def _absorb_gen(self, state, queue, pending_delivery,
                    flush: bool = False):
        """Move queued pages into chunk buffers; dispatch whenever a worker's
        next page does not fit what is left of its chunk (or, in flush mode,
        whenever any rows remain at all). A page is never split when it fits
        a chunk: the chunk that cannot take it whole goes out partly filled
        (padding on the wire) and the page opens a fresh one, so the fill is
        one prefix sum, one index scatter and a gather a column. Only a page
        LONGER than its
        chunk (the override, a chunk the in-flight bound capped, a carry) is
        filled to the brim with its leftover re-queued. Returns the
        still-undelivered dispatch."""
        C = self.chunk_rows
        ncols = len(self.types)
        while True:
            self._check_live()
            # resolve producer pages' live counts in one batched transfer;
            # carry counts stay deferred so this never syncs on the
            # in-flight collective
            self._resolve_lives(queue, include_carry=False)
            for w in range(self.W):
                st = state[w]
                while queue[w] and st.count < C:
                    if queue[w][0].live is None:
                        # a carry buffer reached the front: NOW its count is
                        # worth the sync (it gates further progress here)
                        self._resolve_lives(queue)
                    qp = queue[w][0]
                    room = C - st.count
                    if room < qp.live <= C:
                        break   # fits a chunk, not this one: dispatch first
                    queue[w].pop(0)
                    if qp.charged_bytes:
                        self._release_bytes(qp.charged_bytes)
                    if not qp.live:
                        continue
                    self.stats["fills"] += 1
                    absorbed = min(room, qp.live)
                    if qp.live <= room:
                        st.datas, st.nulls, st.mask = _append_chunk_jit()(
                            st.datas, st.nulls, st.mask, st.count,
                            qp.datas, qp.nulls, qp.mask)
                    else:
                        nd, nn, nm, ld, ln, lm = _fill_chunk_jit(ncols, C)(
                            st.datas, st.nulls, st.mask, st.count,
                            qp.datas, qp.nulls, qp.mask)
                        st.datas, st.nulls, st.mask = nd, nn, nm
                        # leftover goes back to the FRONT; its live count is
                        # known arithmetically — no device sync
                        self.stats["refills"] += 1
                        queue[w].insert(0, _QueuedPage(
                            ld, ln, lm, live=qp.live - absorbed,
                            is_carry=qp.is_carry))
                    st.count += absorbed
                    if not qp.is_carry:
                        self.stats["rows_in"] += absorbed
            # a page still queued behind a chunk is one the chunk cannot
            # take: full, or too little room for it whole
            must_dispatch = any(queue)
            if not must_dispatch and flush and any(s.count for s in state):
                must_dispatch = True
            if not must_dispatch:
                return pending_delivery
            if self._skew is not None:
                # freeze OUR hot sample first (from the staged chunks about
                # to dispatch), then wait for the peer's — routing is only
                # well-defined once BOTH sets froze: a chunk hashed out
                # before the peer's freeze would miss rows that split or
                # replicate after it. Freeze-before-wait means the two
                # sides can never deadlock; the waits are bounded so the
                # pool step parks and re-arms instead of wedging a worker
                # (a peer that never dispatches freezes empty at pump end
                # or teardown)
                if not self._skew.frozen(self._skew_role):
                    own = self._detect_hot(state)
                    self._skew.freeze(self._skew_role, own)
                    self.stats["hot_keys"] = int(len(own))
                if not self._skew.wait_peer(self._skew_role, timeout=0):
                    self._enter(SKEW_WAIT)
                    while not self._skew.wait_peer(self._skew_role,
                                                   timeout=STEP_WAIT_S):
                        self._check_live()
                        yield from self._yield(WAIT, SKEW_WAIT)
                    self._enter(FILL)
            new_pending = self._dispatch(state, queue)
            # deliver the PREVIOUS chunk now that this one is in flight —
            # its live-count sync overlaps the new in-flight collective
            # (double buffering)
            if pending_delivery is not None:
                yield from self._deliver_gen(pending_delivery)
            pending_delivery = new_pending
            # fairness checkpoint between chunk dispatches
            yield from self._yield(AGAIN, FILL)

    def _release_bytes(self, n: int) -> None:
        """A page absorbed into chunk buffers stops counting against the
        in-flight budget (the chunk buffers are fixed-shape). `n` is the
        exact amount add_page charged for it."""
        with self._cv:
            self._inbox_bytes = max(0, self._inbox_bytes - n)
            self._cv.notify_all()
        self._charge_memory()  # releasing can only shrink the reservation

    # -------------------------------------------------------------- dispatch

    def _assemble(self, shards, L):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.make_array_from_single_device_arrays(
            (self.W * L,), NamedSharding(self.mesh.mesh, P(WORKER_AXIS)),
            shards)

    def _dispatch(self, state, queue):
        """Issue the collective for the current chunks (async) and re-queue
        the carry at the BACK of the absorb queue (its live count is an
        output of this collective — back placement plus the deferred sync
        keep the next chunk's fill off the collective's critical path). The
        caller delivers the PREVIOUS dispatch after this one is in flight —
        its live-count sync overlaps the new collective (double
        buffering)."""
        W, C = self.W, self.chunk_rows
        ncols = len(self.types)
        t0 = self._enter(DISPATCH, kind=self.kind, program="exchange-stream")
        range_keys = None
        if self.kind == MERGE:
            range_keys = self._merge_range_keys(state)
        # skew plan (REPARTITION only; both sets frozen by _absorb_gen's
        # freeze-then-wait handshake before the first dispatch): a non-empty
        # plan swaps in the skew routing program for the whole stream
        skew_mode = None
        skew_args = None
        if self._skew is not None and self.kind == REPARTITION:
            spray, repl = self._skew.plan(self._skew_role)

            def _pad(keys):
                # pad with a REAL member (membership stays exact); all-zero
                # pads of an EMPTY set are disabled by the count arg
                out = np.full(SKEW_MAX_HOT,
                              keys[0] if len(keys) else 0, dtype=np.int64)
                out[:len(keys)] = keys
                return out

            if len(spray) or len(repl):
                skew_mode = "skew"
                skew_args = (
                    _pad(spray), np.asarray([len(spray)], dtype=np.int32),
                    _pad(repl), np.asarray([len(repl)], dtype=np.int32),
                    np.asarray([self.stats["chunks"]], dtype=np.int32))
        dev_arrays = [self._assemble([state[w].datas[c] for w in range(W)], C)
                      for c in range(ncols)]
        dev_arrays += [self._assemble([state[w].nulls[c] for w in range(W)],
                                      C) for c in range(ncols)]
        dev_mask = self._assemble([state[w].mask for w in range(W)], C)
        program, compiled = _streaming_program(
            self.mesh.mesh, self.kind, self.key_idx, ncols, W, C,
            self.out_cap, self._range_dtype, skew=skew_mode)
        if compiled:
            self.stats["compiles"] += 1
            if self.book is not None:
                self.book.bump("collective_compiles")
        hot_out = hot_mask = hot_carry = hot_carry_mask = None
        self._enter(LOCK_WAIT)
        with COLLECTIVE_DISPATCH_LOCK:
            self._enter(DISPATCH)
            if self.kind == MERGE:
                g_rk = self._assemble(range_keys, C)
                out_arrays, out_mask, carry_arrays, carry_mask = program(
                    tuple(dev_arrays), dev_mask, g_rk, self._splitters)
            elif self.kind == REPARTITION and skew_mode == "skew":
                (out_arrays, out_mask, hot_out, hot_mask, carry_arrays,
                 carry_mask, hot_carry, hot_carry_mask) = program(
                    tuple(dev_arrays), dev_mask, *skew_args)
            elif self.kind == REPARTITION:
                out_arrays, out_mask, carry_arrays, carry_mask = program(
                    tuple(dev_arrays), dev_mask)
            else:
                out_arrays, out_mask = program(tuple(dev_arrays), dev_mask)
                carry_arrays = carry_mask = None
        with self._cv:
            producing = (self._open_producers or 0) > 0
        self.stats["chunks"] += 1
        chunk_no = self.stats["chunks"]
        self._span.note(chunk=chunk_no, overlap=producing,
                        fills=self.stats["fills"],
                        refills=self.stats["refills"])
        # the dispatch ends here; re-queueing the carry below is the send
        # side's bookkeeping again
        dt = (self._enter(FILL) - t0) / 1e9
        if producing:
            self.stats["overlap_chunks"] += 1
            self.stats["overlap_s"] += dt
        if self.book is not None:
            self.book.bump("chunks")
            if producing:
                self.book.bump("overlap_chunks")

        # reset chunks to the cached zero shards and re-queue the carry as a
        # front-of-queue pseudo-page (live count resolved in the next batch)
        for w in range(W):
            state[w] = self._fresh_chunk(w)
        if carry_mask is not None:
            # re-queued at the BACK with live=None: producer pages already
            # staged absorb first (their counts are known), and the carry's
            # count — an output of the collective just dispatched — is only
            # synced when the entry is actually reached, so nothing here
            # blocks on the collective. Order across the queue is free:
            # repartition/merge consumers are order-insensitive (hash state
            # or a downstream sort).
            carry_per_worker = self._shards_by_worker(carry_mask, C)
            carry_cols = [self._shards_by_worker(a, C)
                          for a in carry_arrays]
            for w in range(W):
                queue[w].append(_QueuedPage(
                    tuple(carry_cols[c][w] for c in range(ncols)),
                    tuple(carry_cols[ncols + c][w] for c in range(ncols)),
                    carry_per_worker[w], is_carry=True))
        if hot_carry_mask is not None:
            # the replicate variant's second carry: hot rows beyond the
            # all_gather lane's capacity re-feed exactly like base carry
            # (membership re-resolves when the next chunk dispatches)
            hc_per_worker = self._shards_by_worker(hot_carry_mask, C)
            hc_cols = [self._shards_by_worker(a, C) for a in hot_carry]
            for w in range(W):
                queue[w].append(_QueuedPage(
                    tuple(hc_cols[c][w] for c in range(ncols)),
                    tuple(hc_cols[ncols + c][w] for c in range(ncols)),
                    hc_per_worker[w], is_carry=True))
        # the dispatch timestamp + chunk number ride along so delivery can
        # histogram the FULL chunk latency (collective issue -> pages on
        # the consumer queues); the replicate variant's hot lane delivers
        # alongside the regular output
        hot_part = (hot_out, hot_mask) if hot_mask is not None else None
        return (out_arrays, out_mask, hot_part, t0, chunk_no)

    def _merge_range_keys(self, state):
        """Per-worker routing keys for this chunk (eager, on each worker's
        device); splitters fix at the FIRST dispatch so every later chunk
        routes through the same ranges (global disjointness across the
        whole stream, the invariant worker-order concatenation needs)."""
        import jax

        ch, desc, nf = self.orderings[0]
        keys = []
        for w in range(self.W):
            st = state[w]
            keys.append(_range_key_for(st.datas[ch], st.nulls[ch],
                                       self.types[ch], self.dicts[ch],
                                       desc, nf))
        self._range_dtype = str(keys[0].dtype)
        if self._splitters is None:
            samples = []
            for w in range(self.W):
                lw = state[w].count
                if lw:
                    stride = max(1, lw // 128)
                    samples.append(np.asarray(keys[w][:lw:stride][:128]))
            pooled = np.sort(np.concatenate(samples)) if samples else \
                np.zeros(1, dtype=keys[0].dtype)
            self._splitters = np.asarray(
                [pooled[len(pooled) * i // self.W]
                 for i in range(1, self.W)], dtype=pooled.dtype)
        return [jax.device_put(keys[w], self.mesh.devices[w])
                for w in range(self.W)]

    def _detect_hot(self, state) -> np.ndarray:
        """Heavy-hitter sample over the FIRST chunk's staged rows (all
        workers' send buffers — up to W * chunk_rows rows, one batched
        device_get, once per exchange): keys holding >= SKEW_HOT_FRACTION
        of the sample, top-SKEW_MAX_HOT by count. The cheap per-chunk
        top-k the JSPIM line of work runs in hardware, run on the host."""
        import jax
        import jax.numpy as jnp

        from ..ops.hash_join import combined_key

        samples = []
        for w in range(self.W):
            st = state[w]
            if not st.count:
                continue
            keys = [jnp.where(st.nulls[i], 0, st.datas[i]).astype(jnp.int64)
                    for i in self.key_idx]
            # chunks pack live rows at the front: [:count] is the live set
            ck = combined_key(keys)
            self._enter(SYNC, of="hot")
            ck = jax.device_get(ck)
            self._enter(FILL)
            samples.append(np.asarray(ck)[:st.count])
        pooled = np.concatenate(samples) if samples else \
            np.zeros(0, dtype=np.int64)
        if len(pooled) < SKEW_MIN_SAMPLE:
            return np.zeros(0, dtype=np.int64)
        uniq, counts = np.unique(pooled, return_counts=True)
        top = np.argsort(counts)[::-1][:SKEW_MAX_HOT]
        hot = uniq[top][counts[top] >= SKEW_HOT_FRACTION * len(pooled)]
        return hot.astype(np.int64)

    # -------------------------------------------------------------- delivery

    def _shards_by_worker(self, arr, L: int):
        out = [None] * self.W
        for sh in arr.addressable_shards:
            start = sh.index[0].start or 0  # W=1: index is slice(None)
            out[start // L] = sh.data
        return out

    def _deliver_gen(self, dispatched):
        """Compact each worker's received shard and enqueue it as standard
        pow2 pages on the consumer queue (parking on the queue's byte bound
        — the downstream half of the backpressure loop; a full queue parks
        the pump STEP, never a pool worker). The replicate variant's hot
        lane (every worker holds a full copy) delivers through the same
        path as a second part."""
        out_arrays, out_mask, hot_part, dispatch_t0, chunk_no = dispatched
        self._enter(DELIVER, chunk=chunk_no)
        yield from self._deliver_part(out_arrays, out_mask, chunk_no)
        if hot_part is not None:
            hot_arrays, hot_mask = hot_part
            replicated = yield from self._deliver_part(hot_arrays, hot_mask,
                                                       chunk_no)
            self.stats["replicated_rows"] += replicated
        self._charge_memory()
        # per-chunk latency = dispatch issue -> pages delivered; the /v1/
        # metrics percentiles the serving roadmap needs come from here
        METRICS.histogram("exchange.chunk_latency_s",
                          (self._enter(FILL) - dispatch_t0) / 1e9)

    def _deliver_part(self, out_arrays, out_mask, chunk_no: int):
        """One output lane (regular or hot) -> consumer queues. Returns the
        total live rows delivered; per-partition counts accumulate into
        stats["partition_rows"] (the skew-spread observable).

        Each consumer's received rows are PACKED into a page of the shard's
        length and handed over only when it is full (the stream's last page
        at its end): how many rows a chunk carries for a consumer depends on
        when the pump dispatched it, and pages cut at chunk boundaries gave
        the consumer fragment a page count that changed from run to run --
        every join build and aggregation fold above an exchange then traced
        a new shape and compiled again, in a warm query. Packed, a consumer
        sees ceil(rows / length) pages whatever the timing was."""
        import jax

        W, ncols = self.W, len(self.types)
        out_len = out_mask.shape[0] // W
        data_shards = [self._shards_by_worker(out_arrays[c], out_len)
                       for c in range(ncols)]
        null_shards = [self._shards_by_worker(out_arrays[ncols + c], out_len)
                       for c in range(ncols)]
        mask_shards = self._shards_by_worker(out_mask, out_len)
        # ONE host sync for all workers' live counts + null-mask presence
        facts = _shard_facts_jit()
        synced = [facts(mask_shards[w],
                        tuple(null_shards[c][w] for c in range(ncols)))
                  for w in range(W)]
        self._enter(SYNC, of="deliver")
        synced = jax.device_get(synced)
        self._enter(DELIVER, chunk=chunk_no)
        lives = [int(live) for live, _has in synced]
        has_nulls = [has for _live, has in synced]
        packing = self._recv.setdefault(out_len, [None] * W)
        for w in range(W):
            live_w = lives[w]
            if not live_w:
                continue
            hn = np.asarray(has_nulls[w])
            acc = packing[w]
            if acc is None:
                acc = packing[w] = self._fresh_chunk(w, out_len)
                acc.has_nulls = np.zeros(ncols, dtype=bool)
            shard = (tuple(data_shards[c][w] for c in range(ncols)),
                     tuple(null_shards[c][w] for c in range(ncols)),
                     mask_shards[w])
            absorbed = min(out_len - acc.count, live_w)
            self.stats["fills"] += 1
            if live_w == absorbed:
                # the usual case: the shard fits the page being packed
                acc.datas, acc.nulls, acc.mask = _append_chunk_jit()(
                    acc.datas, acc.nulls, acc.mask, acc.count, *shard)
                rest = None
            else:
                # what does not fit opens the next page (same shape, live
                # rows packed at the front): no second fill, so no refill
                nd, nn, nm, ld, ln, lm = _fill_chunk_jit(ncols, out_len)(
                    acc.datas, acc.nulls, acc.mask, acc.count, *shard)
                acc.datas, acc.nulls, acc.mask = nd, nn, nm
                rest = _ChunkState(ld, ln, lm, live_w - absorbed, hn)
            acc.count += absorbed
            acc.has_nulls = acc.has_nulls | hn
            if acc.count >= out_len:
                yield from self._emit_gen(w, acc, out_len, chunk_no)
                packing[w] = rest
            live_bytes = live_w * exchange_row_bytes(self.types, hn)
            self.stats["rows_out"] += live_w
            self.stats["live_bytes"] += live_bytes
            self.stats["partition_rows"][w] += live_w
            if self.book is not None:
                self.book.bump("rows", live_w)
                self.book.bump("live_bytes", live_bytes)
        return sum(lives)

    def _emit_gen(self, w: int, acc: _ChunkState, out_len: int,
                  chunk_no: int, last: bool = False):
        """Enqueue one packed receive buffer for consumer `w` as standard
        pow2 pages (parking on the queue's byte bound: a full queue parks
        the pump STEP, never a pool worker). A consumer whose WHOLE stream
        is this one buffer (`last`, nothing handed before) gets one page cut
        to the pow2 of its rows: the chunk is sized from the fragment's page
        capacity, not from the rows that came, and the operators above a
        small exchange (a final aggregation, a TopN's merge) should trace
        the shape of their input, as they do above a short table's scan.
        Only where the rows a consumer receives do not depend on the pumps'
        timing, so that this length does not either: not under MERGE's
        splitters or a skew role, which sample the first chunk."""
        cap = min(max(self.page_capacity, 1 << 9), out_len)
        if last and not self._pages_to[w] and self.kind != MERGE \
                and self._skew is None:
            cap = min(cap, _pow2(acc.count, floor=MIN_CHUNK_ROWS))
        n_pages = -(-acc.count // cap)
        self.stats["pages_out"] += n_pages
        self._pages_to[w] += n_pages
        whole = cap == out_len   # the usual case: no slice, no program
        for off in range(0, n_pages * cap, cap):
            blocks = []
            for c, (t, d) in enumerate(zip(self.types, self.dicts)):
                nm = None
                if acc.has_nulls[c]:
                    nm = acc.nulls[c] if whole else acc.nulls[c][off:off + cap]
                data = acc.datas[c] if whole else acc.datas[c][off:off + cap]
                blocks.append(Block(t, data, nm, d))
            page = Page(tuple(blocks),
                        acc.mask if whole else acc.mask[off:off + cap])
            if self._out[w].try_put(page):
                continue
            # consumer backpressure: the queue is full, park the step
            self._enter(BACKPRESSURE, to=w)
            while not self._out[w].try_put(page, wait_s=STEP_WAIT_S):
                self._check_live()
                yield from self._yield(WAIT, BACKPRESSURE, to=w)
            self._enter(DELIVER, chunk=chunk_no)

    def _publish_stats(self) -> None:
        if self.book is not None:
            ns = self._state_ns
            state_s = {st: ns[st] / 1e9 for st in PUMP_STATES}
            # the lock wait is a part of the dispatch, as it was counted
            # before it had a name
            state_s[DISPATCH] += ns[LOCK_WAIT] / 1e9
            # seconds by the names they reach /v1/metrics under
            # (`exchange.<name>`): <state>_s, but for the starved wait,
            # which keeps the name it had
            seconds = {"stall_s" if st == STARVED else f"{st}_s": v
                       for st, v in state_s.items()}
            seconds["lock_wait_s"] = ns[LOCK_WAIT] / 1e9
            entry = dict(self.stats)
            entry["state_s"] = {st: round(v, 6) for st, v in state_s.items()}
            for k in ("overlap_s", "pump_s"):
                entry[k] = round(entry[k], 6)
            for k in ("dispatch_s", "stall_s", "lock_wait_s"):
                entry[k] = round(seconds[k], 6)
            entry["partition_rows"] = list(self.stats["partition_rows"])
            if self._skew_role is not None:
                entry["skew_role"] = self._skew_role
            self.book.add_exchange(entry)
            self.book.bump("overlap_s", self.stats["overlap_s"])
            for name, s in seconds.items():
                self.book.bump(name, s)
            self.book.bump("carry_rows", self.stats["carry_rows"])
            self.book.bump("fills", self.stats["fills"])
            self.book.bump("refills", self.stats["refills"])


# ---------------------------------------------------------------------------
# consumer-side operator
# ---------------------------------------------------------------------------

class StreamingExchangeSource(LocalExchangeSource):
    """Consumer endpoint over one worker's chunk queue. Identical protocol
    to a local-exchange source, plus: closing ABANDONS the queue — an
    early-finishing consumer (a satisfied LIMIT above the exchange) must
    not leave a full byte-bounded buffer wedging the pump and, through the
    budget, every producer driver."""

    def close(self) -> None:
        self.buffer.abandon()
        super().close()


# ---------------------------------------------------------------------------
# producer-side operator
# ---------------------------------------------------------------------------

class ExchangeSinkOperator(Operator):
    """Tail of a producer driver: pages flow into the streaming exchange's
    staging (the PartitionedOutputOperator analogue — but the 'serialize +
    enqueue' here is appending a device-page handle). Parks BLOCKED when the
    exchange's in-flight byte budget is full."""

    def __init__(self, context: OperatorContext, exchange: StreamingExchange,
                 types: List[Type]):
        super().__init__(context)
        self.exchange = exchange
        self._types = types
        self._reported = False

    @property
    def output_types(self) -> List[Type]:
        return self._types

    def needs_input(self) -> bool:
        return super().needs_input() and self.exchange.has_capacity()

    def is_blocked(self):
        if self.exchange.has_capacity():
            return None
        return self.exchange.has_capacity  # poll-able: drain frees budget

    @timed("add_input_ns")
    def add_input(self, page: Page) -> None:
        self.context.record_input(page, page.capacity)
        self.exchange.add_page(self.context.worker, page)

    def get_output(self) -> Optional[Page]:
        return None

    def finish(self) -> None:
        if not self._reported:
            self._reported = True
            self.exchange.producer_finished()
        super().finish()

    def close(self) -> None:
        self.finish()
        super().close()

    def is_finished(self) -> bool:
        return self._finishing


class ExchangeSinkOperatorFactory(OperatorFactory):
    """Sink factory for a non-root fragment in streaming mode. `created`
    counts sink operators so the runner can declare the exact producer count
    before execution starts."""

    def __init__(self, operator_id: int, exchange: StreamingExchange,
                 types: List[Type]):
        super().__init__(operator_id, "ExchangeSink")
        self.exchange = exchange
        self.types = types
        self.created = 0

    def create_operator(self, worker: int = 0) -> Operator:
        self.created += 1
        return ExchangeSinkOperator(self.context(worker), self.exchange,
                                    self.types)
