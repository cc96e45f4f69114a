"""Hash join on TPU: dense-domain and sort-merge lookup kernels.

Analogue of the reference join stack: HashBuilderOperator.java (build),
PagesIndex.java:74 + PagesHash.java:34 (open-addressed table over row addresses),
LookupJoinOperator.java:53 + JoinProbe (probe), LookupJoinPageBuilder (output),
PartitionedLookupSourceFactory (sharing the table across probe drivers).

TPU re-design: per-row open addressing is scatter-chasing and serial, so the lookup
structure is one of:

1. DENSE — build keys scattered into a dense int32 row-index table over the key
   domain [min, min + pow2 bucket of the range); probing is ONE gather. Every
   single-column TPC-H dimension join (custkey, orderkey, partkey, suppkey,
   nationkey) is a dense-PK join, so this is the common fast path. Chosen at
   BUILD time, from the data (since PR 30): a unique single-key integer build
   whose table stays within DENSE_JOIN_MAX_TABLE_BYTES takes it
   (`JoinBuildOperator._dense_plan` reads the live keys' min, max and count in
   one host sync); nothing sets it.
2. SORTED — build rows sorted by 64-bit key; probe via vectorized binary search
   (jnp.searchsorted over the sorted key array). Handles duplicate build keys via
   [lo,hi) ranges and arbitrary key domains; multi-column keys are packed
   bijectively into the 64 bits where their ranges fit (`_plan_packing`), else go
   through a 64-bit mix with post-match verification on the true key columns
   (collisions only mask rows, never corrupt results). The TPC-H join that
   takes this form is the two-column one unique only as a pair: partsupp on
   (ps_partkey, ps_suppkey), probed by lineitem in Q9 - a direct-address
   table over the packed pair would have 2^32 slots at SF1. Q5's customer
   join on (c_nationkey, c_custkey) is not built on two columns: the build
   is unique on the integer c_custkey alone, so the planner keys it on that
   clause (a direct-address table) and checks the nation as a filter behind
   the probe (`LocalExecutionPlanner._narrowed_clause`).

What a build became and what a probe page searched is counted: `join.builds`
with `.dense`, `.sorted`, `.multikey` and `.narrowed` (keyed on one clause of
several) beside it, `join.probe.pages` with
`.sorted_pages` and `.expanded_pages` (a build that is not unique on the
clauses: the path a fan-out takes), and the build's host seconds as the span `join.build` and the
histogram `join.build_s` (`/v1/metrics`).

Those two are all there is, and no option selects between them.

Join row expansion (output cardinality > input) is the two-pass count-then-emit the
reference's LookupJoinPageBuilder does with position lists: cumsum of match counts,
then per-output-slot inverse search. The unique-build path (declared by the planner
for PK joins) skips all of that and emits exactly one output row per probe row.

The build result is shared through a LookupSourceFactory future: probe drivers block
on it exactly like LookupJoinOperator blocks on lendLookupSource in the reference.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..block import Block, Dictionary, Page
from ..exec.spill import storage_type_for
from ..types import BIGINT, Type
from ..utils import trace
from ..utils.metrics import METRICS
from .operator import Operator, OperatorContext, OperatorFactory, timed
from .sorting import lexsort_fast

INNER, LEFT, RIGHT, FULL, SEMI, ANTI = "inner", "left", "right", "full", "semi", "anti"

# Largest direct-address table (bytes: 4 a key slot) a join build may take in
# place of the sorted form; JoinBuildOperator._dense_plan reads it. Set from
# tools/dense_join_sweep.py on a v5e (PERF.md section 6, PR 30): speed sets no
# bound inside the sweep. Probing one 2^20-row page takes 8.5 ms from every
# table up to 64 MB and 15 ms from 128 MB to 2 GB, against the binary
# search's 284-627 ms (33-74x); the table's build is 1-7x faster than the sort
# it replaces (58 against 426 ms at 2^23 rows). So the bound is memory: 2^28 =
# 1/64 of a v5e's HBM, which admits SF10's orderkey range on one chip (2^26
# slots) and refuses SF100's (2^30 slots, 4.3 GB), and sixteen of which stay
# within the default query_max_memory_bytes the table is charged against.
DENSE_JOIN_MAX_TABLE_BYTES = 1 << 28


def _mix64(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> 33)) * jnp.uint64(0xFF51AFD7ED558CCD)
    x = (x ^ (x >> 33)) * jnp.uint64(0xC4CEB9FE1A85EC53)
    return x ^ (x >> 33)


def combined_key(keys: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Multi-column equi-key -> one int64 (exact for single int key, mixed otherwise)."""
    if len(keys) == 1:
        return keys[0].astype(jnp.int64)
    acc = _mix64(keys[0].astype(jnp.int64))
    for k in keys[1:]:
        acc = _mix64(acc ^ (k.astype(jnp.int64).astype(jnp.uint64) * jnp.uint64(0x9E3779B97F4A7C15)))
    return acc.astype(jnp.int64)


@dataclasses.dataclass
class LookupSource:
    kind: str                          # "dense" | "sorted"
    key_arrays: Tuple[jnp.ndarray, ...]  # true build key columns (compacted)
    payload: Tuple[jnp.ndarray, ...]   # build output columns (compacted)
    payload_meta: List[Tuple[Type, Optional[Dictionary]]]
    build_count: jnp.ndarray           # scalar int32 live rows
    unique: bool
    # dense:
    table: Optional[jnp.ndarray] = None   # (domain,) int32 row idx, -1 empty
    base: int = 0
    # sorted:
    sorted_key: Optional[jnp.ndarray] = None  # (n,) int64 combined keys, invalid rows +inf
    sorted_row: Optional[jnp.ndarray] = None  # (n,) int32 original row index
    # exact multi-key packing (offsets/shifts/widths per key column): when the
    # build key ranges fit 63 bits, the combined key is a bijective pack — no
    # mixed-hash collisions, so every multi-key path gets the exact fast paths
    pack_offsets: Optional[jnp.ndarray] = None
    pack_shifts: Optional[jnp.ndarray] = None
    pack_widths: Optional[jnp.ndarray] = None
    # per-payload-column null masks (None entries = column has no nulls):
    payload_nulls: Tuple = ()
    # whether any live build row had a NULL key (drives null-aware NOT IN semantics)
    has_null_key: bool = False
    # FULL-join side buffer: build rows whose key was NULL never match but must
    # still appear unmatched in the output (tracked separately because matching
    # structures exclude them)
    null_key_payload: Optional[Tuple] = None
    null_key_nulls: Tuple = ()
    null_key_count: int = 0

    @property
    def exact_keys(self) -> bool:
        """True when sorted_key equality implies true key equality: single
        INTEGER key, or a bijectively packed multi-key. Un-packable
        multi-key mixes (ranges beyond 63 bits) AND float single keys
        (combined_key's astype(int64) truncates 1.2 and 1.5 to the same
        sorted key) must range-scan + verify candidates instead of trusting
        the one searchsorted position."""
        if len(self.key_arrays) > 1:
            return self.pack_offsets is not None
        if self.key_arrays and not (
                np.issubdtype(np.dtype(self.key_arrays[0].dtype), np.integer)
                or np.dtype(self.key_arrays[0].dtype) == np.bool_):
            return False
        return True

    def combine_probe(self, probe_keys) -> jnp.ndarray:
        """Probe keys -> the build's combined-key space (packed when exact;
        out-of-range probes map to a negative sentinel that matches nothing)."""
        if self.pack_offsets is None:
            return combined_key(probe_keys)
        return _pack_key(tuple(probe_keys), self.pack_offsets,
                         self.pack_shifts, self.pack_widths)


class LookupSourceFactory:
    """PartitionedLookupSourceFactory analogue: a future the probes block on.

    One slot per worker task — each worker's build pipeline publishes its own
    lookup source and only that worker's probe drivers consume it (the reference
    scopes the factory to a task; here the factory is shared across workers for
    kernel reuse, so the handoff is worker-keyed)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._slots = {}

    def _slot(self, worker: int):
        with self._lock:
            slot = self._slots.get(worker)
            if slot is None:
                slot = self._slots[worker] = [threading.Event(), None]
            return slot

    def set(self, source: LookupSource, worker: int = 0) -> None:
        slot = self._slot(worker)
        slot[1] = source
        slot[0].set()

    def done(self, worker: int = 0) -> bool:
        return self._slot(worker)[0].is_set()

    def get(self, worker: int = 0) -> LookupSource:
        return self._slot(worker)[1]


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------

class JoinBuildOperator(Operator):
    """HashBuilderOperator analogue (sink side of the build pipeline)."""

    def __init__(self, context: OperatorContext, factory: "JoinBuildOperatorFactory"):
        super().__init__(context)
        self.f = factory
        self._pages: List[Page] = []       # device-resident
        self._host_pages: List[Page] = []  # spilled to host RAM (numpy)
        self._disk_runs: List = []         # spilled to disk (exec/spill.py runs)
        self._null_key_pages: List[Page] = []  # FULL join: unmatched-by-construction
        self._saw_null_key = None  # device bool accumulator, synced once at build

    @property
    def output_types(self) -> List[Type]:
        return []

    @timed("add_input_ns")
    def add_input(self, page: Page) -> None:
        self.context.record_input(page, page.capacity)
        for c in self.f.key_channels:
            if page.blocks[c].nulls is not None:
                seen = jnp.any(page.blocks[c].nulls & page.mask)
                self._saw_null_key = seen if self._saw_null_key is None \
                    else (self._saw_null_key | seen)
        self._pages.append(_compact_for_build(page, tuple(self.f.key_channels),
                                              tuple(self.f.payload_channels)))
        if self.f.track_unmatched and \
                any(page.blocks[c].nulls is not None
                    for c in self.f.key_channels):
            # FULL join: keep NULL-key build rows aside — they never match but
            # must surface as unmatched rows in the output. No device sync
            # here: the rows are filtered by mask once at _build.
            nk = jnp.zeros_like(page.mask)
            for c in self.f.key_channels:
                if page.blocks[c].nulls is not None:
                    nk = nk | page.blocks[c].nulls
            nk = nk & page.mask
            sel = page.select_channels(list(self.f.payload_channels))
            self._null_key_pages.append(sel.with_mask(nk))
        self.context.update_revocable(self.revocable_bytes(),
                                      self.start_memory_revoke)

    # spill protocol: one revoke walks the whole ladder (HashBuilderOperator
    # spill states :155-180 analogue). Rung 1 offloads accumulated device
    # pages to host RAM; rung 2 (when the query has a disk tier attached)
    # compacts host pages into PCOL runs via exec/spill.py — _build re-admits
    # disk runs to host and host pages to device before the fused build.
    # Revocable = device pages + disk-eligible host pages; host pages whose
    # dtypes have no pcol storage type stay in RAM (disk is an optimisation
    # rung, never a correctness requirement) and stop counting as revocable.
    def revocable_bytes(self) -> int:
        total = 0
        for p in self._pages + self._null_key_pages:
            if isinstance(p.mask, np.ndarray):
                continue  # already host-resident (revoked earlier)
            rows = p.capacity
            total += rows  # mask
            for b in p.blocks:
                total += rows * np.dtype(b.data.dtype).itemsize
                if b.nulls is not None:
                    total += rows
        if self.context.spill is not None:
            for p in self._host_pages:
                if _page_disk_eligible(p):
                    total += _host_page_bytes(p)
        return total

    def start_memory_revoke(self) -> None:
        self._host_pages.extend(jax.device_get(p) for p in self._pages)
        self._pages = []
        self._null_key_pages = [p if isinstance(p.mask, np.ndarray)
                                else jax.device_get(p)
                                for p in self._null_key_pages]
        if self.context.spill is not None:
            self._spill_host_to_disk()
        self.context.revocable_memory.set_bytes(self.revocable_bytes())

    def _spill_host_to_disk(self) -> None:
        """Rung 2: host pages -> compacted on-disk PCOL runs. Dictionary
        blocks write their code arrays; the Dictionary objects (small,
        shared) ride along in run.meta so the read side rebuilds bit-exact
        Blocks. Ineligible pages are kept in host RAM."""
        mgr = self.context.spill
        keep: List[Page] = []
        for p in self._host_pages:
            if not _page_disk_eligible(p):
                keep.append(p)
                continue
            live = np.flatnonzero(np.asarray(p.mask))
            if len(live) == 0:
                continue  # nothing to rebuild — drop the page
            names, cols, specs = [], [], []
            for i, b in enumerate(p.blocks):
                names.append(f"c{i}")
                cols.append(np.ascontiguousarray(np.asarray(b.data)[live]))
                if b.nulls is not None:
                    names.append(f"n{i}")
                    cols.append(np.ascontiguousarray(
                        np.asarray(b.nulls)[live]))
                specs.append((b.type, b.dictionary, b.nulls is not None))
            self._disk_runs.append(mgr.write_columns(
                names, cols, kind="join", meta={"blocks": specs}))
        self._host_pages = keep

    def get_output(self) -> Optional[Page]:
        return None

    def finish(self) -> None:
        if self._finishing:
            return
        super().finish()
        # partitioned parallel build (PartitionedLookupSourceFactory
        # analogue): N build drivers per worker ingest concurrently; the LAST
        # one to finish merges every driver's collected pages and runs the
        # single fused device build (one sort kernel over the union — on TPU
        # the chip parallelizes the sort, so the drivers' job is overlapping
        # host generation/upload/page prep, which is where build wall goes)
        self.f._builder_done(self)
        self.context.revocable_memory.set_bytes(0)

    def _build(self) -> LookupSource:
        kc = len(self.f.key_channels)
        if self._disk_runs:  # re-admit disk runs first (disk -> host RAM)
            runs, self._disk_runs = self._disk_runs, []
            mgr = self.context.spill
            for run in runs:
                self._host_pages.append(_page_from_run(mgr, run))
                mgr.release(run)
        if self._host_pages:  # re-admit spilled pages (host -> device upload)
            self._pages = self._host_pages + self._pages
            self._host_pages = []
        if not self._pages:
            empty = tuple(jnp.zeros(1, dtype=jnp.int64) for _ in range(kc))
            empty_payload = tuple(jnp.zeros(1, dtype=t.np_dtype)
                                  for (t, _) in self.f.payload_meta)
            return LookupSource(
                kind="sorted", key_arrays=empty, payload=empty_payload,
                payload_meta=self.f.payload_meta, build_count=jnp.asarray(0, jnp.int32),
                unique=True,
                sorted_key=jnp.full(1, np.iinfo(np.int64).max, dtype=jnp.int64),
                sorted_row=jnp.zeros(1, dtype=jnp.int32),
                payload_nulls=tuple(None for _ in self.f.payload_meta))
        # one fused kernel: concat across pages + count + (dense table | key
        # sort). On the device this is ONE dispatch instead of one eager
        # concatenate per column plus a host count sync
        # (operator/PagesHash.java:34's role).
        null_cols = tuple(i for i in range(len(self.f.payload_channels))
                          if any(p.blocks[kc + i].nulls is not None
                                 for p in self._pages))
        # pad the page count to its pow2 bucket with a zero-row dummy so the
        # fused build kernel's trace signature is bounded by O(log pages)
        # distinct counts (a build compiles for 15-30 s on the v5e)
        pages = list(self._pages)
        want = 1 << max(0, (len(pages) - 1).bit_length())
        if want > len(pages):
            # numpy zeros, not jnp: an eager jnp.zeros dispatch compiles a
            # throwaway kernel per dtype; np arrays device_put at the jit call
            p0 = pages[0]
            zb = tuple(Block(b.type,
                             np.zeros((0,), dtype=b.data.dtype),
                             np.zeros((0,), dtype=np.bool_)
                             if b.nulls is not None else None,
                             b.dictionary)
                       for b in p0.blocks)
            zp = Page(zb, np.zeros((0,), dtype=np.bool_))
            pages.extend([zp] * (want - len(pages)))
        pages = tuple(pages)
        dense = self._dense_plan(pages) if kc == 1 else None
        if dense is not None:
            base, domain = dense
            # charged before the table exists, so a query over its limit
            # fails here and not in the allocator; close() releases it
            self.context.user_memory.set_bytes(4 * domain)
            self.context.stats.peak_memory_bytes = max(
                self.context.stats.peak_memory_bytes,
                self.context.revocable_memory.get_bytes() + 4 * domain)
            keys, payload, pnulls, mask, n_dev, table = _fused_build_dense(
                pages, kc, null_cols, np.int64(base), domain)
            src = LookupSource(
                kind="dense", key_arrays=keys, payload=payload,
                payload_meta=self.f.payload_meta,
                build_count=n_dev, unique=self.f.unique, table=table, base=base)
        elif kc == 1:
            keys, payload, pnulls, mask, n_dev, sorted_key, sorted_row = \
                _fused_build_sorted(pages, kc, null_cols)
            src = LookupSource(
                kind="sorted", key_arrays=keys, payload=payload,
                payload_meta=self.f.payload_meta,
                build_count=n_dev, unique=self.f.unique,
                sorted_key=sorted_key, sorted_row=sorted_row)
        else:
            # multi-key: the bijective packing plan needs host min/max
            keys, payload, pnulls, mask, n_dev = _concat_parts(
                pages, kc, null_cols)
            src = _build_sorted(tuple(keys), tuple(payload), mask,
                                n_dev,
                                self.f.payload_meta, self.f.unique)
        src.payload_nulls = tuple(pnulls)
        src.has_null_key = bool(self._saw_null_key) if self._saw_null_key is not None else False
        if self._null_key_pages:
            nmask = np.concatenate([np.asarray(p.mask)
                                    for p in self._null_key_pages])
            keep = np.flatnonzero(nmask)
            cols, nils = [], []
            for i in range(len(self.f.payload_channels)):
                col = np.concatenate([np.asarray(p.blocks[i].data)
                                      for p in self._null_key_pages])
                cols.append(col[keep])
                if any(p.blocks[i].nulls is not None
                       for p in self._null_key_pages):
                    nm = np.concatenate([np.asarray(p.blocks[i].null_mask())
                                         for p in self._null_key_pages])
                    nils.append(nm[keep])
                else:
                    nils.append(None)
            src.null_key_payload = tuple(cols)
            src.null_key_nulls = tuple(nils)
            src.null_key_count = len(keep)
        return src

    def _dense_plan(self, pages) -> Optional[Tuple[int, int]]:
        """(base, domain) of a direct-address table for this build, or None
        to keep the sorted form. The table stores ONE row per key slot and
        has no sorted_row order, so only a unique single-key integer build
        of an INNER/LEFT join takes it (FULL reads sorted_row for its
        unmatched rows), and only while its bytes stay within
        DENSE_JOIN_MAX_TABLE_BYTES. ONE host sync per build reads the live
        keys' [min, max, count]; `domain` is the pow2 bucket of the range,
        so builds whose ranges share a bucket share a compiled program."""
        f = self.f
        dtype = np.dtype(pages[0].blocks[0].data.dtype)
        if not f.unique or f.track_unmatched \
                or not (np.issubdtype(dtype, np.integer)
                        or dtype == np.bool_):
            return None
        base, domain = dense_table_range(pages)
        if 4 * domain > DENSE_JOIN_MAX_TABLE_BYTES:
            return None
        return base, domain

    def is_finished(self) -> bool:
        return self._finishing


def _page_disk_eligible(page: Page) -> bool:
    """Can this host-resident page round-trip through a pcol spill run?
    Every block's storage array must be 1-D with a mapped storage type."""
    for b in page.blocks:
        a = np.asarray(b.data)
        if a.ndim != 1 or storage_type_for(a.dtype) is None:
            return False
    return True


def _host_page_bytes(page: Page) -> int:
    rows = page.capacity
    total = rows  # mask
    for b in page.blocks:
        total += rows * np.dtype(b.data.dtype).itemsize
        if b.nulls is not None:
            total += rows
    return total


def _page_from_run(mgr, run) -> Page:
    """Rebuild a compacted host page from a spill run written by
    JoinBuildOperator._spill_host_to_disk (all-true mask; null masks were
    stored as bool columns, dictionaries rode along in run.meta)."""
    cols = mgr.read_columns(run)
    blocks, i = [], 0
    for (btype, bdict, has_nulls) in run.meta["blocks"]:
        data = cols[i][0]
        i += 1
        nulls = None
        if has_nulls:
            nulls = cols[i][0]
            i += 1
        blocks.append(Block(btype, data, nulls, bdict))
    return Page(tuple(blocks), np.ones(run.rows, dtype=bool))


def _compact_for_build(page: Page, key_channels: Tuple[int, ...],
                       payload_channels: Tuple[int, ...]) -> Page:
    sel = page.select_channels(list(key_channels) + list(payload_channels))
    # null keys never join: mask them out before compaction
    mask = sel.mask
    for i in range(len(key_channels)):
        if sel.blocks[i].nulls is not None:
            mask = mask & ~sel.blocks[i].nulls
    return _compact_jit(sel.with_mask(mask))


_compact_jit = jax.jit(lambda p: p.compact())


def _concat_parts_impl(pages, kc: int, null_cols):
    """Concat compacted build pages into flat key/payload/nulls/mask arrays."""
    keys = tuple(jnp.concatenate([p.blocks[i].data for p in pages])
                 for i in range(kc))
    npayload = len(pages[0].blocks) - kc
    payload = tuple(jnp.concatenate([p.blocks[kc + i].data for p in pages])
                    for i in range(npayload))
    pnulls = tuple(
        jnp.concatenate([p.blocks[kc + i].null_mask() for p in pages])
        if i in null_cols else None
        for i in range(npayload))
    mask = jnp.concatenate([p.mask for p in pages])
    n = jnp.sum(mask.astype(jnp.int32))
    return keys, payload, pnulls, mask, n


_concat_parts = functools.partial(jax.jit, static_argnames=(
    "kc", "null_cols"))(_concat_parts_impl)


@jax.jit
def _live_key_range(parts):
    """-> int64 [min, max, count] of the live keys of compacted build pages
    ((key, mask) pairs): what the host reads, once a build, to choose
    between the direct-address table and the sorted form."""
    i64 = np.iinfo(np.int64)
    lo = [jnp.min(k.astype(jnp.int64), where=m, initial=i64.max)
          for k, m in parts]
    hi = [jnp.max(k.astype(jnp.int64), where=m, initial=i64.min)
          for k, m in parts]
    n = [jnp.sum(m, dtype=jnp.int64) for _, m in parts]
    return jnp.stack([jnp.min(jnp.stack(lo)), jnp.max(jnp.stack(hi)),
                      jnp.sum(jnp.stack(n))])


def dense_table_range(pages) -> Tuple[int, int]:
    """(base, domain) of the direct-address table over single-key build
    pages: the smallest live key and the pow2 bucket of the live range
    (0, 1 when no row is live). The build's one host sync."""
    lo, hi, n = (int(x) for x in np.asarray(_live_key_range(
        tuple((p.blocks[0].data, p.mask) for p in pages))))
    if n == 0:
        return 0, 1
    return lo, 1 << (hi - lo).bit_length()


@functools.partial(jax.jit, static_argnames=("kc", "null_cols", "domain"))
def _fused_build_dense(pages, kc, null_cols, base, domain):
    """`base` is traced and `domain` a pow2 bucket: the trace signature is
    (page count bucket, capacity, domain bucket), whatever the smallest live
    key. The host has checked that every live key lies in
    [base, base + domain), so the int32 cast is exact."""
    keys, payload, pnulls, mask, n = _concat_parts_impl(pages, kc, null_cols)
    key = keys[0]
    idx = (key.astype(jnp.int64) - base).astype(jnp.int32)
    idx = jnp.where(mask, idx, domain)  # dropped
    table = jnp.full(domain, -1, dtype=jnp.int32)
    rows = jnp.arange(key.shape[0], dtype=jnp.int32)
    table = table.at[idx].set(rows, mode="drop")
    return keys, payload, pnulls, mask, n, table


def _sort_build_keys(ck, mask):
    """-> (sorted combined keys with dead rows as +inf at the end, the
    permutation). Goes through lexsort_fast's single-array sort: a
    two-operand argsort costs the TPU compiler about twice as long. Dead
    rows sort by the smallest live key so they cannot widen the packed
    domain."""
    big = jnp.int64(np.iinfo(np.int64).max)
    ck = jnp.where(mask, ck, big)
    order = lexsort_fast((jnp.where(mask, ck, jnp.min(ck)), ~mask))
    return ck[order], order


@functools.partial(jax.jit, static_argnames=("kc", "null_cols"))
def _fused_build_sorted(pages, kc, null_cols):
    keys, payload, pnulls, mask, n = _concat_parts_impl(pages, kc, null_cols)
    return (keys, payload, pnulls, mask, n) + \
        _sort_build_keys(combined_key(keys), mask)


_sorted_kernel_ck = jax.jit(_sort_build_keys)


@jax.jit
def _pack_key(keys, offsets, shifts, widths):
    """Bijective multi-key pack; out-of-range values map to a negative
    sentinel (never equal to any packed build key, which is >= 0)."""
    acc = jnp.zeros(keys[0].shape[0], dtype=jnp.int64)
    oob = jnp.zeros(keys[0].shape[0], dtype=jnp.bool_)
    for i, k in enumerate(keys):
        v = k.astype(jnp.int64) - offsets[i]
        oob = oob | (v < 0) | (v >= (jnp.int64(1) << widths[i]))
        acc = acc | (jnp.clip(v, 0, None) << shifts[i])
    sentinel = jnp.int64(np.iinfo(np.int64).min // 2)
    return jnp.where(oob, sentinel, acc)


def _plan_packing(keys, mask):
    """Host-side packing plan: per-key offsets/shifts/widths, or None when the
    combined ranges exceed 62 bits. One device sync per build (the build
    already syncs its row count)."""
    offsets, widths = [], []
    lo64 = np.iinfo(np.int64)
    for k in keys:
        mn = int(jnp.min(jnp.where(mask, k, jnp.int64(lo64.max))))
        mx = int(jnp.max(jnp.where(mask, k, jnp.int64(lo64.min))))
        if mx < mn:  # no live rows
            mn, mx = 0, 0
        offsets.append(mn)
        widths.append(max((mx - mn).bit_length(), 1))
    if sum(widths) > 62:
        return None
    shifts, acc = [], 0
    for w in reversed(widths):
        shifts.append(acc)
        acc += w
    shifts = list(reversed(shifts))
    return (jnp.asarray(offsets, dtype=jnp.int64),
            jnp.asarray(shifts, dtype=jnp.int64),
            jnp.asarray(widths, dtype=jnp.int64))


def _build_sorted(keys, payload, mask, n, payload_meta, unique) -> LookupSource:
    pack = _plan_packing(keys, mask) if len(keys) > 1 else None
    ck = _pack_key(tuple(keys), *pack) if pack is not None \
        else combined_key(keys)
    sorted_key, sorted_row = _sorted_kernel_ck(ck, mask)
    return LookupSource(kind="sorted", key_arrays=keys, payload=payload,
                        payload_meta=payload_meta,
                        build_count=jnp.asarray(n, jnp.int32), unique=unique,
                        sorted_key=sorted_key, sorted_row=sorted_row,
                        pack_offsets=pack[0] if pack else None,
                        pack_shifts=pack[1] if pack else None,
                        pack_widths=pack[2] if pack else None)


class JoinBuildOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, key_channels: List[int],
                 payload_channels: List[int],
                 payload_meta: List[Tuple[Type, Optional[Dictionary]]],
                 unique: bool = False,
                 track_unmatched: bool = False,
                 narrowed: bool = False):
        super().__init__(operator_id, "JoinBuild")
        # FULL joins need the NULL-key build rows preserved for unmatched output
        self.track_unmatched = track_unmatched
        # keyed on one of its join's clauses, the others checked behind the
        # probe (LocalExecutionPlanner._narrowed_clause): counted, no more
        self.narrowed = narrowed
        self.key_channels = key_channels
        self.payload_channels = payload_channels
        self.payload_meta = payload_meta
        self.unique = unique
        self.lookup_factory = LookupSourceFactory()
        self._builders_lock = threading.Lock()
        self._created = {}   # worker -> [JoinBuildOperator]
        self._finished = {}  # worker -> count

    def create_operator(self, worker: int = 0) -> JoinBuildOperator:
        op = JoinBuildOperator(self.context(worker), self)
        with self._builders_lock:
            self._created.setdefault(worker, []).append(op)
        return op

    def _builder_done(self, op: JoinBuildOperator) -> None:
        """Called by each build driver's finish(). The last finisher for the
        worker merges every sibling's collected pages into its own state and
        publishes the lookup source (drivers are all created before execution
        starts, so the expected count is final before any finish)."""
        w = op.context.worker
        with self._builders_lock:
            self._finished[w] = self._finished.get(w, 0) + 1
            if self._finished[w] < len(self._created[w]):
                return
            siblings = [o for o in self._created[w] if o is not op]
        for o in siblings:
            op._pages.extend(o._pages)
            op._host_pages.extend(o._host_pages)
            op._disk_runs.extend(o._disk_runs)
            op._null_key_pages.extend(o._null_key_pages)
            if o._saw_null_key is not None:
                op._saw_null_key = o._saw_null_key \
                    if op._saw_null_key is None \
                    else (op._saw_null_key | o._saw_null_key)
            o._pages, o._host_pages, o._null_key_pages = [], [], []
            o._disk_runs = []
        keys = len(self.key_channels)
        t0 = time.perf_counter()
        # the build's host reads (dense_table_range, _plan_packing) lie inside
        with trace.span(trace.JOIN, "build", keys=keys,
                        pages=len(op._pages) + len(op._host_pages)) as built:
            src = op._build()
            built.note(kind=src.kind)
        METRICS.histogram("join.build_s", time.perf_counter() - t0)
        METRICS.count_many({"builds": 1,
                            "builds.dense": int(src.kind == "dense"),
                            "builds.sorted": int(src.kind == "sorted"),
                            "builds.multikey": int(keys > 1),
                            "builds.narrowed": int(self.narrowed)},
                           prefix="join.")
        self.lookup_factory.set(src, w)
        op._pages = []  # consumed into the lookup source


# ---------------------------------------------------------------------------
# probe stage (pure): the page-local fast paths as ONE composable function
# ---------------------------------------------------------------------------
#
# The unique-build INNER/LEFT probe and the exact-key SEMI/ANTI probe are
# page-local (one output page per probe page, no host sync), so they can run
# as a single fused kernel — standalone (the operator below jits exactly this
# function) or inlined into a pipeline segment (ops/fused_segment.py). The
# lookup-source arrays arrive as jit ARGUMENTS, never trace constants, so a
# rebuilt build side (new query, same shapes) replays the compiled kernel.

@dataclasses.dataclass(frozen=True)
class ProbeStageConfig:
    """Static (hashable) config of a page-local probe stage. Everything the
    traced function branches on lives here; everything data lives in the aux
    pytree from :func:`probe_stage_aux`."""

    kind: str                              # "dense" | "sorted"
    join_type: str                         # INNER | LEFT | SEMI | ANTI
    probe_key_channels: Tuple[int, ...]
    probe_output_channels: Tuple[int, ...]
    build_output_channels: Tuple[int, ...]
    payload_meta: Tuple                    # ((type, dict), ...) per SELECTED build col
    null_aware: bool = False


def probe_plan_fusible(join_type: str, key_channels, unique: bool,
                       filter_fn=None, semi_output_channel=None) -> bool:
    """Plan-time test: will every page of this probe take the page-local
    stage path? INNER/LEFT need a unique single-key build (one output row
    per probe row); SEMI/ANTI need exact keys (single key) and no join
    filter. FULL joins track visited build rows across pages and RIGHT is
    planner-flipped — neither is page-local."""
    if len(key_channels) != 1:
        return False  # multi-key exactness is a runtime (packing) property
    if join_type in (SEMI, ANTI):
        return filter_fn is None and semi_output_channel is None
    if join_type in (INNER, LEFT):
        return unique
    return False


def probe_stage_cfg(f: "LookupJoinOperatorFactory",
                    src: LookupSource) -> ProbeStageConfig:
    return ProbeStageConfig(
        kind=src.kind, join_type=f.join_type,
        probe_key_channels=tuple(f.probe_key_channels),
        probe_output_channels=tuple(f.probe_output_channels),
        build_output_channels=tuple(f.build_output_channels),
        payload_meta=tuple(_payload_meta_selected(src, f)),
        null_aware=f.null_aware)


def probe_stage_aux(src: LookupSource):
    """Traced pytree of everything the stage reads from the build side.
    Host scalars stay numpy (an eager jnp.asarray would compile a throwaway
    convert kernel per query); they device_put at the jit call."""
    if src.kind == "dense":
        match = (src.table, np.asarray(src.base, np.int64))
    else:
        match = (src.sorted_key, src.sorted_row, tuple(src.key_arrays))
    return (match, tuple(src.payload), tuple(src.payload_nulls),
            np.asarray(src.has_null_key))


def probe_stage_key(cfg: ProbeStageConfig) -> tuple:
    """Global kernel-cache identity (dictionary versions included: payload
    meta dictionaries ride into output blocks as static aux data)."""
    from ..utils import kernel_cache as kc

    return ("probe-stage", cfg.kind, cfg.join_type, cfg.probe_key_channels,
            cfg.probe_output_channels, cfg.build_output_channels,
            tuple((t.name, kc.dict_key(d)) for t, d in cfg.payload_meta),
            cfg.null_aware)


def apply_probe_stage(page: Page, aux, cfg: ProbeStageConfig) -> Page:
    """Pure page -> page probe: match rows then emit, in one traceable body.

    Semantics identical to the operator's _match_rows + _emit_unique pair
    (the differential-tested contract): null probe keys never match; SEMI
    keeps matches, ANTI keeps non-matches (null-aware NOT IN empties the
    result under any NULL build key, via the has_null_key aux scalar); LEFT
    emits null build columns for unmatched probe rows."""
    match, payload, payload_nulls, has_null_key = aux
    probe_keys = [page.blocks[c].data for c in cfg.probe_key_channels]
    probe_mask = page.mask
    for c in cfg.probe_key_channels:
        if page.blocks[c].nulls is not None:
            probe_mask = probe_mask & ~page.blocks[c].nulls
    if cfg.kind == "dense":
        table, base = match
        row = probe_match_dense(table, base, probe_keys[0], probe_mask)
    else:
        sorted_key, sorted_row, key_arrays = match
        row = probe_match_sorted(sorted_key, sorted_row,
                                 combined_key(tuple(probe_keys)),
                                 tuple(probe_keys), probe_mask, key_arrays)
    matched = row >= 0
    if cfg.join_type in (SEMI, ANTI):
        if cfg.join_type == SEMI:
            keep = page.mask & matched
        else:
            keep = page.mask & ~matched
            if cfg.null_aware:
                # NOT IN: NULL probe key -> UNKNOWN -> filtered; any NULL
                # build key makes every non-match UNKNOWN -> empty result
                keep = keep & probe_mask & ~has_null_key
        sel = page.select_channels(list(cfg.probe_output_channels))
        return Page(sel.blocks, keep)
    return unique_join_page(page, row, payload, payload_nulls,
                            cfg.probe_output_channels,
                            cfg.build_output_channels, cfg.payload_meta,
                            cfg.join_type == INNER,
                            cfg.join_type in (LEFT, FULL))


def probe_stage_kernel(cfg: ProbeStageConfig):
    """Jitted stage shared through the global kernel cache: identical-config
    probes across operators, workers and queries replay one compile (the
    hash_agg share_kernels pattern, generalized to the join probe)."""
    from ..utils import kernel_cache as kc

    return kc.get_or_install(
        probe_stage_key(cfg),
        lambda: jax.jit(apply_probe_stage, static_argnames=("cfg",)))


# ---------------------------------------------------------------------------
# probe
# ---------------------------------------------------------------------------

def count_probe_pages(sources: Sequence[LookupSource], pages: int = 1) -> None:
    """`pages` probe pages went through each of `sources`: once a page and a
    probe, whether it ran in LookupJoinOperator or as a fused segment's stage."""
    METRICS.count_many(
        {"pages": pages * len(sources),
         "sorted_pages": pages * sum(s.kind == "sorted" for s in sources)},
        prefix="join.probe.")


def probe_match_dense(source_table, base, probe_keys, probe_mask):
    """DENSE unique build: one gather -> build row per probe row (-1 = no
    match). The offset and its range test are 64-bit and the cast comes
    after: a key 2^32 beyond a live slot must not wrap onto it. Pure body —
    the standalone kernel below and the fused stage both call it."""
    domain = source_table.shape[0]
    off = probe_keys.astype(jnp.int64) - base
    in_range = (off >= 0) & (off < domain) & probe_mask
    idx = jnp.where(in_range, off, 0).astype(jnp.int32)
    row = jnp.where(in_range, source_table[idx], jnp.int32(-1))
    return row


_probe_match_unique = jax.jit(probe_match_dense)


def probe_match_sorted(sorted_key, sorted_row, ck, probe_keys_list,
                       probe_mask, key_arrays):
    """SORTED unique build: binary search + verify (ck = the build's
    combined-key space, packed when exact). Pure body shared by the
    standalone kernel and the fused stage."""
    pos = jnp.searchsorted(sorted_key, ck)
    pos = jnp.clip(pos, 0, sorted_key.shape[0] - 1)
    hit = (sorted_key[pos] == ck) & probe_mask
    row = jnp.where(hit, sorted_row[pos], jnp.int32(-1))
    # verify true keys (hash collisions on multi-key mixes)
    for pk, bk in zip(probe_keys_list, key_arrays):
        bv = bk[jnp.where(row >= 0, row, 0)]
        row = jnp.where((row >= 0) & (bv == pk), row, jnp.int32(-1))
    return row


_probe_match_sorted_unique = jax.jit(probe_match_sorted)


class LookupJoinOperator(Operator):
    """Probe side. Unique-build fast path: one output row per probe row, no sync.
    General path: count-then-emit expansion with one scalar sync per probe page."""

    def __init__(self, context: OperatorContext, factory: "LookupJoinOperatorFactory"):
        super().__init__(context)
        self.f = factory
        self._outputs: List[Page] = []
        self._source: Optional[LookupSource] = None
        self._visited = None  # FULL: device bool per build row, OR-accumulated
        self._unmatched_emitted = False
        # page-local stage path (one fused kernel per page, shared via the
        # global kernel cache): resolved lazily from the live lookup source
        self._stage_cfg: Optional[ProbeStageConfig] = None
        self._stage_aux = None

    @property
    def output_types(self) -> List[Type]:
        return self.f.output_types

    def is_blocked(self):
        if self._source is not None:
            return None
        lf = self.f.lookup_factory
        w = self.context.worker
        if lf.done(w):
            self._source = lf.get(w)
            return None
        return lambda: lf.done(w)

    def needs_input(self) -> bool:
        return (not self._finishing and self._source is not None
                and len(self._outputs) < 4)

    @timed("add_input_ns")
    def add_input(self, page: Page) -> None:
        self.context.record_input(page, page.capacity)
        if self._source is None:
            w = self.context.worker
            assert self.f.lookup_factory.done(w), \
                "probe received input before build finished"
            self._source = self.f.lookup_factory.get(w)
        src = self._source
        count_probe_pages((src,))
        probe_keys = [page.blocks[c].data for c in self.f.probe_key_channels]
        probe_mask = page.mask
        for c in self.f.probe_key_channels:
            if page.blocks[c].nulls is not None:
                probe_mask = probe_mask & ~page.blocks[c].nulls
        if self.f.join_type == RIGHT:
            raise NotImplementedError(
                "RIGHT joins are planned as flipped LEFT; the planner must not "
                "route them here")
        if self.f.join_type == FULL and self._visited is None:
            self._visited = jnp.zeros(src.key_arrays[0].shape[0],
                                      dtype=jnp.bool_)
        # unique fast path requires exact key equality through sorted_key/dense table;
        # multi-key hashes must range-scan + verify via the expansion path
        if self.f.join_type in (SEMI, ANTI):
            if self.f.filter_fn is None and src.exact_keys:
                if self._stage_eligible(src):
                    self._push(self._stage_call(src, page))
                else:
                    row = self._match_rows(src, probe_keys, probe_mask)
                    self._emit_unique(page, row, probe_mask)
            else:
                self._emit_semi_expanded(page, probe_keys, probe_mask)
        elif src.unique and (src.kind == "dense" or src.exact_keys):
            if self._stage_eligible(src):
                self._push(self._stage_call(src, page))
            else:
                row = self._match_rows(src, probe_keys, probe_mask)
                self._emit_unique(page, row, probe_mask)
        else:
            self._emit_expanded(page, probe_keys, probe_mask)

    def _stage_eligible(self, src: LookupSource) -> bool:
        """One-kernel page-local path — THE plan-time fusion predicate,
        evaluated against the live build, so the fused and standalone paths
        can never drift apart. exact_keys is the extra RUNTIME condition:
        a float single-key build (sorted-key equality != key equality) must
        take the range-scan + verify expansion path instead of trusting
        the stage's single-position probe."""
        return probe_plan_fusible(self.f.join_type,
                                  self.f.probe_key_channels, src.unique,
                                  self.f.filter_fn,
                                  self.f.semi_output_channel) \
            and src.exact_keys

    def _stage_call(self, src: LookupSource, page: Page) -> Page:
        if self._stage_cfg is None:
            self._stage_cfg = probe_stage_cfg(self.f, src)
            self._stage_aux = probe_stage_aux(src)
            self._stage_kernel = probe_stage_kernel(self._stage_cfg)
        return self._stage_kernel(page, self._stage_aux, cfg=self._stage_cfg)

    def _match_rows(self, src, probe_keys, probe_mask):
        if src.kind == "dense":
            return _probe_match_unique(src.table, np.int64(src.base),
                                       probe_keys[0], probe_mask)
        return _probe_match_sorted_unique(src.sorted_key, src.sorted_row,
                                          src.combine_probe(tuple(probe_keys)),
                                          tuple(probe_keys), probe_mask,
                                          src.key_arrays)

    def _emit_semi_expanded(self, page: Page, probe_keys, probe_mask) -> None:
        """SEMI/ANTI with a join filter or multi-key: range-scan every candidate
        match, verify true keys, evaluate the filter on the (probe,build) pair, and
        OR-reduce per probe row. The SemiJoinOperator-with-filter analogue
        (reference: LookupJoinOperator + JoinFilterFunctionCompiler)."""
        src = self._source
        ck = src.combine_probe(tuple(probe_keys))
        lo, emit, _match, total_dev = _range_kernel(
            src.sorted_key, ck, probe_mask, page.mask, False)
        total = int(total_dev)
        cap = page.capacity
        offsets = jnp.cumsum(emit)
        any_match = jnp.zeros(cap, dtype=jnp.bool_)
        if self.f._semi_kernel is None:
            # jitted once per filter CONFIG (a detached holder: the cached
            # closure must pin only the compiled filter, never the factory and
            # its lookup sources/build tables), shared by every worker's probe
            # operators — and across queries when the planner supplied a
            # filter fingerprint
            f = self.f
            cfg = _SemiFilterKernel(f.filter_fn, f.filter_probe_channels,
                                    f.filter_build_channels)
            if f.filter_key is not None:
                from ..utils import kernel_cache as kc

                self.f._semi_kernel = kc.get_or_install(
                    ("join-semi", f.filter_key,
                     tuple(f.filter_probe_channels),
                     tuple(f.filter_build_channels)),
                    lambda: jax.jit(cfg.chunk))
            else:
                # no planner fingerprint for the ad-hoc filter fn: a
                # per-factory compile IS the contract here (the kernel is
                # memoized on the factory and reused across its chunks)
                self.f._semi_kernel = jax.jit(cfg.chunk)  # prestocheck: ignore[cache-key-hygiene]
        for c in range(max(0, -(-total // cap))):
            any_match = self.f._semi_kernel(
                page, tuple(probe_keys), lo, offsets, src.sorted_row,
                tuple(src.key_arrays), tuple(src.payload),
                tuple(src.payload_nulls), jnp.asarray(c * cap),
                jnp.asarray(total), any_match)
        if self.f.join_type == SEMI:
            keep = page.mask & any_match
        else:
            keep = page.mask & ~any_match
            if self.f.null_aware:
                keep = keep & probe_mask
                if src.has_null_key:
                    keep = jnp.zeros_like(keep)
        sel = page.select_channels(self.f.probe_output_channels)
        self._push(Page(sel.blocks, keep))

    def _emit_unique(self, page: Page, row, probe_mask) -> None:
        src = self._source
        jt = self.f.join_type
        matched = row >= 0
        if jt == FULL:
            self._visited = _mark_rows(self._visited, row, page.mask)
        if jt == SEMI or jt == ANTI:
            if self.f.semi_output_channel is not None:
                # mark column output (SemiJoinOperator semantics): keep all rows,
                # append the membership flag after the selected probe channels
                from ..types import BOOLEAN
                sel = page.select_channels(self.f.probe_output_channels)
                blocks = list(sel.blocks) + [Block(BOOLEAN, matched)]
                self._push(Page(tuple(blocks), page.mask))
            else:
                if jt == SEMI:
                    keep = matched
                else:
                    keep = ~matched & page.mask
                    if self.f.null_aware:
                        # NOT IN: NULL probe key -> UNKNOWN -> filtered; any NULL
                        # build key makes every non-match UNKNOWN -> empty result
                        keep = keep & probe_mask
                        if src.has_null_key:
                            keep = jnp.zeros_like(keep)
                sel = page.select_channels(self.f.probe_output_channels)
                self._push(Page(sel.blocks, page.mask & keep))
            return
        self._push(_emit_unique_kernel(
            page, row, tuple(src.payload), tuple(src.payload_nulls),
            tuple(self.f.probe_output_channels),
            tuple(self.f.build_output_channels),
            tuple(_payload_meta_selected(src, self.f)),
            jt == INNER, jt in (LEFT, FULL)))

    def _emit_expanded(self, page: Page, probe_keys, probe_mask) -> None:
        METRICS.count("join.probe.expanded_pages")
        src = self._source
        jt = self.f.join_type
        if jt not in (INNER, LEFT, FULL):
            raise NotImplementedError(f"{jt} join via expansion")
        left = jt in (LEFT, FULL)
        if left and not src.exact_keys:
            # a mixed-hash collision would mask a probe row's only match slots and
            # silently drop the row; LEFT semantics need exact combined keys
            raise NotImplementedError(
                "multi-key LEFT join on a non-unique build needs exact-key "
                "verification with null-row fallback (single-key LEFT is exact)")
        ck = src.combine_probe(tuple(probe_keys))
        lo, emit, match_counts, total = _range_kernel(
            src.sorted_key, ck, probe_mask, page.mask, left)
        if jt == FULL:
            # exact single-key ranges (guaranteed above): every build row in a
            # live probe row's [lo, lo+match) range is a true match
            self._visited = _mark_ranges(self._visited, src.sorted_row, lo,
                                         lo + match_counts,
                                         probe_mask & page.mask)
        total = int(total)  # host sync: output cardinality for this page
        cap = page.capacity
        n_chunks = max(1, -(-total // cap)) if total > 0 else 0
        offsets = jnp.cumsum(emit)
        for c in range(n_chunks):
            out = _expand_kernel(page, tuple(probe_keys), lo, offsets,
                                 match_counts, src.sorted_row,
                                 tuple(src.key_arrays), tuple(src.payload),
                                 tuple(src.payload_nulls),
                                 tuple(self.f.probe_output_channels),
                                 tuple(self.f.build_output_channels),
                                 c * cap, total, left,
                                 tuple((t, d) for (t, d) in
                                       _payload_meta_selected(src, self.f)))
            self._push(out)

    def _push(self, page: Page) -> None:
        self._outputs.append(page)

    @timed("get_output_ns")
    def get_output(self) -> Optional[Page]:
        if self._outputs:
            out = self._outputs.pop(0)
            self.context.record_output(out, out.capacity)
            return out
        return None

    def finish(self) -> None:
        if self.f.join_type == FULL and not self._unmatched_emitted:
            self._unmatched_emitted = True
            self._emit_unmatched_build()
        super().finish()

    def _emit_unmatched_build(self) -> None:
        """FULL join epilogue: build rows no probe row visited (plus NULL-key
        build rows, unmatched by construction) emit with null probe columns."""
        lf = self.f.lookup_factory
        w = self.context.worker
        if self._source is None:
            if not lf.done(w):
                return  # no probe input ever arrived and build never finished
            self._source = lf.get(w)
        src = self._source
        total_build = int(src.build_count)
        rows = np.zeros(0, dtype=np.int64)
        if total_build > 0:
            # live build rows are NOT a prefix of the concatenated page arrays
            # (pages are capacity-padded); the sort kernel puts the n live rows
            # first in sorted order, so sorted_row[:n] IS the live-row index set
            live = np.asarray(src.sorted_row)[:total_build]
            if self._visited is not None:
                vis = np.asarray(self._visited)
                rows = live[~vis[live]]
            else:
                rows = live
        n_un = len(rows)
        n_null = src.null_key_count
        if n_un + n_null == 0:
            return
        cap = max(1 << 10, 1 << (n_un + n_null - 1).bit_length()) \
            if n_un + n_null else 1 << 10
        cap = min(cap, 1 << 16)
        payload_np = [np.asarray(a) for a in src.payload]
        nulls_np = [np.asarray(x) if x is not None else None
                    for x in src.payload_nulls]
        # assemble [unvisited live rows] + [null-key side buffer] per column
        cols = []
        for bi, (t, d) in zip(self.f.build_output_channels,
                              _payload_meta_selected(src, self.f)):
            parts = [payload_np[bi][rows]] if n_un else []
            nparts = []
            bn = nulls_np[bi] if bi < len(nulls_np) else None
            nparts.append((bn[rows] if bn is not None else
                           np.zeros(n_un, dtype=bool)) if n_un else
                          np.zeros(0, dtype=bool))
            if n_null:
                parts.append(src.null_key_payload[bi])
                nk_n = src.null_key_nulls[bi]
                nparts.append(nk_n if nk_n is not None
                              else np.zeros(n_null, dtype=bool))
            data = np.concatenate(parts) if parts else np.zeros(0)
            nul = np.concatenate(nparts)
            cols.append((t, d, data, nul))
        total = n_un + n_null
        for lo in range(0, total, cap):
            hi = min(lo + cap, total)
            pad = cap - (hi - lo)
            blocks = []
            # probe columns: all NULL
            for (t, d) in self.f.probe_output_meta:
                z = np.zeros(cap, dtype=t.np_dtype)
                blocks.append(Block(t, z, np.ones(cap, dtype=bool), d))
            for (t, d, data, nul) in cols:
                seg = np.concatenate([data[lo:hi],
                                      np.zeros(pad, dtype=data.dtype)]) \
                    if pad else data[lo:hi]
                nseg = np.concatenate([nul[lo:hi], np.zeros(pad, dtype=bool)]) \
                    if pad else nul[lo:hi]
                blocks.append(Block(t, seg.astype(t.np_dtype, copy=False),
                                    nseg if nseg.any() else None, d))
            mask = np.arange(cap) < (hi - lo)
            self._push(Page(tuple(blocks), mask))

    def is_finished(self) -> bool:
        return self._finishing and not self._outputs


def _payload_meta_selected(src: LookupSource, f) -> List[Tuple[Type, Optional[Dictionary]]]:
    return [src.payload_meta[i] for i in f.build_output_channels]


def unique_join_page(page: Page, row, payload, payload_nulls,
                     probe_channels, build_channels, meta,
                     inner: bool, left_outer: bool) -> Page:
    """Unique-build join output: probe-channel passthrough plus a gather per
    build column. Pure body — the standalone kernel below runs it as ONE
    fused dispatch (eagerly this was ~15 separate dispatches per page);
    the fused segment inlines it into its whole-chain kernel."""
    matched = row >= 0
    out_mask = page.mask & (matched if inner else jnp.ones_like(matched))
    safe_row = jnp.where(matched, row, 0)
    blocks = [page.blocks[c] for c in probe_channels]
    for bi, (t, d) in zip(build_channels, meta):
        arr = payload[bi][safe_row]
        bn = payload_nulls[bi] if bi < len(payload_nulls) else None
        nulls = bn[safe_row] if bn is not None else None
        if left_outer:
            unmatched = ~matched  # unmatched probe rows -> null build columns
            nulls = unmatched if nulls is None else (nulls | unmatched)
        blocks.append(Block(t, arr, nulls, d))
    return Page(tuple(blocks), out_mask)


_emit_unique_kernel = functools.partial(
    jax.jit, static_argnames=("probe_channels", "build_channels", "meta",
                              "inner", "left_outer"))(unique_join_page)


@jax.jit
def _mark_rows(visited, row, mask):
    """OR build rows matched by this probe page into the visited set."""
    idx = jnp.where((row >= 0) & mask, row, visited.shape[0])
    return visited.at[idx].set(True, mode="drop")


@jax.jit
def _mark_ranges(visited, sorted_row, lo, hi, probe_mask):
    """Visited-marking for range matches: coverage via a difference array —
    O(n) regardless of match multiplicity."""
    n = sorted_row.shape[0]
    add = jnp.where(probe_mask, 1, 0).astype(jnp.int32)
    delta = jnp.zeros(n + 1, dtype=jnp.int32)
    delta = delta.at[jnp.where(probe_mask, lo, n)].add(add, mode="drop")
    delta = delta.at[jnp.where(probe_mask, hi, n)].add(-add, mode="drop")
    covered = jnp.cumsum(delta[:-1]) > 0
    return visited.at[sorted_row].max(covered)


@functools.partial(jax.jit, static_argnames=("left",))
def _range_kernel(sorted_key, probe_ck, probe_mask, emit_mask, left=False):
    """Match ranges per probe row. Returns (lo, emit_counts, match_counts, total).
    LEFT joins emit one row for match-less live probe rows (null build side)."""
    lo = jnp.searchsorted(sorted_key, probe_ck, side="left")
    hi = jnp.searchsorted(sorted_key, probe_ck, side="right")
    lo = jnp.where(probe_mask, lo, 0)
    hi = jnp.where(probe_mask, hi, 0)
    match = (hi - lo).astype(jnp.int32)
    if left:
        emit = jnp.where(emit_mask, jnp.maximum(match, 1), 0).astype(jnp.int32)
    else:
        emit = match
    return lo.astype(jnp.int32), emit, match, jnp.sum(emit)


@functools.partial(jax.jit, static_argnames=("probe_channels", "build_channels",
                                             "left", "payload_meta"))
def _expand_kernel(page: Page, probe_keys, lo, offsets, match_counts, sorted_row,
                   key_arrays, payload, payload_nulls, probe_channels,
                   build_channels, out_base, total, left, payload_meta):
    """Emit output rows [out_base, out_base+cap) of the expanded join. For LEFT,
    an emit slot beyond a probe row's match count is its null-build row."""
    cap = page.mask.shape[0]
    j = jnp.arange(cap, dtype=jnp.int32) + out_base
    live = j < total
    # probe row for output slot j: first i with offsets[i] > j
    pi = jnp.searchsorted(offsets, j, side="right").astype(jnp.int32)
    pi = jnp.clip(pi, 0, cap - 1)
    prev = jnp.where(pi > 0, offsets[jnp.maximum(pi - 1, 0)], 0)
    k = j - prev
    is_match = k < match_counts[pi]
    spos = lo[pi] + k
    spos = jnp.clip(spos, 0, sorted_row.shape[0] - 1)
    brow = jnp.where(is_match, sorted_row[spos], 0)
    # verify true keys (collision safety on multi-key mixes)
    ok = live
    for pkc, bk in zip(range(len(probe_keys)), key_arrays):
        pv = probe_keys[pkc][pi]
        bv = bk[brow]
        ok = ok & (~is_match | (bv == pv)) if left else ok & (bv == pv)
    blocks = []
    for c in probe_channels:
        b = page.blocks[c]
        nulls = b.nulls[pi] if b.nulls is not None else None
        blocks.append(Block(b.type, b.data[pi], nulls, b.dictionary))
    for bi, (t, d) in zip(build_channels, payload_meta):
        bn = payload_nulls[bi] if bi < len(payload_nulls) else None
        nulls = bn[brow] if bn is not None else None
        if left:
            nulls = ~is_match if nulls is None else (nulls | ~is_match)
        blocks.append(Block(t, payload[bi][brow], nulls, d))
    return Page(tuple(blocks), ok)


class LookupJoinOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, lookup_factory: LookupSourceFactory,
                 probe_key_channels: List[int], probe_output_channels: List[int],
                 probe_output_meta: List[Tuple[Type, Optional[Dictionary]]],
                 build_output_channels: List[int],
                 build_output_meta: List[Tuple[Type, Optional[Dictionary]]],
                 join_type: str = INNER, semi_output_channel: Optional[int] = None,
                 null_aware: bool = False, filter_fn=None,
                 filter_probe_channels: Optional[List[int]] = None,
                 filter_build_channels: Optional[List[int]] = None,
                 filter_key: Optional[tuple] = None,
                 unique_build: bool = False):
        super().__init__(operator_id, f"LookupJoin({join_type})")
        # plan-time build-side uniqueness claim (JoinBuildOperatorFactory's
        # `unique`): the segment compiler fuses INNER/LEFT probes only when
        # the build guarantees one output row per probe row
        self.unique_build = unique_build
        # global kernel-cache identity of the compiled join filter (expression
        # + layout fingerprint from the local planner); None -> per-factory jit
        self.filter_key = filter_key
        # join filter: compiled expression over [filter_probe_channels... page
        # channels, filter_build_channels... payload columns] evaluated per
        # candidate (probe,build) pair — JoinFilterFunctionCompiler analogue
        self.filter_fn = filter_fn
        self.filter_probe_channels = filter_probe_channels or []
        self.filter_build_channels = filter_build_channels or []
        self._semi_kernel = None  # lazily jitted, shared across workers
        self.lookup_factory = lookup_factory
        self.probe_key_channels = probe_key_channels
        self.probe_output_channels = probe_output_channels
        self.probe_output_meta = list(probe_output_meta)
        self.build_output_meta = list(build_output_meta)
        self.build_output_channels = build_output_channels
        self.join_type = join_type
        self.semi_output_channel = semi_output_channel
        # null_aware = SQL IN/NOT IN semantics: a NULL probe key (or any NULL build
        # key on NOT IN) compares UNKNOWN, so the row is filtered. Default False =
        # EXISTS/NOT EXISTS semantics where a null key simply never matches.
        self.null_aware = null_aware
        self.output_types = [t for (t, _) in probe_output_meta] + \
                            [t for (t, _) in build_output_meta]
        if semi_output_channel is not None:
            from ..types import BOOLEAN
            # mark-column mode appends the membership flag as the LAST channel
            self.output_types = [t for (t, _) in probe_output_meta] + [BOOLEAN]

    def create_operator(self, worker: int = 0) -> LookupJoinOperator:
        return LookupJoinOperator(self.context(worker), self)


class _SemiFilterKernel:
    """Join-filter config holder for the cached semi/anti probe kernel.

    Deliberately detached from the operator factory: the kernel cache keeps
    the jitted bound method alive for the process lifetime, and a factory
    would drag its LookupSourceFactory (the build-side hash tables in HBM)
    along with it."""

    def __init__(self, filter_fn, filter_probe_channels, filter_build_channels):
        self.filter_fn = filter_fn
        self.filter_probe_channels = list(filter_probe_channels)
        self.filter_build_channels = list(filter_build_channels)

    def chunk(self, page, probe_keys, lo, offsets, sorted_row, key_arrays,
              payload, payload_nulls, out_base, total, any_match):
        """One output chunk of the verified semi/anti probe: range-positions
        -> candidate build rows -> exact key check -> filter -> OR per probe."""
        cap = page.mask.shape[0]
        j = jnp.arange(cap, dtype=jnp.int32) + out_base
        live = j < total
        pi = jnp.clip(jnp.searchsorted(offsets, j, side="right").astype(jnp.int32),
                      0, cap - 1)
        prev = jnp.where(pi > 0, offsets[jnp.maximum(pi - 1, 0)], 0)
        spos = jnp.clip(lo[pi] + (j - prev), 0, sorted_row.shape[0] - 1)
        brow = sorted_row[spos]
        ok = live
        for pk, bk in zip(probe_keys, key_arrays):
            ok = ok & (bk[brow] == pk[pi])
        if self.filter_fn is not None:
            datas, nulls = [], []
            for pc in self.filter_probe_channels:
                b = page.blocks[pc]
                datas.append(b.data[pi])
                nulls.append(b.nulls[pi] if b.nulls is not None else None)
            for bc in self.filter_build_channels:
                datas.append(payload[bc][brow])
                bn = payload_nulls[bc] if bc < len(payload_nulls) else None
                nulls.append(bn[brow] if bn is not None else None)
            fd, fnu = self.filter_fn(tuple(datas), tuple(nulls))
            ok = ok & fd
            if fnu is not None:
                ok = ok & ~fnu
        return any_match.at[pi].max(ok)
