"""Hash aggregation on TPU: sort-based and direct-index grouping kernels.

Analogue of operator/HashAggregationOperator.java:47 with
operator/aggregation/builder/InMemoryHashAggregationBuilder and the group-by hashes
(MultiChannelGroupByHash.java:54, BigintGroupByHash fast path).

TPU re-design (NOT a translation): open-addressing with per-row scatter is serial and
hostile to the VPU, so grouping is done with the two strategies that vectorize:

1. DIRECT: when every group key is a small-domain integer (dictionary codes, flags),
   group id = linear index over the domain product; aggregation is one segment-reduce
   into a dense state table. This is the BigintGroupByHash analogue and covers TPC-H
   Q1 (4 groups) with zero sorts.
2. SORT: general case — lexicographic sort of the key columns, adjacent-difference
   group boundaries, segment-reduce. Exact (no hash collisions), static shapes,
   O(n log n) on the TPU's bitonic sorter. Research on TPU databases reaches the same
   conclusion: sort + segment-reduce beats scatter hash tables on this hardware.
Those two are all there is; the planner picks by the key domains (make_builder).

Cross-page accumulation keeps a compact state table (<= max_groups) plus a pending
buffer of per-page partials; when the buffer fills it is folded into the table by the
same sort+segment kernel (the tree-combine is the analogue of partial->final
aggregation inside one operator).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..block import Block, Dictionary, Page
from ..types import BIGINT, BOOLEAN, Type, is_string
from ..utils import kernel_cache
from ..utils.batching import clamp_capacity
from ..utils.metrics import METRICS
from .aggregates import ACARRY, AMAX, AMIN, MAX, MIN, SUM, AggregateCall
from .operator import Operator, OperatorContext, OperatorFactory, timed
from .sorting import lexsort_fast


def _builder_key(tag, b, page: "Page" = None, input_dicts=None) -> tuple:
    """Kernel-cache identity of a builder's static config: everything its
    jitted kernel reads from `self` (channels, call fingerprints, domains)
    PLUS the input page's dictionary versions — _call_contributions embeds
    `d.sort_keys()` as a trace constant for min/max over unsorted
    dictionaries, and Dictionary.extend mutates IN PLACE (same identity), so
    the (token, len) version must be part of the key or an INSERT-extended
    dictionary would replay a stale kernel. `input_dicts` supplies the
    dictionaries directly when the caller knows the builder's input layout
    without a live page (the fused-segment compiler)."""
    dicts = ()
    if page is not None:
        dicts = tuple(kernel_cache.dict_key(blk.dictionary)
                      for blk in page.blocks)
    elif input_dicts is not None:
        dicts = tuple(kernel_cache.dict_key(d) for d in input_dicts)
    return ("agg", tag,
            tuple(t.name for t in getattr(b, "key_types", ())),
            getattr(b, "_key_channels", None),
            tuple(getattr(b, "domains", ())),
            b.from_intermediate,
            dicts,
            tuple(kernel_cache.agg_call_key(c) for c in b.calls))


# Largest segment count whose scalar segment reduce runs as one masked
# reduction per column instead of a scatter. XLA:TPU lowers
# jax.ops.segment_* to a serial scatter (64-85 ms per 2^20-row 64-bit column
# whatever the segment count); the masked form is one fused pass whose work
# grows as rows x segments. Set from tools/dense_reduce_sweep.py on a v5e
# (PERF.md section 6, PR 28): the largest S of the sweep at which the masked
# form is at least 2x faster for int64 and float64 alike (7.3x and 3.3x at
# 4096; 98x and 96x at Q1's 13).
DENSE_REDUCE_MAX_SEGMENTS = 4096

_SCATTER = {SUM: jax.ops.segment_sum, MIN: jax.ops.segment_min,
            MAX: jax.ops.segment_max}
_COMBINE = {SUM: jax.lax.add, MIN: jax.lax.min, MAX: jax.lax.max}


def _reduce_identity(kind: str, dtype):
    """What jax.ops.segment_sum/min/max leave in a segment no row maps to."""
    if kind == SUM:
        return np.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return np.asarray(np.inf if kind == MIN else -np.inf, dtype)
    info = jnp.iinfo(dtype)
    return np.asarray(info.max if kind == MIN else info.min, dtype)


def _masked_segment_reduce(kind: str, values, seg_ids, num_segments: int):
    """out[g] = reduce(where(seg_ids == g, values, identity)) for every g, in
    the dtype of `values`: a compare-select over a broadcast (segments, rows)
    view that XLA fuses into the reduction (no such array reaches HBM)."""
    ident = _reduce_identity(kind, values.dtype)
    segs = jnp.arange(num_segments, dtype=seg_ids.dtype)
    hit = seg_ids[None, :] == segs[:, None]
    return jax.lax.reduce(jnp.where(hit, values[None, :], ident), ident,
                          _COMBINE[kind], (1,))


def _segment_reduce(kind: str, values, seg_ids, num_segments: int):
    """Scalar segment reduce; the form follows the (static) segment count.
    Ids outside [0, num_segments) are dropped in both forms."""
    if (num_segments <= DENSE_REDUCE_MAX_SEGMENTS and values.ndim == 1
            and values.dtype != jnp.bool_):
        return _masked_segment_reduce(kind, values, seg_ids, num_segments)
    return _SCATTER[kind](values, seg_ids, num_segments=num_segments)


# Width of the blocks of the sorted-segment scan (_segmented_scan): one row
# of the TPU's 128 lanes, so a block is log2(128) = 7 shifted passes and the
# block totals scan again at 1/128 the size. Chosen over jnp.cumsum and a flat
# lax.associative_scan by tools/sorted_reduce_sweep.py on a v5e (PERF.md
# section 6).
SCAN_BLOCK = 128


def _lane_scan(op, starts, values):
    """Inclusive segmented scan along the last axis, Hillis-Steele: pass d
    folds each element's running value into the one d lanes later unless a
    segment starts between them. Returns (any start so far, scan)."""
    width = values.shape[-1]
    lane = jnp.arange(width, dtype=jnp.int32)
    d = 1
    while d < width:
        reach = lane >= d
        take = reach & ~starts
        values = jnp.where(take, op(jnp.roll(values, d, axis=-1), values),
                           values)
        starts = starts | (reach & jnp.roll(starts, d, axis=-1))
        d *= 2
    return starts, values


def _segmented_scan(op, starts, values):
    """out[i] = op-reduction of values[s..i], s the last row <= i where
    `starts` is set (row 0 where none is): a scan of blocks of SCAN_BLOCK
    rows, then of the block totals (the same function, 1/SCAN_BLOCK the
    size), whose running value each block's rows before its first start
    take in. Only shifts and selects: no scatter, no reduce-window."""
    n = values.shape[0]
    if n <= SCAN_BLOCK:
        return _lane_scan(op, starts[None], values[None])[1][0]
    pad = -n % SCAN_BLOCK
    if pad:  # tail rows that start segments of their own
        starts = jnp.concatenate([starts, jnp.ones(pad, dtype=jnp.bool_)])
        values = jnp.concatenate([values, jnp.zeros(pad, values.dtype)])
    seen, run = _lane_scan(op, starts.reshape(-1, SCAN_BLOCK),
                           values.reshape(-1, SCAN_BLOCK))
    totals = _segmented_scan(op, seen[:, -1], run[:, -1])
    carry = jnp.roll(totals, 1)[:, None]  # the scan up to the block before
    first_block = (jnp.arange(run.shape[0], dtype=jnp.int32) == 0)[:, None]
    out = jnp.where(seen | first_block, run, op(carry, run))
    return out.reshape(-1)[:n]


def _sorted_scan_columns(kinds, widths) -> Tuple[bool, ...]:
    """Which state columns sort_group_reduce reduces by the segmented scan
    over its sorted rows: the scalar SUM, MIN and MAX ones. AMIN/AMAX with
    their ACARRY payloads and wide (sketch) states keep _reduce_all's
    scatters."""
    return tuple(k in _COMBINE and w == 1 for k, w in zip(kinds, widths))


WIDE_STATE_MAX_GROUPS = 1 << 13  # scatter-table bound for sketch aggregates


def _state_widths(calls) -> Tuple[int, ...]:
    return tuple(col.width for c in calls for col in c.function.state)


def _empty_state(kind_count_widths):
    """Zero-group state arrays matching each column's width (numpy: an
    eager jnp.zeros is a program of its own; these reach the device as
    arguments of the result's program)."""
    return tuple(np.zeros((0, w) if w > 1 else 0, dtype=np.float64)
                 for w in kind_count_widths)


def _reduce_contrib(kind: str, c, gid, num_segments: int, width: int,
                    ident):
    """Reduce one contribution column into a (num_segments[, width]) state.

    Wide (vector) state columns arrive as a `(bucket, value)` tuple per row and
    scatter into state[group, bucket] — never materializing a rows x width
    one-hot. Scalar columns segment-reduce as before. 2-D plain arrays are
    already-built states being re-grouped (combine path)."""
    if isinstance(c, tuple):
        bucket, vals = c
        base = jnp.full((num_segments, width), ident, dtype=vals.dtype)
        at = base.at[gid, bucket]
        upd = at.add if kind == SUM else (at.min if kind == MIN else at.max)
        return upd(vals, mode="drop")
    return _segment_reduce(kind, c, gid, num_segments)


def _reduce_all(contribs, kinds, identities, widths, gid, out_groups):
    """Reduce every contribution column into (out_groups,) states, handling
    the AMIN/AMAX + ACARRY pairs jointly: segment argmin/argmax over the
    ordering key, then gather the winning row's payload (min_by/max_by).
    Rows routed to the trash segment (gid == out_groups) are excluded."""
    n_seg = out_groups + 1
    states = []
    i = 0
    while i < len(kinds):
        kind = kinds[i]
        if kind in (AMIN, AMAX):
            y = contribs[i]
            m = _segment_reduce(MIN if kind == AMIN else MAX, y, gid, n_seg)
            nr = y.shape[0]
            idx = jnp.arange(nr, dtype=jnp.int32)
            best = jnp.where(y == m[gid], idx, nr)
            first = _segment_reduce(MIN, best, gid, n_seg)
            win = jnp.clip(first, 0, max(nr - 1, 0))
            states.append(m[:out_groups])
            i += 1
            while i < len(kinds) and kinds[i] == ACARRY:
                states.append(contribs[i][win][:out_groups])
                i += 1
            continue
        states.append(_reduce_contrib(kind, contribs[i], gid, n_seg,
                                      widths[i], identities[i])[:out_groups])
        i += 1
    return states


def _merge_tables(kinds, old, new):
    """Element-wise combine of two same-shape state tables (cross-page fold
    of the direct builder), joint over AMIN/AMAX + ACARRY pairs."""
    out = []
    i = 0
    while i < len(kinds):
        kind = kinds[i]
        if kind in (AMIN, AMAX):
            better = (new[i] < old[i]) if kind == AMIN else (new[i] > old[i])
            out.append(jnp.where(better, new[i], old[i]))
            i += 1
            while i < len(kinds) and kinds[i] == ACARRY:
                out.append(jnp.where(better, new[i], old[i]))
                i += 1
            continue
        if kind == SUM:
            out.append(old[i] + new[i])
        elif kind == MIN:
            out.append(jnp.minimum(old[i], new[i]))
        else:
            out.append(jnp.maximum(old[i], new[i]))
        i += 1
    return out


def _where_valid(gvalid, s, ident):
    """Identity-fill invalid group slots, broadcasting over vector states."""
    cond = gvalid[:, None] if s.ndim == 2 else gvalid
    return jnp.where(cond, s, jnp.asarray(ident, dtype=s.dtype))


def _null_safe_keys(page: Page, key_channels) -> Tuple:
    """Interleaved (value, is_null) arrays per key channel.

    SQL groups NULL as its OWN key (reference: MultiChannelGroupByHash over
    nullable blocks), so the null flag joins the sort key and the value lane is
    zeroed under NULL — two NULL rows always collide, and never with value 0."""
    out = []
    for c in key_channels:
        b = page.blocks[c]
        if b.nulls is not None:
            flag = b.nulls
            data = jnp.where(flag, jnp.zeros((), dtype=b.data.dtype), b.data)
        else:
            flag = jnp.zeros(page.mask.shape, dtype=jnp.bool_)
            data = b.data
        out.append(data)
        out.append(flag)
    return tuple(out)


def _call_contributions(calls, page: Page, from_intermediate: bool):
    """Per-row state contributions for every call, SQL-null-aware: a NULL input row
    contributes nothing (mask excludes it), matching the reference accumulators'
    @SqlNullable handling."""
    datas = tuple(b.data for b in page.blocks)
    mask = page.mask
    contribs = []
    for call in calls:
        if from_intermediate:
            for ch in call.intermediate_channels:
                contribs.append(datas[ch])
        else:
            args = []
            for ai, c in enumerate(call.input_channels):
                a = datas[c]
                d = page.blocks[c].dictionary
                name = call.function.name
                ordering_arg = name in ("min", "max") or \
                    (name in ("min_by", "max_by") and ai == 1)
                if ordering_arg and d is not None and not d.is_sorted():
                    # codes of an INSERT-extended dictionary are append-ordered,
                    # not lexicographic — compare RANKS instead; min/max's
                    # output path maps the winning rank back to a code
                    # (min_by/max_by discard the ordering state, so no
                    # back-mapping is needed there)
                    a = jnp.asarray(d.sort_keys())[a]
                args.append(a)
            args = tuple(args)
            m = mask
            skip = call.function.null_skip_channels
            for ai, c in enumerate(call.input_channels):
                if skip is not None and ai not in skip:
                    continue  # NULL here does not exclude the row (min_by x)
                if page.blocks[c].nulls is not None:
                    m = m & ~page.blocks[c].nulls
            if call.mask_channel is not None:
                mc = datas[call.mask_channel].astype(jnp.bool_)
                if page.blocks[call.mask_channel].nulls is not None:
                    mc = mc & ~page.blocks[call.mask_channel].nulls
                m = m & mc
            if call.function.needs_arg_nulls:
                arg_nulls = tuple(page.blocks[c].null_mask()
                                  for c in call.input_channels)
                contribs.extend(call.function.input_map(args, arg_nulls, m))
            else:
                contribs.extend(call.function.input_map(args, m))
    return contribs


# ---------------------------------------------------------------------------
# sort-based grouping kernel
# ---------------------------------------------------------------------------

def sort_group_reduce(keys: Tuple[jnp.ndarray, ...], mask: jnp.ndarray,
                      contribs: Tuple, kinds: Tuple[str, ...],
                      identities: Tuple, out_groups: int,
                      widths: Optional[Tuple[int, ...]] = None):
    """Group rows by `keys` (exact, multi-column) and reduce `contribs`.

    Returns (group_keys, group_states, group_valid_mask). Invalid input rows and
    groups beyond out_groups are dropped (caller sizes out_groups to capacity).

    The rows are sorted, so a group's rows are one run: valid rows first, in
    group order, then the overflow groups, then the invalid rows. Scalar
    SUM/MIN/MAX states are a segmented scan over the sorted rows read at each
    group's last row (_sorted_scan_columns); the rest go through _reduce_all.
    """
    n = mask.shape[0]
    widths = widths or (1,) * len(kinds)
    if n == 0:  # nothing to sort, and nothing to gather from
        z = (jnp.zeros(out_groups, dtype=k.dtype) for k in keys)
        return (tuple(z), tuple(
            jnp.full((out_groups, w) if w > 1 else (out_groups,), ident,
                     dtype=c[1].dtype if isinstance(c, tuple) else c.dtype)
            for c, ident, w in zip(contribs, identities, widths)),
            jnp.zeros(out_groups, dtype=jnp.bool_), jnp.int32(0))
    invalid = ~mask
    order = lexsort_fast(tuple(reversed(keys)) + (invalid,))
    sk = tuple(k[order] for k in keys)
    sv = mask[order]
    sc = tuple((c[0][order], c[1][order]) if isinstance(c, tuple) else c[order]
               for c in contribs)

    diff = jnp.arange(n, dtype=jnp.int32) == 0
    for k in sk:
        diff = diff | (k != jnp.roll(k, 1))
    new_group = sv & diff
    # the groups' prefix count by the same scan (a jnp.cumsum is a
    # reduce-window on the TPU, many times slower to compile)
    gid = _segmented_scan(jax.lax.add, jnp.zeros(n, dtype=jnp.bool_),
                          new_group.astype(jnp.int32)) - 1
    num_groups = gid[-1] + 1
    gid = jnp.where(sv, gid, out_groups)  # trash bin
    gid = jnp.minimum(gid, out_groups)    # overflow also lands in the bin

    # each group's first sorted row: ONE int32 segment_min, the bin's too
    first = _segment_reduce(MIN, jnp.arange(n, dtype=jnp.int32), gid,
                            out_groups + 1)
    # ... and its last: the row before the next group's first, or the last
    # valid row (an empty slot's `first` is the int32 max)
    valid_rows = jnp.sum(sv, dtype=jnp.int32)
    last = jnp.clip(jnp.minimum(first[1:], valid_rows) - 1, 0, n - 1)
    scan = _sorted_scan_columns(kinds, widths)
    others = [i for i, s in enumerate(scan) if not s]
    rest = iter(_reduce_all(*([seq[i] for i in others] for seq in (
        sc, kinds, identities, widths)), gid, out_groups))
    states = [_segmented_scan(_COMBINE[kind], new_group, c)[last] if s
              else next(rest) for c, kind, s in zip(sc, kinds, scan)]
    # group keys: a gather per key column at the first row (empty slots
    # gather garbage; gvalid masks them)
    safe = jnp.clip(first[:out_groups], 0, n - 1)
    gkeys = [k[safe] for k in sk]
    gvalid = jnp.arange(out_groups, dtype=jnp.int32) < jnp.minimum(num_groups, out_groups)
    # overwrite empty-group states with identities so MIN/MAX don't leak sentinels
    fixed_states = [_where_valid(gvalid, s, ident)
                    for s, ident in zip(states, identities)]
    return tuple(gkeys), tuple(fixed_states), gvalid, num_groups


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

# disk-spill partitioning: target bytes per partition (the merge-on-read
# working set) — the partition count adapts so each partition's merge fits
# comfortably in host RAM
_DISK_PARTITION_TARGET_BYTES = 64 << 20
_DISK_MAX_PARTITIONS = 256


def _key_row_hash(keys) -> np.ndarray:
    """Deterministic per-row uint64 hash over the interleaved key columns —
    the disk-spill partitioner. VALUE-cast (not bit-cast) to int64 so float
    +0.0/-0.0 (equal keys) hash equal; NULL lanes are already canonical
    (zeroed value + flag, _null_safe_keys). Must agree between write time
    and merge-on-read: it only sees numpy values, which round-trip pcol
    bit-exactly."""
    n = len(keys[0])
    h = np.full(n, 0x9E3779B97F4A7C15, dtype=np.uint64)
    for k in keys:
        with np.errstate(invalid="ignore"):
            v = np.asarray(k).astype(np.int64, copy=False).view(np.uint64)
        h = h ^ v
        # splitmix64 finalizer (wraps mod 2^64; numpy uint64 arrays wrap
        # silently)
        h = (h ^ (h >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        h = (h ^ (h >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        h = h ^ (h >> np.uint64(31))
    return h


class GroupedAggregationBuilder:
    """Sort-strategy accumulator (InMemoryHashAggregationBuilder analogue)."""

    # finish() returns a prefix-valid table whose live rows are data: the
    # operator reads their count (HashAggregationOperator._build_result)
    result_rows = None

    def __init__(self, key_types: Sequence[Type], key_dicts: Sequence[Optional[Dictionary]],
                 calls: Sequence[AggregateCall], page_capacity: int,
                 max_groups: int = 1 << 20, from_intermediate: bool = False):
        self.user_key_types = list(key_types)
        # internal key signature interleaves a BOOLEAN null-flag column per key
        # (_null_safe_keys): every internal loop over key arrays (fold, spill
        # merge, finish) then handles NULL groups with no special cases
        self.key_types = [x for t in key_types for x in (t, BOOLEAN)]
        self.key_dicts = list(key_dicts)
        self.calls = list(calls)
        self.max_groups = max_groups
        self.from_intermediate = from_intermediate
        self.kinds: Tuple[str, ...] = tuple(
            col.reduce for c in calls for col in c.function.state)
        self.identities: Tuple = tuple(
            col.identity for c in calls for col in c.function.state)
        self.widths: Tuple[int, ...] = _state_widths(calls)
        # what each sort_group_reduce call of this builder does with its
        # state columns, counted where a partial or a fold passes the host
        scan = sum(_sorted_scan_columns(self.kinds, self.widths))
        self._column_forms = {"scan_columns": scan,
                              "scatter_columns": len(self.kinds) - scan}
        # vector (sketch) states scatter into (groups, width) tables — bound
        # BOTH the per-page group table and the device accumulator; overflow
        # beyond max_groups spills compacted partials to host RAM as usual
        self._wide_cap = WIDE_STATE_MAX_GROUPS if any(
            w > 1 for w in self.widths) else None
        if self._wide_cap is not None:
            self.max_groups = min(self.max_groups, self._wide_cap)
        self._acc = None            # (keys, states, valid) compact table, <= max_groups
        self._pending: List = []    # list of (keys, states, mask) partials
        self._pending_rows = 0
        # installed lazily (set_channels runs after __init__) via the global
        # kernel cache so equal-config builders across queries share one compile
        self._page_kernel = None
        # spilled partial tables on HOST RAM (numpy) — rung 2 of the ladder
        # (SpillableHashAggregationBuilder analogue): device HBM holds at
        # most max_groups live groups; overflow and revocation move
        # compacted partials to host, merged exactly at finish()
        self._spilled: List = []    # list of (np keys tuple, np states tuple, np valid)
        # rung 3, DISK: under sustained pressure the operator calls
        # spill_to_disk() and the host partials become hash-partitioned,
        # sorted, partially-reduced PCOL runs (exec/spill.py). The partition
        # count adapts to the OBSERVED group cardinality as runs accumulate
        # (the dynamic hybrid-hash-join design: commit to a partition count
        # at runtime, not up front) — merge-on-read at finish() then works
        # one partition at a time, so peak host RAM is bounded by the
        # largest partition, not the whole group table.
        self._spill_mgr = None      # exec/spill.SpillManager (attach_spill)
        self._disk_runs: List = []  # SpillRun list, meta={"P","part","nk"}
        self._disk_parts = 1        # pow2 partition count; grows, never shrinks
        # adaptive compact-table size: starts at the first fold's true group count
        # (rounded up to a power of two) and grows on demand — the rehash analogue
        # of MultiChannelGroupByHash.java:363-409, but table growth here re-runs one
        # sort kernel at the next size bucket instead of rehashing in place
        self._table_size: Optional[int] = None
        # adaptive PER-PAGE strategy, decided once from the first page's true
        # group count (one scalar sync, the price the fold already pays):
        # - defer=True: grouping is NOT reducing (groups ~ rows), so the
        #   per-page sort+reduce is pure overhead — pages contribute their
        #   raw (keys, contribs, mask) rows and ONE fold does all the sort
        #   work. No further syncs: raw absorption is shape-static.
        # - _out_groups: grouping reduces a lot — later partials emit a
        #   SHRUNKEN table sized to the observed count, so fold inputs and
        #   per-page segment reductions scale with groups, not capacity.
        #   Needs a per-page overflow check (one scalar sync), so it engages
        #   only on the synchronous CPU backend; accelerators keep full-size
        #   partials and their fully async dispatch.
        self._defer: Optional[bool] = None
        self._out_groups: Optional[int] = None
        self._raw_kernel = None

    # --- per page ---------------------------------------------------------

    def _page_partial(self, page: Page, out_groups: int):
        mask = page.mask
        keys = _null_safe_keys(page, self._key_channels)
        contribs = _call_contributions(self.calls, page, self.from_intermediate)
        return sort_group_reduce(keys, mask, tuple(contribs), self.kinds,
                                 self.identities, out_groups, self.widths)

    def _page_raw(self, page: Page):
        """Defer mode: per-row keys/contributions, no per-page reduction.
        Structurally identical to a partial's (keys, states, valid) triple,
        so the fold/spill machinery consumes both interchangeably."""
        keys = _null_safe_keys(page, self._key_channels)
        contribs = _call_contributions(self.calls, page, self.from_intermediate)
        return keys, tuple(contribs), page.mask

    def set_channels(self, key_channels: Sequence[int]):
        self._key_channels = tuple(key_channels)
        return self

    def share_kernels(self, donor: "GroupedAggregationBuilder") -> None:
        """Adopt a sibling builder's jitted kernel (identical static config) so
        per-worker builder instances trace/compile once per factory, not once
        per worker."""
        self._page_kernel = donor._page_kernel

    def page_out_groups(self, capacity: int) -> int:
        og = capacity if self._wide_cap is None \
            else min(capacity, self._wide_cap)
        if self._out_groups is not None:
            og = min(og, self._out_groups)
        return og

    def defer_raw(self) -> bool:
        """True once the first page proved grouping does not reduce."""
        return self._defer is True

    def _install_page_kernel(self, page: Page) -> None:
        if self._page_kernel is None:
            self._page_kernel = kernel_cache.get_or_install(
                _builder_key("sort", self, page), lambda: jax.jit(
                    self._page_partial, static_argnames=("out_groups",)))

    def _install_raw_kernel(self, page: Page) -> None:
        if self._raw_kernel is None:
            self._raw_kernel = kernel_cache.get_or_install(
                _builder_key("sort-raw", self, page),
                lambda: jax.jit(self._page_raw))

    def add_page(self, page: Page) -> None:
        if self.defer_raw():
            self._install_raw_kernel(page)
            self.absorb_raw(self._raw_kernel(page), page.capacity)
            return
        self._install_page_kernel(page)
        out_groups = self.page_out_groups(page.capacity)
        if not self.absorb_partial(self._page_kernel(page, out_groups),
                                   page.capacity, out_groups):
            # shrunken table overflowed: redo this one page at full size
            out_groups = self.page_out_groups(page.capacity)
            ok = self.absorb_partial(self._page_kernel(page, out_groups),
                                     page.capacity, out_groups)
            assert ok, "full-size partial cannot overflow"

    def absorb_raw(self, raw, capacity: int) -> None:
        """Defer mode: install one page's per-row (keys, contribs, mask)."""
        keys, contribs, mask = raw
        self._pending.append((keys, contribs, mask))
        self._pending_rows += capacity
        if self._pending_rows >= 4 * self.max_groups:
            self._fold()

    def absorb_partial(self, partial, capacity: int, out_groups: int) -> bool:
        """Install one page's (gkeys, gstates, gvalid, ng) partial — computed
        by this builder's own kernel or by a fused pipeline segment whose
        final stage ran the identical `_page_partial` config. Returns False
        when a SHRUNKEN table overflowed (the page's tail groups were clamped
        into the trash bin): the caller must recompute that page at the
        then-reset full size."""
        gkeys, gstates, gvalid, ng = partial
        METRICS.count_many(self._column_forms, prefix="agg.sort.")
        full = capacity if self._wide_cap is None \
            else min(capacity, self._wide_cap)
        if out_groups < full:
            # shrunken partial: verify the observed bound still holds (one
            # scalar sync — the shrink is only picked on sync-cheap backends)
            if int(ng) > out_groups:
                self._out_groups = None  # data disproved the bound
                return False
        elif self._wide_cap is not None and int(ng) > out_groups:
            # a capped group table would silently merge groups — fail loudly
            # (sketch aggregates target few groups; the reference's qdigest /
            # HLL states would OOM long before this bound too)
            raise RuntimeError(
                f"sketch aggregate over more than {out_groups} groups in one "
                f"page is not supported")
        elif self._defer is None and self._wide_cap is None:
            self._decide_strategy(int(ng), capacity)
        self._pending.append((gkeys, gstates, gvalid))
        # account the partial's actual table rows (static shape, no sync):
        # shrunken partials then reach the fold threshold by live state, not
        # by input capacity, sparing needless mid-stream folds
        self._pending_rows += int(gvalid.shape[0])
        if self._pending_rows >= 4 * self.max_groups:
            self._fold()
        return True

    def _decide_strategy(self, first_ng: int, capacity: int) -> None:
        """One-shot adaptation from the first page's true group count (one
        scalar sync, same price a fold pays). Groups ~ rows: per-page
        sort+reduce buys nothing — defer pages as raw rows into the fold.
        Groups << rows: shrink later partials' tables to the observed count
        (CPU backend only: the overflow guard syncs per page)."""
        self._defer = first_ng > capacity // 2
        if self._defer:
            return
        import jax as _jax

        on_cpu = _jax.default_backend() == "cpu"
        if on_cpu and first_ng <= capacity // 8:
            self._out_groups = max(1024, _pow2(int(first_ng * 1.5) + 1))

    # --- combine ----------------------------------------------------------

    def _fold(self, final: bool = False) -> None:
        """Merge pending partials (+ current table) into a fresh compact table.
        If the live group count exceeds max_groups, the inputs are SPILLED to
        host RAM instead (merged exactly at finish) — never silently dropped.
        `final` marks the finish()-time fold: no further folds will read the
        table, so the tighten-to-pow2 slicing pass is skipped."""
        parts = list(self._pending)
        self._pending = []
        self._pending_rows = 0
        if self._acc is not None:
            parts.append(self._acc)
            self._acc = None
        # pad the part count to its pow2 bucket with zero-row dummies so the
        # fused combine kernel's trace signature is bounded by O(log parts)
        # distinct counts, not one compile per exact count
        n_parts = len(parts)
        want = _pow2_count(n_parts)
        if want > n_parts:
            # numpy zeros: eager jnp.zeros dispatches compile a throwaway
            # kernel per dtype; np arrays device_put at the jit call
            z_keys = tuple(np.zeros(0, dtype=p.dtype)
                           for p in parts[0][0])
            z_states = tuple(
                np.zeros((0,) + tuple(s.shape[1:]), dtype=s.dtype)
                for s in parts[0][1])
            z_valid = np.zeros(0, dtype=np.bool_)
            parts = parts + [(z_keys, z_states, z_valid)] * (want - n_parts)
        key_parts = tuple(tuple(p[0][i] for p in parts)
                          for i in range(len(self.key_types)))
        state_parts = tuple(tuple(p[1][i] for p in parts)
                            for i in range(len(self.kinds)))
        valid_parts = tuple(p[2] for p in parts)
        total_rows = sum(int(v.shape[0]) for v in valid_parts)
        size = self._table_size or _pow2(min(total_rows, self.max_groups))
        while True:
            # concat + sort + reduce in ONE jitted dispatch (the eager
            # per-column concatenates were a dispatch, and on first use a
            # compiled program, each)
            gkeys, gstates, gvalid, ngroups = _combine_parts_kernel(
                key_parts, valid_parts, state_parts, self.kinds,
                self.identities, size, self.widths)
            METRICS.count_many(self._column_forms, prefix="agg.sort.")
            n = int(ngroups)
            if n <= size or size >= self.max_groups:
                break
            size = min(_pow2(n), self.max_groups)  # grow and refold
        if n > self.max_groups:
            # more live groups than the device table can hold: move the (still
            # complete) input rows to host and keep accumulating fresh
            self._spilled.append((
                tuple(np.concatenate([np.asarray(x) for x in kp])
                      for kp in key_parts),
                tuple(np.concatenate([np.asarray(x) for x in sp])
                      for sp in state_parts),
                np.concatenate([np.asarray(v) for v in valid_parts])))
            self._table_size = None
            return
        # shrink the table to the true group count's bucket: gvalid is a prefix,
        # so slicing keeps every live group and future folds sort less. The
        # FINAL fold skips this — nothing reads the table again, and the
        # slice kernels would be pure overhead
        tight = min(_pow2(max(n, 1)), self.max_groups)
        if tight < size and not final:
            gkeys = tuple(k[:tight] for k in gkeys)
            gstates = tuple(s[:tight] for s in gstates)
            gvalid = gvalid[:tight]
        self._table_size = tight
        self._acc = (gkeys, gstates, gvalid)

    # --- spill (HBM -> host RAM; FileSingleStreamSpiller analogue) ---------

    def memory_bytes(self) -> int:
        """Device-resident bytes (pending partials + compact table)."""
        per_row = sum(np.dtype(t.np_dtype).itemsize for t in self.key_types) + \
            sum(np.dtype(col.dtype).itemsize * col.width
                for c in self.calls for col in c.function.state) + 1
        rows = self._pending_rows
        if self._acc is not None:
            rows += int(self._acc[2].shape[0])
        return rows * per_row

    def spill(self) -> None:
        """Move ALL device state to host (start_memory_revoke path)."""
        parts = list(self._pending)
        self._pending = []
        self._pending_rows = 0
        if self._acc is not None:
            parts.append(self._acc)
            self._acc = None
            self._table_size = None
        for p in parts:
            self._spilled.append((
                tuple(np.asarray(k) for k in p[0]),
                tuple(np.asarray(s) for s in p[1]),
                np.asarray(p[2])))

    def _merge_spilled(self):
        """Exact host-side merge of spilled partials + device table: sort rows
        by key tuple, segment boundaries, per-kind reduceat. Unbounded group
        counts are fine here — host RAM is the spill medium. When disk runs
        exist, the merge goes partition-at-a-time instead (_merge_disk)."""
        parts = list(self._spilled)
        self._spilled = []
        if self._acc is not None:
            parts.append((tuple(np.asarray(k) for k in self._acc[0]),
                          tuple(np.asarray(s) for s in self._acc[1]),
                          np.asarray(self._acc[2])))
            self._acc = None
        if self._disk_runs:
            return self._merge_disk(parts)
        keys, states = self._host_merge_parts(parts)
        n = len(keys[0]) if keys else 0
        if n == 0:
            return self._no_groups()
        return tuple(keys), tuple(states), np.ones(n, dtype=bool)

    def _host_merge_parts(self, parts):
        """Merge (keys, states, valid) numpy triples exactly: filter valid,
        lexsort by key tuple, reduceat per kind -> ([key col...], [state
        col...]) with ONE row per distinct key, sorted. The shared core of
        the host-RAM merge and the per-partition disk merge."""
        nk = len(self.key_types)
        keys = [np.concatenate([np.asarray(p[0][i]) for p in parts])
                for i in range(nk)]
        states = [np.concatenate([np.asarray(p[1][i]) for p in parts])
                  for i in range(len(self.kinds))]
        valid = np.concatenate([np.asarray(p[2]) for p in parts])
        keys = [k[valid] for k in keys]
        states = [s[valid] for s in states]
        if len(keys[0]) == 0:
            return keys, states
        order = np.lexsort(tuple(reversed(keys)))
        keys = [k[order] for k in keys]
        states = [s[order] for s in states]
        boundary = np.zeros(len(keys[0]), dtype=bool)
        boundary[0] = True
        for k in keys:
            boundary[1:] |= k[1:] != k[:-1]
        starts = np.flatnonzero(boundary)
        # stay on HOST: the merged table can exceed device capacity (that is
        # why it spilled); _build_result pages it out page-capacity at a time
        out_keys = [k[starts] for k in keys]
        out_states = []
        i = 0
        nrows = len(keys[0])
        while i < len(self.kinds):
            kind = self.kinds[i]
            s = states[i]
            if kind in (AMIN, AMAX):
                y = states[i]
                red = np.minimum if kind == AMIN else np.maximum
                m = red.reduceat(y, starts)
                counts = np.diff(np.append(starts, nrows))
                cand = np.where(y == np.repeat(m, counts),
                                np.arange(nrows), nrows)
                win = np.clip(np.minimum.reduceat(cand, starts), 0,
                              max(nrows - 1, 0))
                out_states.append(m)
                i += 1
                while i < len(self.kinds) and self.kinds[i] == ACARRY:
                    out_states.append(states[i][win])
                    i += 1
                continue
            red = {SUM: np.add, MIN: np.minimum, MAX: np.maximum}[kind]
            out_states.append(red.reduceat(s, starts))
            i += 1
        return out_keys, out_states

    # --- disk tier (host RAM -> PCOL runs; exec/spill.py) ------------------

    def attach_spill(self, mgr) -> None:
        """Wire the query's SpillManager (or None) — done once per operator
        from its OperatorContext."""
        self._spill_mgr = mgr

    def disk_eligible(self) -> bool:
        # wide (vector/sketch) states scatter into 2-D tables pcol does not
        # speak; they stay on the host rung. Dtype eligibility is checked
        # per flush (spill_to_disk declines, never raises).
        return self._spill_mgr is not None and self._wide_cap is None

    def host_spill_bytes(self) -> int:
        """Host-RAM bytes held by spilled partials — the disk-flushable
        rung the operator reports as revocable when disk is attached."""
        total = 0
        for p in self._spilled:
            for a in p[0]:
                total += np.asarray(a).nbytes
            for a in p[1]:
                total += np.asarray(a).nbytes
            total += np.asarray(p[2]).nbytes
        return total

    def _adapt_disk_parts(self, new_rows: int, row_bytes: int) -> None:
        """Grow the pow2 partition count from OBSERVED cardinality: distinct
        rows seen so far (disk runs are an upper bound — duplicates across
        runs merge away) sized so one partition's merge stays near the
        target working set. Grow-only: a run written at P=4 is still
        addressable when later runs use P=16 (part = hash & (P-1), so the
        coarse index is a suffix of the fine one)."""
        est_rows = new_rows + sum(r.rows for r in self._disk_runs)
        want = _pow2_count(
            max(1, (est_rows * max(row_bytes, 1)
                    + _DISK_PARTITION_TARGET_BYTES - 1)
                // _DISK_PARTITION_TARGET_BYTES))
        self._disk_parts = min(max(self._disk_parts, want),
                               _DISK_MAX_PARTITIONS)

    def spill_to_disk(self) -> int:
        """Flush the host-RAM partials as hash-partitioned, sorted,
        partially-reduced PCOL runs; returns bytes written (0 = declined:
        no manager, wide states, or a dtype pcol cannot store — the state
        simply stays in host RAM; disk is an optimisation rung, never a
        correctness requirement)."""
        mgr = self._spill_mgr
        if mgr is None or self._wide_cap is not None or not self._spilled:
            return 0
        from ..exec.spill import storage_type_for
        sample = self._spilled[0]
        probe = [np.asarray(a) for a in sample[0]] + \
                [np.asarray(a) for a in sample[1]]
        if any(a.ndim != 1 or storage_type_for(a.dtype) is None
               for a in probe):
            return 0
        parts = self._spilled
        self._spilled = []
        keys, states = self._host_merge_parts(parts)
        n = len(keys[0]) if keys else 0
        if n == 0:
            return 0
        row_bytes = sum(a.dtype.itemsize for a in keys) + \
            sum(a.dtype.itemsize for a in states)
        self._adapt_disk_parts(n, row_bytes)
        P = self._disk_parts
        part = (_key_row_hash(keys) & np.uint64(P - 1)).astype(np.int64)
        names = [f"k{i}" for i in range(len(keys))] + \
                [f"s{i}" for i in range(len(states))]
        written = 0
        for p in range(P):
            sel = part == p
            if not sel.any():
                continue
            # boolean selection preserves order: each partition stays
            # sorted by key tuple — a sorted, partially-reduced run
            cols = [a[sel] for a in keys] + [a[sel] for a in states]
            run = mgr.write_columns(
                names, cols, kind="agg",
                meta={"P": P, "part": p, "nk": len(keys)})
            self._disk_runs.append(run)
            written += run.nbytes
        return written

    def _merge_disk(self, resident_parts):
        """Exact merge-on-read over the disk runs + the in-RAM residual,
        one finest-granularity partition at a time. Runs written at a
        coarser P contribute rows to every fine partition that refines
        theirs — the recomputed hash filter keeps the merge exact across
        mixed granularities. Peak host RAM is one partition's rows, not
        the whole group table."""
        mgr = self._spill_mgr
        runs = self._disk_runs
        self._disk_runs = []
        res_keys, res_states = (self._host_merge_parts(resident_parts)
                                if resident_parts else ([], []))
        have_res = bool(res_keys) and len(res_keys[0]) > 0
        p_max = self._disk_parts
        res_part = None
        if have_res:
            res_part = (_key_row_hash(res_keys)
                        & np.uint64(p_max - 1)).astype(np.int64)
        out_keys: List[List[np.ndarray]] = [[] for _ in self.key_types]
        out_states: List[List[np.ndarray]] = [[] for _ in self.kinds]
        total = 0
        for f in range(p_max):
            chunk_parts = []
            for run in runs:
                if run.meta["part"] != (f & (run.meta["P"] - 1)):
                    continue
                cols = mgr.read_columns(run)
                nk = run.meta["nk"]
                rkeys = [c[0] for c in cols[:nk]]
                rstates = [c[0] for c in cols[nk:]]
                if run.meta["P"] < p_max:
                    sel = (_key_row_hash(rkeys)
                           & np.uint64(p_max - 1)).astype(np.int64) == f
                    rkeys = [k[sel] for k in rkeys]
                    rstates = [s[sel] for s in rstates]
                if len(rkeys[0]) == 0:
                    continue
                chunk_parts.append(
                    (tuple(rkeys), tuple(rstates),
                     np.ones(len(rkeys[0]), dtype=bool)))
            if have_res:
                sel = res_part == f
                if sel.any():
                    chunk_parts.append(
                        (tuple(k[sel] for k in res_keys),
                         tuple(s[sel] for s in res_states),
                         np.ones(int(sel.sum()), dtype=bool)))
            if not chunk_parts:
                continue
            mk, ms = self._host_merge_parts(chunk_parts)
            if not mk or len(mk[0]) == 0:
                continue
            for i, k in enumerate(mk):
                out_keys[i].append(k)
            for i, s in enumerate(ms):
                out_states[i].append(s)
            total += len(mk[0])
        for run in runs:
            mgr.release(run)
        if total == 0:
            return self._no_groups()
        return (tuple(np.concatenate(c) for c in out_keys),
                tuple(np.concatenate(c) for c in out_states),
                np.ones(total, dtype=bool))

    def _no_groups(self):
        """(keys, states, valid) of zero groups, in numpy (see _empty_state)."""
        z = tuple(np.zeros(0, dtype=t.np_dtype) for t in self.key_types)
        return z, _empty_state(self.widths), np.zeros(0, dtype=np.bool_)

    def finish(self):
        """-> (keys, states, valid), compact: on device, or in host RAM
        after a spill."""
        if self._pending or self._acc is None:
            if not self._pending and self._acc is None \
                    and not self._spilled and not self._disk_runs:
                return self._no_groups()  # empty input
            if self._pending:
                self._fold(final=True)
        if self._spilled or self._disk_runs:
            out = self._merge_spilled()
        else:
            out = self._acc
        # drop device references: the first builder per cache key stays alive
        # in the kernel cache (its jitted bound method), so lingering state
        # would pin the final group tables in HBM past the query's end
        self._acc = None
        self._pending = []
        self._spilled = []
        self._table_size = None
        return out


def _cut_page(arrs, nulls, valid, lo, cap):
    """One output page: every column's [lo, lo+cap) slice, zero-padded."""
    def seg(a):
        n = a.shape[0]
        padded = jnp.concatenate([a, jnp.zeros(cap, dtype=a.dtype)])
        return jax.lax.dynamic_slice_in_dim(
            padded, jnp.clip(lo, 0, n), cap)

    datas = tuple(seg(a) for a in arrs)
    nmasks = tuple(None if nl is None else seg(nl) for nl in nulls)
    return datas, nmasks, seg(valid)


# a page of a result whose size is data: one dispatch for all its columns
_slice_result_page = jax.jit(_cut_page, static_argnames=("cap",))


def _result_columns(calls, step, dtypes, keys, states, orders, finals):
    """Traced: every output column as (data in its type, null mask or None):
    the group keys (their flags as masks), then per call its state columns
    (PARTIAL) or its final map's output under the (data, null_mask)
    contract, `finals[i]` where call i's final map ran on the host.
    `orders[i]` is the rank -> code map of call i's unsorted dictionary,
    whose min/max states hold RANKS (_call_contributions)."""
    cols = [(keys[i], keys[i + 1]) for i in range(0, len(keys), 2)]
    si = 0
    for call, order, final in zip(calls, orders, finals):
        ncols = len(call.function.state)
        group_states = states[si: si + ncols]
        si += ncols
        if step == PARTIAL:
            cols.extend((s, None) for s in group_states)
            continue
        out = call.function.final_map(group_states) if final is None \
            else final
        nulls = None
        if isinstance(out, tuple):
            out, nulls = out
        if order is not None:
            # empty groups clip to an arbitrary code; their null flag masks
            # them. States may arrive as f64 from the mesh exchange's
            # common-dtype collectives: index with ints
            out = order[jnp.clip(out, 0, order.shape[0] - 1
                                 ).astype(jnp.int32)]
        cols.append((out, nulls))
    return (tuple(jnp.asarray(d, dtype=dt) for (d, _n), dt in zip(cols, dtypes)),
            tuple(n for _d, n in cols))


def _static_result(decode, calls, step, dtypes, rows, cap, state, orders,
                   finals):
    """The whole result of a builder whose size is static (`rows`), as ONE
    program: the builder's decode, every final map and cast, and every page
    cut. Nothing is read back to the host."""
    keys, states, valid = decode(state)
    arrs, nulls = _result_columns(calls, step, dtypes, keys, states, orders,
                                  finals)
    return tuple(_cut_page(arrs, nulls, valid, lo, cap)
                 for lo in range(0, max(rows, 1), cap))


def _result_epilogue(calls, step, dtypes, keys, states, valid, orders,
                     finals):
    """A result whose size is data: its columns, the live group count and
    whether each key holds a NULL, in one program, for ONE host read."""
    arrs, nulls = _result_columns(calls, step, dtypes, keys, states, orders,
                                  finals)
    any_null = tuple(jnp.any(keys[i]) for i in range(1, len(keys), 2))
    return arrs, nulls, jnp.count_nonzero(valid), any_null


@functools.partial(jax.jit, static_argnames=("kinds", "identities",
                                             "max_groups", "widths"))
def _combine_kernel(keys, valid, states, kinds, identities, max_groups,
                    widths=None):
    return sort_group_reduce(keys, valid, states, kinds, identities,
                             max_groups, widths)


@functools.partial(jax.jit, static_argnames=("kinds", "identities",
                                             "max_groups", "widths"))
def _combine_parts_kernel(key_parts, valid_parts, state_parts, kinds,
                          identities, max_groups, widths=None):
    """_combine_kernel with the cross-part concatenation fused in: one
    dispatch folds N pending partials into the compact table."""
    keys = tuple(jnp.concatenate(list(kp)) for kp in key_parts)
    states = tuple(jnp.concatenate(list(sp)) for sp in state_parts)
    valid = jnp.concatenate(list(valid_parts))
    return sort_group_reduce(keys, valid, states, kinds, identities,
                             max_groups, widths)


def _pow2(n: int) -> int:
    return 1 << max(4, (n - 1).bit_length())


def _pow2_count(n: int) -> int:
    """Next power of two >= n (no floor) — part-count bucketing."""
    return 1 << max(0, (n - 1).bit_length())


class DirectAggregationBuilder:
    """Small-domain strategy: dense state table indexed by linear key code.

    BigintGroupByHash analogue; domain = product of per-key dictionary/domain sizes."""

    def __init__(self, key_types, key_dicts, domains: Sequence[int], calls,
                 from_intermediate: bool = False):
        self.key_types = list(key_types)
        self.key_dicts = list(key_dicts)
        # one extra slot per key for its NULL group (code == base domain):
        # SQL groups NULL as its own key even in the dense-domain strategy
        self.base_domains = [int(d) for d in domains]
        self.domains = [int(d) + 1 for d in domains]
        self.calls = list(calls)
        self.from_intermediate = from_intermediate
        self.D = int(np.prod(self.domains))
        # the result is the whole domain-indexed table, holes and all: its
        # size is static, so the operator finishes it in one program
        self.result_rows = self.D
        self.kinds = tuple(col.reduce for c in calls for col in c.function.state)
        self.identities = tuple(col.identity for c in calls for col in c.function.state)
        self.widths = _state_widths(calls)
        self._table = None  # tuple of (D,) / (D, width) state arrays
        self._seen = None   # (D,) bool: group occurred
        self._kernel = None  # lazy: set_channels runs after __init__

    def set_channels(self, key_channels):
        self._key_channels = tuple(key_channels)
        return self

    def share_kernels(self, donor: "DirectAggregationBuilder") -> None:
        self._kernel = donor._kernel

    def _accumulate(self, page: Page, table, seen):
        datas = tuple(b.data for b in page.blocks)
        mask = page.mask
        gid = jnp.zeros(page.mask.shape[0], dtype=jnp.int32)
        for ch, base, dom in zip(self._key_channels, self.base_domains,
                                 self.domains):
            code = jnp.clip(datas[ch].astype(jnp.int32), 0, base - 1)
            if page.blocks[ch].nulls is not None:
                code = jnp.where(page.blocks[ch].nulls, base, code)
            gid = gid * dom + code
        gid = jnp.where(mask, gid, self.D)  # dead rows -> trash segment
        contribs = _call_contributions(self.calls, page, self.from_intermediate)
        parts = _reduce_all(contribs, self.kinds, self.identities,
                            self.widths, gid, self.D)
        new_table = _merge_tables(self.kinds, table, parts)
        new_seen = seen | (_segment_reduce(
            SUM, mask.astype(jnp.int32), gid, self.D + 1)[: self.D] > 0)
        return tuple(new_table), new_seen

    def _identity(self):
        """The empty (table, seen) accumulator (the init program's body)."""
        table = tuple(
            jnp.full((self.D, col.width) if col.width > 1 else (self.D,),
                     col.identity, dtype=np.dtype(col.dtype))
            for c in self.calls for col in c.function.state)
        return table, jnp.zeros(self.D, dtype=jnp.bool_)

    def init_state(self):
        """(table, seen) accumulator, made on first use by ONE cached
        program (an eager fill was two programs a state column) — threaded
        through the page kernel as jit arguments (fused segments pass it the
        same way)."""
        if self._table is None:
            make = kernel_cache.get_or_install(
                _builder_key("direct-init", self),
                lambda: jax.jit(self._identity))
            self._table, self._seen = make()
        return self._table, self._seen

    def absorb_state(self, state) -> None:
        """Take the accumulator one page kernel returned (add_page's or a
        fused segment's): the one place a direct page is counted."""
        self._table, self._seen = state
        dense = self.D + 1 <= DENSE_REDUCE_MAX_SEGMENTS
        METRICS.count_many({"pages": 1, "dense_pages": int(dense)},
                           prefix="agg.direct.")

    def add_page(self, page: Page) -> None:
        if self._kernel is None:
            self._kernel = kernel_cache.get_or_install(
                _builder_key("direct", self, page),
                lambda: jax.jit(self._accumulate))
        table, seen = self.init_state()
        self.absorb_state(self._kernel(page, table, seen))

    def take_state(self):
        """Hand the accumulator over (the identity if no page came): the
        builder keeps no device reference to it (see
        GroupedAggregationBuilder.finish)."""
        state = self.init_state()
        self._table = self._seen = None
        return state

    def decode(self, state):
        """(table, seen) -> (keys, states, valid): the linear group id
        decoded back to interleaved (value, null_flag) key columns. Traced
        inside the operator's result program."""
        table, seen = state
        rem = jnp.arange(self.D, dtype=jnp.int32)
        pairs = []
        for base, dom, t in zip(reversed(self.base_domains),
                                reversed(self.domains),
                                reversed(self.key_types)):
            code = rem % dom
            flag = code == base
            pairs.append((jnp.where(flag, 0, code).astype(t.np_dtype), flag))
            rem = rem // dom
        keys = tuple(x for v, f in reversed(pairs) for x in (v, f))
        return keys, table, seen

    def finish(self):
        return self.decode(self.take_state())


class GlobalAggregationBuilder:
    """No GROUP BY: scalar states (AggregationOperator analogue)."""

    result_rows = 1  # static: the operator finishes it in one program

    def __init__(self, calls: Sequence[AggregateCall], from_intermediate: bool = False):
        self.calls = list(calls)
        self.from_intermediate = from_intermediate
        self.kinds = tuple(col.reduce for c in calls for col in c.function.state)
        self.identities = tuple(col.identity for c in calls for col in c.function.state)
        self.widths = _state_widths(calls)
        self._state = None
        self._kernel = None  # lazy: keyed on the first page's dict versions

    def set_channels(self, key_channels):
        return self

    def share_kernels(self, donor: "GlobalAggregationBuilder") -> None:
        self._kernel = donor._kernel

    def _accumulate(self, page: Page, state):
        mask = page.mask
        contribs = _call_contributions(self.calls, page, self.from_intermediate)
        state = self._state_or(state)
        new_state = []
        i = 0
        while i < len(self.kinds):
            kind = self.kinds[i]
            c = contribs[i]
            ident = self.identities[i]
            w = self.widths[i]
            s = state[i]
            if kind in (AMIN, AMAX):
                # joint pair reduce over rows, then combine with the state
                y = contribs[i]
                am = (jnp.argmin if kind == AMIN else jnp.argmax)(y)
                red_y = y[am]
                better = (red_y < s) if kind == AMIN else (red_y > s)
                new_state.append(jnp.where(better, red_y, s))
                i += 1
                while i < len(self.kinds) and self.kinds[i] == ACARRY:
                    new_state.append(jnp.where(better, contribs[i][am],
                                               state[i]))
                    i += 1
                continue
            if isinstance(c, tuple):
                bucket, vals = c
                base = jnp.full((w,), ident, dtype=vals.dtype)
                at = base.at[bucket]
                red = (at.add if kind == SUM else
                       (at.min if kind == MIN else at.max))(vals, mode="drop")
            else:
                if self.from_intermediate:
                    cond = mask if c.ndim == 1 else mask[:, None]
                    c = jnp.where(cond, c, jnp.asarray(ident, dtype=c.dtype))
                # axis=0 keeps (rows, width) vector contributions per-column
                red = {SUM: jnp.sum, MIN: jnp.min,
                       MAX: jnp.max}[kind](c, axis=0)
            new_state.append({SUM: lambda a, b: a + b,
                              MIN: jnp.minimum, MAX: jnp.maximum}[kind](s, red))
            i += 1
        return tuple(new_state)

    def _state_or(self, state):
        return state

    def _identity_state(self):
        """The empty state (the init program's body)."""
        return tuple(
            jnp.full((col.width,) if col.width > 1 else (), col.identity,
                     dtype=np.dtype(col.dtype))
            for c in self.calls for col in c.function.state)

    def init_state(self):
        """The state, made on first use by ONE cached program."""
        if self._state is None:
            make = kernel_cache.get_or_install(
                _builder_key("global-init", self),
                lambda: jax.jit(self._identity_state))
            self._state = make()
        return self._state

    def absorb_state(self, state) -> None:
        self._state = state

    def add_page(self, page: Page) -> None:
        if self._kernel is None:
            self._kernel = kernel_cache.get_or_install(
                _builder_key("global", self, page),
                lambda: jax.jit(self._accumulate))
        self.absorb_state(self._kernel(page, self.init_state()))

    def take_state(self):
        """Hand the state over (the identity if no page came)."""
        state = self.init_state()
        self._state = None  # see GroupedAggregationBuilder.finish
        return state

    @staticmethod
    def decode(state):
        """state -> (keys, states, valid) of one group (traced inside the
        operator's result program)."""
        states = tuple(jnp.reshape(s, (1, -1) if s.ndim else (1,))
                       for s in state)
        return (), states, jnp.ones(1, dtype=jnp.bool_)

    def finish(self):
        return self.decode(self.take_state())


# ---------------------------------------------------------------------------
# operator
# ---------------------------------------------------------------------------

PARTIAL, FINAL, SINGLE = "partial", "final", "single"


class HashAggregationOperator(Operator):
    """Steps: PARTIAL emits [keys..., state_cols...]; FINAL consumes those;
    SINGLE does both (HashAggregationOperator.java:352-390 step wiring)."""

    def __init__(self, context: OperatorContext, builder, key_channels: List[int],
                 key_types: List[Type], key_dicts, calls: List[AggregateCall],
                 step: str, output_capacity: int):
        super().__init__(context)
        self.builder = builder.set_channels(key_channels)
        # disk tier: hand the builder the query's SpillManager so revocation
        # can walk host partials down to PCOL runs (grouped builder only —
        # global/direct builders have no spillable state)
        attach = getattr(self.builder, "attach_spill", None)
        if attach is not None:
            attach(context.spill)
        self.key_types = key_types
        self.key_dicts = key_dicts
        self.calls = calls
        self.step = step
        self.output_capacity = output_capacity
        self._result_pages: Optional[List[Page]] = None

    @property
    def output_types(self) -> List[Type]:
        out = list(self.key_types)
        for c in self.calls:
            if self.step == PARTIAL:
                out.extend(c.function.intermediate_types)
            else:
                out.append(c.function.output_type)
        return out

    @timed("add_input_ns")
    def add_input(self, page: Page) -> None:
        self.context.record_input(page, page.capacity)
        self.builder.add_page(page)
        if getattr(self.builder, "memory_bytes", None) is not None:
            self.context.update_revocable(self.revocable_bytes(),
                                          self.start_memory_revoke)

    # spill protocol (operator/Operator.java:68 startMemoryRevoke analogue):
    # the revoker asks; ONE revoke call walks the whole ladder — the builder
    # moves its device table to host RAM, then (when the query has a disk
    # tier and the state shape is disk-eligible) flushes the host partials
    # to PCOL runs. With no disk tier the host partials stay (the pre-disk
    # behavior) and only device bytes count as revocable.
    def _disk_capable(self) -> bool:
        eligible = getattr(self.builder, "disk_eligible", None)
        return self.context.spill is not None and eligible is not None \
            and eligible()

    def revocable_bytes(self) -> int:
        b = getattr(self.builder, "memory_bytes", None)
        total = b() if b is not None else 0
        if self._disk_capable():
            total += self.builder.host_spill_bytes()
        return total

    def start_memory_revoke(self) -> None:
        spill = getattr(self.builder, "spill", None)
        if spill is not None:
            spill()
            if self._disk_capable():
                self.builder.spill_to_disk()
            self.context.revocable_memory.set_bytes(self.revocable_bytes())

    @timed("get_output_ns")
    def get_output(self) -> Optional[Page]:
        if self._result_pages:
            out = self._result_pages.pop(0)
            self.context.record_output(out, out.capacity)
            return out
        return None

    def is_finished(self) -> bool:
        return self._finishing and self._result_pages is not None and not self._result_pages

    def finish(self) -> None:
        super().finish()
        if self._result_pages is None:
            self._build_result()

    def _rank_orders(self) -> Tuple:
        """Per call: the rank -> code map of the unsorted varchar dictionary
        whose min/max reduced sort RANKS (see _call_contributions), else
        None. An argument of the result's program, not a trace constant."""
        out = []
        for call in self.calls:
            d = call.output_dictionary
            ranked = (self.step != PARTIAL and d is not None
                      and call.function.name in ("min", "max")
                      and not d.is_sorted())
            out.append(d.sort_order() if ranked else None)
        return tuple(out)

    def _host_finals(self, states_of) -> Tuple:
        """Per call: the (data, nulls) its final map returned on the host
        (AggregateFunction.host_final_map), else None. `states_of()` gives
        the finished states; it is asked only where such a call is."""
        host = [self.step != PARTIAL and c.function.host_final_map
                for c in self.calls]
        if not any(host):
            return (None,) * len(self.calls)
        states, out, si = states_of(), [], 0
        for call, on_host in zip(self.calls, host):
            ncols = len(call.function.state)
            r = call.function.final_map(states[si: si + ncols]) \
                if on_host else None
            out.append(r if r is None or isinstance(r, tuple) else (r, None))
            si += ncols
        return tuple(out)

    def _build_result(self) -> None:
        """The result pages. The builder's size decides the path: static
        (the direct builder's D slots, the global builder's one row) -> ONE
        cached program decodes, finishes, casts and cuts every page, and the
        host reads nothing; data (the sort and collect builders' compact
        tables) -> one program for the columns, the live count and each
        key's any-NULL, ONE read of those, then a program a page."""
        layout = list(zip(self.key_types, self.key_dicts))
        for call in self.calls:
            if self.step == PARTIAL:
                layout.extend((it, None)
                              for it in call.function.intermediate_types)
            else:
                layout.append((call.function.output_type,
                               call.output_dictionary))
        dtypes = tuple(np.dtype(t.np_dtype) for t, _d in layout)
        calls = tuple(self.calls)
        orders = self._rank_orders()
        rows = self.builder.result_rows
        if rows is not None:
            cap = clamp_capacity(rows, self.output_capacity)
            program = kernel_cache.get_or_install(
                _builder_key("result", self.builder) + (self.step, cap),
                lambda: jax.jit(functools.partial(
                    _static_result, self.builder.decode, calls, self.step,
                    dtypes, rows, cap)))
            state = self.builder.take_state()
            finals = self._host_finals(lambda: self.builder.decode(state)[1])
            pages = program(state, orders, finals)
            METRICS.count("agg.result.fused")
            METRICS.count("agg.result.host_reads", 0)
        else:
            keys, states, valid = self.builder.finish()
            epilogue = kernel_cache.get_or_install(
                _builder_key("epilogue", self.builder) + (self.step,),
                lambda: jax.jit(functools.partial(
                    _result_epilogue, calls, self.step, dtypes)))
            arrs, nulls, total, any_null = epilogue(
                keys, states, valid, orders, self._host_finals(lambda: states))
            total, any_null = jax.device_get((total, any_null))
            METRICS.count("agg.result.host_reads")
            nulls = tuple(None if i < len(any_null) and not any_null[i]
                          else n for i, n in enumerate(nulls))
            # size result pages to the live groups' pow2 bucket: at the
            # accelerator page capacity (1 << 22) a 4-group result padded to
            # a full page made every downstream sort and the result
            # transfer scale with the padding
            cap = clamp_capacity(int(total), self.output_capacity)
            pages = [_slice_result_page(arrs, nulls, valid, np.int32(lo), cap)
                     for lo in range(0, max(int(total), 1), cap)]
        self.context.revocable_memory.set_bytes(0)  # builder state consumed
        self._result_pages = [
            Page(tuple(Block(t, dd, nn, d) for (t, d), dd, nn
                       in zip(layout, datas, nmasks)), m)
            for datas, nmasks, m in pages]


def make_builder(key_types, key_dicts, key_domains, calls, page_capacity,
                 max_groups=1 << 20, from_intermediate=False,
                 direct_domain_limit=1 << 16):
    """Strategy pick (LocalExecutionPlanner's group-by-hash choice analogue)."""
    from .collect_agg import COLLECT_NAMES, CollectAggregationBuilder
    if any(c.function.name in COLLECT_NAMES for c in calls):
        # ragged collectors keep every row; one sorted pass at finish
        return CollectAggregationBuilder(key_types, key_dicts, calls,
                                         page_capacity, max_groups,
                                         from_intermediate)
    if not key_types:
        return GlobalAggregationBuilder(calls, from_intermediate)
    wide = any(w > 1 for w in _state_widths(calls))
    if key_domains is not None and all(d is not None for d in key_domains):
        D = int(np.prod(key_domains))
        # vector (sketch) states make the dense table D x width: keep the
        # direct strategy only while that stays small
        if D <= (direct_domain_limit if not wide
                 else min(direct_domain_limit, WIDE_STATE_MAX_GROUPS)):
            return DirectAggregationBuilder(key_types, key_dicts, key_domains, calls,
                                            from_intermediate)
    return GroupedAggregationBuilder(key_types, key_dicts, calls, page_capacity,
                                     max_groups, from_intermediate)


class HashAggregationOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, key_channels, key_types, key_dicts,
                 key_domains, calls, step: str, page_capacity: int,
                 max_groups: int = 1 << 20):
        super().__init__(operator_id, f"HashAggregation({step})")
        self.key_channels = list(key_channels)
        self.key_types = list(key_types)
        self.key_dicts = list(key_dicts)
        self.key_domains = key_domains
        self.calls = list(calls)
        self.step = step
        self.page_capacity = page_capacity
        self.max_groups = max_groups
        self._kernel_donor = None

    def create_operator(self, worker: int = 0) -> Operator:
        from_intermediate = self.step == FINAL
        builder = make_builder(self.key_types, self.key_dicts, self.key_domains,
                               self.calls, self.page_capacity, self.max_groups,
                               from_intermediate)
        # all builders of this factory share one jitted kernel: instance state
        # (tables, pending buffers) is per-builder, the traced computation is
        # pure factory config — workers must not each pay the trace+compile
        if self._kernel_donor is None:
            self._kernel_donor = builder
        else:
            builder.share_kernels(self._kernel_donor)
        return HashAggregationOperator(
            self.context(worker), builder,
            self.key_channels, self.key_types, self.key_dicts, self.calls,
            self.step, self.page_capacity)
