"""Page coalescing after selective filters: dense pages for downstream ops.

The engine's pages are FIXED-capacity device arrays with row masks; a
selective fused filter-scan emits pages whose live rows are a small
fraction of capacity, and every downstream operator (join probe, hash
aggregation) still pays full-capacity kernel work per page. This operator
COMPACTS each input page on device and packs live rows into an
accumulator, emitting only FULL pages (plus one tail) — the reference's
PageProcessor output coalescing / MergePages.java, re-shaped for static
XLA shapes:

- compact: block._compact, one program a schema (a 64-bit column moves by
  a gather through one 32-bit row index, a narrower one by its own scatter);
- pack: `lax.dynamic_update_slice` at the accumulator's live count — a
  dynamic OFFSET is fine under jit (shapes stay static);
- overflow: concat(acc, incoming)[:C] emits, [C:] is the new accumulator —
  all static shapes, one compiled kernel per schema.

Downstream work drops by the filter's selectivity (a 0.02-selective Q6
scan feeds ~50x fewer pages into the aggregation), and each page saved is
one dispatch per downstream operator saved.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..block import Block, Page, _compact
from ..types import Type
from ..utils.metrics import METRICS
from .operator import Operator, OperatorContext, OperatorFactory, timed


@functools.partial(jax.jit, donate_argnums=())
def _pack(acc: Page, count, page: Page):
    """(accumulator, live count, compacted incoming) ->
    (emit page, emit flag, new accumulator, new count).

    The incoming page is already compacted (live rows in prefix). Result
    shapes are static: emit is capacity C; the combined view is 2C wide."""
    cap = acc.capacity
    n_in = jnp.sum(page.mask.astype(jnp.int32))

    def combine(a, b):
        return jnp.concatenate([a, b])

    blocks = []
    for ab, pb in zip(acc.blocks, page.blocks):
        # place incoming prefix at offset `count` inside a 2C scratch
        scratch = combine(ab.data, jnp.zeros_like(pb.data))
        scratch = jax.lax.dynamic_update_slice(
            scratch, pb.data, (count,))
        nulls = None
        if ab.nulls is not None or pb.nulls is not None:
            ns = combine(ab.null_mask(), jnp.zeros_like(pb.null_mask()))
            ns = jax.lax.dynamic_update_slice(ns, pb.null_mask(), (count,))
            nulls = ns
        blocks.append((scratch, nulls, ab))
    total = count + n_in
    emit = total >= cap
    # emit the first C rows; the remainder [C:2C) becomes the accumulator
    out_blocks = []
    rest_blocks = []
    for scratch, nulls, ab in blocks:
        out_blocks.append(Block(ab.type, scratch[:cap],
                                None if nulls is None else nulls[:cap],
                                ab.dictionary))
        # when not emitting, the accumulator keeps the packed prefix
        keep = jnp.where(emit, scratch[cap:], scratch[:cap])
        kn = None
        if nulls is not None:
            kn = jnp.where(emit, nulls[cap:], nulls[:cap])
        rest_blocks.append(Block(ab.type, keep, kn, ab.dictionary))
    idx = jnp.arange(cap, dtype=jnp.int32)
    out_mask = idx < jnp.minimum(total, cap)
    new_count = jnp.where(emit, total - cap, total)
    rest_mask = idx < new_count
    return (Page(tuple(out_blocks), out_mask), emit,
            Page(tuple(rest_blocks), rest_mask), new_count)


class CoalesceOperator(Operator):
    def __init__(self, context: OperatorContext, types: List[Type], dicts):
        super().__init__(context)
        self._types = types
        self._dicts = dicts
        self._acc: Optional[Page] = None
        self._count = None
        self._pending: List[Page] = []
        self._flushed = False

    @property
    def output_types(self) -> List[Type]:
        return self._types

    def needs_input(self) -> bool:
        return not self._finishing and not self._pending

    #: live fraction above which packing cannot pay for itself
    PASSTHROUGH_SELECTIVITY = 0.5

    _mode = None  # None (undecided) | "pack" | "pass"

    @timed("add_input_ns")
    def add_input(self, page: Page) -> None:
        self.context.record_input(page, page.capacity)
        if self._mode is None:
            # adapt on the FIRST page: an unselective filter makes packing
            # pure overhead, so switch to permanent pass-through (per-scan
            # selectivity is stationary — one decision suffices). The sync
            # below runs once per stream, not per page — and through numpy,
            # so the decision compiles no throwaway XLA kernels.
            mask_np = np.asarray(page.mask)  # prestocheck: ignore[host-sync]
            if mask_np.mean() > self.PASSTHROUGH_SELECTIVITY:
                self._mode = "pass"
            else:
                self._mode = "pack"
                self._first_count = int(mask_np.sum())
        pack = self._mode == "pack"
        # the one place a page is counted, and whether it went through
        # block._compact (pack) or straight on (pass)
        METRICS.count_many({"pages": 1, "packed_pages": int(pack)},
                           prefix="coalesce.")
        if not pack:
            self._pending.append(page)
            return
        compacted = _compact(page)
        if self._acc is not None and \
                self._acc.capacity != compacted.capacity:
            # sources with per-chunk capacities (parquet/orc clamp to the
            # chunk's pow2 bucket) change shape mid-stream: flush the
            # accumulator as a partial page and restart at the new capacity
            self._pending.append(self._acc)
            self._acc = None
        if self._acc is None:
            self._acc = compacted
            # host int (counted during the mode decision) — _pack takes it
            # as a traced argument either way, and the eager jnp.sum here
            # compiled two throwaway kernels per schema
            count = getattr(self, "_first_count", None)
            if count is None:  # capacity-change restart mid-stream
                count = int(np.asarray(  # prestocheck: ignore[host-sync]
                    compacted.mask).sum())
            self._first_count = None
            self._count = np.int32(count)
            return
        out, emit, rest, new_count = _pack(self._acc, self._count, compacted)
        self._acc, self._count = rest, new_count
        # host sync on the 4-byte flag only; the page stays on device
        if bool(emit):
            self._pending.append(out)

    @timed("get_output_ns")
    def get_output(self) -> Optional[Page]:
        if self._pending:
            page = self._pending.pop(0)
            self.context.record_output(page, page.capacity)
            return page
        if self._finishing and not self._flushed:
            self._flushed = True
            if self._acc is not None:
                tail = self._acc
                self._acc = None
                self.context.record_output(tail, tail.capacity)
                return tail
        return None

    def is_finished(self) -> bool:
        return self._finishing and self._flushed and not self._pending


class CoalesceOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, types: List[Type], dicts=None):
        super().__init__(operator_id, "Coalesce")
        self.types = types
        self.dicts = dicts or [None] * len(types)

    def create_operator(self, worker: int = 0) -> CoalesceOperator:
        return CoalesceOperator(self.context(worker), self.types, self.dicts)


class DictionaryRemapOperator(Operator):
    """Re-encode dictionary codes through per-channel remap arrays (the
    UNION dictionary-unification pass: minority branches map their codes
    into the union dictionary on device, one gather per column)."""

    def __init__(self, context: OperatorContext, types: List[Type], remaps,
                 target_dicts=None):
        super().__init__(context)
        self._types = types
        self._remaps = [None if r is None else jnp.asarray(r)
                        for r in remaps]
        self._target_dicts = target_dicts or [None] * len(types)
        self._pending: List[Page] = []

    @property
    def output_types(self) -> List[Type]:
        return self._types

    def needs_input(self) -> bool:
        return not self._finishing and not self._pending

    @timed("add_input_ns")
    def add_input(self, page: Page) -> None:
        self.context.record_input(page, page.capacity)
        blocks = []
        for b, r in zip(page.blocks, self._remaps):
            # explicit None test: a virtual FormattedDictionary has len 0
            # and would be dropped by a truthiness check
            td = self._target_dicts[len(blocks)]
            if td is None:
                td = b.dictionary
            if r is None:
                # no code translation needed, but the block must still
                # carry the UNION dictionary: downstream page merges take
                # the FIRST block's dictionary, and a null-branch block
                # with none would strip decoding from the whole column
                if td is b.dictionary:
                    blocks.append(b)
                else:
                    blocks.append(Block(b.type, b.data, b.nulls, td))
            else:
                data = jnp.take(r, jnp.clip(b.data.astype(jnp.int32), 0,
                                            r.shape[0] - 1))
                blocks.append(Block(b.type, data, b.nulls, td))
        self._pending.append(Page(tuple(blocks), page.mask))

    @timed("get_output_ns")
    def get_output(self):
        if self._pending:
            page = self._pending.pop(0)
            self.context.record_output(page, page.capacity)
            return page
        return None

    def is_finished(self) -> bool:
        return self._finishing and not self._pending


class DictionaryRemapOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, types: List[Type], remaps,
                 target_dicts=None):
        super().__init__(operator_id, "DictionaryRemap")
        self.types = types
        self.remaps = remaps
        self.target_dicts = target_dicts

    def create_operator(self, worker: int = 0) -> DictionaryRemapOperator:
        return DictionaryRemapOperator(self.context(worker), self.types,
                                       self.remaps, self.target_dicts)
