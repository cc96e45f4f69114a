"""TopN, full sort, and limit operators.

Analogue of operator/TopNOperator.java:35 (+GroupedTopNBuilder.java:49),
operator/OrderByOperator.java (PagesIndex sort) and operator/LimitOperator.java.

TPU re-design: the reference keeps a row heap; a heap is serial. Here TopN keeps a
fixed N-row device buffer and, per page, sorts [buffer ++ page] by the order key and
keeps the first N — O((N+cap) log) fully on the VPU's bitonic sorter, which for the
N<<cap case is the same asymptotics as the heap without the pointer chasing.

Order keys: multi-column, asc/desc, nulls-last. DESC on numerics sorts by the negated
(or bit-flipped) value; varchar sorts by dictionary rank (Dictionary.sort_keys).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..block import Block, Dictionary, Page
from ..types import Type, is_string
from .operator import Operator, OperatorContext, OperatorFactory, timed
from .sorting import lexsort_fast


@dataclasses.dataclass(frozen=True)
class SortOrder:
    channel: int
    descending: bool = False
    nulls_first: bool = False


def _sort_key_arrays(page: Page, orders: Sequence[SortOrder]) -> Tuple[jnp.ndarray, ...]:
    """Build lexsort key arrays (major key LAST, per jnp.lexsort convention).
    Invalid rows always sort to the very end (handled by caller appending ~mask)."""
    keys = []
    for o in reversed(orders):
        b = page.blocks[o.channel]
        x = b.data
        if is_string(b.type) and b.dictionary is not None:
            d = b.dictionary
            if hasattr(d, "values"):
                ranks = jnp.asarray(d.sort_keys())
                x = ranks[x]
            elif not getattr(d, "monotonic", False):
                # virtual dictionaries sort by code only when the format is
                # order-preserving (e.g. zero-padded Supplier#%09d)
                raise NotImplementedError(
                    f"ORDER BY over non-monotonic virtual dictionary {d!r}")
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.int32)
        if b.nulls is not None:
            # neutralize the undefined payload under a null so null rows
            # order by the REMAINING sort keys (ties among nulls break on
            # the next ORDER BY column, matching the N-way merge comparator
            # in cluster/exchange_client.py MergingRemoteSource)
            x = jnp.where(b.nulls, jnp.zeros((), dtype=x.dtype), x)
        if o.descending:
            x = -x
        keys.append(x)
        if b.nulls is not None:
            # appended AFTER the value => more significant in lexsort: null rows sort
            # wholly before/after non-null rows regardless of their payload value
            nullv = jnp.asarray(-1 if o.nulls_first else 1, dtype=jnp.int32)
            keys.append(jnp.where(b.nulls, nullv, 0))
    return tuple(keys)


def topn_merge_stage(page: Page, buffer: Optional[Page],
                     orders: Tuple[SortOrder, ...], n: int) -> Page:
    """Pure TopN contribution: sort [page ++ buffer], keep the first n rows.

    The un-jitted stage body — the standalone operator dispatches the jitted
    `_topn_merge` below; a fused pipeline segment (ops/fused_segment.py)
    inlines this into its one-kernel-per-page composition with the buffer
    threaded through as a jit argument."""
    if buffer is not None:
        blocks = tuple(
            Block(b.type,
                  jnp.concatenate([b.data, bb.data]),
                  None if b.nulls is None and bb.nulls is None else
                  jnp.concatenate([b.null_mask(), bb.null_mask()]),
                  b.dictionary)
            for b, bb in zip(page.blocks, buffer.blocks))
        merged = Page(blocks, jnp.concatenate([page.mask, buffer.mask]))
    else:
        merged = page
    keys = _sort_key_arrays(merged, orders) + (~merged.mask,)
    order = lexsort_fast(keys)
    top = order[:n]
    blocks = []
    for b in merged.blocks:
        nulls = b.nulls[top] if b.nulls is not None else None
        blocks.append(Block(b.type, b.data[top], nulls, b.dictionary))
    return Page(tuple(blocks), merged.mask[top])


# shared across operator instances: one compile per (schema, orders, n)
_topn_merge = functools.partial(jax.jit, static_argnames=("orders", "n"))(
    topn_merge_stage)


class TopNOperator(Operator):
    def __init__(self, context: OperatorContext, n: int, orders: List[SortOrder],
                 types: List[Type], dicts: List[Optional[Dictionary]]):
        super().__init__(context)
        self.n = n
        self.orders = tuple(orders)
        self._types = types
        self._dicts = dicts
        self._buffer: Optional[Page] = None
        self._emitted = False

    @property
    def output_types(self) -> List[Type]:
        return self._types

    @timed("add_input_ns")
    def add_input(self, page: Page) -> None:
        self.context.record_input(page, page.capacity)
        self._buffer = _topn_merge(page, self._buffer, self.orders, self.n)

    @timed("get_output_ns")
    def get_output(self) -> Optional[Page]:
        if self._finishing and not self._emitted:
            self._emitted = True
            if self._buffer is not None:
                self.context.record_output(self._buffer, self.n)
                return self._buffer
        return None

    def is_finished(self) -> bool:
        return self._finishing and self._emitted


class TopNOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, n: int, orders: List[SortOrder],
                 types: List[Type], dicts: Optional[List[Optional[Dictionary]]] = None):
        super().__init__(operator_id, "TopN")
        self.n = n
        self.orders = orders
        self.types = types
        self.dicts = dicts or [None] * len(types)

    def create_operator(self, worker: int = 0) -> TopNOperator:
        return TopNOperator(self.context(worker),
                            self.n, self.orders, self.types, self.dicts)


@functools.partial(jax.jit, static_argnames=("orders",))
def _sort_pages(pages: Tuple[Page, ...],
                orders: Tuple[SortOrder, ...]) -> Page:
    """Concatenate the buffered pages and order their rows — one dispatch
    (run eagerly, every step was its own compiled program, and the sort's
    loop recompiled on each call)."""
    merged_blocks = []
    for i, b0 in enumerate(pages[0].blocks):
        datas = jnp.concatenate([p.blocks[i].data for p in pages])
        anynull = any(p.blocks[i].nulls is not None for p in pages)
        nulls = (jnp.concatenate([p.blocks[i].null_mask() for p in pages])
                 if anynull else None)
        merged_blocks.append(Block(b0.type, datas, nulls, b0.dictionary))
    merged = Page(tuple(merged_blocks),
                  jnp.concatenate([p.mask for p in pages]))
    order = lexsort_fast(_sort_key_arrays(merged, orders) + (~merged.mask,))
    blocks = []
    for b in merged.blocks:
        nulls = b.nulls[order] if b.nulls is not None else None
        blocks.append(Block(b.type, b.data[order], nulls, b.dictionary))
    return Page(tuple(blocks), merged.mask[order])


class OrderByOperator(Operator):
    """Full sort: buffers all pages, sorts once at finish (OrderByOperator.java).
    Spill arrives with the revocation rev; a query-sized sort fits HBM for the TPC
    workloads this round targets."""

    def __init__(self, context: OperatorContext, orders: List[SortOrder],
                 types: List[Type], dicts, output_channels: Optional[List[int]] = None):
        super().__init__(context)
        self.orders = orders
        self._types = types
        self._dicts = dicts
        self.output_channels = output_channels
        self._pages: List[Page] = []
        self._result: Optional[List[Page]] = None

    @property
    def output_types(self) -> List[Type]:
        if self.output_channels is None:
            return self._types
        return [self._types[c] for c in self.output_channels]

    @timed("add_input_ns")
    def add_input(self, page: Page) -> None:
        self.context.record_input(page, page.capacity)
        self._pages.append(page)

    def finish(self) -> None:
        if self._finishing:
            return
        super().finish()
        self._result = self._sort() if self._pages else []

    def _sort(self) -> List[Page]:
        cap = self._pages[0].capacity
        sorted_page = _sort_pages(tuple(self._pages), tuple(self.orders))
        if self.output_channels is not None:
            sorted_page = sorted_page.select_channels(self.output_channels)
        # re-page to capacity-sized pages
        out = []
        total = sorted_page.capacity
        for lo in range(0, total, cap):
            hi = min(lo + cap, total)
            blocks = []
            for b in sorted_page.blocks:
                seg = b.data[lo:hi]
                if hi - lo < cap:
                    seg = jnp.concatenate([seg, jnp.zeros(cap - (hi - lo), seg.dtype)])
                nseg = None
                if b.nulls is not None:
                    nseg = b.nulls[lo:hi]
                    if hi - lo < cap:
                        nseg = jnp.concatenate(
                            [nseg, jnp.zeros(cap - (hi - lo), jnp.bool_)])
                blocks.append(Block(b.type, seg, nseg, b.dictionary))
            m = sorted_page.mask[lo:hi]
            if hi - lo < cap:
                m = jnp.concatenate([m, jnp.zeros(cap - (hi - lo), jnp.bool_)])
            out.append(Page(tuple(blocks), m))
        return out

    @timed("get_output_ns")
    def get_output(self) -> Optional[Page]:
        if self._result:
            out = self._result.pop(0)
            self.context.record_output(out, out.capacity)
            return out
        return None

    def is_finished(self) -> bool:
        return self._finishing and self._result is not None and not self._result


class OrderByOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, orders, types, dicts=None,
                 output_channels=None):
        super().__init__(operator_id, "OrderBy")
        self.orders = orders
        self.types = types
        self.dicts = dicts or [None] * len(types)
        self.output_channels = output_channels

    def create_operator(self, worker: int = 0) -> OrderByOperator:
        return OrderByOperator(self.context(worker),
                               self.orders, self.types, self.dicts,
                               self.output_channels)


class LimitOperator(Operator):
    """operator/LimitOperator.java — passes through the first `limit` live rows."""

    def __init__(self, context: OperatorContext, limit: int, types: List[Type]):
        super().__init__(context)
        self.remaining = limit
        self._types = types
        self._pending: Optional[Page] = None

    @property
    def output_types(self) -> List[Type]:
        return self._types

    def needs_input(self) -> bool:
        return not self._finishing and self._pending is None and self.remaining > 0

    @timed("add_input_ns")
    def add_input(self, page: Page) -> None:
        self.context.record_input(page, page.capacity)
        live = jnp.cumsum(page.mask.astype(jnp.int32))
        keep = page.mask & (live <= self.remaining)
        taken = int(jnp.sum(keep.astype(jnp.int32)))
        self.remaining -= taken
        self._pending = page.with_mask(keep)

    @timed("get_output_ns")
    def get_output(self) -> Optional[Page]:
        out, self._pending = self._pending, None
        if out is not None:
            self.context.record_output(out, out.capacity)
        if self.remaining <= 0:
            self._finishing = True
        return out

    def is_finished(self) -> bool:
        return self._finishing and self._pending is None


class LimitOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, limit: int, types: List[Type]):
        super().__init__(operator_id, "Limit")
        self.limit = limit
        self.types = types

    def create_operator(self, worker: int = 0) -> LimitOperator:
        return LimitOperator(self.context(worker), self.limit, self.types)
