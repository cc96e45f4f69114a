"""Collect aggregation: array_agg / map_agg / histogram (ragged outputs).

Analogue of the reference's accumulator-state collectors
(operator/aggregation/arrayagg/ArrayAggregationFunction.java:50,
MapAggregationFunction.java, histogram/Histogram.java) — re-designed for the
engine's sort-based grouping: where the reference appends rows into per-group
BlockBuilders, here the builder keeps every input row on device, and at
finish ONE lexicographic sort by the group keys makes each group's values a
CONTIGUOUS SEGMENT — the ragged result is exactly the (offsets, values)
device pair of spi/block/ArrayBlock.java, with offsets = the group-boundary
positions. Host materialization happens once at the output boundary: the
segments install into a block.ArrayValues store and the output column is the
int32 HANDLE array (the same codes+host-store scheme varchar uses).

Mixing with algebraic aggregates in one GROUP BY is supported: the collected
rows feed the ordinary sort_group_reduce for those calls (both passes sort by
the same null-safe keys, so group order aligns).

Scope: local tier (LocalQueryRunner / task executor). The SPMD and cluster
tiers keep these single-phase and run them on the gathered side (splittable
is False, so the exchange planner never splits them)."""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..block import ArrayValues, Block, Dictionary, Page
from ..types import Type
from .aggregates import AggregateCall
from .hash_agg import (_call_contributions, _null_safe_keys, _reduce_all,
                       _state_widths)
from .sorting import lexsort_fast

#: aggregate names the collect builder implements
COLLECT_NAMES = ("array_agg", "map_agg", "histogram")


def _pow2(n: int) -> int:
    return 1 << max(10, (max(n, 1) - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("kinds", "identities",
                                             "widths"))
def _collect_combined(keys, mask, contribs, cols, kinds, identities, widths):
    """ONE lexicographic sort by the null-safe keys feeds both halves:
    algebraic states via segment reduction over the shared permutation, and
    the collect columns permuted with the group-boundary mask — the device
    half of the ragged pair (boundaries ARE the offsets)."""
    from .hash_agg import _where_valid

    n = mask.shape[0]
    invalid = ~mask
    order = lexsort_fast(tuple(reversed(keys)) + (invalid,))
    sk = tuple(k[order] for k in keys)
    sv = mask[order]
    sc = tuple((c[0][order], c[1][order]) if isinstance(c, tuple)
               else c[order] for c in contribs)
    scol = tuple(c[order] for c in cols)

    first = jnp.zeros(n, dtype=jnp.bool_).at[0].set(True)
    diff = jnp.zeros(n, dtype=jnp.bool_)
    for k in sk:
        diff = diff | (k != jnp.roll(k, 1))
    new_group = sv & (first | diff)
    gid = jnp.cumsum(new_group.astype(jnp.int32)) - 1
    num_groups = jnp.where(n > 0, gid[-1] + 1, 0)
    gid = jnp.where(sv, gid, n)

    states = _reduce_all(sc, kinds, identities, widths, gid, n)
    gkeys = []
    for k in sk:
        out = jnp.zeros(n, dtype=k.dtype)
        gkeys.append(out.at[gid].set(k, mode="drop"))
    gvalid = jnp.arange(n, dtype=jnp.int32) < num_groups
    states = [_where_valid(gvalid, s, ident)
              for s, ident in zip(states, identities)]
    return tuple(gkeys), tuple(states), gvalid, scol, sv, new_group


class CollectAggregationBuilder:
    """Keeps all input rows; one sorted pass at finish (see module doc)."""

    result_rows = None  # a compact table whose live rows are data

    def __init__(self, key_types: Sequence[Type], key_dicts, calls:
                 Sequence[AggregateCall], page_capacity: int,
                 max_groups: int = 1 << 20, from_intermediate: bool = False):
        if from_intermediate:
            raise NotImplementedError(
                "collect aggregates are single-phase (splittable=False)")
        self.user_key_types = list(key_types)
        from ..types import BOOLEAN
        self.key_types = [x for t in key_types for x in (t, BOOLEAN)]
        self.key_dicts = list(key_dicts)
        self.calls = list(calls)
        self.from_intermediate = False
        self.max_groups = max_groups
        self._pages: List[Page] = []
        self._host_pages: List = []
        self._bytes = 0

    def set_channels(self, key_channels):
        self._key_channels = tuple(key_channels)
        return self

    def share_kernels(self, donor) -> None:
        pass  # the sort kernel is module-level jitted (shared by shape)

    def add_page(self, page: Page) -> None:
        self._pages.append(page)
        self._bytes += sum(b.data.nbytes for b in page.blocks)

    # ---- spill protocol (device HBM -> host RAM) -------------------------
    def memory_bytes(self) -> int:
        return self._bytes

    def spill(self) -> None:
        for p in self._pages:
            self._host_pages.append(jax.device_get(p))
        self._pages = []
        self._bytes = 0

    # ----------------------------------------------------------------------

    def _concat_page(self) -> Optional[Page]:
        pages = self._host_pages + self._pages
        self._host_pages, self._pages = [], []
        if not pages:
            return None
        total = sum(p.capacity for p in pages)
        cap = _pow2(total)
        nblocks = len(pages[0].blocks)
        blocks = []
        for i in range(nblocks):
            data = jnp.concatenate([jnp.asarray(p.blocks[i].data)
                                    for p in pages])
            if cap > total:
                data = jnp.concatenate(
                    [data, jnp.zeros(cap - total, dtype=data.dtype)])
            nulls = None
            if any(p.blocks[i].nulls is not None for p in pages):
                nulls = jnp.concatenate([jnp.asarray(p.blocks[i].null_mask())
                                         for p in pages])
                if cap > total:
                    nulls = jnp.concatenate(
                        [nulls, jnp.zeros(cap - total, dtype=jnp.bool_)])
            b0 = pages[0].blocks[i]
            blocks.append(Block(b0.type, data, nulls, b0.dictionary))
        mask = jnp.concatenate([jnp.asarray(p.mask) for p in pages])
        if cap > total:
            mask = jnp.concatenate(
                [mask, jnp.zeros(cap - total, dtype=jnp.bool_)])
        return Page(tuple(blocks), mask)

    @staticmethod
    def _decode(vals: np.ndarray, nulls: Optional[np.ndarray], t: Type,
                d: Optional[Dictionary]):
        """numpy slice -> python values (the to_pylist recipe)."""
        if d is not None:
            out = list(d.lookup(vals.astype(np.int64)))
        else:
            out = [t.to_python(v) for v in vals]
        if nulls is not None:
            out = [None if n else v for v, n in zip(out, nulls)]
        return out

    def _collect_columns(self, page: Page):
        """Per collect call: (arrays to permute, metadata for host decode)."""
        cols = []
        meta = []  # (call, mode, slots: [(type, dict, has_nulls)...])
        for call in self.calls:
            name = call.function.name
            if name not in COLLECT_NAMES:
                meta.append(None)
                continue
            part = page.mask
            if call.mask_channel is not None:
                mc = page.blocks[call.mask_channel]
                mcv = mc.data.astype(jnp.bool_)
                if mc.nulls is not None:
                    mcv = mcv & ~mc.nulls
                part = part & mcv
            # map keys / histogram values never include NULL entries
            skip_null_args = {"map_agg": (0,), "histogram": (0,),
                              "array_agg": ()}[name]
            for ai in skip_null_args:
                b = page.blocks[call.input_channels[ai]]
                if b.nulls is not None:
                    part = part & ~b.nulls
            slot_info = []
            arrs = [part]
            for ch in call.input_channels:
                b = page.blocks[ch]
                arrs.append(b.data)
                has_n = b.nulls is not None
                if has_n:
                    arrs.append(b.nulls)
                slot_info.append((b.type, b.dictionary, has_n))
            cols.extend(arrs)
            mode = "array" if name == "array_agg" else "map"
            meta.append((call, mode, slot_info, len(arrs)))
        return cols, meta

    def finish(self):
        page = self._concat_page()
        from ..types import BOOLEAN
        if page is None:
            if not self.user_key_types:
                # global collect over empty input: one all-NULL group
                return self._global_empty()
            # numpy: they reach the device as the result program's arguments
            z = tuple(np.zeros(0, dtype=t.np_dtype) for t in self.key_types)
            states = []
            for call in self.calls:
                if call.function.name in COLLECT_NAMES:
                    states.append(np.zeros(0, dtype=np.int32))
                    continue
                for col in call.function.state:
                    shape = (0, col.width) if col.width > 1 else (0,)
                    states.append(np.zeros(shape, dtype=np.dtype(col.dtype)))
            return z, tuple(states), np.zeros(0, dtype=np.bool_)

        keys = _null_safe_keys(page, self._key_channels) \
            if self._key_channels else \
            (jnp.zeros(page.capacity, dtype=jnp.int32),
             jnp.zeros(page.capacity, dtype=jnp.bool_))
        cap = page.capacity

        # ONE sorted pass: algebraic states + permuted collect columns share
        # the same lexsort (the permutation is the expensive kernel here)
        algebraic = [c for c in self.calls
                     if c.function.name not in COLLECT_NAMES]
        contribs = _call_contributions(algebraic, page, False)
        kinds = tuple(col.reduce for c in algebraic
                      for col in c.function.state)
        idents = tuple(col.identity for c in algebraic
                       for col in c.function.state)
        widths = _state_widths(algebraic)
        cols, meta = self._collect_columns(page)
        gkeys, states, gvalid, sc, sv, new_group = _collect_combined(
            keys, page.mask, tuple(contribs), tuple(cols), kinds, idents,
            widths)
        alg_states = {}
        it = iter(states)
        for c in algebraic:
            alg_states[id(c)] = [next(it) for _ in c.function.state]

        # host materialization: boundaries are the ragged offsets
        n_live = int(np.asarray(sv).sum())
        starts = np.flatnonzero(np.asarray(new_group))
        num_groups = len(starts)
        ends = np.append(starts[1:], n_live)

        collect_handles: List[np.ndarray] = []
        col_cursor = 0
        for call, m in zip(self.calls, meta):
            if m is None:
                continue
            _call, mode, slot_info, n_arrs = m
            arrs = [np.asarray(sc[col_cursor + k]) for k in range(n_arrs)]
            col_cursor += n_arrs
            part = arrs[0]
            slots = []
            ai = 1
            for (t, d, has_n) in slot_info:
                vals = arrs[ai]
                ai += 1
                nulls = arrs[ai] if has_n else None
                if has_n:
                    ai += 1
                slots.append((t, d, vals, nulls))
            store: ArrayValues = call.function.output_dict
            handles = np.full(max(num_groups, 1), -1, dtype=np.int32)
            for g in range(num_groups):
                lo, hi = starts[g], ends[g]
                keep = np.flatnonzero(part[lo:hi]) + lo
                if len(keep) == 0:
                    continue
                decoded = [self._decode(vals[keep],
                                        nulls[keep] if nulls is not None
                                        else None, t, d)
                           for (t, d, vals, nulls) in slots]
                if call.function.name == "array_agg":
                    entry = tuple(decoded[0])
                elif call.function.name == "map_agg":
                    seen = {}
                    for k_, v_ in zip(decoded[0], decoded[1]):
                        if k_ not in seen:
                            seen[k_] = v_
                    entry = tuple(seen.items())
                else:  # histogram
                    from collections import Counter
                    entry = tuple(Counter(decoded[0]).items())
                handles[g] = store.extend([entry])[0]
            collect_handles.append(handles)

        if not self.user_key_types:
            # global: exactly one group (handles[0]; empty input never gets
            # here — _global_empty covers it)
            out_states = []
            it = iter(collect_handles)
            for call, m in zip(self.calls, meta):
                if m is None:
                    out_states.extend(s[:1] for s in alg_states[id(call)])
                else:
                    out_states.append(jnp.asarray(next(it)[:1]))
            return (), tuple(out_states), jnp.ones(1, dtype=jnp.bool_)

        # grouped: collect handles (host order = sorted group order) align
        # with gkeys/gvalid from sort_group_reduce (same sort -> same order)
        out_states = []
        it = iter(collect_handles)
        for call, m in zip(self.calls, meta):
            if m is None:
                out_states.extend(alg_states[id(call)])
            else:
                h = next(it)
                full = np.full(cap, -1, dtype=np.int32)
                full[:min(len(h), cap)] = h[:cap]
                out_states.append(jnp.asarray(full))
        return gkeys, tuple(out_states), gvalid

    def _global_empty(self):
        states = []
        for call in self.calls:
            if call.function.name in COLLECT_NAMES:
                states.append(np.full(1, -1, dtype=np.int32))
            else:
                for col in call.function.state:
                    w = col.width
                    arr = np.full((1, w) if w > 1 else (1,), col.identity,
                                  dtype=np.dtype(col.dtype))
                    states.append(arr)
        return (), tuple(states), np.ones(1, dtype=np.bool_)
