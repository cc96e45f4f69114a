"""Columnar data substrate: Block and Page.

Analogue of the reference layer-1 substrate (presto-spi/.../spi/Page.java:34,
spi/block/Block.java:23 and its 64 concrete block classes), re-designed for TPU:

- A Block is ONE dense, fixed-dtype device array (+ optional validity bitmap as a bool
  array, + optional host-side string dictionary). There is no variable-width block: the
  roles of VariableWidthBlock / DictionaryBlock / RunLengthEncodedBlock collapse into
  "int32 codes + host dictionary" and XLA's own broadcast/fusion.
- A Page is a tuple of equal-capacity Blocks plus a *row mask*. Pages are padded to a
  fixed capacity so every kernel sees static shapes (XLA traces once per capacity
  bucket); the mask plays the role of the reference's positionCount + selection vectors
  (operator/project/PageProcessor.java selectedPositions).
- Block and Page are registered as JAX pytrees: jitted operators take and return them
  directly. Type and dictionary ride along as static aux data, so a change of schema
  (not of data) is what triggers recompilation — exactly the reference's distinction
  between Block data and BlockEncoding.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Iterable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .types import (BIGINT, BOOLEAN, DATE, DOUBLE, INTEGER, REAL, DateType,
                    Type, VarcharType, VARCHAR, DecimalType, is_string)

Array = Union[np.ndarray, jax.Array]

_SAME_NULLS = object()  # sentinel: "keep this block's null mask"


class Dictionary:
    """Host-side string dictionary shared by varchar blocks of one column.

    Identity-hashed so it can ride through jit as static aux data without
    content-hashing megabytes of strings (DictionaryBlock's dictionarySourceId plays
    the same role in the reference: spi/block/DictionaryBlock.java).
    """

    __slots__ = ("values", "_index", "_ranks", "_order", "_sorted", "_token")

    _next_token = itertools.count()

    def __init__(self, values: Sequence[str]):
        self.values = np.asarray(values, dtype=object)
        self._index = None
        self._ranks = None
        self._order = None
        self._sorted = None
        # monotonic identity for the kernel cache: unlike id(), never reused
        # after GC (utils/kernel_cache.dict_key)
        self._token = next(Dictionary._next_token)

    def token(self) -> int:
        # lazy: virtual-dictionary subclasses skip super().__init__
        t = getattr(self, "_token", None)
        if t is None:
            t = next(Dictionary._next_token)
            self._token = t
        return t

    def __len__(self):
        return len(self.values)

    def index(self) -> dict:
        if self._index is None:
            self._index = {v: i for i, v in enumerate(self.values)}
        return self._index

    def code_of(self, value: str) -> int:
        """Code for value, or -1 if absent (comparisons against it are then const-false)."""
        return self.index().get(value, -1)

    def codes_where(self, predicate) -> np.ndarray:
        """Host-side predicate over dictionary entries -> int32 array of matching codes."""
        return np.asarray([i for i, v in enumerate(self.values) if predicate(v)], dtype=np.int32)

    def lookup(self, codes: np.ndarray) -> np.ndarray:
        out = np.empty(len(codes), dtype=object)
        mask = codes >= 0
        out[mask] = self.values[codes[mask]]
        out[~mask] = None
        return out

    def extend(self, values: Sequence[str]) -> List[int]:
        """Codes for `values`, appending entries this dictionary lacks (used
        by INSERT re-encoding into a table-private dictionary). Invalidates
        the cached reverse index on growth."""
        pos = self.index()
        out = []
        new_vals = None
        for v in values:
            code = pos.get(v)
            if code is None:
                if new_vals is None:
                    new_vals = list(self.values)
                code = len(new_vals)
                new_vals.append(v)
                pos[v] = code
            out.append(code)
        if new_vals is not None:
            self.values = np.asarray(new_vals, dtype=object)
            self._index = pos
        return out

    # sort_keys: rank of each code in lexicographic order, for ORDER BY on varchar.
    def sort_keys(self) -> np.ndarray:
        if self._ranks is None or len(self._ranks) != len(self.values):
            order = np.argsort(self.values.astype(str), kind="stable")
            ranks = np.empty(len(self.values), dtype=np.int32)
            ranks[order] = np.arange(len(self.values), dtype=np.int32)
            self._ranks = ranks
            self._order = order.astype(np.int32)
        return self._ranks

    def sort_order(self) -> np.ndarray:
        """Inverse of sort_keys: rank -> code (argsort of the values)."""
        self.sort_keys()
        return self._order

    def is_sorted(self) -> bool:
        """True when codes ARE lexicographic ranks (ingest-built dictionaries
        are sorted; INSERT's Dictionary.extend appends, breaking this —
        min/max over codes is only valid when this holds)."""
        if self._sorted is None or self._sorted[1] != len(self.values):
            v = self.values.astype(str)
            ok = bool(np.all(v[:-1] <= v[1:])) if len(v) > 1 else True
            self._sorted = (ok, len(self.values))
        return self._sorted[0]

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other

    def __repr__(self):
        return f"Dictionary({len(self.values)} entries)"


class ArrayValues(Dictionary):
    """Host-side store of ragged ARRAY/MAP values; device blocks hold int32
    handles into it (the exact design varchar uses: codes + host store).

    The collect aggregation computes the ragged (offsets, values) pair on
    device, then installs each group's slice here and hands the handle array
    to the output block — spi/block/ArrayBlock.java's offsets+child layout,
    with the host boundary at materialization instead of per-operator.
    `mode` controls decoding: "array" -> list, "map" -> dict (entries are
    stored as hashable tuples so handles dedup via the inherited index)."""

    def __init__(self, mode: str = "array"):
        super().__init__([])
        self.mode = mode

    def lookup(self, codes: "np.ndarray") -> "np.ndarray":
        out = np.empty(len(codes), dtype=object)
        for i, c in enumerate(np.asarray(codes, dtype=np.int64)):
            if c < 0:
                out[i] = None
            elif self.mode == "map":
                out[i] = dict(self.values[c])
            else:
                out[i] = list(self.values[c])
        return out

    def __repr__(self):
        return f"ArrayValues({len(self.values)} {self.mode} entries)"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Block:
    """One column: dense array + optional null mask + optional dictionary."""

    type: Type
    data: Array
    nulls: Optional[Array] = None  # True where NULL; None == no nulls
    dictionary: Optional[Dictionary] = None

    def tree_flatten(self):
        return (self.data, self.nulls), (self.type, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, nulls = children
        t, d = aux
        return cls(t, data, nulls, d)

    def __len__(self):
        return int(self.data.shape[0])

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def null_mask(self) -> Array:
        if self.nulls is None:
            return jnp.zeros(self.data.shape[0], dtype=jnp.bool_)
        return self.nulls

    def with_data(self, data: Array, nulls: Union[Optional[Array], object] = _SAME_NULLS) -> "Block":
        return Block(self.type, data, self.nulls if nulls is _SAME_NULLS else nulls,
                     self.dictionary)

    def to_numpy(self, size: Optional[int] = None) -> np.ndarray:
        arr = np.asarray(self.data)
        if size is not None:
            arr = arr[:size]
        return arr

    def to_pylist(self, size: Optional[int] = None) -> list:
        """Decode to Python values (strings via dictionary, decimals via Decimal)."""
        arr = self.to_numpy(size)
        nulls = np.asarray(self.nulls)[: len(arr)] if self.nulls is not None else None
        if self.dictionary is not None:
            vals = self.dictionary.lookup(arr.astype(np.int64))
        else:
            vals = [self.type.to_python(v) for v in arr]
        out = list(vals)
        if nulls is not None:
            out = [None if n else v for v, n in zip(out, nulls)]
        return out


def block_from_numpy(type_: Type, arr: np.ndarray, dictionary: Optional[Dictionary] = None,
                     nulls: Optional[np.ndarray] = None) -> Block:
    arr = np.ascontiguousarray(arr)
    if arr.dtype != type_.np_dtype:
        arr = arr.astype(type_.np_dtype)
    return Block(type_, arr, nulls, dictionary)


def block_from_strings(values: Sequence[Optional[str]], type_: Type = VARCHAR,
                       dictionary: Optional[Dictionary] = None) -> Block:
    """Dictionary-encode python strings into a varchar block (ingest path)."""
    if dictionary is None:
        uniq = sorted({v for v in values if v is not None})
        dictionary = Dictionary(uniq)
    index = dictionary.index()
    codes = np.fromiter(
        ((index[v] if v is not None else 0) for v in values), dtype=np.int32, count=len(values))
    nulls = None
    if any(v is None for v in values):
        nulls = np.fromiter((v is None for v in values), dtype=np.bool_, count=len(values))
    return Block(type_, codes, nulls, dictionary)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Page:
    """A batch of rows: equal-capacity blocks + row-validity mask.

    `mask[i]` says whether row i is live. All arrays share capacity; `count()` (traced)
    or `size()` (host int) give live-row counts. This replaces the reference Page's
    positionCount and the selection machinery of PageProcessor.
    """

    blocks: Tuple[Block, ...]
    mask: Array  # bool (capacity,)

    def tree_flatten(self):
        return (tuple(self.blocks), self.mask), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        blocks, mask = children
        return cls(tuple(blocks), mask)

    @property
    def capacity(self) -> int:
        return int(self.mask.shape[0])

    @property
    def channel_count(self) -> int:
        return len(self.blocks)

    def count(self):
        """Traced live-row count."""
        return jnp.sum(self.mask.astype(jnp.int32))

    def size(self) -> int:
        """Host-side live-row count (forces a device sync)."""
        return int(self.count())

    def block(self, i: int) -> Block:
        return self.blocks[i]

    def types(self) -> List[Type]:
        return [b.type for b in self.blocks]

    def append_block(self, b: Block) -> "Page":
        return Page(self.blocks + (b,), self.mask)

    def select_channels(self, channels: Sequence[int]) -> "Page":
        return Page(tuple(self.blocks[c] for c in channels), self.mask)

    def with_mask(self, mask: Array) -> "Page":
        return Page(self.blocks, mask)

    def compact(self) -> "Page":
        """Pack live rows to the front (cumsum, then scatter or gather by the
        column's width; no dynamic shapes).

        Returns a page of the same capacity whose mask is a prefix. This is the moment
        the reference would materialize selected positions into a new Page
        (PageProcessor output); here it is one program a schema.
        """
        return _compact(self)

    def to_pylists(self, limit: Optional[int] = None) -> List[list]:
        """Rows of decoded Python values (host side, for tests/protocol)."""
        mask = np.asarray(self.mask)
        idx = np.nonzero(mask)[0]
        if limit is not None:
            idx = idx[:limit]
        cols = []
        for b in self.blocks:
            arr = np.asarray(b.data)[idx]
            nulls = np.asarray(b.nulls)[idx] if b.nulls is not None else None
            if b.dictionary is not None:
                vals = list(b.dictionary.lookup(arr.astype(np.int64)))
            else:
                vals = [b.type.to_python(v) for v in arr]
            if nulls is not None:
                vals = [None if n else v for v, n in zip(vals, nulls)]
            cols.append(vals)
        return [list(row) for row in zip(*cols)] if cols else []


@jax.jit
def _compact(page: Page) -> Page:
    mask = page.mask
    cap = mask.shape[0]
    rows = jnp.arange(cap, dtype=jnp.int32)
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1  # target slot per live row
    n = pos[-1] + 1
    new_mask = rows < n
    tgt = jnp.where(mask, pos, cap)  # dead rows target out-of-bounds -> dropped
    # src[j] = the row that lands in slot j. Built for the 64-bit columns
    # alone (XLA drops it from a page that has none): the chip emulates a
    # 64-bit scatter as a u32 pair at 13 times a 32-bit scatter's cost and
    # gathers it at 3 times, while a narrow column's own scatter is cheaper
    # than its gather (tools/compact_sweep.py, PERF.md section 6, PR 32).
    src = jnp.zeros_like(rows).at[tgt].set(rows, mode="drop")

    def move(a):
        if a.dtype.itemsize < 8:
            return jnp.zeros_like(a).at[tgt].set(a, mode="drop")
        packed = a.at[src].get(mode="promise_in_bounds")
        return jnp.where(new_mask, packed, 0)

    return Page(tuple(
        Block(b.type, move(b.data),
              None if b.nulls is None else move(b.nulls), b.dictionary)
        for b in page.blocks), new_mask)


def page_from_arrays(types: Sequence[Type], arrays: Sequence[Array],
                     dictionaries: Optional[Sequence[Optional[Dictionary]]] = None,
                     count: Optional[int] = None, capacity: Optional[int] = None) -> Page:
    """Build a page from host arrays, padding to capacity."""
    n = int(np.asarray(arrays[0]).shape[0]) if arrays else 0
    if count is None:
        count = n
    cap = capacity or n
    blocks = []
    for i, (t, a) in enumerate(zip(types, arrays)):
        a = np.asarray(a)
        if a.dtype != t.np_dtype:
            a = a.astype(t.np_dtype)
        if cap > n:
            a = np.concatenate([a, np.zeros(cap - n, dtype=a.dtype)])
        d = dictionaries[i] if dictionaries else None
        blocks.append(Block(t, a, None, d))
    mask = np.arange(cap) < count
    return Page(tuple(blocks), mask)


def page_from_pylists(types: Sequence[Type], rows: Iterable[Sequence[Any]],
                      dictionaries: Optional[Sequence[Optional[Dictionary]]] = None,
                      capacity: Optional[int] = None) -> Page:
    """Test helper: rows of Python values -> Page (RowPagesBuilder analogue,
    presto-main test util RowPagesBuilder.java)."""
    rows = list(rows)
    cols = list(zip(*rows)) if rows else [[] for _ in types]
    blocks = []
    n = len(rows)
    cap = capacity or max(n, 1)
    mask = np.arange(cap) < n
    for i, t in enumerate(types):
        vals = list(cols[i]) if rows else []
        d = dictionaries[i] if dictionaries else None
        if is_string(t):
            b = block_from_strings(vals + [None] * (cap - n), t, d)
        else:
            nulls = np.fromiter(((v is None) for v in vals), dtype=np.bool_, count=n)
            conv = []
            for v in vals:
                if v is None:
                    conv.append(0)
                elif isinstance(t, DecimalType):
                    conv.append(round(float(v) * 10 ** t.scale))
                elif isinstance(t, DateType):
                    import datetime
                    conv.append((v - datetime.date(1970, 1, 1)).days
                                if isinstance(v, datetime.date) else int(v))
                else:
                    conv.append(v)
            arr = np.zeros(cap, dtype=t.np_dtype)
            arr[:n] = np.asarray(conv, dtype=t.np_dtype) if conv else []
            nl = None
            if nulls.any():
                nl = np.zeros(cap, dtype=np.bool_)
                nl[:n] = nulls
            b = Block(t, arr, nl, None)
        blocks.append(b)
    return Page(tuple(blocks), mask)


def empty_page(types: Sequence[Type], capacity: int,
               dictionaries: Optional[Sequence[Optional[Dictionary]]] = None) -> Page:
    blocks = []
    for i, t in enumerate(types):
        d = dictionaries[i] if dictionaries else None
        blocks.append(Block(t, np.zeros(capacity, dtype=t.np_dtype), None, d))
    return Page(tuple(blocks), np.zeros(capacity, dtype=np.bool_))
