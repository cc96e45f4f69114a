"""The sweep behind the form of `block._compact`.

Times the ways of packing a page's live rows to the front on whatever
device JAX gives (run it on the chip: `chiprun -- python3 -m
tools.compact_sweep`, about 6 min). A CPU run says nothing about the chip.

Every form starts from the same `pos = cumsum(mask) - 1`, whose program the
v5e compiler takes 28 s over at 2^20 rows: it is computed ONCE a capacity
(`positions_s`, `positions_compile_s`) and handed to the forms, which are
timed from there.

Part 1, the source index alone (`src[j]` = the row that lands in slot `j`),
for each capacity, with each one's compile seconds beside its median:
- `scatter`: `zeros.at[where(mask, pos, cap)].set(arange)`, dead rows dropped;
- `perm`: the same scatter made a full permutation (dead row `i` goes to
  slot `n + dead rows before i`), so `unique_indices` and
  `promise_in_bounds` are true and are given;
- `sort`: a single-array int32 sort of `where(mask, row, cap + row)`.

Part 2, the whole page, for dtype x columns x capacity, and for the two
`MIXED` pages of Q3 (lineitem's and orders' columns) at every capacity:
- `scatter`: one `.at[tgt].set` a column (the form before PR 32);
- `gather`: the index by `scatter`, then `data[src]` a column;
- `gather_perm`: the index by `perm`, then `data[src]` a column;
- `split` (int64 alone): each column as two `uint32` halves, a 32-bit
  scatter each. The v5e compiler refuses the `bitcast_convert_type` a
  float64 would need ("rewriting is not implemented"), so it is no form for
  `_compact`; it is here for the kernels that scatter into a live buffer;
- `engine`: `block._compact` as it stands, its cumsum included, at
  `ENGINE_COLUMNS` columns and on the mixed pages only (each such program
  pays the cumsum's compile).
Every form is checked bit for bit against `scatter` before it is timed. One
JSON line per (part, capacity[, dtype, columns]). The compile seconds are a
compiler's only where the compile cache has not seen the programs (a cache
hit reads 0.01-0.02 s).

The rule `_compact` took from it (PERF.md section 6, PR 32): a 64-bit
column goes by index and gather (at 2^20 rows 18 ms a column against its own
scatter's 74), a narrower column and a null mask keep their own scatter (5-7
ms against a gather's 8-24), and a page with no 64-bit column builds no
index.
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import presto_tpu  # noqa: F401  (enables 64-bit types)
from presto_tpu.block import Block, Page, _compact
from presto_tpu.types import BIGINT, BOOLEAN, DOUBLE, INTEGER
from tools.dense_reduce_sweep import _median_seconds

CAPACITIES = (1 << 14, 1 << 18, 1 << 20)
COLUMNS = (1, 2, 4, 8)
TYPES = (INTEGER, BIGINT, DOUBLE, BOOLEAN)
MIXED = {"3xint64+1xint32": (BIGINT, BIGINT, BIGINT, INTEGER),
         "2xint64+2xint32": (BIGINT, BIGINT, INTEGER, INTEGER)}
ENGINE_COLUMNS = 2
LIVE_SHARE = 0.4  # lineitem's page in Q3: 0.715 full x 0.54 selected


@jax.jit
def _positions(mask):
    return jnp.cumsum(mask.astype(jnp.int32)) - 1


def _index_scatter(mask, pos):
    rows = jnp.arange(mask.shape[0], dtype=jnp.int32)
    tgt = jnp.where(mask, pos, mask.shape[0])
    return jnp.zeros_like(rows).at[tgt].set(rows, mode="drop")


def _index_perm(mask, pos):
    rows = jnp.arange(mask.shape[0], dtype=jnp.int32)
    tgt = jnp.where(mask, pos, pos[-1] + rows - pos)
    return jnp.zeros_like(rows).at[tgt].set(
        rows, mode="promise_in_bounds", unique_indices=True)


def _index_sort(mask, pos):
    cap = mask.shape[0]
    rows = jnp.arange(cap, dtype=jnp.int32)
    return jnp.sort(jnp.where(mask, rows, cap + rows)) % cap


INDEXES = {"scatter": jax.jit(_index_scatter), "perm": jax.jit(_index_perm),
           "sort": jax.jit(_index_sort)}


def _by_scatter(page: Page, pos) -> Page:
    cap = page.mask.shape[0]
    tgt = jnp.where(page.mask, pos, cap)
    blocks = []
    for b in page.blocks:
        out = jnp.zeros_like(b.data).at[tgt].set(b.data, mode="drop")
        nulls = None
        if b.nulls is not None:
            nulls = jnp.zeros(cap, dtype=jnp.bool_).at[tgt].set(
                b.nulls, mode="drop")
        blocks.append(Block(b.type, out, nulls, b.dictionary))
    return Page(tuple(blocks),
                jnp.arange(cap, dtype=jnp.int32) <= pos[-1])


def _by_gather(index):
    def compact(page: Page, pos) -> Page:
        src = index(page.mask, pos)
        live = jnp.arange(page.mask.shape[0], dtype=jnp.int32) <= pos[-1]

        def move(a):
            return jnp.where(live, a.at[src].get(mode="promise_in_bounds"),
                             jnp.zeros((), a.dtype))  # `a` may be a bool

        return Page(tuple(
            Block(b.type, move(b.data),
                  None if b.nulls is None else move(b.nulls), b.dictionary)
            for b in page.blocks), live)
    return compact


def _by_split(page: Page, pos) -> Page:
    cap = page.mask.shape[0]
    tgt = jnp.where(page.mask, pos, cap)

    def half(a):
        return jnp.zeros(cap, jnp.uint32).at[tgt].set(
            a.astype(jnp.uint32), mode="drop").astype(jnp.int64)

    return Page(tuple(
        Block(b.type, (half(b.data >> 32) << 32) | half(b.data))
        for b in page.blocks), jnp.arange(cap, dtype=jnp.int32) <= pos[-1])


FORMS = {"scatter": jax.jit(_by_scatter),
         "gather": jax.jit(_by_gather(INDEXES["scatter"])),
         "gather_perm": jax.jit(_by_gather(INDEXES["perm"]))}
SPLIT = jax.jit(_by_split)


def _compile_seconds(jitted, *args) -> float:
    t0 = time.perf_counter()
    jitted.lower(*args).compile()
    return time.perf_counter() - t0


def _same(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _page(rng, types, cap: int, mask) -> Page:
    blocks = []
    for type_ in types:
        dt = np.dtype(type_.np_dtype)
        if dt == np.bool_:
            data = rng.integers(0, 2, cap).astype(dt)
        elif dt.kind == "f":
            data = rng.integers(-10 ** 9, 10 ** 9, cap) * 0.01
        elif dt.itemsize == 8:  # both halves at work
            data = rng.integers(-2 ** 62, 2 ** 62, cap)
        else:
            data = rng.integers(-10 ** 9, 10 ** 9, cap).astype(dt)
        blocks.append(Block(type_, jnp.asarray(data, dtype=dt)))
    return Page(tuple(blocks), mask)


def main(capacities=CAPACITIES, columns=COLUMNS, types=TYPES) -> int:
    dev = jax.devices()[0]
    where = {"device": dev.device_kind, "platform": dev.platform}
    rng = np.random.default_rng(32)
    for cap in capacities:
        mask = jnp.asarray(rng.random(cap) < LIVE_SHARE)
        live = int(np.asarray(mask).sum())
        line = dict(where, part="index", capacity=cap, live_rows=live,
                    positions_compile_s=_compile_seconds(_positions, mask))
        line["positions_s"] = _median_seconds(_positions, mask, budget_s=0.3)
        pos = _positions(mask)
        want = np.flatnonzero(np.asarray(mask))
        for name, index in INDEXES.items():
            line[name + "_compile_s"] = _compile_seconds(index, mask, pos)
            line[name + "_same_rows"] = bool(np.array_equal(
                np.asarray(index(mask, pos))[:live], want))
            line[name + "_s"] = _median_seconds(index, mask, pos,
                                                budget_s=0.3)
        print(json.dumps(line), flush=True)
        pages = [(np.dtype(t.np_dtype).name, k, (t,) * k)
                 for t in types for k in columns]
        pages += [(name, len(ts), ts) for name, ts in MIXED.items()]
        for name, k, ts in pages:
            page = _page(rng, ts, cap, mask)
            ref = FORMS["scatter"](page, pos)
            forms = dict(FORMS, split=SPLIT) if name == "int64" else FORMS
            line = dict(where, part="page", capacity=cap, live_rows=live,
                        dtype=name, columns=k)
            for form_name, form in forms.items():
                line[form_name + "_same_page"] = _same(form(page, pos), ref)
                line[form_name + "_s"] = _median_seconds(
                    form, page, pos, budget_s=0.3, most=10)
            if k == ENGINE_COLUMNS or name in MIXED:
                line["engine_same_page"] = _same(_compact(page), ref)
                line["engine_s"] = _median_seconds(
                    _compact, page, budget_s=0.3, most=10)
            print(json.dumps(line), flush=True)
            del page, ref
    return 0


if __name__ == "__main__":
    sys.exit(main())
