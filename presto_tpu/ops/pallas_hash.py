"""Masked open-addressing hash tables in Pallas: join build/probe + grouping.

The engine's join and aggregation strategies are sort-based because XLA's
scatter is weak on TPU — but the reference engine's hash build
(operator/PagesHash.java:34, MultiChannelGroupByHash.java:54) is the
motivating case, and the deferred VERDICT ask is "one Pallas kernel that
wins — or a written negative result". This module is that kernel pair:

- **insert** — a power-of-two-slot table with linear probing. Insertion is
  vectorized over PROBE DISTANCE, not serialized over rows: every still-
  pending row bids for slot ``(h(key) + d) & (S - 1)`` in round ``d``; the
  winner of an empty slot (scatter-min over row ids — the scatter-bound
  build the reference does with CAS loops) claims it, rows whose key already
  owns the slot adopt it (insert-or-lookup: the grouping path's group id),
  and everyone else carries to round ``d + 1``. The trip count is FIXED at
  trace time (mask-based termination, no data-dependent control flow — the
  Pallas/TPU contract); rows still pending after the last round raise the
  ``overflow`` flag so callers fall back to the sorted path instead of
  silently dropping rows.
- **probe** — fixed-trip linear scan from ``h(key)``: a key match yields the
  stored row id, an EMPTY slot terminates as a miss (mask-based ``done``
  accumulation). The required trip count is the longest occupied run in the
  table — measured by the build (a doubled-array prefix-max, not a host
  loop) and handed to the probe as a static, pow2-bucketed trip count so
  adversarial clustering can never truncate a scan.

Both kernels run through ``pl.pallas_call``; off-TPU they run with
``interpret=True``, which is the only way they have ever run: the v5e
compiler refuses both ("64-bit types are not supported" — the int64 slot
components and the uint64 mixer; tests/test_chip_compile.py keeps the strict
xfail). Load factor is held at <= 0.5
(``table_slots`` returns 2N slots), which keeps expected probe distances
O(1) under the mix64 hash the rest of the engine already routes with.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ..utils import kernel_cache

# insert rounds per build attempt: with load <= 0.5 and a mixed hash the
# expected max probe distance is O(log n / log log n); 64 rounds is far past
# any non-adversarial clustering, and the overflow flag catches the rest
INSERT_TRIPS = 64
# a probe that must scan this many slots per row has already lost to the
# sorted path; builds whose longest occupied run exceeds it fall back
PROBE_TRIPS_CAP = 1 << 12
# table-size ceiling (slots): beyond this the build falls back to sorted —
# on a real TPU a larger table would also outgrow VMEM residency
MAX_TABLE_SLOTS = 1 << 22

EMPTY = -1  # free-slot / miss sentinel (plain int: kernels must not capture jnp constants)


@functools.lru_cache(maxsize=1)
def interpret_mode() -> bool:
    """Pallas interprets everywhere except on a TPU backend, where the
    kernels go to the chip's compiler (which today refuses them — see the
    module docstring)."""
    return jax.default_backend() != "tpu"


def table_slots(n_rows: int) -> Optional[int]:
    """Power-of-two slot count at load factor <= 0.5, or None when the
    table would exceed the slot ceiling (callers fall back to sorted)."""
    slots = 1 << max(4, (2 * max(int(n_rows), 1) - 1).bit_length())
    return slots if slots <= MAX_TABLE_SLOTS else None


# THE engine-wide 64-bit mixer: exchange routing, join builds and these
# tables must hash identically so they can never disagree on placement —
# one definition, imported (hash_join imports this module lazily, so there
# is no cycle)
from .hash_join import _mix64  # noqa: E402


def _hash_base(comps: Sequence[jnp.ndarray], slots: int) -> jnp.ndarray:
    """Row -> home slot. Multi-component keys fold through the mixer the
    same way combined_key does; the table compares FULL components on every
    probe, so hash collisions cost probes, never correctness."""
    acc = _mix64(comps[0])
    for c in comps[1:]:
        acc = _mix64(acc ^ (c.astype(jnp.uint64) *
                            jnp.uint64(0x9E3779B97F4A7C15)))
    return (acc & jnp.uint64(slots - 1)).astype(jnp.int32)


def _max_occupied_run(used: jnp.ndarray) -> jnp.ndarray:
    """Longest circular run of occupied slots (the probe's worst-case scan:
    adjacent clusters merge, so this can exceed any single insert's probe
    distance). Doubled-array prefix-max of the last-empty index — load
    <= 0.5 guarantees an empty slot, so no run wraps the full table."""
    S = used.shape[0]
    u2 = jnp.concatenate([used, used])
    idx = jnp.arange(2 * S, dtype=jnp.int32)
    last_empty = lax.cummax(jnp.where(u2, jnp.int32(-1), idx))
    return jnp.max((idx - last_empty)[S:])


# ---------------------------------------------------------------------------
# insert kernel
# ---------------------------------------------------------------------------

def _insert_body(ncomps: int, slots: int, trips: int):
    """Kernel body for ``pl.pallas_call``: refs are
    [comp_0..comp_{n-1}, mask] -> [slot_comp_0.., slot_rows, gid, stats]."""

    def kernel(*refs):
        comp_refs = refs[:ncomps]
        mask_ref = refs[ncomps]
        out_comps = refs[ncomps + 1: 2 * ncomps + 1]
        rows_ref = refs[2 * ncomps + 1]
        gid_ref = refs[2 * ncomps + 2]
        stats_ref = refs[2 * ncomps + 3]
        comps = [r[:] for r in comp_refs]
        mask = mask_ref[:]
        n = mask.shape[0]
        h = _hash_base(comps, slots)
        rowid = lax.broadcasted_iota(jnp.int32, (n, 1), 0).reshape(n)

        def one_round(_d, carry):
            used, slot_comps, slot_rows, gid, pending, dist = carry
            cand = (h + dist) & (slots - 1)
            # bid for empty slots: the scatter-min winner claims the slot
            tryers = pending & ~used[cand]
            bid_tgt = jnp.where(tryers, cand, slots)
            claims = jnp.full(slots, n, dtype=jnp.int32).at[bid_tgt].min(
                rowid, mode="drop")
            winner = tryers & (claims[cand] == rowid)
            wtgt = jnp.where(winner, cand, slots)
            used = used.at[wtgt].set(True, mode="drop")
            slot_comps = tuple(
                sc.at[wtgt].set(c, mode="drop")
                for sc, c in zip(slot_comps, comps))
            slot_rows = slot_rows.at[wtgt].set(rowid, mode="drop")
            # a slot now holding this row's key resolves it (claimed by this
            # row, claimed this round by a same-key sibling, or pre-existing)
            same = used[cand]
            for sc, c in zip(slot_comps, comps):
                same = same & (sc[cand] == c)
            resolved = pending & same
            gid = jnp.where(resolved, cand, gid)
            pending = pending & ~resolved
            dist = jnp.where(pending, dist + 1, dist)
            return used, slot_comps, slot_rows, gid, pending, dist

        init = (jnp.zeros(slots, dtype=jnp.bool_),
                tuple(jnp.zeros(slots, dtype=jnp.int64)
                      for _ in range(ncomps)),
                jnp.full(slots, EMPTY, dtype=jnp.int32),
                jnp.full(n, EMPTY, dtype=jnp.int32),
                mask,
                jnp.zeros(n, dtype=jnp.int32))
        used, slot_comps, slot_rows, gid, pending, _dist = lax.fori_loop(
            0, trips, one_round, init)
        for ref, sc in zip(out_comps, slot_comps):
            ref[:] = sc
        rows_ref[:] = slot_rows
        gid_ref[:] = gid
        stats_ref[:] = jnp.stack([
            jnp.any(pending).astype(jnp.int32),          # overflow
            _max_occupied_run(used).astype(jnp.int32),   # probe scan bound
            jnp.sum(used.astype(jnp.int32)),             # distinct keys (ng)
        ]).astype(jnp.int32)
    return kernel


def insert_table(comps: Tuple[jnp.ndarray, ...], mask: jnp.ndarray,
                 slots: int, trips: int = 0):
    """Traceable insert-or-lookup: build the open-addressing table over the
    masked rows of ``comps`` (each component cast to int64).

    Returns ``(slot_comps, slot_rows, gid, stats)``:
    - slot_comps: per-component (slots,) int64 key storage (empty = garbage,
      gated by slot_rows)
    - slot_rows: (slots,) int32 — FIRST inserting row id per slot, EMPTY(-1)
      for free slots
    - gid: (n,) int32 — each masked row's slot (its dense-ish group id);
      EMPTY for masked-off or overflowed rows
    - stats: (3,) int32 — [overflow_flag, max_occupied_run, distinct_keys]
    """
    trips = trips or INSERT_TRIPS
    ncomps = len(comps)
    comps = tuple(c.astype(jnp.int64) for c in comps)
    n = comps[0].shape[0]
    out_shape = (
        tuple(jax.ShapeDtypeStruct((slots,), jnp.int64)
              for _ in range(ncomps)) +
        (jax.ShapeDtypeStruct((slots,), jnp.int32),
         jax.ShapeDtypeStruct((n,), jnp.int32),
         jax.ShapeDtypeStruct((3,), jnp.int32)))
    outs = pl.pallas_call(
        _insert_body(ncomps, slots, trips),
        out_shape=out_shape,
        interpret=interpret_mode(),
    )(*comps, mask)
    slot_comps = tuple(outs[:ncomps])
    slot_rows, gid, stats = outs[ncomps], outs[ncomps + 1], outs[ncomps + 2]
    return slot_comps, slot_rows, gid, stats


def insert_table_jit(ncomps: int, n: int, slots: int,
                     trips: int = 0):
    """Cached jitted wrapper for the eager (operator-level) build call —
    keyed on the static shape signature so identical builds across queries
    and workers replay one compile."""
    trips = trips or INSERT_TRIPS
    return kernel_cache.get_or_install(
        ("pallas-insert", ncomps, n, slots, trips, interpret_mode()),
        lambda: jax.jit(functools.partial(insert_table, slots=slots,
                                          trips=trips)))


# ---------------------------------------------------------------------------
# probe kernel
# ---------------------------------------------------------------------------

def _probe_body(slots: int, trips: int):
    def kernel(sk_ref, sr_ref, key_ref, mask_ref, out_ref):
        sk = sk_ref[:]
        sr = sr_ref[:]
        key = key_ref[:]
        mask = mask_ref[:]
        n = key.shape[0]
        h = _hash_base([key], slots)

        def one_trip(d, carry):
            row, done = carry
            cand = (h + d) & (slots - 1)
            srow = sr[cand]
            occupied = srow >= 0
            hit = ~done & occupied & (sk[cand] == key)
            row = jnp.where(hit, srow, row)
            # an empty slot ends the cluster: everything after is a miss
            done = done | hit | ~occupied
            return row, done

        row, _done = lax.fori_loop(
            0, trips, one_trip,
            (jnp.full(n, EMPTY, dtype=jnp.int32), ~mask))
        out_ref[:] = row
    return kernel


def probe_table(slot_keys: jnp.ndarray, slot_rows: jnp.ndarray,
                keys: jnp.ndarray, mask: jnp.ndarray, trips: int):
    """Traceable probe: per masked probe row, the matching build row id or
    EMPTY(-1) — the miss mask is ``result < 0``. ``trips`` must be the
    build's max-occupied-run bound (pow2-bucketed by the caller so the trace
    signature stays small); masked rows never match."""
    keys = keys.astype(jnp.int64)
    n = keys.shape[0]
    # traceable helper: only ever invoked inside the module-level-jitted
    # probe_match_pallas wrapper (ops/hash_join.py), so the fresh
    # pallas_call identity is cached by the outer trace, not re-dispatched
    return pl.pallas_call(  # prestocheck: ignore[cache-key-hygiene]
        _probe_body(slot_keys.shape[0], trips),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret_mode(),
    )(slot_keys, slot_rows, keys, mask)


def probe_trips_for(max_run: int) -> int:
    """Static probe trip count for a measured longest occupied run: the run
    plus its terminating empty slot, bucketed to pow2 (bounded compile
    diversity — one probe kernel per bucket, not per build)."""
    return 1 << max(3, int(max_run)).bit_length()
