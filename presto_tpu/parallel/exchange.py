"""Collective exchange kernels: the ICI data plane.

Analogue of the reference's shuffle stack — producer side
operator/PartitionedOutputOperator.java:297,380-440 (row->partition, serialize,
enqueue) + buffer classes, consumer side operator/ExchangeClient.java pulling over
HTTP with LZ4 pages (execution/buffer/PagesSerde.java:39).

TPU re-design: there is no serialization, no HTTP, no LZ4 — a partitioned exchange is
ONE collective inside the SPMD program:

    repartition = rank rows within their target partition + lax.all_to_all over the mesh axis
    broadcast   = lax.all_gather
    single      = all_gather then mask to worker 0

Pages stay fixed-capacity: each worker sends exactly `cap` row slots to every other
worker (count-carrying, tail-masked), so the collective has a static shape — the
price is padding bandwidth, the win is a single fused XLA program with the collective
overlapped against compute (what the reference approximates with async HTTP +
isBlocked futures).

These functions are pure and designed to be called INSIDE shard_map; they are the
building blocks the distributed planner stitches into stage programs.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
from jax import lax

from ..ops.hash_join import _mix64
from .mesh import WORKER_AXIS


def partition_ids(key: jnp.ndarray, n_parts: int) -> jnp.ndarray:
    """Row -> target partition (PartitionFunction.getPartition analogue): mix then mod
    so dense keys spread (HashGenerationOptimizer's raw-hash + modulo). Uses the SAME
    mix as the join kernels' combined_key so exchange routing and build hashing can
    never diverge."""
    x = _mix64(key)
    return (x % jnp.uint64(n_parts)).astype(jnp.int32)


def range_partition_ids(range_key: jnp.ndarray, splitters: jnp.ndarray,
                        mask: jnp.ndarray, n_parts: int) -> jnp.ndarray:
    """Row -> target partition by VALUE RANGE: worker w receives keys in
    (splitters[w-1], splitters[w]] — the distributed-ORDER-BY routing where
    worker order equals global order (MergeOperator's re-design; see
    sql/planner/plan.py MERGE)."""
    pid = jnp.searchsorted(splitters, range_key, side="left").astype(jnp.int32)
    return jnp.where(mask, jnp.clip(pid, 0, n_parts - 1), n_parts)


def _rank_in_partition(pid: jnp.ndarray, n_parts: int) -> jnp.ndarray:
    """Each row's slot within its partition, rows in their own order (what a
    stable sort by `pid` would give), with no sort: one prefix sum a
    partition, so `n_parts` of them a collective (a prefix sum over 2^20 rows
    runs in 0.8 ms on the v5e, PERF.md section 7). Compile seconds for a
    described v5e on the builder's HOST, no chip number (PERF.md section 6,
    PR 37): a collective that argsorts a 2^16-row chunk 25 s, this one 3.0 s
    for 4 workers and 3.2 s for 8; ONE prefix sum over an (n_parts, n)
    one-hot compiles in the same seconds and holds 1.4x the scratch, so the
    loop stays."""
    pos = jnp.zeros(pid.shape[0], dtype=jnp.int32)
    for p in range(n_parts):
        mine = pid == p
        pos = jnp.where(mine, jnp.cumsum(mine.astype(jnp.int32)) - 1, pos)
    return pos


def move_rows(arrays: Sequence[jnp.ndarray], tgt: jnp.ndarray, n_slots: int,
              into: Sequence[jnp.ndarray] = ()):
    """Rows -> slots `tgt` of (n_slots,) buffers (`tgt == n_slots` drops the
    row): the buffers `into` where given, whose other slots keep what they
    hold, else fresh zeros. ONE int32 scatter builds each slot's source row
    and every column moves by a gather (block._compact's form, PR 32: a
    64-bit scatter is 74 ms a 2^20 rows on the v5e, a 64-bit gather 17.5, and
    a scatter a column is what the compiler spends its seconds on: for a
    described v5e on the builder's HOST, no chip number, 6.4 s for a fill of
    4 columns at 2^16 rows against 1.3 s, PERF.md section 6, PR 37).
    -> (buffers, slots filled)"""
    src = jnp.full(n_slots, -1, dtype=jnp.int32).at[tgt].set(
        jnp.arange(tgt.shape[0], dtype=jnp.int32), mode="drop")
    filled = src >= 0
    idx = jnp.maximum(src, 0)
    into = into or [jnp.zeros((), a.dtype) for a in arrays]
    return [jnp.where(filled, a[idx], b)
            for a, b in zip(arrays, into)], filled


def repartition_by_pid_with_carry(arrays: Sequence[jnp.ndarray],
                                  mask: jnp.ndarray, pid: jnp.ndarray,
                                  n_parts: int, out_cap_per_peer: int,
                                  axis_name: str = WORKER_AXIS):
    """Route rows to the peers named by `pid` (n_parts = masked-off), the
    STREAMING exchange's hash REPARTITION and range MERGE; within a partition
    rows keep their order. Overflow rows (those past `out_cap_per_peer` in a
    peer's slice of this chunk) are returned compacted to the front of
    same-shape carry buffers, staying resident on this worker for the pump to
    re-feed into the next chunk. Skewed keys are therefore correct by
    construction — capacity only bounds per-dispatch volume, never rows.

    Returns (recv_arrays, recv_mask, carry_arrays, carry_mask)."""
    n = mask.shape[0]
    pos_in_part = _rank_in_partition(pid, n_parts)
    live = pid < n_parts
    keep = live & (pos_in_part < out_cap_per_peer)
    overflow = live & ~keep
    outs, recv_mask = _route_kept(arrays, pid, pos_in_part, keep,
                                  n_parts, out_cap_per_peer, axis_name)
    # compact the overflow rows to the front of (n,) carry buffers
    cpos = jnp.cumsum(overflow.astype(jnp.int32)) - 1
    carry, carry_mask = move_rows(arrays, jnp.where(overflow, cpos, n), n)
    return outs, recv_mask, carry, carry_mask


def _route_kept(arrays, pid, pos_in_part, keep, n_parts: int,
                out_cap_per_peer: int, axis_name: str):
    """Move the kept rows into (n_parts, cap) send buffers and run the
    all_to_all."""
    slots = n_parts * out_cap_per_peer
    tgt = jnp.where(keep, pid * out_cap_per_peer + pos_in_part, slots)
    bufs, send_mask = move_rows(arrays, tgt, slots)
    # the collective: peer p receives every worker's partition-p slice
    recv = [lax.all_to_all(b.reshape(n_parts, out_cap_per_peer), axis_name,
                           split_axis=0, concat_axis=0, tiled=False)
            for b in bufs]
    recv_mask = lax.all_to_all(
        send_mask.reshape(n_parts, out_cap_per_peer), axis_name,
        split_axis=0, concat_axis=0, tiled=False)
    return [r.reshape(slots) for r in recv], recv_mask.reshape(slots)


def broadcast_gather(arrays: Sequence[jnp.ndarray], mask: jnp.ndarray,
                     axis_name: str = WORKER_AXIS):
    """FIXED_BROADCAST: replicate every worker's rows to all workers
    (BroadcastOutputBuffer + replicated join build analogue)."""
    outs = [lax.all_gather(a, axis_name, tiled=True) for a in arrays]
    m = lax.all_gather(mask, axis_name, tiled=True)
    return outs, m


def gather_to_single(arrays: Sequence[jnp.ndarray], mask: jnp.ndarray,
                     axis_name: str = WORKER_AXIS):
    """SINGLE distribution: all rows on worker 0, masked off elsewhere
    (the coordinator-pull root exchange)."""
    outs, m = broadcast_gather(arrays, mask, axis_name)
    widx = lax.axis_index(axis_name)
    return outs, m & (widx == 0)
