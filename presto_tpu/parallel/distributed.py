"""Distributed SPMD stage programs.

Where the reference runs a stage as N independent JVM tasks wired by HTTP shuffle
(SqlStageExecution + ExchangeClient), a distributed stage here is ONE SPMD program
over the mesh: every worker-chip executes the same jitted function on its shard of
splits, and the stage's REMOTE exchanges are collectives inside the program
(parallel/exchange.py). XLA overlaps the collective with compute and there is no
serialization on the wire.

Stage programs compose the same pure kernels the single-chip operators use
(sort_group_reduce, join probe kernels) — the analogue of the reference reusing
operators across LocalQueryRunner and distributed tasks.

This module carries the two canonical stage shapes:
 - partial->final aggregation with an all-gather/psum final exchange (Q1 shape)
 - build-broadcast + probe-repartition hash join with partial aggregation (Q3 shape)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..ops.hash_agg import sort_group_reduce
from ..utils import kernel_cache
from .exchange import repartition
from .mesh import WORKER_AXIS, MeshContext


def dist_q1_step(mesh_ctx: MeshContext, n_flags: int = 3, n_status: int = 2):
    """Distributed TPC-H Q1 kernel: per-worker direct grouping + psum final exchange.

    Input (per worker shard, leading axis = workers under shard_map):
      rf, ls: int32 dictionary codes; qty/ep/disc/tax: int64 cents; sd: int32 days;
      mask: live rows. Output: replicated dense group table (n_flags*n_status groups).
    """
    from ..models.kernels import q1_partials

    def stage(rf, ls, qty, ep, disc, tax, sd, mask):
        sums = q1_partials(rf, ls, qty, ep, disc, tax, sd, mask,
                           n_flags=n_flags, n_status=n_status)
        # final exchange: one psum replaces the entire partial->final HTTP shuffle
        return tuple(lax.psum(s, WORKER_AXIS) for s in sums)

    mesh = mesh_ctx.mesh
    sharded = P(WORKER_AXIS)
    # per-(mesh, group-domain) program: rebuilding the stage for every query
    # submission was a fresh jit identity (a silent recompile) per call
    return kernel_cache.get_or_install(
        ("dist-q1", mesh, n_flags, n_status),
        lambda: jax.jit(shard_map(stage, mesh=mesh,
                                  in_specs=(sharded,) * 8,
                                  out_specs=(P(),) * 6)))


def dist_join_agg_step(mesh_ctx: MeshContext, probe_cap_per_peer: int):
    """Distributed Q3-shape stage: repartition probe+build by join key over ICI,
    local dense join per worker, partial agg, gather.

    Demonstrates the three exchange modes of the engine on one program:
      - build side: hash-REPARTITION (FIXED_HASH) via all_to_all
      - probe side: hash-REPARTITION via all_to_all on the same key
      - final:      all_gather of per-worker partials (root SINGLE exchange)
    """
    W = mesh_ctx.n_workers

    def stage(bkey, bval, bmask, pkey, pval, pmask):
        # exchange both sides so equal keys land on the same worker
        (bk, bv), bm, bdrop = repartition([bkey, bval], bmask, bkey, W,
                                          probe_cap_per_peer)
        (pk, pv), pm, pdrop = repartition([pkey, pval], pmask, pkey, W,
                                          probe_cap_per_peer)
        # local sort-merge join (unique build keys)
        big = jnp.int64(np.iinfo(np.int64).max)
        skey = jnp.where(bm, bk, big)
        order = jnp.argsort(skey)
        skey_s = skey[order]
        srow = order.astype(jnp.int32)
        pos = jnp.clip(jnp.searchsorted(skey_s, pk), 0, skey_s.shape[0] - 1)
        hit = (skey_s[pos] == pk) & pm
        brow = jnp.where(hit, srow[pos], 0)
        joined_val = jnp.where(hit, pv + bv[brow], 0)
        # partial aggregation by build value bucket (stand-in group key)
        gid = jnp.where(hit, (bv[brow] % 64).astype(jnp.int32), 64)
        part = jax.ops.segment_sum(joined_val, gid, num_segments=65)[:64]
        cnt = jax.ops.segment_sum(hit.astype(jnp.int64), gid, num_segments=65)[:64]
        # final exchange
        total = lax.psum(part, WORKER_AXIS)
        count = lax.psum(cnt, WORKER_AXIS)
        dropped = lax.psum(bdrop + pdrop, WORKER_AXIS)
        return total, count, dropped

    mesh = mesh_ctx.mesh
    s = P(WORKER_AXIS)
    return kernel_cache.get_or_install(
        ("dist-join-agg", mesh, probe_cap_per_peer),
        lambda: jax.jit(shard_map(stage, mesh=mesh, in_specs=(s,) * 6,
                                  out_specs=(P(), P(), P()))))


def dist_grouped_agg_step(mesh_ctx: MeshContext, n_keys: int, n_states: int,
                          kinds, identities, max_groups: int):
    """General distributed GROUP BY: local sort-group partials, repartition groups by
    key hash (so each group lands wholly on one worker), final sort-group combine.
    This is the engine's scalable aggregation exchange (the analogue of partial agg ->
    FIXED_HASH exchange -> final agg that AddExchanges.java:253 plans)."""
    W = mesh_ctx.n_workers

    def stage(*args):
        keys = args[:n_keys]
        contribs = args[n_keys:n_keys + n_states]
        mask = args[-1]
        cap = mask.shape[0]
        gkeys, gstates, gvalid, _ = sort_group_reduce(
            keys, mask, contribs, kinds, identities, cap)
        # route each partial group to the worker owning its key
        (arrs), m, dropped = repartition(
            list(gkeys) + list(gstates), gvalid, gkeys[0], W, max_groups)
        rkeys = tuple(arrs[:n_keys])
        rstates = tuple(arrs[n_keys:])
        fkeys, fstates, fvalid, fnum = sort_group_reduce(
            rkeys, m, rstates, kinds, identities, max_groups)
        # distinct groups beyond max_groups land in sort_group_reduce's trash bin;
        # surface them in the drop count so callers can fail loudly instead of
        # accepting silently truncated aggregates
        overflow = jnp.maximum(fnum - max_groups, 0).astype(dropped.dtype)
        return fkeys + fstates + (fvalid, lax.psum(dropped + overflow, WORKER_AXIS))

    mesh = mesh_ctx.mesh
    s = P(WORKER_AXIS)
    n_in = n_keys + n_states + 1
    n_out = n_keys + n_states + 2
    return kernel_cache.get_or_install(
        ("dist-grouped-agg", mesh, n_keys, n_states, tuple(kinds),
         tuple(identities), max_groups),
        lambda: jax.jit(shard_map(stage, mesh=mesh, in_specs=(s,) * n_in,
                                  out_specs=(s,) * (n_out - 1) + (P(),))))
