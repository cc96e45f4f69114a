"""Shared sort-order kernels.

`jnp.lexsort` lowers to ONE variadic sort over every key column, and on the
TPU both its run time and — far worse — its compile time grow with the
operand count and with 64-bit (emulated) comparators: the v5e compiler takes
~26 s for a single-array int64 sort of 2^20 rows and many minutes for a
seven-key variadic one (measured, PERF.md). Since SQL group/order keys are
almost always ints with modest ranges (keys, dates, dictionary codes, flags),
`lexsort_fast` packs every key column into ONE int64 — bias each column to
zero by its batch minimum, multiply into mixed-radix digits, append the row
index as the lowest bits — and sorts that single array. The row index makes
the pack unique per row, so the result is stable and the permutation falls
out of a mask.

When the packed domain would overflow, the same single-array sort runs as an
LSD radix loop instead: every key column splits into order-preserving
32-bit digits, and one pass per digit (least significant first) sorts
`digit << bits | position`. Both cases are ONE `lax.fori_loop` over a stack
of digit rows — the packed case is simply a one-trip loop — so a compiled
kernel holds exactly one sort instance whatever the key count.

Float keys take `jnp.lexsort` unconditionally: their bit patterns span
nearly the whole int64 line, so the packed domain can never fit — and the
order-preserving f64->s64 bitcast is rejected by XLA's TPU x64 rewriter
anyway. The dtype check is static (trace time).

This is the engine's answer to the reference's compiled `OrderingCompiler`
(sql/gen/OrderingCompiler.java): specialize the comparator at runtime —
except here the specialization turns the comparator into integer arithmetic
the hardware sorts natively.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp


def _digits32(k: jnp.ndarray) -> List[jnp.ndarray]:
    """Split an integral/bool key column into order-preserving digits in
    [0, 2^32), least significant first, each as int64."""
    if k.dtype == jnp.bool_:
        return [k.astype(jnp.int64)]
    if k.dtype.itemsize <= 4:
        return [k.astype(jnp.int64) - int(jnp.iinfo(k.dtype).min)]
    # flip the sign bit: unsigned order of `u` == signed order of `k`
    u = jax.lax.bitcast_convert_type(k.astype(jnp.int64), jnp.uint64) \
        ^ jnp.uint64(1 << 63)
    return [(u & jnp.uint64(0xFFFFFFFF)).astype(jnp.int64),
            (u >> jnp.uint64(32)).astype(jnp.int64)]


def lexsort_fast(keys: Tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    """Drop-in `jnp.lexsort(keys)`: stable permutation ordering rows by the
    key columns, LAST key primary (the numpy/jnp lexsort convention).

    Returns int32 positions. Jit-safe: the packed/radix choice is the trip
    count of one loop, decided on the measured key ranges, so one compiled
    kernel serves any data distribution.
    """
    assert keys, "lexsort_fast needs at least one key"
    n = keys[0].shape[0]
    if n == 0:
        return jnp.zeros(0, dtype=jnp.int32)
    if any(jnp.issubdtype(k.dtype, jnp.floating) or k.dtype == jnp.uint64
           for k in keys):
        # float bit spans overflow the packed domain in all but degenerate
        # cases, and the TPU backend cannot bitcast f64->s64 at all (uint64
        # does not fit the int64 digits): the general sort serves these
        return jnp.lexsort(tuple(keys)).astype(jnp.int32)
    ks = [k.astype(jnp.int64) for k in keys]
    mins = [jnp.min(k) for k in ks]
    maxs = [jnp.max(k) for k in ks]

    # the row position rides in the low `bits` bits of every sorted word
    # (shift/mask, not multiply/modulo: emulated 64-bit multiplies fused
    # into the sort's input cost the TPU compiler half as much again)
    bits = max((n - 1).bit_length(), 1)
    assert bits <= 31, "a 32-bit digit plus the position must fit an int64"
    # overflow check in float64: int64 `max - min` itself wraps for wide
    # domains, so the spans feeding the decision must never touch int math.
    # 2**61 leaves margin for the <=2^11 ulp error of rounding i64 -> f64.
    span = jnp.asarray(float(1 << bits), dtype=jnp.float64)
    for mn, mx in zip(mins, maxs):
        span = span * (mx.astype(jnp.float64) - mn.astype(jnp.float64) + 1.0)
    fits = span < float(2 ** 61)

    # under `fits`, every per-column span (and their product) < 2^61 / 2^bits,
    # so the int arithmetic below cannot overflow; otherwise it wraps
    # harmlessly and the result is not used
    packed = jnp.zeros(n, dtype=jnp.int64)
    # primary key (last) becomes the most significant digit
    for k, mn, mx in zip(reversed(ks), reversed(mins), reversed(maxs)):
        packed = packed * jnp.maximum(mx - mn + 1, 1) + (k - mn)

    digits = [d for k in keys for d in _digits32(k)]
    digits[0] = jnp.where(fits, packed, digits[0])
    stacked = jnp.stack(digits)
    trips = jnp.where(fits, 1, len(digits))
    pos = jnp.arange(n, dtype=jnp.int64)

    def one_pass(i, order):
        d = jax.lax.dynamic_index_in_dim(stacked, i, keepdims=False)[order]
        perm = jnp.sort((d << bits) | pos) & ((1 << bits) - 1)
        return order[perm.astype(jnp.int32)]

    order = jnp.arange(n, dtype=jnp.int32)
    # inside shard_map the loop's carry must vary over the mesh axes its
    # result varies over, i.e. the keys'
    axes = tuple(jax.typeof(stacked).vma)
    if axes:
        order = jax.lax.pcast(order, axes, to="varying")
    return jax.lax.fori_loop(0, trips, one_pass, order)
