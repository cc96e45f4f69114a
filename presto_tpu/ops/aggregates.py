"""Aggregate function library.

Analogue of presto-main operator/aggregation/ (87 files: sum/count/avg/min/max/
approx_distinct/stddev/...) and AccumulatorCompiler.java:80. The reference compiles
per-function accumulator classes over flat state memory; here each function is a small
descriptor whose pieces (input transform, segment-combine, final transform) slot into
the segment-reduce grouping kernels — state is a struct-of-arrays indexed by group id,
which is exactly what TPU scatter/segment ops want.

Every function must be decomposable as
    partial:   contribution_j = input_map(x_j)          (per row)
    combine:   state_g = REDUCE_j-in-g contribution_j    (sum / min / max per column)
    final:     output_g = final_map(state_g)
which covers the algebraic aggregates. Non-algebraic ones (approx_percentile) get
fixed-size sketch states (qdigest/HLL analogues) in later revisions.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..types import (BIGINT, BOOLEAN, DOUBLE, REAL, Type, DecimalType, UNKNOWN,
                     is_floating, is_string)

# reduce kinds understood by the grouping kernels
SUM, MIN, MAX = "sum", "min", "max"
# paired (joint) kinds: an AMIN/AMAX state column holds an order-preserving
# int64 ordering key; the IMMEDIATELY FOLLOWING column must be ACARRY and
# receives the payload of the row that won the ordering (min_by/max_by,
# reference operator/aggregation/minmaxby/AbstractMinMaxBy.java). The
# grouping kernels reduce the pair jointly (segment argmin/argmax + gather).
AMIN, AMAX, ACARRY = "amin", "amax", "acarry"

_I64_MAX = np.int64(2**63 - 1)
_I64_MIN = np.int64(-(2**63))


@dataclasses.dataclass
class StateColumn:
    """One array in the aggregate's state struct.

    width > 1 makes this a VECTOR state: the per-group entry is a (width,)
    array, per-row contributions are (rows, width), and the grouping kernels
    reduce over the leading axis only — the sketch-aggregate shape
    (approx_percentile histograms, approx_distinct HLL registers), which maps
    to one wide segment-reduce instead of `width` scalar ones."""
    dtype: np.dtype
    reduce: str          # SUM | MIN | MAX
    identity: object     # fill value for empty groups
    width: int = 1


@dataclasses.dataclass
class AggregateFunction:
    """Descriptor: how to turn input rows into state contributions and state to output."""
    name: str
    output_type: Type
    state: List[StateColumn]
    # (input_arrays, valid_mask) -> per-row contribution arrays (one per state column)
    input_map: Callable
    # state arrays -> output array
    final_map: Callable
    intermediate_types: List[Type] = dataclasses.field(default_factory=list)
    # splittable: state columns can ride pages between PARTIAL and FINAL steps
    # (vector states cannot — the exchange planner keeps those single-phase)
    splittable: bool = True
    # canonical resolve-time identity (name, arg type names, distinct, params)
    # for the global kernel cache (utils/kernel_cache.agg_call_key) — set by
    # resolve_aggregate; two functions with equal fingerprints compile to
    # behaviorally identical contributions
    fingerprint: tuple = ()
    # string-producing aggregates (ml learn_*): a Dictionary allocated at
    # RESOLVE time so the plan layout can reference it; final_map fills it
    # with the actual values (codes index into it) when the query runs
    output_dict: object = None
    # which arg indices' NULLs exclude the row from the aggregate; None =
    # all (the @SqlNullable default). min_by/max_by skip only NULL ORDERING
    # rows — a NULL payload still participates and can win.
    null_skip_channels: Optional[tuple] = None
    # input_map is called as input_map(args, arg_null_masks, mask) when set
    needs_arg_nulls: bool = False
    # final_map runs on the HOST, numpy in and out (it fills output_dict:
    # ml's learn_*); every other final_map is traced into the result program
    host_final_map: bool = False


def _ones_i64(args, mask):
    shape = jnp.shape(mask)
    return (jnp.where(mask, jnp.int64(1), jnp.int64(0)),)


def resolve_aggregate(name: str, arg_types: Sequence[Type],
                      distinct: bool = False,
                      params: Sequence[object] = ()) -> AggregateFunction:
    """FunctionManager.resolveFunction analogue for aggregates.

    `params` carries literal (non-column) arguments extracted by the planner —
    e.g. approx_percentile's fraction."""
    fn = _resolve_aggregate(name, arg_types, distinct, params)
    # the resolve arguments fully determine the function's behavior, so they
    # ARE its kernel-cache identity
    fn.fingerprint = (name.lower(), tuple(t.name for t in arg_types),
                      bool(distinct), tuple(params))
    return fn


def _resolve_aggregate(name: str, arg_types: Sequence[Type],
                       distinct: bool = False,
                       params: Sequence[object] = ()) -> AggregateFunction:
    name = name.lower()
    if name == "count":
        if not arg_types:  # count(*)
            return AggregateFunction(
                "count", BIGINT,
                [StateColumn(np.dtype(np.int64), SUM, 0)],
                _ones_i64,
                lambda s: s[0],
                [BIGINT])
        t = arg_types[0]
        return AggregateFunction(
            "count", BIGINT,
            [StateColumn(np.dtype(np.int64), SUM, 0)],
            lambda args, mask: (jnp.where(mask, jnp.int64(1), jnp.int64(0)),),
            lambda s: s[0],
            [BIGINT])

    if name == "sum":
        t = arg_types[0]
        # second state column = contributing-row count; SQL sum over an empty/all-null
        # group is NULL, surfaced via the (data, null_mask) final_map contract
        if isinstance(t, DecimalType):
            out = DecimalType(18, t.scale)
            return AggregateFunction(
                "sum", out,
                [StateColumn(np.dtype(np.int64), SUM, 0),
                 StateColumn(np.dtype(np.int64), SUM, 0)],
                lambda args, mask: (jnp.where(mask, args[0].astype(jnp.int64), 0),
                                    jnp.where(mask, jnp.int64(1), jnp.int64(0))),
                lambda s: (s[0], s[1] == 0),
                [out, BIGINT])
        if is_floating(t):
            return AggregateFunction(
                "sum", DOUBLE,
                [StateColumn(np.dtype(np.float64), SUM, 0.0),
                 StateColumn(np.dtype(np.int64), SUM, 0)],
                lambda args, mask: (jnp.where(mask, args[0].astype(jnp.float64), 0.0),
                                    jnp.where(mask, jnp.int64(1), jnp.int64(0))),
                lambda s: (s[0], s[1] == 0),
                [DOUBLE, BIGINT])
        return AggregateFunction(
            "sum", BIGINT,
            [StateColumn(np.dtype(np.int64), SUM, 0),
             StateColumn(np.dtype(np.int64), SUM, 0)],
            lambda args, mask: (jnp.where(mask, args[0].astype(jnp.int64), 0),
                                jnp.where(mask, jnp.int64(1), jnp.int64(0))),
            lambda s: (s[0], s[1] == 0),
            [BIGINT, BIGINT])

    if name == "avg":
        t = arg_types[0]
        scale = t.scale if isinstance(t, DecimalType) else 0
        div = 10.0 ** scale
        return AggregateFunction(
            "avg", DOUBLE,
            [StateColumn(np.dtype(np.float64), SUM, 0.0),
             StateColumn(np.dtype(np.int64), SUM, 0)],
            lambda args, mask: (jnp.where(mask, args[0].astype(jnp.float64) / div, 0.0),
                                jnp.where(mask, jnp.int64(1), jnp.int64(0))),
            lambda s: (s[0] / jnp.maximum(s[1], 1).astype(jnp.float64), s[1] == 0),
            [DOUBLE, BIGINT])

    if name in ("min", "max"):
        t = arg_types[0]
        if is_string(t):
            # min/max on varchar reduces over dictionary CODES — correct only for
            # lexicographically-sorted dictionaries (block_from_strings builds sorted
            # ones). The planner must re-encode through Dictionary.sort_keys() before
            # aggregating an unsorted dictionary; AggregateCall.output_dictionary
            # carries the dictionary to the output block.
            dtype = np.dtype(np.int32)
            ident = np.int32(2**31 - 1) if name == "min" else np.int32(-(2**31))
        else:
            dtype = t.np_dtype
            if dtype.kind == "f":
                ident = np.inf if name == "min" else -np.inf
            else:
                info = np.iinfo(dtype)
                ident = info.max if name == "min" else info.min
        red = MIN if name == "min" else MAX
        return AggregateFunction(
            name, t,
            [StateColumn(dtype, red, ident),
             StateColumn(np.dtype(np.int64), SUM, 0)],
            lambda args, mask, _i=ident: (jnp.where(mask, args[0], jnp.asarray(_i)),
                                          jnp.where(mask, jnp.int64(1), jnp.int64(0))),
            lambda s: (s[0], s[1] == 0),
            [t, BIGINT])

    if name in ("min_by", "max_by"):
        # min_by(x, y): x of the row with minimal y. State = (sortable-int64
        # ordering key, carried payload, count); the kernels reduce the
        # AMIN/ACARRY pair jointly. Reference:
        # operator/aggregation/minmaxby/AbstractMinMaxBy.java.
        if len(arg_types) != 2:
            raise NotImplementedError(
                f"{name} takes exactly 2 arguments (the top-n form is not "
                f"supported)")
        tx, ty = arg_types[0], arg_types[1]
        is_min = name == "min_by"
        okind = AMIN if is_min else AMAX
        oident = _I64_MAX if is_min else _I64_MIN
        carry_dtype = np.dtype(np.int32) if is_string(tx) else tx.np_dtype
        carry_ident = False if carry_dtype.kind == "b" else carry_dtype.type(0)

        def input_map(args, arg_nulls, mask, _oident=oident):
            x, y = args[0], args[1]
            ys = jnp.where(mask, _sortable_i64(y), jnp.int64(_oident))
            carry = jnp.where(mask, x, jnp.asarray(carry_ident,
                                                   dtype=carry_dtype))
            xn = arg_nulls[0]
            carry_null = jnp.where(mask, xn.astype(jnp.int64), jnp.int64(0))
            return (ys, carry, carry_null,
                    jnp.where(mask, jnp.int64(1), jnp.int64(0)))

        return AggregateFunction(
            name, tx,
            [StateColumn(np.dtype(np.int64), okind, oident),
             StateColumn(carry_dtype, ACARRY, carry_ident),
             StateColumn(np.dtype(np.int64), ACARRY, 0),
             StateColumn(np.dtype(np.int64), SUM, 0)],
            input_map,
            lambda s: (s[1], (s[3] == 0) | (s[2] != 0)),
            [], splittable=False,
            null_skip_channels=(1,), needs_arg_nulls=True)

    if name in ("array_agg", "map_agg", "histogram"):
        # ragged collectors (ArrayAggregationFunction.java:50,
        # MapAggregationFunction.java, histogram/Histogram.java): routed to
        # ops/collect_agg.CollectAggregationBuilder; the state column is the
        # int32 HANDLE into the host ArrayValues store allocated here
        from ..block import ArrayValues
        from ..types import ArrayType, MapType
        if name == "array_agg":
            out_t = ArrayType(arg_types[0])
            store = ArrayValues("array")
        elif name == "map_agg":
            out_t = MapType(arg_types[0], arg_types[1])
            store = ArrayValues("map")
        else:
            out_t = MapType(arg_types[0], BIGINT)
            store = ArrayValues("map")
        return AggregateFunction(
            name, out_t,
            [StateColumn(np.dtype(np.int32), "collect", -1)],
            None,  # the collect builder bypasses input_map
            lambda s: (s[0], s[0] < 0),
            [], splittable=False, output_dict=store)

    if name in ("stddev", "stddev_samp", "stddev_pop", "variance", "var_samp", "var_pop"):
        pop = name.endswith("_pop")
        is_std = name.startswith("stddev")
        t = arg_types[0]
        scale = t.scale if isinstance(t, DecimalType) else 0
        div = 10.0 ** scale

        def input_map(args, mask):
            x = jnp.where(mask, args[0].astype(jnp.float64) / div, 0.0)
            return (x, x * x, jnp.where(mask, jnp.int64(1), jnp.int64(0)))

        def final_map(s, _pop=pop, _std=is_std):
            n = jnp.maximum(s[2], 1).astype(jnp.float64)
            mean = s[0] / n
            var = s[1] / n - mean * mean
            if not _pop:
                var = var * n / jnp.maximum(n - 1, 1)
            var = jnp.maximum(var, 0.0)
            return (jnp.sqrt(var) if _std else var), s[2] == 0

        return AggregateFunction(
            name, DOUBLE,
            [StateColumn(np.dtype(np.float64), SUM, 0.0),
             StateColumn(np.dtype(np.float64), SUM, 0.0),
             StateColumn(np.dtype(np.int64), SUM, 0)],
            input_map, final_map,
            [DOUBLE, DOUBLE, BIGINT])

    if name == "bool_or" or name == "bool_and":
        is_or = name == "bool_or"
        return AggregateFunction(
            name, BOOLEAN,
            [StateColumn(np.dtype(np.int64), MAX if is_or else MIN, 0 if is_or else 1),
             StateColumn(np.dtype(np.int64), SUM, 0)],
            lambda args, mask: (
                jnp.where(mask, args[0].astype(jnp.int64), 0 if is_or else 1),
                jnp.where(mask, jnp.int64(1), jnp.int64(0))),
            lambda s: (s[0] != 0, s[1] == 0),
            [BOOLEAN, BIGINT])

    if name == "count_if":
        return AggregateFunction(
            "count_if", BIGINT,
            [StateColumn(np.dtype(np.int64), SUM, 0)],
            lambda args, mask: (jnp.where(mask & (args[0].astype(jnp.bool_)),
                                          jnp.int64(1), jnp.int64(0)),),
            lambda s: s[0],
            [BIGINT])

    if name == "every":
        return resolve_aggregate("bool_and", arg_types, distinct)

    if name in ("arbitrary", "any_value"):
        # deterministic "any": max over values (dictionary codes for varchar,
        # same caveat-free since ANY value is acceptable)
        t = arg_types[0]
        dtype = np.dtype(np.int32) if is_string(t) else t.np_dtype
        if dtype.kind == "f":
            ident = -np.inf
        elif dtype.kind == "b":
            ident = False
        else:
            ident = np.iinfo(dtype).min
        return AggregateFunction(
            name, t,
            [StateColumn(dtype, MAX, ident),
             StateColumn(np.dtype(np.int64), SUM, 0)],
            lambda args, mask, _i=ident: (
                jnp.where(mask, args[0], jnp.asarray(_i)),
                jnp.where(mask, jnp.int64(1), jnp.int64(0))),
            lambda s: (s[0], s[1] == 0),
            [t, BIGINT])

    if name in ("covar_samp", "covar_pop", "corr"):
        tx, ty = arg_types[0], arg_types[1]
        dx = 10.0 ** (tx.scale if isinstance(tx, DecimalType) else 0)
        dy = 10.0 ** (ty.scale if isinstance(ty, DecimalType) else 0)
        want_corr = name == "corr"
        pop = name == "covar_pop"

        def input_map(args, mask):
            x = jnp.where(mask, args[0].astype(jnp.float64) / dx, 0.0)
            y = jnp.where(mask, args[1].astype(jnp.float64) / dy, 0.0)
            return (x, y, x * y, x * x, y * y,
                    jnp.where(mask, jnp.int64(1), jnp.int64(0)))

        def final_map(s, _corr=want_corr, _pop=pop):
            n = jnp.maximum(s[5], 1).astype(jnp.float64)
            mx, my = s[0] / n, s[1] / n
            cov = s[2] / n - mx * my
            if _corr:
                vx = jnp.maximum(s[3] / n - mx * mx, 0.0)
                vy = jnp.maximum(s[4] / n - my * my, 0.0)
                denom = jnp.sqrt(vx * vy)
                out = jnp.where(denom > 0, cov / jnp.maximum(denom, 1e-300), 0.0)
                return out, (s[5] == 0) | (denom <= 0)
            if not _pop:
                cov = cov * n / jnp.maximum(n - 1, 1)
                return cov, s[5] <= 1
            return cov, s[5] == 0

        return AggregateFunction(
            name, DOUBLE,
            [StateColumn(np.dtype(np.float64), SUM, 0.0) for _ in range(5)] +
            [StateColumn(np.dtype(np.int64), SUM, 0)],
            input_map, final_map,
            [DOUBLE] * 5 + [BIGINT])

    if name == "approx_distinct":
        # HyperLogLog, m=2048 registers (standard error 1.04/sqrt(m) ~ 2.3%,
        # matching the reference's default HLL accuracy,
        # operator/aggregation/ApproximateCountDistinctAggregation). One
        # VECTOR state per group: register j holds max(rho) of hashes landing
        # in bucket j; per-row contribution is a one-hot (rows, m) scatter
        # reduced by MAX — one wide segment-reduce on the VPU.
        M = 2048
        LOG2M = 11

        def input_map(args, mask, _m=M):
            h = _hash_to_u64(args[0])
            bucket = (h >> jnp.uint64(64 - LOG2M)).astype(jnp.int32)
            rest = (h << jnp.uint64(LOG2M)) | jnp.uint64((1 << LOG2M) - 1)
            # rho = leading zeros + 1, via the float exponent (msb index);
            # float64's 52-bit mantissa can misplace the msb on ~2^-52 of
            # inputs — irrelevant at sketch accuracy
            msb = jnp.floor(jnp.log2(rest.astype(jnp.float64)))
            rho = jnp.clip(64.0 - msb, 1.0, 64.0 - LOG2M + 1.0
                           ).astype(jnp.float32)
            # wide-state contribution: (bucket, value) pair; the grouping
            # kernels scatter value into state[group, bucket] with MAX
            return ((jnp.where(mask, bucket, _m), rho),)

        def final_map(s, _m=M):
            regs = s[0]                               # (groups, m) f32
            est = (0.7213 / (1 + 1.079 / _m)) * _m * _m / \
                jnp.sum(jnp.exp2(-regs), axis=-1)
            zeros = jnp.sum(regs == 0, axis=-1)
            # small-range correction (linear counting)
            small = _m * jnp.log(_m / jnp.maximum(zeros, 1).astype(jnp.float64))
            est = jnp.where((est <= 2.5 * _m) & (zeros > 0), small, est)
            return jnp.round(est).astype(jnp.int64)

        return AggregateFunction(
            "approx_distinct", BIGINT,
            [StateColumn(np.dtype(np.float32), MAX, 0.0, width=M)],
            input_map, final_map, [], splittable=False)

    if name == "approx_percentile":
        # log-bucketed histogram sketch: octaves 2^-16..2^31 x 8 sub-buckets
        # x 2 signs (+1 zero bucket) of f64 counts as ONE vector state; the
        # percentile is read off the per-group cumulative histogram with the
        # bucket's geometric midpoint (reference: qdigest-based
        # approx_percentile, ApproximateLongPercentileAggregations).
        # Relative error ~= half a sub-bucket ~= 4% for 2^-16 <= |v| < 2^32;
        # smaller magnitudes clamp into the lowest octave.
        OCT_LO, OCT_HI, SUB = -16, 31, 8
        N_OCT = OCT_HI - OCT_LO + 1
        HALF = N_OCT * SUB
        K = 2 * HALF + 1
        t = arg_types[0]
        int_out = not is_floating(t)  # decimals/ints stay scaled ints

        centers = np.zeros(K, dtype=np.float64)
        for i in range(N_OCT):
            for sub_i in range(SUB):
                mid = 2.0 ** (OCT_LO + i) * (1.0 + (sub_i + 0.5) / SUB)
                centers[HALF + 1 + i * SUB + sub_i] = mid
                centers[HALF - 1 - i * SUB - sub_i] = -mid
        centers_j = jnp.asarray(centers)

        def bucket_of(v):
            mag = jnp.abs(v.astype(jnp.float64))
            exp = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(mag, 1e-300))),
                           OCT_LO, OCT_HI)
            sub = jnp.clip(jnp.floor((mag / jnp.exp2(exp) - 1.0) * SUB),
                           0, SUB - 1)
            off = ((exp - OCT_LO) * SUB + sub + 1).astype(jnp.int32)
            return jnp.where(v == 0, HALF,
                             jnp.where(v > 0, HALF + off, HALF - off))

        def input_map(args, mask, _k=K):
            b = jnp.where(mask, bucket_of(args[0]), _k)
            return ((b.astype(jnp.int32), jnp.ones_like(b, jnp.float64)),)

        # the percentile fraction is bound at resolve time via params (the
        # planner extracts the literal second argument)
        pct = float(params[0]) if params else 0.5
        if not 0.0 < pct <= 1.0:
            raise ValueError("approx_percentile fraction must be in (0, 1]")

        def final_map(s, _p=pct):
            hist = s[0]                              # (groups, K) f64 counts
            total = jnp.sum(hist, axis=-1)
            target = jnp.ceil(_p * jnp.maximum(total, 1.0))
            cum = jnp.cumsum(hist, axis=-1)
            idx = jnp.argmax(cum >= target[..., None], axis=-1)
            vals = centers_j[idx]
            out = jnp.round(vals).astype(jnp.int64) if int_out else vals
            return out, total == 0

        out_t = t if int_out else DOUBLE
        return AggregateFunction(
            "approx_percentile", out_t,
            [StateColumn(np.dtype(np.float64), SUM, 0.0, width=K)],
            input_map, final_map, [], splittable=False)

    ext = EXTERNAL_AGGREGATES.get(name)
    if ext is not None:
        return ext(arg_types, distinct, params)
    raise NotImplementedError(f"aggregate function {name}({arg_types})")


# pluggable aggregates (Plugin.getFunctions analogue for accumulator
# functions): presto_tpu.functions.* register `(arg_types, distinct, params)
# -> AggregateFunction` resolvers here; sql/analyzer.register_aggregate_name
# makes the parser route the call through aggregation planning
EXTERNAL_AGGREGATES: dict = {}


def register_aggregate(name: str, resolver) -> None:
    EXTERNAL_AGGREGATES[name.lower()] = resolver  # prestocheck: ignore[unbounded-cache] - plugin registry: one entry per registered function, not per request


def _sortable_i64(y):
    """Order-preserving map of a column into int64 (min_by/max_by ordering
    key). Integers/dates/bools widen; floats use the IEEE-754 total-order
    bit trick (negative values flip all bits, positives flip the sign bit,
    then re-biased into signed order)."""
    if jnp.issubdtype(y.dtype, jnp.floating):
        u = jax.lax.bitcast_convert_type(
            y.astype(jnp.float64), jnp.uint64)
        u = jnp.where((u >> jnp.uint64(63)) == 1, ~u,
                      u | jnp.uint64(1) << jnp.uint64(63))
        return jax.lax.bitcast_convert_type(
            u ^ (jnp.uint64(1) << jnp.uint64(63)), jnp.int64)
    return y.astype(jnp.int64)


def _hash_to_u64(a0):
    """Column -> uniform uint64 hash (bitcast floats so 1.25 != 1.75)."""
    if jnp.issubdtype(a0.dtype, jnp.floating):
        x = jax.lax.bitcast_convert_type(
            a0.astype(jnp.float64), jnp.int64).astype(jnp.uint64)
    else:
        x = a0.astype(jnp.int64).astype(jnp.uint64)
    return _sketch_mix(x)


def _sketch_mix(x):
    from .hash_join import _mix64
    return _mix64(x)


@dataclasses.dataclass
class AggregateCall:
    """One aggregate in a GROUP BY: function + input channels + step."""
    function: AggregateFunction
    input_channels: List[int]          # channels in the input page
    mask_channel: Optional[int] = None  # FILTER (WHERE ...) / mark-distinct channel
    # when consuming partial states (FINAL step), channels of the state columns:
    intermediate_channels: Optional[List[int]] = None
    # dictionary for the output block (min/max over varchar passes codes through):
    output_dictionary: Optional[object] = None
