"""ML-in-SQL functions: the presto-ml analogue, TPU-first.

Reference: presto-ml/.../MLFunctions.java (learn_classifier / learn_regressor
aggregates producing a Model value, classify/regress scalars applying it).
The reference trains libsvm models by materializing every row on one node —
the opposite of what a TPU wants. Re-design:

- `learn_linear_regressor(y, x1..xk)` is an ALGEBRAIC aggregate: its state
  is the normal-equation sufficient statistics (XᵀX, Xᵀy flattened into one
  vector state column), accumulated by the same segment-reduce kernels as
  sum() — the chip only ever sums outer products, and finish() solves the
  d×d system on host. Exact (it IS least squares), one pass, any data size.
- `learn_classifier(label, x1..xk)` trains the least-squares classifier on
  ±1 labels (a linear discriminant) with the same statistics.
- Both emit the model as a VARCHAR JSON of coefficients (the reference
  renders models opaquely too; JSON keeps them SELECTable and loggable).
- `regress(model, x1..xk)` / `classify(model, x1..xk)` apply a model
  column: coefficients decode once per DISTINCT model string (dictionary),
  the dot product runs vectorized on device.
- `regr_slope(y, x)` / `regr_intercept(y, x)` / `regr_r2(y, x)`: the
  standard SQL single-feature regression aggregates, scalar states,
  fully splittable across partial/final exchanges.
"""
from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np

from ..block import Dictionary
from ..ops.aggregates import (SUM, AggregateFunction, StateColumn,
                              register_aggregate)
from ..ops.expressions import Call, register_compiler
from ..sql.analyzer import (SemanticError, cast_to, register_aggregate_name,
                            register_scalar_function)
from ..types import (BIGINT, BOOLEAN, DOUBLE, VARCHAR, DecimalType,
                     is_numeric, is_string)


def _to_double(arr, t):
    """Raw column -> float64 value space (decimals are scaled ints)."""
    v = arr.astype(jnp.float64)
    if isinstance(t, DecimalType):
        v = v / (10 ** t.scale)
    return v


# --------------------------------------------------------------------------
# regr_* : standard SQL simple-regression aggregates
# --------------------------------------------------------------------------

def _check_numeric_args(name, arg_types, expect=None):
    if expect is not None and len(arg_types) != expect:
        raise SemanticError(f"{name}() takes {expect} arguments, "
                            f"got {len(arg_types)}")
    for t in arg_types:
        if not (is_numeric(t) or t is BOOLEAN):
            raise SemanticError(
                f"{name}() arguments must be numeric (got {t.name})")


def _regr_resolver(which: str):
    def resolve(arg_types, distinct, params):
        if distinct:
            raise SemanticError(f"{which} DISTINCT is not defined")
        _check_numeric_args(which, arg_types, expect=2)

        tys = list(arg_types)

        def input_map(args, mask, _tys=tys):
            y = jnp.where(mask, _to_double(args[0], _tys[0]), 0.0)
            x = jnp.where(mask, _to_double(args[1], _tys[1]), 0.0)
            n = jnp.where(mask, 1.0, 0.0)
            return (x, y, x * y, x * x, y * y, n)

        def final_map(states):
            sx, sy, sxy, sxx, syy, n = states
            empty = n == 0.0  # SQL: aggregate over no rows is NULL
            n = jnp.maximum(n, 1.0)
            cov = sxy - sx * sy / n
            varx = sxx - sx * sx / n
            vary = syy - sy * sy / n
            slope = cov / jnp.where(varx == 0, 1.0, varx)
            if which == "regr_slope":
                out = slope
            elif which == "regr_intercept":
                out = (sy - slope * sx) / n
            else:  # regr_r2
                denom = jnp.where((varx == 0) | (vary == 0), 1.0,
                                  varx * vary)
                out = jnp.where((varx == 0) | (vary == 0), 0.0,
                                cov * cov / denom)
            return out, empty

        return AggregateFunction(
            which, DOUBLE,
            [StateColumn(np.dtype(np.float64), SUM, 0.0) for _ in range(6)],
            input_map, final_map,
            intermediate_types=[DOUBLE] * 6)
    return resolve


# --------------------------------------------------------------------------
# learn_* : multi-feature linear models via normal equations
# --------------------------------------------------------------------------

def _learn_resolver(classifier: bool):
    def resolve(arg_types, distinct, params):
        if distinct:
            raise SemanticError("learn_* DISTINCT is not defined")
        k = len(arg_types) - 1
        if k < 1:
            raise SemanticError(
                "learn_* takes (label, feature1[, feature2 ...])")
        _check_numeric_args(
            "learn_classifier" if classifier else "learn_linear_regressor",
            arg_types)
        d = k + 1                      # +1 intercept feature
        width = d * d + d              # XᵀX flattened + Xᵀy

        tys = list(arg_types)

        def input_map(args, mask, _d=d, _tys=tys):
            y = jnp.where(mask, _to_double(args[0], _tys[0]), 0.0)
            if classifier:
                y = jnp.where(mask, jnp.where(y > 0, 1.0, -1.0), 0.0)
            feats = [jnp.where(mask, 1.0, 0.0)]       # intercept column
            for a, t in zip(args[1:], _tys[1:]):
                feats.append(jnp.where(mask, _to_double(a, t), 0.0))
            x = jnp.stack(feats, axis=-1)              # (rows, d)
            xtx = x[:, :, None] * x[:, None, :]        # (rows, d, d)
            xty = x * y[:, None]                       # (rows, d)
            return (jnp.concatenate(
                [xtx.reshape(x.shape[0], -1), xty], axis=-1),)

        # plan-visible output dictionary, filled with the model JSON at
        # finish (resolve-time allocation: downstream operators' layouts
        # reference this exact object — see AggregateFunction.output_dict)
        model_dict = Dictionary([])

        def final_map(states, _d=d, _dict=model_dict):
            flat = np.asarray(states[0], dtype=np.float64)
            flat = flat.reshape(-1, _d * _d + _d)
            models = []
            # xtx[0,0] accumulates the intercept column of ones = the
            # group's contributing-row count; 0 rows -> NULL model (SQL
            # empty-group aggregate contract), not an all-zero model
            empty = flat[:, 0] == 0.0
            for row in flat:
                xtx = row[:_d * _d].reshape(_d, _d)
                xty = row[_d * _d:]
                # ridge epsilon keeps singular systems solvable
                coef = np.linalg.solve(
                    xtx + 1e-9 * np.eye(_d), xty)
                models.append(json.dumps({
                    "type": "classifier" if classifier else "regressor",
                    "intercept": coef[0],
                    "coefficients": list(coef[1:])}))
            codes = np.asarray(_dict.extend(models), dtype=np.int64)
            return codes, (empty if empty.any() else None)

        return AggregateFunction(
            "learn_classifier" if classifier else "learn_linear_regressor",
            VARCHAR,
            [StateColumn(np.dtype(np.float64), SUM, 0.0, width=width)],
            input_map, final_map,
            splittable=False, output_dict=model_dict, host_final_map=True)
    return resolve


# --------------------------------------------------------------------------
# regress / classify : apply a model column
# --------------------------------------------------------------------------

def _t_apply_model(name, args):
    if len(args) < 2:
        raise SemanticError(f"{name}(model, feature1[, ...])")
    if not is_string(args[0].type):
        raise SemanticError(f"{name}() first argument must be a model "
                            "(varchar from learn_*)")
    feats = tuple(cast_to(a, DOUBLE) for a in args[1:])
    out = BIGINT if name == "classify" else DOUBLE
    return Call(out, name, (args[0],) + feats)


def _c_apply_model(classify: bool):
    def compile_(compiler, expr):
        d = compiler._dictionary_of(expr.args[0])
        if d is None or not hasattr(d, "values"):
            raise NotImplementedError(
                "model argument needs a materialized dictionary column "
                "(the learn_* output)")
        fmodel = compiler._compile(expr.args[0])[0]
        ffeats = [compiler._compile(a)[0] for a in expr.args[1:]]
        k = len(ffeats)

        def _coef_table():
            # TRACE-time read: learn_*'s output dictionary fills when the
            # aggregation finishes, which precedes the first page through
            # this (post-join) projection; the kernel cache keys on the
            # dictionary's (token, len), so growth forces a re-trace
            coefs = np.zeros((max(len(d.values), 1), k + 1))
            for i, v in enumerate(d.values):
                try:
                    m = json.loads(str(v))
                except (ValueError, TypeError) as e:
                    raise ValueError(
                        f"{'classify' if classify else 'regress'}(): model "
                        f"column value {str(v)[:40]!r} is not a learn_* "
                        f"model JSON") from e
                got = list(m.get("coefficients", []))[:k]
                coefs[i, 0] = float(m.get("intercept", 0.0))
                coefs[i, 1:1 + len(got)] = got
            return jnp.asarray(coefs)

        def fn(datas, nulls):
            code, n = fmodel(datas, nulls)
            _t = _coef_table()
            _hi = max(len(d.values) - 1, 0)
            c = _t[jnp.clip(code.astype(jnp.int32), 0, _hi)]  # (rows, k+1)
            acc = c[:, 0]
            for j, f in enumerate(ffeats):
                v, nv = f(datas, nulls)
                acc = acc + c[:, j + 1] * v.astype(jnp.float64)
                n = nv if n is None else (n if nv is None else n | nv)
            if classify:
                return (acc > 0).astype(jnp.int64), n
            return acc, n
        return fn, None
    return compile_


# --------------------------------------------------------------------------
# registration
# --------------------------------------------------------------------------

def _regr_output_typer(which):
    def typer(arg_types):
        _check_numeric_args(which, arg_types, expect=2)  # fail at ANALYSIS
        return DOUBLE
    return typer


def _learn_output_typer(which):
    def typer(arg_types):
        if len(arg_types) < 2:
            raise SemanticError(f"{which}(label, feature1[, ...])")
        _check_numeric_args(which, arg_types)
        return VARCHAR
    return typer


for _w in ("regr_slope", "regr_intercept", "regr_r2"):
    register_aggregate(_w, _regr_resolver(_w))
    register_aggregate_name(_w, _regr_output_typer(_w))

register_aggregate("learn_linear_regressor", _learn_resolver(False))
register_aggregate("learn_regressor", _learn_resolver(False))
register_aggregate("learn_classifier", _learn_resolver(True))
for _n in ("learn_linear_regressor", "learn_regressor", "learn_classifier"):
    register_aggregate_name(_n, _learn_output_typer(_n))

register_scalar_function("regress", _t_apply_model)
register_scalar_function("classify", _t_apply_model)
register_compiler("regress", _c_apply_model(False))
register_compiler("classify", _c_apply_model(True))
