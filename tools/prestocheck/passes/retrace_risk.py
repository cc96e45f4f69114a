"""retrace-risk: data-dependent values flowing into jit trace keys.

A ``jax.jit`` callable retraces (and pays a full XLA compile) whenever a
``static_argnames`` / ``static_argnums`` argument takes a value it has not
seen before. Static args whose value domain is BOUNDED (operator config,
pow2-bucketed capacities) compile a handful of kernels, ever; a static arg
derived from *data* compiles per distinct value — per page, per chunk, per
row count. On the chip each such miss costs seconds — a minute where the
program sorts (PERF.md) — in the TPU compiler (PR 10 fixed exactly this
class by hand: per-pow2-volume exchange recompiles, eager throwaway
dispatches).

The pass resolves the module's jitted callables — decorated defs,
``jax.jit(f, ...)`` / ``functools.partial(jax.jit, ...)`` bindings
(including results cached through ``kernel_cache`` and stored on ``self``)
— together with their static parameter names, then audits every CALL SITE
that feeds those static parameters:

* **data-derived static arg**: the argument expression reads ``len(...)``,
  ``.shape`` / ``.size`` / ``.nbytes``, ``.item()``, or lifts a scalar off
  an array via ``int(...)`` / ``float(...)`` — with NO canonicalization
  (``_pow2`` / ``clamp_capacity`` / bucket / round_up style call) anywhere
  in the expression. The trace-key cardinality tracks the data.
* **unbounded static domain**: the argument is an f-string or a float-
  producing expression (``float(...)``, true division) — a continuous
  domain, so effectively every call is a cache miss.

Canonicalized derivations (``cap=_pow2(total)``,
``n=clamp_capacity(rows, target)``) are exactly the discipline the engine's
hot paths follow and are exempt.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..core import Finding, Module, Pass, dotted_name, register
from .tracer_safety import (_is_jax_jit, _jit_call_static, _param_names,
                            _static_params)

# a call whose name matches this anywhere in the argument expression is a
# shape canonicalizer: the derived value collapses into a bounded bucket
_CANON_RE = re.compile(
    r"(pow2|pow_2|next_pow|clamp|bucket|round_up|roundup|quantiz)",
    re.IGNORECASE)

_DATA_ATTRS = {"shape", "size", "nbytes", "ndim"}


def _last_name(node: ast.AST) -> Optional[str]:
    name = dotted_name(node)
    return name.split(".")[-1] if name else None


def _is_canonicalized(expr: ast.AST) -> bool:
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Call):
            fn = _last_name(sub.func)
            if fn and _CANON_RE.search(fn):
                return True
    return False


def _data_derivation(expr: ast.AST) -> Optional[str]:
    """Describe the first data-dependent derivation in `expr`, or None."""
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Call):
            callee = dotted_name(sub.func)
            if callee == "len":
                return "len(...)"
            if isinstance(sub.func, ast.Attribute) and sub.func.attr == "item":
                return ".item()"
            if callee in ("int", "float") and sub.args and any(
                    isinstance(s, (ast.Attribute, ast.Subscript))
                    for s in ast.walk(sub.args[0])):
                return f"{callee}(...) on an array expression"
        elif isinstance(sub, ast.Attribute) and sub.attr in _DATA_ATTRS:
            return f".{sub.attr}"
    return None


def _unbounded_domain(expr: ast.AST) -> Optional[str]:
    """Describe a continuous / unbounded value domain in `expr`, or None."""
    for sub in ast.walk(expr):
        if isinstance(sub, ast.JoinedStr):
            return "f-string"
        if isinstance(sub, ast.Call) and dotted_name(sub.func) == "float":
            return "float(...)"
        if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Div):
            return "true division (float result)"
    return None


def _jit_creation(node: ast.Call) -> Optional[Tuple[Set, Optional[ast.AST]]]:
    """If `node` creates a jitted callable, return (static_spec, wrapped_fn
    node or None). Covers ``jax.jit(f, ...)`` and
    ``functools.partial(jax.jit, ...)(f)``."""
    spec = _jit_call_static(node)
    if spec is not None and _is_jax_jit(node.func):
        return spec, (node.args[0] if node.args else None)
    # functools.partial(jax.jit, static_...)(f): outer call of a partial
    if isinstance(node.func, ast.Call):
        inner_spec = _jit_call_static(node.func)
        if inner_spec is not None:
            return inner_spec, (node.args[0] if node.args else None)
    return None


def _binding_names(assign_targets: List[ast.AST]) -> Iterable[str]:
    for t in assign_targets:
        last = _last_name(t)
        if last:
            yield last


@register
class RetraceRiskPass(Pass):
    id = "retrace-risk"
    description = ("data-dependent value (len/.shape/.item()/int-of-array, "
                   "f-string, float) feeding a jit static arg without pow2/"
                   "clamp canonicalization — the trace key tracks the data "
                   "and every page recompiles")

    def check_module(self, module: Module):
        tree = module.tree
        # ---- module function table (for static_argnums -> names)
        defs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)

        def resolve_static_names(spec: Set,
                                 wrapped: Optional[ast.AST]) -> Set[str]:
            names = {str(s) for s in spec if not isinstance(s, int)}
            nums = [s for s in spec if isinstance(s, int)]
            if nums:
                target = _last_name(wrapped) if wrapped is not None else None
                for d in defs.get(target or "", []):
                    names |= _static_params(d, set(nums))
            return names

        # ---- jitted-callable bindings: bound name -> static param names
        jitted: Dict[str, Set[str]] = {}
        for node in ast.walk(tree):
            # decorated defs
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    if isinstance(deco, ast.Call):
                        spec = _jit_call_static(deco)
                        if spec:
                            jitted.setdefault(node.name, set()).update(
                                _static_params(node, spec))
                continue
            if not isinstance(node, ast.Assign):
                continue
            # assignments whose value CONTAINS a jit creation with a static
            # spec (direct, or buried in a kernel_cache make lambda) bind
            # the compiled callable to the target name
            for sub in ast.walk(node.value):
                if not isinstance(sub, ast.Call):
                    continue
                made = _jit_creation(sub)
                if made is None or not made[0]:
                    continue
                statics = resolve_static_names(*made)
                if not statics:
                    continue
                for name in _binding_names(node.targets):
                    jitted.setdefault(name, set()).update(statics)
        if not jitted:
            return

        # ---- audit call sites of the jitted names
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _last_name(node.func)
            statics = jitted.get(callee or "")
            if not statics:
                continue
            if _jit_creation(node) is not None:
                continue  # the creation site itself, not a dispatch
            for kw in node.keywords:
                if kw.arg in statics:
                    yield from self._audit(module, callee, kw.arg, kw.value)

    def _audit(self, module: Module, callee: str, param: str,
               expr: ast.AST) -> Iterable[Finding]:
        if not _is_canonicalized(expr):
            derived = _data_derivation(expr)
            if derived:
                yield Finding(
                    module.path, expr.lineno, expr.col_offset, self.id,
                    f"static arg `{param}` of jitted `{callee}` is derived "
                    f"from data via {derived} with no pow2/clamp "
                    "canonicalization — the trace key tracks the data and "
                    "each new value is a full XLA recompile")
                return
        unbounded = _unbounded_domain(expr)
        if unbounded:
            yield Finding(
                module.path, expr.lineno, expr.col_offset, self.id,
                f"static arg `{param}` of jitted `{callee}` takes a value "
                f"from an unbounded domain ({unbounded}) — effectively "
                "every call is a trace-cache miss")
