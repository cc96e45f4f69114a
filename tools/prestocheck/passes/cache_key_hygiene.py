"""cache-key-hygiene: every compiled callable through one disciplined funnel.

The engine's defense against recompile storms is structural: ALL jitted /
``pl.pallas_call`` artifacts are built under
``utils/kernel_cache.get_or_build`` (single-flight, LRU, hit/miss
counters), keyed by canonical fingerprints. Two ways to break it:

* **jit built outside the funnel** — a ``jax.jit(...)`` /
  ``pl.pallas_call(...)`` created inside a function body acquires a fresh
  function identity per call, so jax's trace cache can never hit: every
  invocation is a silent full recompile (~1s on the CPU backend, seconds
  to a minute in the TPU compiler). Module-level creations (decorators, module constants) compile
  once per process and are fine; so are creations reachable from a
  ``get_or_build`` / ``get_or_install`` builder or an ``lru_cache``-
  memoized factory — those identities are cached by construction.
* **undisciplined key** — a cache key containing an f-string, a computed
  ``float(...)``, an unhashable display (list/dict/set), an ``id(...)``
  (object identity: unbounded, and meaningless after GC reuse), a clock
  read, or a raw ``len(...)`` / ``.shape`` with no pow2/clamp
  canonicalization. The last one is the key-space-growth estimate the PR-10
  exchange bug demonstrated: a key that tracks row count compiles per
  pow2-volume instead of per shape bucket — a finding, not a statistic.

Key expressions are resolved one level deep: a key bound to a local name
is traced to its assignment, and a key built by a module-local helper
(``_builder_key(...)``) is audited at the helper's return expressions.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set

from ..core import Finding, Module, Pass, dotted_name, register
from .retrace_risk import _is_canonicalized, _last_name
from .tracer_safety import _is_jax_jit, _is_pallas_call

_FUNNEL = {"get_or_build", "get_or_install"}
_CLOCK_CALLS = {"time", "monotonic", "perf_counter", "time_ns", "uuid4",
                "uuid1", "random", "randint"}


def _is_funnel_call(node: ast.Call) -> bool:
    last = _last_name(node.func)
    return last in _FUNNEL


def _is_lru_decorated(fn: ast.AST) -> bool:
    for deco in fn.decorator_list:
        target = deco.func if isinstance(deco, ast.Call) else deco
        if (_last_name(target) or "").startswith("lru_cache"):
            return True
    return False


def _creates_jit(node: ast.Call) -> Optional[str]:
    """'jit' / 'pallas' if `node` builds a compiled callable."""
    if _is_jax_jit(node.func):
        return "jax.jit"
    if _is_pallas_call(node.func):
        return "pl.pallas_call"
    # functools.partial(jax.jit, ...)(f)
    if isinstance(node.func, ast.Call) and node.func.args \
            and _is_jax_jit(node.func.args[0]):
        return "jax.jit"
    return None


@register
class CacheKeyHygienePass(Pass):
    id = "cache-key-hygiene"
    description = ("jit/pallas callable built outside utils/kernel_cache "
                   "(fresh identity = recompile per call), or a cache key "
                   "with f-string/float()/unhashable/id()/clock components "
                   "or an uncanonicalized len/.shape (key space grows with "
                   "row count)")

    def check_module(self, module: Module):
        tree = module.tree

        # ---------------------------------------------- lexical parent map
        parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node

        def enclosing_functions(node: ast.AST) -> List[ast.AST]:
            out, cur = [], parents.get(node)
            while cur is not None:
                if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    out.append(cur)
                cur = parents.get(cur)
            return out

        def inside_funnel_args(node: ast.AST) -> bool:
            cur, child = parents.get(node), node
            while cur is not None:
                if isinstance(cur, ast.Call) and _is_funnel_call(cur) \
                        and child is not cur.func:
                    return True
                child, cur = cur, parents.get(cur)
            return False

        # ----------------------------------- funnel-safe function closure
        fns: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fns.setdefault(node.name, []).append(node)

        safe: Set[str] = set()
        work: List[str] = []

        def mark(name: Optional[str]) -> None:
            if name and name in fns and name not in safe:
                safe.add(name)
                work.append(name)

        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and _is_funnel_call(node):
                # every name referenced in the funnel's arguments (builder
                # fns, names inside make-lambdas) is cached-by-construction
                for arg in list(node.args) + [kw.value
                                              for kw in node.keywords]:
                    for sub in ast.walk(arg):
                        mark(_last_name(sub) if isinstance(
                            sub, (ast.Name, ast.Attribute)) else None)
            elif isinstance(node, ast.Call) and _creates_jit(node) \
                    and node.args and not enclosing_functions(node):
                # module-level jit wrap: the wrapped fn's identity is pinned
                # for the process, so jit/pallas traced inside it is keyed
                # by the stable outer callable
                mark(_last_name(node.args[0]))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and _is_lru_decorated(node):
                mark(node.name)
        while work:
            name = work.pop()
            for fn in fns.get(name, []):
                for sub in ast.walk(fn):
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        mark(sub.name)  # nested defs share the cached scope
                    elif isinstance(sub, ast.Call):
                        mark(_last_name(sub.func))

        # --------------------------------------- K1: out-of-funnel builds
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            kind = _creates_jit(node)
            if kind is None:
                continue
            encl = enclosing_functions(node)
            if not encl:
                continue  # module scope (incl. decorators): one per process
            if inside_funnel_args(node):
                continue  # the make-lambda of a get_or_build call
            if any(f.name in safe or _is_lru_decorated(f) for f in encl):
                continue
            yield Finding(
                module.path, node.lineno, node.col_offset, self.id,
                f"{kind} callable built inside `{encl[0].name}` outside "
                "utils/kernel_cache.get_or_build — a fresh function "
                "identity per call means jax's trace cache never hits and "
                "every invocation recompiles; route it through the kernel "
                "cache (or memoize the builder)")

        # ------------------------------------------- K2/K3: key hygiene
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and _is_funnel_call(node)
                    and node.args):
                continue
            key_expr = node.args[0]
            for expr in self._resolve_key(key_expr, node, parents, fns):
                yield from self._audit_key(module, expr)

    # ------------------------------------------------------------ key audit

    def _resolve_key(self, key_expr: ast.AST, call: ast.Call,
                     parents: Dict[ast.AST, ast.AST],
                     fns: Dict[str, List[ast.AST]]) -> List[ast.AST]:
        """The expressions that actually make up the key: the literal
        expression, plus one level through a local name binding or a
        module-local helper's returns."""
        if isinstance(key_expr, ast.Name):
            # nearest enclosing function's assignments to that name
            cur = parents.get(call)
            while cur is not None and not isinstance(
                    cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cur = parents.get(cur)
            if cur is None:
                return [key_expr]
            values = [a.value for a in ast.walk(cur)
                      if isinstance(a, ast.Assign)
                      and any(isinstance(t, ast.Name) and t.id == key_expr.id
                              for t in a.targets)]
            return values or [key_expr]
        if isinstance(key_expr, ast.Call):
            helper = _last_name(key_expr.func)
            returns = [r.value for fn in fns.get(helper or "", [])
                       for r in ast.walk(fn)
                       if isinstance(r, ast.Return) and r.value is not None]
            return [key_expr] + returns
        return [key_expr]

    def _audit_key(self, module: Module, expr: ast.AST) -> Iterable[Finding]:
        def emit(node: ast.AST, what: str, why: str):
            yield Finding(
                module.path, node.lineno, node.col_offset, self.id,
                f"cache key contains {what} — {why}")

        # canonicalizers wrap their operand, so the exemption is judged on
        # the whole key expression (a _pow2/clamp call anywhere vouches for
        # the derived components it wraps)
        canonicalized = _is_canonicalized(expr)
        for sub in ast.walk(expr):
            if isinstance(sub, ast.JoinedStr):
                yield from emit(sub, "an f-string component",
                                "formatting hides floats/reprs and the key "
                                "space is whatever the format can produce")
            elif isinstance(sub, (ast.Dict, ast.DictComp, ast.List,
                                  ast.ListComp, ast.Set, ast.SetComp)):
                yield from emit(sub, "an unhashable display (list/dict/set)",
                                "the cache lookup raises TypeError; use a "
                                "tuple fingerprint")
            elif isinstance(sub, ast.Call):
                callee = dotted_name(sub.func)
                last = _last_name(sub.func)
                if callee == "float":
                    yield from emit(sub, "a computed float()",
                                    "a continuous domain: effectively every "
                                    "call is a distinct key")
                elif callee == "id":
                    yield from emit(sub, "id(...) (object identity)",
                                    "unbounded cardinality, and GC address "
                                    "reuse aliases dead keys to live ones")
                elif last in _CLOCK_CALLS and callee not in ("dict_key",):
                    yield from emit(sub, f"a `{callee}()` read",
                                    "clock/uuid/random components make "
                                    "every key distinct — nothing ever "
                                    "hits")
                elif callee == "len" and not canonicalized:
                    yield from emit(sub, "a raw len(...)",
                                    "the key space grows with row count; "
                                    "pow2/clamp-canonicalize it so it "
                                    "compiles per bucket, not per length")
            elif isinstance(sub, ast.Attribute) and sub.attr == "shape" \
                    and not canonicalized:
                yield from emit(sub, "a raw .shape",
                                "the key space grows with the data's "
                                "shape; pow2/clamp-canonicalize it so it "
                                "compiles per bucket, not per extent")
