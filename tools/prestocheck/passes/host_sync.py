"""host-sync: device synchronization inside the driver's hot page loop.

``Operator.add_input`` / ``Operator.get_output`` run once per page on the
driver's hottest path (exec/driver.py `_process_once`). A ``np.asarray``,
``.item()``, ``jax.device_get`` or ``.block_until_ready()`` there forces a
device->host round-trip per page, and it serializes XLA's async dispatch
pipeline (the whole reason page hand-offs are device-array handles). The fused-segment
work (ops/fused_segment.py) exists to REMOVE per-page dispatch overhead;
this pass keeps new per-page syncs from sneaking back in.

Detection: calls to ``np.asarray`` / ``numpy.asarray`` / ``jax.device_get``
or ``.item()`` / ``.block_until_ready()`` attribute calls, anywhere inside a
method named ``add_input`` or ``get_output`` of a class that looks like a
physical operator (its name or a base class name contains ``Operator``).
Helper methods called FROM add_input are out of scope (no interprocedural
analysis) — the pass catches the direct pattern, reviews catch the rest.

Pallas kernel bodies (functions handed to ``pl.pallas_call``) are checked
too: a ``np.asarray`` / ``jax.device_get`` / ``.item()`` /
``.block_until_ready()`` inside a kernel is never right — the body traces
once into the device program, so a host sync there either fails outright on
a Ref or silently freezes a trace-time value into the kernel.

Known-legitimate syncs (an adaptive decision made once per stream, a
cardinality the host must know to size output) carry an inline
``# prestocheck: ignore[host-sync]`` with a comment saying why.
"""
from __future__ import annotations

import ast

from ..core import Finding, Module, Pass, dotted_name, register

_SYNC_CALLS = {"np.asarray": "np.asarray",
               "numpy.asarray": "numpy.asarray",
               "jax.device_get": "jax.device_get"}
_SYNC_ATTRS = {"item", "block_until_ready"}
_HOT_METHODS = ("add_input", "get_output")


def _is_operator_class(cls: ast.ClassDef) -> bool:
    if "Operator" in cls.name:
        return True
    for base in cls.bases:
        name = dotted_name(base) or ""
        if "Operator" in name:
            return True
    return False


@register
class HostSyncPass(Pass):
    id = "host-sync"
    description = ("device->host sync (np.asarray / .item() / device_get / "
                   "block_until_ready) inside Operator.add_input/get_output "
                   "— one round-trip per page on the driver hot path")

    def check_module(self, module: Module):
        for cls in ast.walk(module.tree):
            if not isinstance(cls, ast.ClassDef) or \
                    not _is_operator_class(cls):
                continue
            for fn in cls.body:
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)) or \
                        fn.name not in _HOT_METHODS:
                    continue
                yield from self._check_method(module, cls, fn)
        yield from self._check_pallas_kernels(module)

    def _check_pallas_kernels(self, module: Module):
        """Kernel bodies handed to ``pl.pallas_call`` are device programs:
        any host sync inside one is a bug, not a perf smell."""
        fns = {}
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fns.setdefault(node.name, []).append(node)
        seen = set()
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            name = dotted_name(node.func)
            if name is None or name.split(".")[-1] != "pallas_call":
                continue
            target = node.args[0]
            tname = target.id if isinstance(target, ast.Name) else \
                target.attr if isinstance(target, ast.Attribute) else None
            kernels = list(fns.get(tname or "", []))
            if isinstance(target, ast.Call):
                # factory pattern: pl.pallas_call(_make_body(...), ...) —
                # the kernel is a closure defined inside the factory
                fname = dotted_name(target.func)
                fname = fname.split(".")[-1] if fname else None
                for factory in fns.get(fname or "", []):
                    kernels.extend(
                        stmt for stmt in factory.body
                        if isinstance(stmt, (ast.FunctionDef,
                                             ast.AsyncFunctionDef)))
            for fn in kernels:
                if id(fn) in seen:
                    continue
                seen.add(id(fn))
                for what, sub in self._sync_sites(fn):
                    yield Finding(
                        module.path, sub.lineno, sub.col_offset, self.id,
                        f"{what} inside pallas kernel `{fn.name}` — the "
                        "body traces once into the device program; a host "
                        "sync there fails on a Ref or freezes a trace-time "
                        "value into the kernel")

    @staticmethod
    def _sync_sites(fn):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name in _SYNC_CALLS:
                yield f"{_SYNC_CALLS[name]}(...)", node
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _SYNC_ATTRS and not node.args:
                yield f".{node.func.attr}()", node

    def _check_method(self, module: Module, cls: ast.ClassDef, fn):
        for what, node in self._sync_sites(fn):
            yield Finding(
                module.path, node.lineno, node.col_offset, self.id,
                f"{what} in {cls.name}.{fn.name} — a device->host sync "
                "per page on the driver hot path; keep pages as device "
                "handles (or justify with an inline suppression)")
