"""Global compiled-kernel cache.

Analogue of the reference's cached expression compiler (sql/gen/
PageFunctionCompiler.java:97 — a guava cache keyed on the RowExpression, so
repeated queries reuse generated bytecode). Here the cached artifact is a
jitted closure; the key is a canonical fingerprint of everything the closure
compiled in: the expression IR, the input layout (types + dictionary
identity/version — string predicates resolve against dictionary CONTENTS at
compile time), and operator-static config.

Without this cache every query plan builds fresh `jax.jit` closures and jit's
function-identity keying forces a full XLA recompile per query (~1s of host
overhead on a warm TPC-H Q3 — measured round 4).
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Dict, Tuple

_LOCK = threading.Lock()
_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_MAX_ENTRIES = 2048  # the reference's expression-cache-size analogue
# single-flight build registry: key -> (done event, builder thread ident).
# Bounded by the number of IN-PROGRESS builds (entries are removed in the
# builder's finally), not by key history.
_BUILDING: Dict[tuple, Tuple[threading.Event, int]] = {}


def get_or_install(key: tuple, make: Callable[[], object]):
    """Return the cached kernel for `key`, building it with `make()` on miss.

    `make` runs outside the lock (it may trace/compile); concurrent misses
    on the same key are SINGLE-FLIGHT: one caller builds, the rest wait on
    the build and share the installed kernel. Under concurrent query load a
    hot workload's fused segments therefore compile once per process — a
    thundering herd of identical queries cannot pay N identical XLA
    compiles (seconds to a minute each from the v5e compiler — PERF.md).
    """
    return get_or_build(key, make)[0]


def get_or_build(key: tuple, make: Callable[[], object]):
    """-> (kernel, built_now). Like :func:`get_or_install`, but also reports
    whether `make` actually ran — callers feeding per-query compile counters
    (the exchange planes' `collective_compiles`) need race-free attribution
    that a global cache-stats diff cannot give. Waiters of a deduplicated
    build report False (their query dispatched a shared kernel, compiled by
    someone else).

    Callers must access this through the module attribute
    (``kernel_cache.get_or_build``), never bind it via
    ``from kernel_cache import get_or_build``: the recompile sanitizer
    (utils/compilesan.py) instruments every engine compile by patching the
    module attribute, and a direct-name binding captured at import time
    would silently escape the census (get_or_install resolves through the
    module global for the same reason)."""
    from .metrics import METRICS

    while True:
        wait_ev = None
        with _LOCK:
            fn = _CACHE.get(key)
            if fn is not None:
                _CACHE.move_to_end(key)
            else:
                entry = _BUILDING.get(key)
                if entry is None:
                    _BUILDING[key] = (threading.Event(),
                                      threading.get_ident())
                elif entry[1] != threading.get_ident():
                    wait_ev = entry[0]
                else:
                    # re-entrant miss (make() of an outer build reached this
                    # key again on the SAME thread): build inline — waiting
                    # on our own event would deadlock
                    _BUILDING[key] = entry  # no-op; fall through to build
        # count outside the lock: METRICS has its own lock, and holding both
        # nests kernel_cache._LOCK -> MetricsRegistry._lock for no benefit
        # (prestocheck's lock-order graph flagged the edge)
        if fn is not None:
            METRICS.count("kernel_cache.hits")
            return fn, False
        if wait_ev is not None:
            # another thread is compiling this exact kernel right now: wait
            # for it instead of compiling a duplicate, then re-check (the
            # builder may have FAILED — in that case the loop retries and
            # this caller becomes the builder)
            METRICS.count("kernel_cache.build_waits")
            wait_ev.wait()
            continue
        break
    METRICS.count("kernel_cache.misses")
    try:
        from . import trace
        # flight-recorder compile marker: jit closures build lazily, so
        # the XLA compile itself lands inside whatever span wraps the
        # first dispatch — this span marks WHICH kernel was (re)built
        program = str(key[0]) if key else "?"
        with trace.span(trace.KERNEL, f"build {program}", program=program):
            fn = make()
        with _LOCK:
            cur = _CACHE.get(key)
            if cur is not None:
                return cur, True
            _CACHE[key] = fn
            while len(_CACHE) > _MAX_ENTRIES:
                _CACHE.popitem(last=False)
        return fn, True
    finally:
        with _LOCK:
            entry = _BUILDING.pop(key, None)
        if entry is not None:
            entry[0].set()  # wake waiters (they re-check the cache)


def clear() -> None:
    with _LOCK:
        _CACHE.clear()


def stats() -> dict:
    with _LOCK:
        return {"entries": len(_CACHE), "building": len(_BUILDING)}


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def dict_key(d) -> tuple:
    """Cache identity of a Dictionary: (token, size).

    Size rides along because INSERT's extend() appends entries — a predicate
    compiled to const-false for an absent value must recompile once the value
    exists. Codes of existing entries never change, so (token, len) versions
    the contents exactly.
    """
    if d is None:
        return None
    return (d.token(), len(d))


def layout_key(types, dictionaries) -> tuple:
    return tuple((t.name, dict_key(d)) for t, d in zip(types, dictionaries))


def expr_key(e) -> tuple:
    """Canonical fingerprint of a RowExpression tree (type-aware, recursive)."""
    from ..ops import expressions as ex

    if e is None:
        return ("none",)
    t = e.type.name
    if isinstance(e, ex.InputRef):
        return ("in", e.channel, t)
    if isinstance(e, ex.SymbolRef):
        return ("sym", e.name, t)
    if isinstance(e, ex.Constant):
        v = e.value
        try:
            hash(v)
        except TypeError:
            v = repr(v)
        return ("const", t, v)
    if isinstance(e, ex.Call):
        return ("call", e.name, t) + tuple(expr_key(a) for a in e.args)
    if isinstance(e, ex.SpecialForm):
        return ("sf", e.form, t) + tuple(expr_key(a) for a in e.args)
    return ("other", repr(e))


def agg_call_key(call) -> tuple:
    """Fingerprint of one AggregateCall's kernel-relevant config."""
    return (call.function.fingerprint,
            tuple(call.input_channels),
            call.mask_channel,
            tuple(call.intermediate_channels or ()))
