"""The collectives of a kept trace: how long the chips spent in them.

On a v5e trace (looked at by hand, PR 29) an `XLA Ops` event is named by its
HLO instruction's text, `%all_to_all.73 = u32[4,1,2048]{...} all-to-all(
%bitcast.30), channel_id=1, ...`: the instruction's NAME follows the jitted
function's primitive (`all_to_all`), the OPCODE after the result shape is
XLA's (`all-to-all`), and an asynchronous collective is a `-start` / `-done`
pair of events. The opcode is what is matched, so an operand that is only
named after a collective (`fusion(%all-gather.48, ...)`) does not count. On
the CPU, where a trace has no device plane, trace_reduce's stand-in events
are named by the instruction alone (`all-to-all.3`): the rehearsal reads
those, and its seconds mean nothing.
"""
import os
import re

from . import engine_spans, trace_reduce

OPCODES = ("all-to-all", "all-gather", "all-reduce", "collective-permute")
_FORMS = "(" + "|".join(OPCODES) + ")(?:-start|-done)?"
_IN_TEXT = re.compile(r"\s" + _FORMS + r"\(")       # `<shape> all-to-all(`
_BARE = re.compile(r"^%?" + _FORMS + r"(?:\.\d+)?$")   # `all-to-all.3`

_CACHE = {}   # path -> (mtime_ns, trace_reduce.read's result): parsed once


def opcode(name):
    """The collective opcode of an `XLA Ops` event's name, or None."""
    m = _IN_TEXT.search(name) or _BARE.match(name)
    return m.group(1) if m else None


def read(path):
    stamp = os.stat(path).st_mtime_ns
    if _CACHE.get(path, (None,))[0] != stamp:
        _CACHE.clear()
        _CACHE[path] = (stamp, trace_reduce.read(path))
    return _CACHE[path][1]


def seconds_per_query(trace):
    """-> {"total": s, <opcode>: s}: seconds of the collective ops inside the
    benchmark's spans (first start to last end, as trace_reduce.reduce takes
    its window), mean over the chips, per traced query. None where the trace
    holds no span, no device op or no collective: never 0."""
    spans, devices = trace["spans"], trace["devices"]
    if not spans or not devices:
        return None
    w0, w1 = spans[0][0], max(e for _s, e in spans)
    by_op = {}
    for ops in devices.values():
        for s, e, name in ops:
            if e <= w0 or s >= w1:
                continue
            op = opcode(name)
            if op:
                by_op[op] = by_op.get(op, 0) + (min(e, w1) - max(s, w0))
    if not sum(by_op.values()):
        return None
    per = 1e9 * len(devices) * len(spans)
    out = {op: ns / per for op, ns in by_op.items()}
    out["total"] = sum(by_op.values()) / per
    return out


def of_window(window):
    """seconds_per_query of the run's own trace: None for an untraced run
    (an older trace may lie in the directory)."""
    if not window.get("trace"):
        return None
    path = engine_spans.newest()
    return seconds_per_query(read(path)) if path else None


def ici_roofline_pct(live_bytes_per_query, chips, ici_bytes_per_s,
                     collective_s):
    """The routing collectives' share of their roofline, in percent: the
    least time one chip needs to put its share of a query's exchanged bytes
    on the wire (a chip holds 1/W of the rows and sends (W-1)/W of them to
    its peers), over the seconds the chips spent in collectives. None where
    either side is missing or one chip has no peer; over 100 is a fault of
    the count and is reported as it reads."""
    if not live_bytes_per_query or not collective_s or chips < 2:
        return None
    least_s = live_bytes_per_query * (chips - 1) / chips / chips / \
        ici_bytes_per_s
    return 100.0 * least_s / collective_s
