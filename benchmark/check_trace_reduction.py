"""Check the trace reduction against the small recorded trace. Runs on the CPU.

    python3 benchmark/check_trace_reduction.py            # exit 0 when the numbers agree
    python3 benchmark/check_trace_reduction.py --record <file.xplane.pb>

`--record` is for the PR that replaces the fixture: it copies nothing, it
writes fixtures/expected.json from what the reduction gives for that file
today, to be read by a person before it is committed.
"""
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import trace_reduce  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
EXPECTED = os.path.join(FIXTURES, "expected.json")


def differences():
    """What the reduction gives for the recorded trace against the recorded
    numbers: [] when they agree."""
    with open(EXPECTED) as f:
        expected = json.load(f)
    got = trace_reduce.reduce(trace_reduce.read(
        os.path.join(FIXTURES, expected["file"])))
    bad = []
    for key in ("window_s", "busy_s", "queries", "programs"):
        if not math.isclose(got[key], expected[key], rel_tol=1e-9):
            bad.append(f"{key}: {got[key]!r} != {expected[key]!r}")
    for key in ("device_ops", "idle_gaps"):
        for (n1, s1), (n2, s2) in zip(got[key], expected[key]):
            if n1 != n2 or not math.isclose(s1, s2, rel_tol=1e-9):
                bad.append(f"{key}: {n1} {s1!r} != {n2} {s2!r}")
        if len(got[key]) != len(expected[key]):
            bad.append(f"{key}: {len(got[key])} entries != {len(expected[key])}")
    return bad


def main():
    if len(sys.argv) == 3 and sys.argv[1] == "--record":
        got = trace_reduce.reduce(trace_reduce.read(sys.argv[2]))
        got["file"] = os.path.basename(sys.argv[2])
        with open(EXPECTED, "w") as f:
            json.dump(got, f, indent=1)
        print(f"wrote {EXPECTED}")
        return
    bad = differences()
    for line in bad:
        print(line, file=sys.stderr)
    print("trace reduction:", "DIFFERS" if bad else "agrees with the fixture")
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
