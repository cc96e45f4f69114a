"""CPU rehearsal: every cell of BENCHMARK.json (or --workload) end to end at
schema `tiny`, both with and without the trace, on whatever device JAX has.

It proves paths, arguments and control flow before chip time is spent (on-chip
guide, section 2). It is NOT a run of the benchmark: it prints no result line
on standard output, only a summary on standard error, and its times mean
nothing. Exit 0 when every rehearsed run compared correct.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.run import run_cell  # noqa: E402

TINY = {"schema": "tiny", "scale_factor": 0.01}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", action="append")
    ap.add_argument("--seed", type=int, default=2**31 + 12345)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = args.workload or [w["name"] for w in json.load(f)["workloads"]]
    ok = True
    for name in names:
        for trace in (False, True):
            r = run_cell(name, args.seed, args.seconds, trace,
                         need_chips=False, scale=TINY)
            ok = ok and r["correct"]
            print(f"rehearsal {name} trace={int(trace)} (CPU, tiny: times "
                  f"mean nothing): {json.dumps(r)}", file=sys.stderr)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
