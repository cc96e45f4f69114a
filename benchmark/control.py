"""The control of `correct`: the reference in the program's place, computed in
float32 (the nearest precision below the exact 64-bit decimals the
configuration states, and the one a chip that emulates 64-bit lanes tempts a
later PR with). It has to come out as NOT correct on every seed.

    python3 benchmark/control.py --workload q6_sf1 --seeds 11 12 13 [--scale tiny]

Prints one JSON line per seed with the numbers compared, then a verdict line;
exit 0 only when the control failed on every seed and the exact reference,
compared with itself, passed. Needs no chip: it is numpy at the cell's size.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, compare  # noqa: E402
from benchmark.harness.traffic import Plan  # noqa: E402
from benchmark.rehearse import TINY  # noqa: E402


def control_numbers(cell, seed, sf, rel_tol):
    """-> (numbers of the control, numbers of the exact reference vs itself)"""
    plan = Plan(cell.traffic, cell.queries, seed)
    exact = {q: cell.queries[q].reference(sf, plan.params[q])
             for q in plan.sql}
    lower = [(q, cell.queries[q].reference(sf, plan.params[q], lower=True))
             for q in plan.sql]
    control, _ = compare.judge(lower, exact, rel_tol)
    sound, _ = compare.judge(list(exact.items()), exact, rel_tol)
    return plan.params, control, sound


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--scale", choices=("own", "tiny"), default="own")
    args = ap.parse_args()
    cell = cells.Cell(args.workload)
    sf = (TINY if args.scale == "tiny" else cell.config)["scale_factor"]
    rel_tol = cell.config["guarantees"]["double_rel_tol"]
    all_failed = True
    for seed in args.seeds:
        t0 = time.perf_counter()
        params, control, sound = control_numbers(cell, seed, sf, rel_tol)
        failed = not compare.within(control)
        all_failed = all_failed and failed and compare.within(sound)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "scale_factor": sf,
            "parameters": params, "control_came_out_not_correct": failed,
            "control": {k: v["value"] for k, v in control.items()},
            "exact_against_itself": {k: v["value"] for k, v in sound.items()},
            "seconds": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "control_failed_on_every_seed": all_failed}))
    sys.exit(0 if all_failed else 1)


if __name__ == "__main__":
    main()
