"""Readings that set a cell's limits: the program's and the float8
control's, on several seeds, in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 30

Each seed is a whole run of the cell (set-up, a window at the cell's own
load, the reference over the same sample of served requests a run
compares).  Then the control takes the program's place: the reference
computed in float8, its first choice at every row where the program chose a
token and its logits at the first token, judged by the same comparison and
the same limits.  One JSON line per seed; the benchmark's own runs never do
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = run.execute(run.ROOT, args.workload, seed, args.seconds, False,
                          control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": res["correct"], "checks": res["checks"],
                          "control": res["control"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
