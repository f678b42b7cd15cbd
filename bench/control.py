"""Run a cell with the bfloat16 control in the program's place, on several
seeds in one process, and print the numbers the check compares.

    python3 bench/control.py --workload <cell> --seconds 5 --seeds 1 2 3

The control is the reference's exact brute-force kNN computed with
bfloat16 coordinates (``reference.Bf16Control``), the precision below the
float32 that the configurations state; every seed has to come out not
correct.  Each seed prints one JSON line with the compared numbers.  The
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import harness
    fails = 0
    for seed in args.seeds:
        try:
            r = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                                 False, t_start=time.perf_counter(),
                                 control=True)
        except harness.NoAccelerator as e:
            print(f"no result: {e}", file=sys.stderr)
            return 2
        fails += not r["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": r["correct"],
                          "attempted": r["attempted"],
                          "compared": r["compared"]}), flush=True)
    return 0 if fails == len(args.seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
