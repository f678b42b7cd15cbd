"""Run one cell several times, one process a run, and summarise the runs:
the sets of runs that bound a metric, and traced runs.

    python3 bench/sets.py --workload <cell> --seconds 51 --out <dir> \\
        --seeds 11 12 13 --sets A B --trace-seeds 21 22

Every set runs ``bench/run.py`` once on each seed, untraced, in the given
order; then each trace seed runs once with ``--trace 1``.  Each run's
standard output and error are kept as ``<out>/<cell>.<set>.<seed>.<trace>
.{out,err}``.  The summary gives each run's metrics and compared numbers,
then per metric and set the median and the spread (interquartile range
over the median, from ``statistics.quantiles``), and the gap between the
first two sets' medians.  Exits 0 only when every run printed a result and
every result was correct.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def run_once(args, label: str, seed: int, trace: int):
    stem = os.path.join(args.out, f"{args.workload}.{label}.{seed}.{trace}")
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
           args.workload, "--seed", str(seed), "--seconds",
           str(args.seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    with open(stem + ".out", "w") as out, open(stem + ".err", "w") as err:
        rc = subprocess.run(cmd, cwd=ROOT, stdout=out, stderr=err).returncode
    wall = time.perf_counter() - t0
    with open(stem + ".out") as f:
        lines = f.read().strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, ValueError):
        result = None
    return rc, wall, result


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q3 - q1) / median if median else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--sets", nargs="*", default=["A", "B"])
    ap.add_argument("--trace-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--stop-after", type=float, default=float("inf"),
                    help="start no run after this many seconds")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    plan = [(s, seed, 0) for s in args.sets for seed in args.seeds]
    plan += [("T", seed, 1) for seed in args.trace_seeds]
    t0 = time.perf_counter()
    values = {}                 # (set, metric) -> values
    n_run = n_result = n_correct = 0
    for label, seed, trace in plan:
        if time.perf_counter() - t0 > args.stop_after:
            print(f"stopped before {label} {seed}: --stop-after reached")
            break
        rc, wall, r = run_once(args, label, seed, trace)
        n_run += 1
        n_result += r is not None
        n_correct += bool(r and r["correct"] is True)
        line = {"set": label, "seed": seed, "trace": trace, "rc": rc,
                "wall_s": round(wall, 1)}
        if r is not None:
            line.update(correct=r["correct"], metrics={
                m: v["value"] for m, v in r["metrics"].items()},
                device=r["device"], compared=r.get("compared"))
            for m, v in r["metrics"].items():
                values.setdefault((label, m), []).append(v["value"])
        print(json.dumps(line), flush=True)
    first = args.sets[:2]
    for (label, m), vals in sorted(values.items()):
        if len(vals) >= 2:
            print(f"{m} set {label}: n {len(vals)} median "
                  f"{statistics.median(vals)!r} spread {spread(vals)!r}")
    for m in sorted({m for _, m in values}):
        if len(first) == 2 and all((s, m) in values for s in first):
            a, b = (statistics.median(values[(s, m)]) for s in first)
            gap = (b - a) / a if a else float("nan")
            print(f"{m} gap {first[1]} over {first[0]}: {gap!r}")
    print(f"runs {n_run}, results {n_result}, correct {n_correct}")
    return 0 if n_run == n_result == n_correct else 1


if __name__ == "__main__":
    sys.exit(main())
