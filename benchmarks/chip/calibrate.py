#!/usr/bin/env python3
"""Repeated runs of one cell, for setting its bounds and limits.

    python3 benchmarks/chip/calibrate.py --workload W --seeds 1 2 3 \\
        [--sets 2] [--seconds S] [--trace 0|1] [--control 0|1] --out F

Runs ``run.py`` once per seed and set, one process after another (this
parent never touches JAX, so each child gets the chip), appends every
result line to the JSON-lines file F, and prints per metric the median
and the spread (interquartile range over the median, as
``statistics.quantiles(values, n=4)`` gives it) of each set, and the
largest logit gap of the served tokens and the least of the fp8
control's (a ``--control 1`` run judges the control's tokens).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values):
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                               "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
    rows = []
    for s in range(args.sets):
        for seed in args.seeds:
            t0 = time.perf_counter()
            p = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"),
                 "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(seconds), "--trace", str(args.trace),
                 "--control", str(args.control)],
                capture_output=True, text=True)
            wall = time.perf_counter() - t0
            tail = p.stderr.strip().splitlines()[-30:]
            line = p.stdout.strip().splitlines()[-1] if p.stdout.strip() \
                else ""
            row = {"set": s, "seed": seed, "rc": p.returncode,
                   "wall_s": wall, "stderr_tail": tail}
            if p.returncode == 0 and line.startswith("{"):
                row["result"] = json.loads(line)
            rows.append(row)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
            res = row.get("result", {})
            print(f"set {s} seed {seed} rc {p.returncode} wall {wall:.1f} s "
                  f"correct {res.get('correct')} "
                  + json.dumps({k: v["value"] for k, v in
                                res.get("metrics", {}).items()})
                  + " " + json.dumps(res.get("checks", {}))
                  + " " + json.dumps(res.get("control", {})), flush=True)
            if p.returncode != 0:
                print("\n".join(tail), flush=True)
    ok = [r for r in rows if "result" in r]
    names = sorted({m for r in ok for m in r["result"]["metrics"]})
    for m in names:
        for s in range(args.sets):
            vals = [r["result"]["metrics"][m]["value"] for r in ok
                    if r["set"] == s and m in r["result"]["metrics"]]
            if vals:
                print(f"{m} set {s}: median {statistics.median(vals)!r} "
                      f"spread {spread(vals)!r} n {len(vals)}")
    gaps = [r["result"]["control"]["served_max_logit_gap"]
            if "control" in r["result"]
            else r["result"]["checks"]["max_logit_gap"]["value"] for r in ok]
    if gaps:
        print(f"served max_logit_gap over {len(gaps)} runs: max "
              f"{max(gaps)!r} all {gaps}")
    ctrl = [r["result"]["checks"]["max_logit_gap"]["value"] for r in ok
            if "control" in r["result"]]
    if ctrl:
        print(f"fp8 control gap over {len(ctrl)} runs: min {min(ctrl)!r} "
              f"all {ctrl}; correct "
              f"{[r['result']['correct'] for r in ok if 'control' in r['result']]}")
    return 0 if len(ok) == len(rows) else 1


if __name__ == "__main__":
    sys.exit(main())
