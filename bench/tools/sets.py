#!/usr/bin/env python3
"""Two sets of runs of one cell, as the check makes them, and the spread of
each end-to-end metric: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python bench/tools/sets.py --workload <cell> --seeds 6 --seconds 40 \
        --out <file.json> [--trace-seeds 1]

Each run is its own process (``bench/run.py``), the second set on the same
seeds as the first. Also runs ``--trace-seeds`` traced runs after them.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def one(workload, seed, seconds, trace):
    t = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=ROOT, capture_output=True, text=True)
    line = None
    if proc.returncode == 0 and proc.stdout.strip():
        line = json.loads(proc.stdout.strip().splitlines()[-1])
    return {"seed": seed, "trace": trace, "rc": proc.returncode,
            "wall_s": time.time() - t, "line": line,
            "stderr_tail": proc.stderr[-1500:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--first-seed", type=int, default=3_200_000_000)
    ap.add_argument("--trace-seeds", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [args.first_seed + i for i in range(args.seeds)]
    runs = []
    for s in range(2):
        for seed in seeds:
            r = one(args.workload, seed, args.seconds, 0)
            r["set"] = s
            print(json.dumps({k: r[k] for k in ("set", "seed", "rc",
                                                "wall_s")}),
                  json.dumps(r["line"] and r["line"]["metrics"]),
                  json.dumps(r["line"] and r["line"]["checks"]), flush=True)
            runs.append(r)
    for i in range(args.trace_seeds):
        r = one(args.workload, args.first_seed + 100 + i, args.seconds, 1)
        print(json.dumps({k: r[k] for k in ("seed", "rc", "wall_s")}),
              json.dumps(r["line"]), flush=True)
        runs.append(r)
    summary = {}
    for s in range(2):
        lines = [r["line"] for r in runs
                 if r.get("set") == s and r["line"] is not None]
        for name in (lines[0]["metrics"] if lines else {}):
            vals = [ln["metrics"][name]["value"] for ln in lines]
            summary.setdefault(name, []).append(
                {"median": statistics.median(vals),
                 "spread": spread(vals) if len(vals) >= 2 else None,
                 "values": vals})
    print(json.dumps(summary, indent=1), flush=True)
    with open(args.out, "w") as f:
        json.dump({"runs": runs, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
