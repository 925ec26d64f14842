#!/usr/bin/env python3
"""Sweep a serving cell's arrival rate once, to find the knee: the highest
rate the system sustains without a growing backlog.

    python bench/tools/knee.py --workload <cell> --rates 8,12,16 \
        --seconds 30 --out <file.json>

Each rate runs the cell's traffic (its own warm-up, then ``--seconds``) in
one process on the chip and prints the tails, the completed tokens per
second and what was still queued at the close. No correctness check.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=int, default=30)
    ap.add_argument("--seed", type=int, default=3_100_000_000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import device, spec
    from bench.drivers import serve
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    device.require_chip(cell["chips"])
    conf = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    out = []
    for rate in map(float, args.rates.split(",")):
        got = serve.run(conf=conf, traffic=traffic, seed=args.seed,
                        seconds=args.seconds, rate=rate,
                        t_start=time.time())
        rec = {"rate": rate, **got["e2e"], **got["readings"],
               "decoded_tokens_per_s":
                   got["counters"]["decoded_tokens"] / args.seconds,
               "steps_per_s": got["counters"]["steps"] / args.seconds}
        print(json.dumps(rec), flush=True)
        out.append(rec)
        del got
        gc.collect()
        jax.clear_caches()
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
