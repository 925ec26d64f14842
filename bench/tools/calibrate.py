#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python bench/tools/calibrate.py --workload <cell> --seeds 12 \
        --control-seeds 3 --faults half_batch --out <file.json>

In one process, on the chip: the program as the cell runs it on
``--seeds`` seeds (the lower readings), the control and each planted
fault on ``--control-seeds`` seeds (the upper readings). For training the
control is the program's own path one precision down (the traffic file's
``control`` format), or, where the traffic file names
``reference_int8``, the reference on an int8-rounded base in the
program's place; a run needs no measured window. For serving it is the
int8 reference read at the same prompts and served tokens as the
program's first seeds.
"""
from __future__ import annotations

import argparse
import gc
import importlib
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import device, spec
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    device.require_chip(cell["chips"])
    conf = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    serving = traffic["driver"] == "serve"
    runs = [("program", None, args.first_seed + i)
            for i in range(args.seeds)]
    for i in range(args.control_seeds):
        if not serving:
            runs.append(("control", None, args.first_seed + 1000 + i))
        for f in filter(None, args.faults.split(",")):
            runs.append((f, f, args.first_seed + 2000 + i))
    out = []
    for i, (kind, fault, seed) in enumerate(runs):
        t = time.time()
        by_reference = traffic.get("control") == "reference_int8"
        extra = ({"quantize": traffic["control"]}
                 if kind == "control" and not by_reference else {})
        got = driver.run(conf=conf, traffic=traffic, seed=seed,
                         seconds=args.seconds, fault=fault,
                         t_start=time.time(), **extra)
        gc.collect()
        rec = {"kind": kind, "seed": seed, "numbers": got["verify"](
            control=kind == "control" and by_reference)}
        if serving and kind == "program" and i < args.control_seeds:
            # the control: the int8 reference in the program's place, at
            # the same prompts and served tokens
            rec["control"] = got["verify"](control=True)
        rec["seconds"] = time.time() - t
        print(json.dumps(rec), flush=True)
        out.append(rec)
        del got
        gc.collect()
        jax.clear_caches()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
