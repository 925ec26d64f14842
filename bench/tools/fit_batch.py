#!/usr/bin/env python3
"""Memory of a training cell's compiled step at several batch sizes.

    python bench/tools/fit_batch.py --workload <cell> --batches 1,2,3,4

Compiles the step as the cell builds it (``Trainer.compile_step``) for
each batch and prints the compiler's memory analysis: the largest batch
whose arguments, outputs and temporaries fit is the one to confirm by a
run. Compiles only; nothing is executed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batches", default="1,2,3,4")
    args = ap.parse_args(argv)

    import jax

    from bench import spec
    from repro.api import Trainer, TrainSpec
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    conf = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    cfg = spec.arch_config(conf)
    for b in map(int, args.batches.split(",")):
        ts = TrainSpec(arch=conf["arch"], engine=traffic["engine"],
                       quantize=traffic["quantize"], batch=b,
                       seq=traffic["seq"], degrade="off",
                       guard=traffic["guard"], quiet=True)
        tr = Trainer.from_spec(ts, cfg=cfg)
        try:
            ma = tr.compile_step().memory_analysis()
            rec = {"batch": b, "argument": ma.argument_size_in_bytes,
                   "output": ma.output_size_in_bytes,
                   "alias": ma.alias_size_in_bytes,
                   "temp": ma.temp_size_in_bytes,
                   "total": ma.argument_size_in_bytes
                   + ma.output_size_in_bytes - ma.alias_size_in_bytes
                   + ma.temp_size_in_bytes}
        except Exception as e:   # a compile-time OOM is a reading here
            rec = {"batch": b, "error": str(e)[:400]}
        print(json.dumps(rec), flush=True)
        del tr
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
