#!/usr/bin/env python3
"""Run one benchmark cell once, on the machine this starts on.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``, which names its architecture module in
``bench/archs/``) and a traffic mix
(``bench/traffic/<traffic>.json``, which names its driver in
``bench/drivers/``). Inputs and weights come from ``--seed``. Set-up warms
every shape the window uses; the window then measures for ``--seconds``.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
a shorter window and reports its per-layer metrics, each read by
``bench/metrics/<metric>.py``. Either way the run then compares what the
timed path produced with the float32 reference, prints each compared
number beside its limit as the last lines of standard error, and prints
one JSON line last on standard output.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, when the program is not there, or when the run
is unsound (interpret mode, a kernel missing from the compiled program, a
compilation inside the window, an OOM).
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def process_start() -> float:
    """Wall-clock time this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


T_START = process_start()


def run_cell(workload: str, seed: int, seconds: int, trace: bool, *,
             root: str = ROOT, fault=None) -> dict:
    """One run of ``workload``; returns the result line's object."""
    from bench import check, device, spec

    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, workload)
    conf = spec.load_config(bench, cell["config"], root)
    arch = spec.load_arch(conf, root)
    traffic = spec.load_traffic(cell["traffic"], root)
    limits = spec.load_limits(workload, root)
    wanted = spec.metrics_of_cell(bench, cell, trace)
    readers = {m["name"]: spec.load_reader(m["name"], root)
               for m in wanted} if trace else {}

    from repro.launch.compile_cache import enable_compile_cache
    device.require_chip(cell["chips"])
    enable_compile_cache()
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        got = driver.run(conf=conf, traffic=traffic, seed=seed,
                         seconds=seconds, trace_dir=trace_dir,
                         fault=fault, t_start=T_START, root=root)
        gc.collect()
        dev = {**device.describe(cell["chips"]),
               "memory_peak_bytes": got["memory_peak_bytes"]}
        metrics, breakdown = {}, None
        if trace:
            from bench import peaks
            from bench.trace import Trace, latest_xplane, reduce_xplane

            reduced = reduce_xplane(latest_xplane(trace_dir))
            tr = Trace(reduced)
            win = tr.window(got["module"])
            if win is None:
                raise device.RunFault("the trace holds fewer than two "
                                      "executions of the timed program")
            t0, t1, n = win
            ctx = {"trace": tr, "module": got["module"], "window": win,
                   "widths": arch.Widths.from_config(conf), "arch": arch,
                   "traffic": traffic,
                   "kind": dev["kind"], "peaks": peaks.peaks(dev["kind"]),
                   "counters": got.get("counters", {})}
            for m in wanted:
                value = readers[m["name"]](ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            dev["busy_s"] = tr.busy_ns(t0, t1) / 1e9
            dev["window_s"] = (t1 - t0) / 1e9
            ops = sorted(tr.op_ns(t0, t1).items(), key=lambda kv: -kv[1])
            breakdown = {
                "device_ops": [[k, v / 1e9] for k, v in ops[:10]],
                "idle_gaps": tr.idle_gaps(t0, t1)}
        else:
            for m in wanted:
                if m["name"] in got["e2e"]:
                    metrics[m["name"]] = {"value": got["e2e"][m["name"]],
                                          "unit": m["unit"]}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    numbers = got["verify"]()
    correct, checks = check.judge(numbers, limits)
    line = {"correct": correct, "attempted": got["attempted"],
            "failed": got["failed"], "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["readings"] = {**got.get("readings", {}),
                        **{k: v for k, v in numbers.items()
                           if k not in checks}}
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from bench import device, spec
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except ImportError as e:
        print(f"bench: the program is not here ({e}); nothing was run",
              file=sys.stderr)
        return 1
    except (device.NoChip, spec.CellError, device.RunFault) as e:
        print(f"bench: {type(e).__name__}: {e}; no result", file=sys.stderr)
        return 1
    from bench.check import print_checks
    print_checks(line["checks"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
