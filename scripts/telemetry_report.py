"""Render, validate, and benchmark telemetry run directories.

Three modes:

* **report** (default) — load every ``*.jsonl`` under a run directory
  (``--run DIR``), print the event-kind counts, step-loss trajectory,
  checkpoint/fault/degrade timeline, and span totals from ``trace.json``
  when present, in the loops' order (``telemetry.spans.LOOP_SPANS``, then
  ``SERVE_SPANS``) with each span's mean in ms.

* **validate** (``--validate``) — schema-check every record
  (``repro.telemetry.events.validate_record``): envelope version, required
  per-kind fields, no unknown fields. ``--expect-kinds step,fault`` adds a
  hard coverage check that each named kind appears at least once (the
  chaos-smoke CI job uses this to assert faults/degradations/guard
  rejections actually landed in the timeline). Exit 1 on any problem.

* **sweep** (``--sweep``) — run tiny reduced fits across engine × quantize
  with ``--telemetry on`` and write ``BENCH_telemetry.json`` rows of
  *measured* peak memory (``repro.telemetry.memwatch``) vs the memsim
  *predicted* peak for the same live spec, plus step timings and event
  counts. ``scripts/check_bench_regression.py --telemetry`` gates schema
  version and row coverage against the committed baseline; the
  measured/predicted ratio itself is annotate-only on CPU, where
  ``memory_stats()`` is unavailable and the ``live_arrays`` fallback is a
  lower bound (in-jit temporaries are invisible).

    PYTHONPATH=src python scripts/telemetry_report.py --run /tmp/tele
    PYTHONPATH=src python scripts/telemetry_report.py --run /tmp/tele \\
        --validate --expect-kinds run,step,watermark
    PYTHONPATH=src python scripts/telemetry_report.py --sweep \\
        --out benchmarks/results/BENCH_telemetry.json
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.telemetry import events as ev  # noqa: E402
from repro.telemetry.spans import LOOP_SPANS, SERVE_SPANS  # noqa: E402

RESULTS_DIR = (Path(__file__).resolve().parent.parent / "benchmarks" /
               "results")
DEFAULT_OUT = str(RESULTS_DIR / "BENCH_telemetry.json")

#: engine × quantize grid for --sweep (every row reduced-config; small
#: enough for the CI smoke job, wide enough to cover a recomputation
#: engine, a baseline-BP engine, and the packed-int4 weight path)
SWEEP_ENGINES = ("mesp", "mebp")
SWEEP_QUANTIZE = ("none", "int8", "int4")
SWEEP_STEPS = 3


# --------------------------------------------------------------------- load
def load_run(run_dir: str) -> list[dict]:
    """All JSONL records under ``run_dir`` (single-run ``events.jsonl``,
    fleet ``worker_*.jsonl`` shards, or a merged ``fleet.jsonl``)."""
    records: list[dict] = []
    for path in sorted(glob.glob(os.path.join(run_dir, "*.jsonl"))):
        records.extend(ev.read_jsonl(path))
    records.sort(key=lambda r: (r.get("ts", 0.0), str(r.get("worker", "")),
                                r.get("seq", 0)))
    return records


def validate(records: list[dict],
             expect_kinds: list[str] | None = None) -> list[str]:
    """Schema errors (and kind-coverage gaps) across a record list."""
    errors: list[str] = []
    for i, rec in enumerate(records):
        for problem in ev.validate_record(rec):
            errors.append(f"record {i}: {problem}")
    seen = {r.get("kind") for r in records}
    for kind in expect_kinds or []:
        if kind not in seen:
            errors.append(f"expected kind {kind!r} absent from the timeline "
                          f"(present: {sorted(k for k in seen if k)})")
    return errors


# ------------------------------------------------------------------- report
def summarize(records: list[dict], run_dir: str) -> dict:
    by_kind: dict[str, int] = {}
    for r in records:
        by_kind[r.get("kind", "?")] = by_kind.get(r.get("kind", "?"), 0) + 1
    steps = [r for r in records if r.get("kind") == "step"]
    out: dict = {"records": len(records), "by_kind": by_kind}
    if steps:
        secs = sorted(r["seconds"] for r in steps)
        out["steps"] = {"count": len(steps),
                        "first_loss": steps[0]["loss"],
                        "final_loss": steps[-1]["loss"],
                        "median_step_s": secs[len(secs) // 2]}
    marks = [r for r in records if r.get("kind") == "watermark"]
    if marks:
        out["watermark"] = {"peak_mb": max(r["peak_mb"] for r in marks),
                            "source": marks[-1].get("source", "")}
    timeline = [r for r in records if r.get("kind") in
                ("fault", "degrade", "guard", "checkpoint")]
    if timeline:
        out["incidents"] = [
            {k: r[k] for k in ("kind", "step") if k in r} |
            {k: r[k] for k in ("fault", "rung", "reason", "action")
             if r.get(k)}
            for r in timeline]
    trace = os.path.join(run_dir, "trace.json")
    if os.path.exists(trace):
        with open(trace) as f:
            spans = json.load(f).get("traceEvents", [])
        totals: dict[str, dict] = {}
        for s in spans:
            t = totals.setdefault(s["name"], {"count": 0, "total_s": 0.0})
            t["count"] += 1
            t["total_s"] += s["dur"] / 1e6
        order = {n: i for i, n in enumerate(LOOP_SPANS + SERVE_SPANS)}
        out["spans"] = {
            k: {"count": v["count"], "total_s": round(v["total_s"], 4),
                "mean_ms": round(1e3 * v["total_s"] / v["count"], 4)}
            for k, v in sorted(totals.items(),
                               key=lambda kv: (order.get(kv[0], len(order)),
                                               kv[0]))}
    return out


# -------------------------------------------------------------------- sweep
def sweep_row(engine: str, quantize: str, steps: int, workdir: str) -> dict:
    """One tiny telemetry-on fit; measured vs predicted peak for the row."""
    from repro.api import TrainSpec, Trainer

    tdir = os.path.join(workdir, f"{engine}_{quantize}")
    spec = TrainSpec(arch="qwen2.5-0.5b", reduced=True, engine=engine,
                     quantize=quantize, steps=steps, seq=32, batch=2,
                     ckpt_dir=os.path.join(tdir, "ckpt"),
                     telemetry="on", telemetry_dir=tdir, quiet=True)
    result = Trainer.from_spec(spec).fit()
    m = result.metrics
    wm = m.get("watermark", {})
    reg = m.get("registry", {})
    hist = reg.get("train.step_seconds", {})
    return {"engine": engine, "quantize": quantize,
            "steps": len(result.history),
            "final_loss": round(result.final_loss, 6),
            "measured_peak_mb": wm.get("measured_peak_mb", 0.0),
            "predicted_peak_mb": wm.get("predicted_peak_mb", 0.0),
            "ratio": wm.get("ratio", 0.0),
            "source": wm.get("source", ""),
            "mean_step_s": round(hist.get("mean", 0.0), 4),
            "events": m.get("events_by_kind", {})}


def run_sweep(out: str, steps: int = SWEEP_STEPS) -> dict:
    import shutil
    import tempfile

    import jax

    from repro.kernels import ops
    from repro.telemetry import SCHEMA_VERSION

    workdir = tempfile.mkdtemp(prefix="bench_telemetry_")
    rows = []
    try:
        for engine in SWEEP_ENGINES:
            for quantize in SWEEP_QUANTIZE:
                rows.append(sweep_row(engine, quantize, steps, workdir))
                r = rows[-1]
                print(f"  {engine}/{quantize}: measured "
                      f"{r['measured_peak_mb']} MB vs predicted "
                      f"{r['predicted_peak_mb']} MB (ratio {r['ratio']}, "
                      f"source={r['source']})")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    interp = ops.pallas_interpret()
    doc = {
        "benchmark": "telemetry",
        "schema_version": SCHEMA_VERSION,
        "backend": jax.default_backend(),
        "interpret": interp,
        "note": ("CPU/interpret: memory_stats() unavailable — the "
                 "live_arrays source lower-bounds the true peak (in-jit "
                 "temporaries invisible), so the measured/predicted ratio "
                 "is annotate-only here" if interp else
                 "device allocator stats; ratio is comparable"),
        "setting": {"arch": "qwen2.5-0.5b", "reduced": True, "steps": steps,
                    "seq": 32, "batch": 2,
                    "engines": list(SWEEP_ENGINES),
                    "quantize": list(SWEEP_QUANTIZE)},
        "rows": rows,
    }
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {out}")
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run", default=None, metavar="DIR",
                    help="telemetry run directory (JSONL + trace.json)")
    ap.add_argument("--validate", action="store_true",
                    help="schema-check every record; exit 1 on problems")
    ap.add_argument("--expect-kinds", default="",
                    help="comma-separated kinds that must appear (with "
                         "--validate)")
    ap.add_argument("--sweep", action="store_true",
                    help="run the engine×quantize telemetry sweep and "
                         "write BENCH_telemetry.json")
    ap.add_argument("--steps", type=int, default=SWEEP_STEPS,
                    help="steps per sweep fit")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="sweep output path (default: committed baseline)")
    args = ap.parse_args(argv)

    if args.sweep:
        run_sweep(args.out, steps=args.steps)
        return 0
    if not args.run:
        ap.error("pass --run DIR (report/validate) or --sweep")
    records = load_run(args.run)
    if not records:
        print(f"FAIL: no JSONL records under {args.run}")
        return 1
    if args.validate:
        kinds = [k for k in args.expect_kinds.split(",") if k]
        errors = validate(records, kinds)
        for e in errors:
            print(f"FAIL: {e}")
        if errors:
            return 1
        print(f"OK: {len(records)} records valid "
              f"(schema v{ev.SCHEMA_VERSION}"
              + (f"; kinds cover {kinds}" if kinds else "") + ")")
        return 0
    print(json.dumps(summarize(records, args.run), indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
