"""The per-layer readers' values on the recorded v5e trace, pinned: a change
to the trace reduction (which host events it keeps, how it names idle
gaps) must leave every reading of modules and ops exactly as it is."""
import json
import os

import pytest

from bench import peaks, spec
from bench.trace import Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "qwen2.5-0.5b.ft.paper"
PINNED = {
    "train.host_gap_ms": 79.552524,
    "train.step_device_ms": 17.497386,
    "train.mfu": 2.7252136450277042,
    "train.lora_roofline": 61.713887835380106,
    "train.flash_roofline": 9.570571320755148,
    "train.idle_share": 50.5867372777574,
}


@pytest.fixture(scope="module")
def ctx():
    with open(os.path.join(DATA, "trace_v5e_05b.json")) as f:
        rec = json.load(f)
    tr = Trace(rec["reduced"])
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, CELL)
    conf = spec.load_config(bench, cell["config"])
    arch = spec.load_arch(conf)
    return {"trace": tr, "module": rec["module"],
            "window": tr.window(rec["module"]),
            "widths": arch.Widths.from_config(conf), "arch": arch,
            "traffic": spec.load_traffic(cell["traffic"]),
            "kind": "TPU v5 lite", "peaks": peaks.peaks("TPU v5 lite"),
            "counters": {}}


@pytest.mark.parametrize("metric", sorted(PINNED))
def test_reader_value_on_recorded_trace(ctx, metric):
    bench = spec.load_benchmark()
    listed = {m["name"] for m in spec.metrics_of_cell(
        bench, spec.find_cell(bench, CELL), trace=True)}
    assert metric in listed
    assert spec.load_reader(metric)(ctx) == pytest.approx(PINNED[metric],
                                                          rel=1e-12)
