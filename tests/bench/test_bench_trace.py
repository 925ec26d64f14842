"""The reduction from a profiler trace to metrics."""
import json
import os

import pytest

from bench.trace import DISPATCH, Trace, base_name

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# three executions of jit_step at 0, 20 and 40 (ns), an eager op between the
# first two, overlapping kernel calls inside, a dispatch span on the host
SMALL = {
    "devices": [{
        "plane": "/device:TPU:0",
        "modules": [["jit_step(1)", 0, 10], ["jit_convert(2)", 12, 2],
                    ["jit_step(1)", 20, 10], ["jit_step(1)", 40, 10]],
        "ops": [["lora_fwd.3", 0, 4], ["fusion.1", 3, 7],
                ["convert.7", 12, 2],
                ["lora_fwd.5", 20, 5], ["flash_fwd", 25, 5],
                ["lora_fwd.3", 40, 10]]}],
    "host": [[DISPATCH, 15, 6], ["other", 30, 10]],
}


def test_window_and_executions():
    tr = Trace(SMALL)
    assert tr.executions("jit_step") == [(0, 10), (20, 30), (40, 50)]
    assert tr.window("jit_step") == (0, 40, 2)
    assert tr.window("jit_convert") is None
    assert tr.exec_ms("jit_step") == pytest.approx(10 / 1e6)
    assert tr.gap_ms("jit_step") == pytest.approx(10 / 1e6)


def test_busy_is_the_union_of_op_intervals():
    tr = Trace(SMALL)
    # [0,10] (two ops overlapping), [12,14], [20,30]; clipped to [0,40]
    assert tr.busy_ns(0, 40) == 10 + 2 + 10
    assert tr.busy_ns(5, 22) == 5 + 2 + 2


def test_ops_are_attributed_by_kernel_name():
    tr = Trace(SMALL)
    assert base_name("lora_fwd.12") == "lora_fwd"
    assert tr.op_ns(0, 40) == {"lora_fwd": 9, "fusion": 7, "convert": 2,
                               "flash_fwd": 5}
    assert tr.op_calls(0, 50) == {"lora_fwd": 3, "fusion": 1, "convert": 1,
                                  "flash_fwd": 1}


def test_idle_gaps_are_named_by_host_span():
    tr = Trace(SMALL)
    gaps = tr.idle_gaps(0, 40)
    # 14..20 mostly under the dispatch span; 10..12 under none
    assert gaps[0] == [DISPATCH, pytest.approx(6e-9)]
    assert gaps[1] == ["host loop", pytest.approx(2e-9)]


def test_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        Trace({"devices": [{"plane": "/device:TPU:0", "modules": [],
                            "ops": []}], "host": []})


def test_recorded_chip_trace():
    """A few steps of a qwen2.5-0.5b.ft.paper trace taken on a v5e."""
    with open(os.path.join(DATA, "trace_v5e_05b.json")) as f:
        rec = json.load(f)
    tr = Trace(rec["reduced"])
    t0, t1, n = tr.window(rec["module"])
    want = rec["expect"]
    assert n == want["steps"]
    assert tr.busy_ns(t0, t1) == want["busy_ns"]
    assert tr.op_calls(t0, t1)["lora_fwd"] == want["lora_fwd_calls"]
    assert tr.gap_ms(rec["module"]) == pytest.approx(want["gap_ms"])


def test_readers_on_recorded_chip_trace():
    """The training readers on the recorded v5e trace: shares of a peak
    or a roofline stay within 100 %."""
    from bench import peaks, spec

    with open(os.path.join(DATA, "trace_v5e_05b.json")) as f:
        rec = json.load(f)
    tr = Trace(rec["reduced"])
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, "qwen2.5-0.5b.ft.paper")
    conf = spec.load_config(bench, cell["config"])
    arch = spec.load_arch(conf)
    ctx = {"trace": tr, "module": rec["module"],
           "window": tr.window(rec["module"]),
           "widths": arch.Widths.from_config(conf), "arch": arch,
           "traffic": spec.load_traffic(cell["traffic"]),
           "kind": "TPU v5 lite", "peaks": peaks.peaks("TPU v5 lite"),
           "counters": {}}
    got = {m["name"]: spec.load_reader(m["name"])(ctx)
           for m in spec.metrics_of_cell(bench, cell, trace=True)}
    assert all(v is not None for v in got.values()), got
    for name, v in got.items():
        if name.endswith(("roofline", "mfu", "idle_share")):
            assert 0 < v <= 100, (name, v)
