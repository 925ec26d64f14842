"""The training and serve loops on the profiler's clock: each loop phase is
a host span in the profiler's own trace (``telemetry.spans.LOOP_SPANS``,
``SERVE_SPANS``), each training step attempt a ``StepTraceAnnotation``;
the loop counts its device→host reads; the kernel dispatch counts its jnp
fallbacks; with ``--profile on`` ``trace.json`` shares the profile's
origin."""
import glob
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.api import Trainer, TrainSpec
from repro.kernels import ops
from repro.telemetry import LOOP_SPANS, SERVE_SPANS


def _tiny_spec(tmp_path, **kw):
    base = dict(arch="qwen2.5-0.5b", reduced=True, engine="mesp",
                steps=3, seq=32, batch=2, quiet=True,
                ckpt_dir=str(tmp_path / "ckpt"))
    base.update(kw)
    return TrainSpec(**base)


def _profile(log_dir):
    """A profiler session without the Python tracer (host annotations and
    the runtime's own events only)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(log_dir, profiler_options=opts)


def _host_events(log_dir, names):
    """(name, start_ns, end_ns, stats) of the host events named ``names``
    in the newest profile under ``log_dir``, by start; ``stats`` only for
    the step annotations."""
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats) if e.name == "train" else {})
                    for e in line.events if e.name in names]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_fit_spans_sit_in_their_step_in_loop_order(tmp_path):
    """Telemetry off, guard on: every step attempt is one "train" step
    annotation holding each loop phase once, in loop order."""
    spec = _tiny_spec(tmp_path)
    tr = Trainer.from_spec(spec)
    log_dir = str(tmp_path / "prof")
    with _profile(log_dir):
        result = tr.fit()
    assert len(result.history) == 3
    events = _host_events(log_dir, set(LOOP_SPANS) | {"train"})
    steps = [e for e in events if e[0] == "train"]
    assert [int(e[3]["step_num"]) for e in steps] == [0, 1, 2]
    want = ["train/data", "train/dispatch", "train/loss_sync", "train/guard",
            "train/on_step", "train/checkpoint"]
    assert [n for n in LOOP_SPANS if n in want] == want   # the tuple's order
    for _, s0, s1, _ in steps:
        inside = [e[0] for e in events
                  if e[0] != "train" and s0 <= e[1] and e[2] <= s1]
        assert inside == want
    # outside the steps: the restore before them and the final save
    outside = [e[0] for e in events if e[0] != "train"
               and not any(s0 <= e[1] <= s1 for _, s0, s1, _ in steps)]
    assert outside == ["train/restore", "train/checkpoint"]
    # one read a step: the loss together with the guard's update norm
    assert dict(tr.counters) == {"steps": 3, "host_syncs": 3 * 1}


def test_host_syncs_without_the_guard(tmp_path):
    tr = Trainer.from_spec(_tiny_spec(tmp_path, guard="off", steps=2))
    tr.fit()
    assert dict(tr.counters) == {"steps": 2, "host_syncs": 2}


def test_nan_loss_step_is_rejected_and_rewound(tmp_path):
    """A fault-injected NaN loss is still rejected and rewound with the
    guard's norm read beside the loss: the update is discarded, its batch
    skipped, one guard skip counted, and every attempt reads the host
    once."""
    from repro.checkpoint import Checkpointer
    from repro.data import make_batch_iterator
    from repro.runtime.fault_tolerance import ResilientLoop
    from repro.runtime.faults import FaultInjector, FaultPlan
    from repro.runtime.guard import StepGuard

    def init_state():
        return {"w": jnp.zeros((4,), jnp.float32),
                "b": jnp.zeros((3,), jnp.bfloat16),
                "q": jnp.ones((2,), jnp.int8)}, None

    def step_fn(params, opt_state, batch):
        new = {"w": params["w"] + 1.0, "b": params["b"] + 1.0,
               "q": params["q"]}
        return new, opt_state, jnp.float32(1.0)

    guard = StepGuard(warmup=100)
    loop = ResilientLoop(
        step_fn, init_state, make_batch_iterator(50, 4, 2, n_tokens=2048),
        Checkpointer(str(tmp_path / "ckpt"), interval=100), 3,
        guard=guard, injector=FaultInjector(FaultPlan.parse("nan@1")))
    params, _, results, counters = loop.run()
    assert counters.guard_skips == 1
    assert guard.state()["by_reason"]["nonfinite_loss"] == 1
    assert [r.step for r in results] == [1, 2, 3]
    # three updates kept of four computed
    assert float(params["w"][0]) == 3.0 and float(params["b"][0]) == 3.0
    assert int(params["q"][0]) == 1
    # the norm of each accepted update: sqrt(4 + 3)
    assert abs(guard.state()["norm_ewma"] - 7 ** 0.5) < 1e-6
    assert dict(loop.train_counters) == {"steps": 3, "host_syncs": 4}


def test_enabled_fit_records_the_loop_spans(tmp_path):
    """Telemetry on: the same spans land in ``trace.json``'s totals, the
    watermark's among them (memwatch runs), and the registry holds the
    ``train`` counters."""
    spec = _tiny_spec(tmp_path, steps=2, telemetry="on",
                      telemetry_dir=str(tmp_path / "tele"))
    tr = Trainer.from_spec(spec)
    result = tr.fit()
    spans = result.metrics["spans"]
    assert set(spans) <= set(LOOP_SPANS)
    assert spans["train/watermark"]["count"] == 2
    assert spans["train/dispatch"]["count"] == 2
    reg = result.metrics["registry"]
    assert reg["train.steps"] == 2
    assert reg["train.host_syncs"] == 2 * 1
    # the report lists trace.json's spans in the loop's order
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "scripts", "telemetry_report.py")
    modspec = importlib.util.spec_from_file_location("telemetry_report",
                                                     path)
    report = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(report)
    tdir = str(tmp_path / "tele")
    listed = list(report.summarize(report.load_run(tdir), tdir)["spans"])
    assert listed == [n for n in LOOP_SPANS if n in spans]
    assert listed[:2] == ["train/restore", "train/data"]


def test_profile_on_shares_the_profile_origin(tmp_path):
    """``--profile on``: a span's start in trace.json and in the profile
    differ by under 0.5 ms."""
    tdir = str(tmp_path / "tele")
    spec = _tiny_spec(tmp_path, steps=2, telemetry="on", telemetry_dir=tdir,
                      profile="on")
    Trainer.from_spec(spec).fit()
    with open(os.path.join(tdir, "trace.json")) as f:
        recorded = sorted(json.load(f)["traceEvents"],
                          key=lambda e: e["ts"])
    profiled = _host_events(os.path.join(tdir, "profile"), set(LOOP_SPANS))
    assert [e["name"] for e in recorded] == [e[0] for e in profiled]
    assert len(recorded) >= 10
    for rec, prof in zip(recorded, profiled):
        assert abs(rec["ts"] * 1e3 - prof[1]) < 0.5e6, (rec, prof)


def test_serve_step_spans(tmp_path):
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve import (AdapterStore, ContinuousBatcher, Request,
                             synthetic_adapters)

    cfg = get_config("qwen2.5-0.5b").reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    bat = ContinuousBatcher(cfg, AdapterStore(params, capacity=2), slots=4,
                            tile=2, max_len=16, page_size=8)
    bat.register_adapter("u0", synthetic_adapters(params, 0))
    reqs = [Request(f"r{i}", "u0", (1 + i, 2, 3), 2) for i in range(2)]
    log_dir = str(tmp_path / "prof")
    with _profile(log_dir):
        bat.run(reqs)
    events = _host_events(log_dir, set(SERVE_SPANS))
    steps = bat.counters["steps"]
    names = [e[0] for e in events]
    assert names.count("serve/dispatch") == steps
    assert names.count("serve/argmax_sync") == steps
    # admission runs once more than the steps: the last finds nothing
    assert names.count("serve/admission") == steps + 1
    resets = [e for e in events if e[0] == "serve/reset_slot"]
    assert len(resets) == bat.counters["admitted"] == 2
    admissions = [e for e in events if e[0] == "serve/admission"]
    for _, r0, r1, _ in resets:
        assert any(a0 <= r0 and r1 <= a1 for _, a0, a1, _ in admissions)


@pytest.mark.parametrize("seq,fallbacks", [(32, 1), (128, 0)])
def test_sdpa_fallbacks_are_counted(seq, fallbacks):
    """Below ``PALLAS_ATTN_MIN_SEQ`` each attention call falls back to the
    jnp path and is counted; at 128 positions none is."""
    q = jnp.zeros((1, 4, seq, 16), jnp.float32)
    k = jnp.zeros((1, 2, seq, 16), jnp.float32)
    before = dict(ops.FALLBACKS)
    jax.eval_shape(lambda q, k: ops.sdpa(q, k, k), q, k)
    jax.eval_shape(lambda q, k: ops.sdpa(q, k, k), q, k)
    assert ops.FALLBACKS["sdpa"] - before["sdpa"] == 2 * fallbacks
    assert ops.FALLBACKS["lora_linear"] == before["lora_linear"]


@pytest.mark.parametrize("seq,per_call", [(32, 1), (128, 0)])
def test_step_fallbacks_by_sequence(tmp_path, monkeypatch, seq, per_call):
    """Tracing the ``mesp_pallas`` step counts one fallback for each
    attention call at 32 positions, none at 128; no LoRA linear falls
    back at either."""
    calls = []
    sdpa = ops.sdpa
    monkeypatch.setattr(ops, "sdpa",
                        lambda *a, **k: calls.append(1) or sdpa(*a, **k))
    tr = Trainer.from_spec(_tiny_spec(tmp_path, engine="mesp_pallas",
                                      seq=seq, batch=1))
    before = dict(ops.FALLBACKS)
    pstruct, ostruct = tr._state_struct(tr.live_spec)
    jax.eval_shape(tr._jit_step, pstruct, ostruct, tr.batch_struct())
    assert calls
    assert ops.FALLBACKS["sdpa"] - before["sdpa"] == per_call * len(calls)
    assert ops.FALLBACKS["lora_linear"] == before["lora_linear"]
