"""Telemetry subsystem tests: the inert disabled path, the JSONL event
schema, metric registry namespacing, the memory watermark vs the memsim
prediction, fleet shard-merge determinism, and the typed-event timeline of
a chaos run through ``Trainer.fit``."""
import dataclasses
import json
import os
import random

import jax
import pytest

from repro.api import Trainer, TrainSpec
from repro.telemetry import (DISABLED, CounterGroup, MemoryWatermark,
                             MetricRegistry, SCHEMA_VERSION,
                             StepEvent, Telemetry)
from repro.telemetry import events as ev
from repro.telemetry import spans as sp
from repro.runtime.degrade import WatermarkTrigger
from repro.runtime.guard import REASONS, StepGuard


def _tiny_spec(tmp_path, **kw):
    base = dict(arch="qwen2.5-0.5b", reduced=True, engine="mesp",
                steps=3, seq=32, batch=2, quiet=True,
                ckpt_dir=str(tmp_path / "ckpt"))
    base.update(kw)
    return TrainSpec(**base)


# ------------------------------------------ disabled = one annotation a span
def test_disabled_singleton_is_inert():
    assert DISABLED.enabled is False
    assert DISABLED.sinks == []
    # a disabled span is the bare profiler annotation: the tracer records
    # nothing and no event is emitted
    span = DISABLED.span("a")
    assert type(span) is jax.profiler.TraceAnnotation
    with span, DISABLED.span("b"):
        pass
    assert DISABLED.tracer.finished == []
    assert DISABLED.tracer._stack == []
    DISABLED.emit(StepEvent(step=1, loss=0.5, seconds=0.1))   # no-op
    assert DISABLED.events() == []
    assert DISABLED.counts_by_kind() == {}


def test_disabled_fit_never_touches_telemetry_machinery(tmp_path,
                                                        monkeypatch):
    """With --telemetry off the loop must run the exact pre-telemetry code:
    no span enters, no record is built. Poison both paths and fit."""
    def boom(*a, **k):
        raise AssertionError("telemetry machinery invoked on disabled path")

    monkeypatch.setattr(sp.Tracer, "span", boom)
    monkeypatch.setattr(ev, "to_record", boom)
    spec = _tiny_spec(tmp_path)
    tr = Trainer.from_spec(spec)
    step_fn_before = tr.step_fn
    result = tr.fit()
    assert len(result.history) == 3
    # the jitted step object is the one built at spec time — telemetry
    # added no wrapper around it
    assert tr.step_fn is step_fn_before
    assert "registry" not in result.metrics
    assert not (tmp_path / "ckpt" / "telemetry").exists()


# ------------------------------------------------------------ event schema
def test_event_round_trip_and_validation():
    for kind, cls in ev.EVENT_TYPES.items():
        event = cls()
        rec = ev.to_record(event, seq=3, worker=1, ts=123.5)
        assert rec["v"] == SCHEMA_VERSION
        assert rec["kind"] == kind
        assert (rec["ts"], rec["seq"], rec["worker"]) == (123.5, 3, 1)
        assert ev.validate_record(rec) == []
        assert ev.from_record(rec) == event


def test_validate_record_catches_drift():
    rec = ev.to_record(StepEvent(step=1, loss=2.0, seconds=0.1), seq=0)
    bad = dict(rec, v=99)
    assert any("schema version" in e for e in ev.validate_record(bad))
    bad = {k: v for k, v in rec.items() if k != "loss"}
    assert any("missing field 'loss'" in e for e in ev.validate_record(bad))
    bad = dict(rec, surprise=1)
    assert any("unexpected field 'surprise'" in e
               for e in ev.validate_record(bad))
    assert any("unknown kind" in e
               for e in ev.validate_record(dict(rec, kind="meteor")))


def test_jsonl_sink_round_trip(tmp_path):
    tel = Telemetry(enabled=True, out_dir=str(tmp_path))
    for i in range(4):
        tel.emit(StepEvent(step=i, loss=1.0 / (i + 1), seconds=0.01))
    tel.close()
    recs = ev.read_jsonl(str(tmp_path / "events.jsonl"))
    assert len(recs) == 4
    assert [r["seq"] for r in recs] == [0, 1, 2, 3]
    assert all(ev.validate_record(r) == [] for r in recs)
    # in-memory sink saw the same records
    assert tel.events("step") == recs


# -------------------------------------------------------- metrics registry
def test_counter_group_is_dict_compatible():
    g = CounterGroup("pages", ("reserved", "freed"))
    g["reserved"] += 3
    g.counter("freed").inc()
    assert dict(g) == {"reserved": 3, "freed": 1}
    assert g.namespaced() == {"pages.reserved": 3, "pages.freed": 1}
    g.update({k: 0 for k in g})          # the benchmark warmup-reset idiom
    assert dict(g) == {"reserved": 0, "freed": 0}


def test_registry_unifies_groups_and_scalars():
    reg = MetricRegistry()
    pages = CounterGroup("pages", ("reserved",))
    reg.register_group(pages)
    pages["reserved"] += 2
    reg.counter("ckpt.saves").inc()
    reg.gauge("train.loss").set(0.25)
    reg.histogram("train.step_seconds").record(0.02)
    snap = reg.snapshot()
    assert snap["pages.reserved"] == 2
    assert snap["ckpt.saves"] == 1
    assert snap["train.loss"] == 0.25
    assert snap["train.step_seconds"]["count"] == 1


def test_paged_allocator_counters_namespaced():
    from repro.serve.paged import PagedKVAllocator
    alloc = PagedKVAllocator(n_pages=4, page_size=8)
    assert alloc.reserve("a", 20)        # 3 pages
    assert not alloc.reserve("b", 16)    # 2 > 1 free -> rejected
    alloc.free("a")
    reg = MetricRegistry()
    reg.register_group(alloc.counters)
    snap = reg.snapshot()
    assert snap["pages.reserved"] == 3
    assert snap["pages.rejected"] == 1
    assert snap["pages.freed"] == 3


def test_autotune_cache_counters(monkeypatch):
    import jax.numpy as jnp
    from repro.kernels import autotune
    # isolate the module-global measured cache (autotune() is in-memory
    # only — save_cache() is explicit — so a dict copy restores it)
    monkeypatch.setattr(autotune, "_CACHE", dict(autotune._CACHE))
    autotune.COUNTERS.update({k: 0 for k in autotune.COUNTERS})
    autotune.choose_blocks("flash", Nq=256, Nk=256, D=64)   # heuristic: miss
    autotune.autotune("flash", lambda blocks: jnp.zeros(()),
                      candidates=[{"bq": 256, "bk": 256}],
                      repeats=1, Nq=256, Nk=256, D=64)
    autotune.choose_blocks("flash", Nq=256, Nk=256, D=64)   # measured: hit
    stats = autotune.cache_stats()
    assert stats["cache_miss"] >= 1
    assert stats["cache_hit"] >= 1
    assert stats["sweeps"] == 1
    assert stats["sweep_candidates"] == 1


# ------------------------------------------------------------------- spans
def test_tracer_nesting_and_chrome_export(tmp_path):
    tr = sp.Tracer(enabled=True)
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    assert [n for n, *_ in tr.finished] == ["inner", "outer"]
    path = str(tmp_path / "trace.json")
    tr.save(path)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    assert {e["name"] for e in events} == {"outer", "inner"}
    assert all(e["ph"] == "X" for e in events)
    inner = next(e for e in events if e["name"] == "inner")
    assert inner["args"]["depth"] == 1
    totals = tr.totals()
    assert totals["outer"]["count"] == 1


# -------------------------------------------------------- watermark trigger
def test_watermark_trigger_hysteresis():
    trig = WatermarkTrigger(budget_mb=100.0)   # threshold 0.9 -> 90 MB
    assert [trig.observe(v) for v in (50, 95, 95, 50)] == \
        [False, False, True, False]
    assert trig.trips == 1
    # re-armed: two more consecutive over-limit samples trip again
    assert [trig.observe(v) for v in (95, 95)] == [False, True]
    assert trig.trips == 2


def test_watermark_trigger_rejects_zero_budget():
    with pytest.raises(ValueError):
        WatermarkTrigger(budget_mb=0.0)


# ------------------------------------------------------------- guard events
def test_guard_by_reason_counts_and_events():
    tel = Telemetry(enabled=True)
    guard = StepGuard(budget=8, warmup=1, telemetry=tel)
    assert guard.observe(1.0) == "accept"
    assert guard.observe(float("nan")) == "reject"
    assert guard.observe(1.0e9) == "reject"            # spike vs EWMA ~1.0
    st = guard.state()
    assert st["accepted"] == 1 and st["rejected"] == 2
    assert st["by_reason"]["nonfinite_loss"] == 1
    assert st["by_reason"]["loss_spike"] == 1
    assert set(st["by_reason"]) == set(REASONS)
    reasons = [r["reason"] for r in tel.events("guard")]
    assert reasons == ["nonfinite_loss", "loss_spike"]
    snap = tel.registry.snapshot()
    assert snap["guard.reject.nonfinite_loss"] == 1
    assert snap["guard.loss_ewma"] == 1.0


# ----------------------------------------------- enabled fit, end to end
def test_fit_telemetry_watermark_vs_memsim(tmp_path):
    tdir = str(tmp_path / "tele")
    spec = _tiny_spec(tmp_path, telemetry="on", telemetry_dir=tdir)
    result = Trainer.from_spec(spec).fit()
    m = result.metrics
    wm = m["watermark"]
    assert wm["measured_peak_mb"] > 0
    assert wm["predicted_peak_mb"] > 0          # memsim reduced-cfg peak
    assert wm["source"] in ("device_stats", "live_arrays")
    assert wm["samples"] == 3
    assert m["events_by_kind"]["step"] == 3
    assert m["events_by_kind"]["run"] == 2      # start + end
    assert m["events_by_kind"]["watermark"] == 3
    assert m["registry"]["train.steps"] == 3
    assert m["spans"]["train/dispatch"]["count"] == 3
    # files on disk: schema-valid JSONL + a Chrome trace
    recs = ev.read_jsonl(os.path.join(tdir, "events.jsonl"))
    assert all(ev.validate_record(r) == [] for r in recs)
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "run" and kinds[-1] == "run"
    assert os.path.exists(os.path.join(tdir, "trace.json"))


def test_chaos_fit_emits_typed_timeline(tmp_path):
    """Injected faults, ladder rungs and guard rejections must all appear
    as typed events in the JSONL timeline (the chaos-smoke CI contract)."""
    tdir = str(tmp_path / "tele")
    spec = _tiny_spec(tmp_path, steps=8, telemetry="on", telemetry_dir=tdir,
                      inject_faults="oom@2,nan@4", max_retries=4)
    result = Trainer.from_spec(spec).fit()
    assert len(result.history) == 8
    kinds = result.metrics["events_by_kind"]
    assert kinds.get("fault", 0) >= 2           # injector fire + loop handle
    assert kinds.get("degrade", 0) >= 1         # oom walked the ladder
    assert kinds.get("guard", 0) >= 1           # nan rejected
    recs = ev.read_jsonl(os.path.join(tdir, "events.jsonl"))
    assert all(ev.validate_record(r) == [] for r in recs)
    faults = [r for r in recs if r["kind"] == "fault"]
    assert any(r["source"] == "injector" and r["injected"] for r in faults)
    assert any(r["source"] == "loop" for r in faults)
    degr = [r for r in recs if r["kind"] == "degrade"]
    assert degr and degr[0]["trigger"] == "oom"
    guards = [r for r in recs if r["kind"] == "guard"]
    assert guards[0]["reason"] == "nonfinite_loss"


def test_mem_budget_triggers_proactive_degrade(tmp_path):
    """A tiny --mem-budget-mb must trip the watermark trigger (live_arrays
    residency exceeds it immediately) and degrade BEFORE any OOM."""
    tdir = str(tmp_path / "tele")
    spec = _tiny_spec(tmp_path, steps=6, telemetry="on", telemetry_dir=tdir,
                      mem_budget_mb=0.05)
    result = Trainer.from_spec(spec).fit()
    assert result.counters.watermark_triggers >= 1
    assert result.counters.oom_events == 0
    assert result.degradations                 # a rung was applied
    recs = ev.read_jsonl(os.path.join(tdir, "events.jsonl"))
    degr = [r for r in recs if r["kind"] == "degrade"]
    assert degr and degr[0]["trigger"] == "watermark"


# -------------------------------------------------------------- fleet merge
def test_fleet_shard_merge_is_deterministic(tmp_path):
    """Merged fleet timeline must be byte-identical regardless of shard
    file order (workers finish in arbitrary order)."""
    shards = []
    for w in range(3):
        path = str(tmp_path / f"worker_{w}.jsonl")
        sink = ev.JsonlSink(path)
        for i in range(4):
            sink.emit(ev.to_record(StepEvent(step=i, loss=1.0, seconds=0.01),
                                   seq=i, worker=w, ts=100.0 + i + 0.1 * w))
        sink.close()
        shards.append(path)
    outs = []
    for trial in range(3):
        order = list(shards)
        random.Random(trial).shuffle(order)
        out = str(tmp_path / f"merged_{trial}.jsonl")
        ev.merge_jsonl_shards(order, out)
        with open(out, "rb") as f:
            outs.append(f.read())
    assert outs[0] == outs[1] == outs[2]
    merged = ev.read_jsonl(str(tmp_path / "merged_0.jsonl"))
    assert len(merged) == 12
    keys = [(r["ts"], str(r["worker"]), r["seq"]) for r in merged]
    assert keys == sorted(keys)


def test_merge_fleet_telemetry_helper(tmp_path):
    from repro.launch.fleet import merge_fleet_telemetry
    assert merge_fleet_telemetry(str(tmp_path)) is None   # no shards yet
    sink = ev.JsonlSink(str(tmp_path / "worker_0.jsonl"))
    sink.emit(ev.to_record(StepEvent(step=0), seq=0, worker=0, ts=1.0))
    sink.close()
    out = merge_fleet_telemetry(str(tmp_path))
    assert out == str(tmp_path / "fleet.jsonl")
    assert len(ev.read_jsonl(out)) == 1


# ---------------------------------------------------------------- CLI flags
def test_telemetry_flags_cli_round_trip():
    spec = TrainSpec(telemetry="on", telemetry_dir="/tmp/t", profile="off",
                     mem_budget_mb=12.5, quiet=True)
    parsed = TrainSpec.from_cli_args(spec.to_cli_args())
    assert parsed.telemetry == "on"
    assert parsed.telemetry_dir == "/tmp/t"
    assert parsed.mem_budget_mb == 12.5
    assert parsed.quiet is True
    with pytest.raises(ValueError):
        TrainSpec(telemetry="maybe").validate()
    with pytest.raises(ValueError):
        TrainSpec(mem_budget_mb=-1.0).validate()


def test_memwatch_sample_and_compare():
    import jax.numpy as jnp
    keep = jnp.ones((256, 1024), jnp.float32)     # 1 MB pinned live
    mw = MemoryWatermark()
    s = mw.sample()
    assert s["source"] in ("device_stats", "live_arrays")
    assert s["measured_mb"] >= 1.0                # at least `keep`
    mw.predicted_mb = 2 * mw.peak_mb
    cmp = mw.compare()
    assert cmp["samples"] == 1
    assert 0 < cmp["ratio"] <= 0.5 + 1e-9
    del keep
