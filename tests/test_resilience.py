"""Chaos-hardening tests: fault injection, degradation ladder, step guard,
checkpoint quarantine/fallback, and the supervised ResilientLoop — unit
level plus a fault-injection matrix through the ``Trainer.fit`` facade."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Trainer, TrainSpec
from repro.checkpoint import Checkpointer, save_checkpoint
from repro.data import make_batch_iterator
from repro.runtime.degrade import (DegradationLadder, LadderExhausted,
                                   carry_opt_state, predicted_peak_mb)
from repro.runtime.fault_tolerance import (ResilientLoop, StragglerPolicy,
                                           run_resilient)
from repro.runtime.faults import (FaultInjector, FaultPlan, InjectedOOM,
                                  corrupt_latest_checkpoint, is_oom_error)
from repro.runtime.guard import (GuardExhausted, StepGuard, _sq_norm,
                                 update_norm)


# ---------------------------------------------------------------- FaultPlan
def test_fault_plan_parse_round_trip():
    text = "oom@4,corrupt@8,crash@9,nan@14,stall@18:1.5"
    plan = FaultPlan.parse(text)
    assert len(plan.events) == 5
    assert plan.to_string() == text
    assert FaultPlan.parse(plan.to_string()) == plan
    stall = [e for e in plan.events if e.kind == "stall"][0]
    assert stall.arg == 1.5


def test_fault_plan_same_step_ordering():
    # corrupt must fire before crash at the same step, or the crash's
    # restore would never see the poisoned checkpoint
    plan = FaultPlan.parse("crash@9,corrupt@9")
    assert [e.kind for e in plan.events] == ["corrupt", "crash"]


def test_fault_plan_rejects_unknown_kind():
    with pytest.raises(ValueError, match="bad fault entry"):
        FaultPlan.parse("meteor@3")


def test_fault_plan_seeded_is_deterministic():
    a = FaultPlan.seeded(seed=7, total_steps=50)
    b = FaultPlan.seeded(seed=7, total_steps=50)
    c = FaultPlan.seeded(seed=8, total_steps=50)
    assert a == b and a.to_string() == b.to_string()
    assert a != c
    assert len(a.events) == 5
    assert len({e.step for e in a.events}) == 5          # distinct steps
    assert all(0 < e.step < 50 for e in a.events)


def test_fault_plan_from_string_random():
    plan = FaultPlan.from_string("random:3", total_steps=30, seed=1)
    assert len(plan.events) == 3
    assert plan == FaultPlan.from_string("random:3", total_steps=30, seed=1)


def test_is_oom_error_classification():
    assert is_oom_error(InjectedOOM("RESOURCE_EXHAUSTED: boo"))
    assert is_oom_error(MemoryError())
    assert is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: hbm"))
    assert not is_oom_error(RuntimeError("device lost"))


def test_injector_fires_each_event_once(tmp_path):
    inj = FaultInjector(FaultPlan.parse("oom@2"), ckpt_dir=str(tmp_path))
    inj.before_step(0)
    with pytest.raises(InjectedOOM):
        inj.before_step(2)
    inj.before_step(2)          # a rewound replay must not re-fire it
    assert inj.summary() == {"oom": 1} and inj.exhausted


def test_spec_validates_fault_plan_early():
    with pytest.raises(ValueError, match="bad fault entry"):
        TrainSpec(inject_faults="nonsense").validate()
    TrainSpec(inject_faults="oom@4,nan@7").validate()


# ---------------------------------------------------------------- StepGuard
def test_guard_rejects_nonfinite_and_exhausts_budget():
    g = StepGuard(budget=2)
    assert g.observe(1.0) == "accept"
    assert g.observe(float("nan")) == "reject"
    assert g.observe(float("inf")) == "reject"
    with pytest.raises(GuardExhausted):
        g.observe(float("nan"))


def test_guard_rejects_loss_spike_after_warmup():
    g = StepGuard(budget=4, spike_factor=10.0, warmup=3)
    for _ in range(3):
        assert g.observe(1.0) == "accept"
    assert g.observe(50.0) == "reject"       # 50 > 10 x EWMA(1.0)
    assert g.observe(1.1) == "accept"        # baseline not poisoned
    assert g.rejected == 1


def test_guard_rejects_update_norm_spike():
    g = StepGuard(budget=4, spike_factor=10.0, warmup=2)
    assert g.observe(1.0, update_norm=0.1) == "accept"
    assert g.observe(1.0, update_norm=0.1) == "accept"
    assert g.observe(1.0, update_norm=5.0) == "reject"


def _norm_trees(seed=0, nan=False):
    """Old and new params: bf16, f32 and int8 leaves (the int8 ones
    changed too, which the norm must not see)."""
    rng = np.random.default_rng(seed)

    def tree():
        return {"base": {"w": jnp.asarray(rng.normal(size=(16, 8)),
                                          jnp.bfloat16),
                         "q": jnp.asarray(rng.integers(-100, 100, (16, 8)),
                                          jnp.int8)},
                "lora": {"a": jnp.asarray(rng.normal(size=(8, 4)),
                                          jnp.float32),
                         "b": jnp.asarray(rng.normal(size=(4, 16)),
                                          jnp.bfloat16)}}
    old, new = tree(), tree()
    if nan:
        new["lora"]["a"] = new["lora"]["a"].at[1, 2].set(jnp.nan)
    return old, new


def _eager_norm(old, new):
    """The per-leaf sum the guard used to compute, leaf by leaf."""
    total = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(old),
                    jax.tree_util.tree_leaves(new)):
        if not jnp.issubdtype(a.dtype, jnp.inexact):
            continue
        d = jnp.asarray(b, jnp.float32) - jnp.asarray(a, jnp.float32)
        total += float(jnp.sum(d * d))
    return total ** 0.5


def test_update_norm_matches_the_eager_per_leaf_sum():
    from repro.telemetry.metrics import Counter
    old, new = _norm_trees()
    syncs = Counter()
    got = update_norm(old, new, syncs=syncs)
    np.testing.assert_allclose(got, _eager_norm(old, new), rtol=1e-5)
    assert syncs.value == 1          # one read back, whatever the leaves


def test_update_norm_skips_int8_leaves():
    old, new = _norm_trees()
    floats_only = lambda t: {"w": t["base"]["w"], **t["lora"]}
    np.testing.assert_allclose(
        update_norm(old, new),
        update_norm(floats_only(old), floats_only(new)), rtol=1e-6)
    same = {"q": jnp.zeros((3,), jnp.int8)}
    assert update_norm(same, {"q": jnp.full((3,), 9, jnp.int8)}) == 0.0


def test_nan_leaf_gives_a_nonfinite_norm_rejection():
    old, new = _norm_trees(nan=True)
    norm = update_norm(old, new)
    assert not np.isfinite(norm)
    g = StepGuard(budget=3)
    assert g.observe(1.0, update_norm=norm) == "reject"
    assert g.by_reason["nonfinite_norm"] == 1


def test_update_norm_compiles_once_per_tree_structure():
    old, new = _norm_trees(seed=1)
    old["lora"]["c"] = jnp.ones((5, 3), jnp.float32)   # a shape of its own
    new["lora"]["c"] = jnp.zeros((5, 3), jnp.float32)
    n0 = _sq_norm._cache_size()
    update_norm(old, new)
    assert _sq_norm._cache_size() == n0 + 1
    update_norm(new, old)
    old2, new2 = _norm_trees(seed=2)
    old2["lora"]["c"], new2["lora"]["c"] = old["lora"]["c"], new["lora"]["c"]
    update_norm(old2, new2)
    assert _sq_norm._cache_size() == n0 + 1


# ---------------------------------------------------------------- straggler
def test_straggler_warmup_discards_compile_step():
    # a 100x jit-compile first step must not seed the EWMA baseline
    sp = StragglerPolicy(factor=3.0, consecutive_limit=2, warmup=1)
    assert sp.observe(10.0) == "ok"          # compile step, discarded
    assert sp.observe(0.1) == "ok"           # seeds the baseline
    assert sp.observe(0.11) == "ok"
    assert sp.observe(1.0) == "slow"
    sp.reset()
    assert sp.observe(10.0) == "ok" and sp.mean is None


# ------------------------------------------------- ladder + opt-state carry
def test_ladder_walks_validated_rungs():
    spec = TrainSpec(engine="mesp_pallas", batch=4, seq=256)
    rungs = dict((r, c) for c, r in DegradationLadder().candidates(spec))
    assert rungs["halve_batch"].batch == 2
    assert rungs["engine_mesp"].engine == "mesp"
    assert rungs["quantize_int8"].quantize == "int8"
    assert rungs["truncate_seq"].seq == 128
    base = predicted_peak_mb(spec)
    if base is not None:     # memsim present: every rung must not grow peak
        for cand in rungs.values():
            assert predicted_peak_mb(cand) <= base + 1e-6


def test_ladder_offers_int4_after_int8():
    """The packed rung is only reachable *from* int8 (one notch of
    quantization error at a time), and is the sole rung left at the
    batch/seq/engine floor."""
    spec = TrainSpec(engine="mesp_seq", batch=1, seq=32, quantize="int8")
    rungs = dict((r, c) for c, r in
                 DegradationLadder(min_batch=1, min_seq=32).candidates(spec))
    assert set(rungs) == {"quantize_int4"}
    assert rungs["quantize_int4"].quantize == "int4"
    # never offered straight from an unquantized spec
    fresh = TrainSpec(engine="mesp_pallas", batch=4, seq=256)
    assert "quantize_int4" not in {
        r for _, r in DegradationLadder().candidates(fresh)}


def test_ladder_exhausts_at_floor():
    spec = TrainSpec(engine="mesp_seq", batch=1, seq=32, quantize="int4")
    with pytest.raises(LadderExhausted):
        list(DegradationLadder(min_batch=1, min_seq=32).candidates(spec))


def test_carry_opt_state_across_int8_rewrite():
    from repro.core.quant import quantize_params

    params = {"blk": {"w": jnp.ones((4, 4)), "a": jnp.ones((4, 2)),
                      "b": jnp.zeros((2, 4))}}
    mom = jax.tree_util.tree_map(lambda x: x * 2.0, params)
    opt_state = {"step": jnp.array(3, jnp.int32), "m": mom}
    qp = quantize_params(params, "int8")
    out = carry_opt_state(opt_state, params, qp)
    assert int(out["step"]) == 3
    # LoRA moments carried verbatim; rewritten frozen slots drop to None
    np.testing.assert_array_equal(out["m"]["blk"]["a"], mom["blk"]["a"])
    np.testing.assert_array_equal(out["m"]["blk"]["b"], mom["blk"]["b"])
    assert out["m"]["blk"]["w"]["q"] is None
    assert out["m"]["blk"]["w"]["scale"] is None


# ------------------------------------------------------- loop satellites
def _counting_loop(tmp_path, fail_calls, total_steps=8, max_retries=1,
                   interval=2):
    it = make_batch_iterator(50, 4, 2, n_tokens=2048)
    ckpt = Checkpointer(str(tmp_path), interval=interval)
    calls = {"n": 0}

    def step_fn(params, opt_state, batch):
        calls["n"] += 1
        if calls["n"] in fail_calls:
            raise RuntimeError(f"boom at call {calls['n']}")
        return params + 1, opt_state, float(params)

    return ResilientLoop(step_fn, lambda: (jnp.array(0.0), None), it, ckpt,
                         total_steps, max_retries=max_retries,
                         backoff_base=0.0)


def test_retry_budget_resets_after_success(tmp_path):
    # two failures separated by successes: with max_retries=1 both must be
    # absorbed (the old accounting never reset and killed the run)
    loop = _counting_loop(tmp_path, fail_calls={3, 8}, max_retries=1)
    params, _, results, counters = loop.run()
    assert results[-1].step == 8
    assert counters.step_failures == 2
    assert float(params) == 8.0


def test_consecutive_failures_still_raise(tmp_path):
    loop = _counting_loop(tmp_path, fail_calls={3, 4, 5}, max_retries=2)
    with pytest.raises(RuntimeError, match="boom"):
        loop.run()


def test_failure_recurring_on_replay_still_raises(tmp_path):
    # step 2 fails every time it is reached and the only restore point is
    # step 0: the replayed steps 0 and 1 succeed, which must not refill the
    # retry budget (the run would otherwise retry forever)
    it = make_batch_iterator(50, 4, 2, n_tokens=2048)
    calls = {"n": 0}

    def step_fn(params, opt_state, batch):
        calls["n"] += 1
        if float(params) == 2.0:
            raise RuntimeError("deterministic failure at step 2")
        return params + 1, opt_state, float(params)

    loop = ResilientLoop(step_fn, lambda: (jnp.array(0.0), None), it,
                         Checkpointer(str(tmp_path), interval=100), 8,
                         max_retries=2, backoff_base=0.0)
    with pytest.raises(RuntimeError, match="deterministic"):
        loop.run()
    assert calls["n"] == 3 * 3     # 1 try + 2 retries, 3 calls each


def test_forced_final_checkpoint_on_exit(tmp_path):
    # total_steps % interval != 0: the loop must still leave a final
    # checkpoint at the last step
    loop = _counting_loop(tmp_path, fail_calls=set(), total_steps=7,
                          interval=5)
    loop.run()
    from repro.checkpoint import latest_step
    assert latest_step(str(tmp_path)) == 7


def test_run_resilient_wrapper_keeps_legacy_contract(tmp_path):
    it = make_batch_iterator(50, 4, 2, n_tokens=2048)
    ckpt = Checkpointer(str(tmp_path), interval=100)
    out = run_resilient(lambda p, o, b: (p, o, 0.0),
                        lambda: (jnp.array(0.0), None), it, ckpt, 2)
    assert len(out) == 3                     # (params, opt_state, results)


# --------------------------------------------- quarantine + fallback restore
def test_restore_latest_falls_back_over_corrupt_checkpoint(tmp_path):
    d = str(tmp_path)
    params = {"w": jnp.arange(4.0)}
    save_checkpoint(d, 2, params, {"step": jnp.array(2)})
    save_checkpoint(d, 4, params, {"step": jnp.array(4)})
    assert corrupt_latest_checkpoint(d) == 4
    ckpt = Checkpointer(d)
    restored = ckpt.restore_latest(params, {"step": jnp.array(0)})
    assert restored["step"] == 2             # fell back past the bad one
    assert [s for s, _ in ckpt.quarantined] == [4]
    assert os.path.isdir(os.path.join(d, "corrupt_step_00000004"))
    assert not os.path.isdir(os.path.join(d, "step_00000004"))


def test_restore_latest_raises_only_when_all_corrupt(tmp_path):
    d = str(tmp_path)
    params = {"w": jnp.arange(4.0)}
    save_checkpoint(d, 1, params)
    corrupt_latest_checkpoint(d)
    ckpt = Checkpointer(d)
    with pytest.raises(IOError, match="no restorable checkpoint"):
        ckpt.restore_latest(params, None)
    # the bad candidate was quarantined, so a retry sees an empty dir
    assert ckpt.restore_latest(params, None) is None


def test_restore_latest_none_when_empty(tmp_path):
    assert Checkpointer(str(tmp_path / "nope")).restore_latest({}) is None


# ------------------------------------------------- Trainer.fit fault matrix
def _spec(tmp_path, name, **kw):
    kw.setdefault("arch", "qwen2.5-0.5b")
    kw.setdefault("reduced", True)
    kw.setdefault("engine", "mesp")
    kw.setdefault("steps", 8)
    kw.setdefault("seq", 32)
    kw.setdefault("batch", 2)
    kw.setdefault("lr", 5e-3)
    kw.setdefault("ckpt_interval", 3)
    kw.setdefault("ckpt_dir", str(tmp_path / name))
    return TrainSpec(**kw)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def test_crash_resumes_exact_token_stream(tmp_path):
    """A mid-run crash must restore + replay the identical token stream:
    final params bit-identical to the fault-free twin."""
    clean = Trainer.from_spec(_spec(tmp_path, "clean")).fit()
    crashed = Trainer.from_spec(
        _spec(tmp_path, "crash", inject_faults="crash@5")).fit()
    assert crashed.fault_counts["step_failures"] == 1
    assert crashed.fault_counts["steps_replayed"] > 0
    for a, b in zip(_leaves(clean.params), _leaves(crashed.params)):
        np.testing.assert_array_equal(a, b)


def test_oom_degrades_to_memsim_valid_spec(tmp_path):
    res = Trainer.from_spec(
        _spec(tmp_path, "oom", inject_faults="oom@3")).fit()
    assert res.history[-1].step == 8
    assert res.fault_counts["oom_events"] == 1
    assert res.degradations == ["halve_batch"]
    assert res.final_spec.batch == 1
    base = predicted_peak_mb(_spec(tmp_path, "oom"))
    peak = predicted_peak_mb(res.final_spec)
    if base is not None and peak is not None:
        assert peak <= base + 1e-6
    # the degraded spec still round-trips the CLI (it is a real TrainSpec)
    res.final_spec.validate()


def test_oom_with_ladder_off_retries_in_place(tmp_path):
    res = Trainer.from_spec(
        _spec(tmp_path, "noladder", inject_faults="oom@3",
              degrade="off")).fit()
    assert res.degradations == []
    assert res.fault_counts["oom_events"] == 1
    assert res.history[-1].step == 8


_HBM_OOM = "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm"


def _failing_compile(monkeypatch, batch, message):
    """Make ``Trainer.compile_step`` raise ``message`` for specs of
    ``batch`` (a compile error of that program), and compile the rest."""
    real = Trainer.compile_step

    def compile_step(self):
        if self.live_spec.batch == batch:
            raise RuntimeError(message)
        return real(self)

    monkeypatch.setattr(Trainer, "compile_step", compile_step)


def test_compile_error_on_ladder_rung_raises(tmp_path, monkeypatch):
    # a rung whose step does not compile (a Mosaic tiling error, say) is a
    # fault of the program: the ladder raises it instead of skipping to a
    # cheaper rung and reporting success there
    _failing_compile(monkeypatch, 1, "Mosaic failed to compile TPU kernel")
    with pytest.raises(RuntimeError, match="Mosaic"):
        Trainer.from_spec(
            _spec(tmp_path, "rungerr", inject_faults="oom@3")).fit()


def test_rung_oom_at_compile_is_skipped(tmp_path, monkeypatch):
    # halve_batch does not fit either (compile-time OOM): the ladder moves
    # on to the next rung
    _failing_compile(monkeypatch, 1, _HBM_OOM)
    res = Trainer.from_spec(
        _spec(tmp_path, "rungoom", inject_faults="oom@3")).fit()
    assert res.degradations == ["engine_mesp_seq"]
    assert (res.final_spec.batch, res.final_spec.engine) == (2, "mesp_seq")
    assert res.history[-1].step == 8


@pytest.mark.parametrize("degrade", ["on", "off"])
def test_compile_oom_at_start(tmp_path, monkeypatch, degrade):
    # the step of the requested spec does not fit at compile: with the
    # ladder on, the first step's OOM (injected here, as the same program
    # would raise it) walks the ladder; with it off, fit raises outright
    _failing_compile(monkeypatch, 2, _HBM_OOM)
    spec = _spec(tmp_path, f"startoom_{degrade}", inject_faults="oom@0",
                 degrade=degrade)
    if degrade == "off":
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            Trainer.from_spec(spec).fit()
        return
    res = Trainer.from_spec(spec).fit()
    assert res.degradations == ["halve_batch"]
    assert res.final_spec.batch == 1
    assert res.history[-1].step == 8


def test_nan_loss_skipped_and_run_converges(tmp_path):
    clean = Trainer.from_spec(_spec(tmp_path, "clean2")).fit()
    res = Trainer.from_spec(
        _spec(tmp_path, "nan", inject_faults="nan@4")).fit()
    assert res.fault_counts["guard_skips"] == 1
    assert np.isfinite(res.final_loss)
    assert all(np.isfinite(r.loss) for r in res.history)
    assert abs(res.final_loss - clean.final_loss) < 0.5


def test_corrupt_checkpoint_falls_back_through_fit(tmp_path):
    res = Trainer.from_spec(
        _spec(tmp_path, "corrupt",
              inject_faults="corrupt@4,crash@5")).fit()
    assert res.history[-1].step == 8
    assert res.fault_counts["ckpt_quarantines"] >= 1
    assert res.fault_counts["injected"] == {"corrupt": 1, "crash": 1}


@pytest.mark.parametrize("engine", ["mesp", "mesp_pallas", "mezo"])
def test_crash_matrix_across_engines(tmp_path, engine):
    kw = {"engine": engine}
    if engine == "mezo":
        kw["lr"] = 1e-3
    res = Trainer.from_spec(
        _spec(tmp_path, f"mx_{engine}", steps=6,
              inject_faults="crash@4", **kw)).fit()
    assert res.history[-1].step == 6
    assert res.fault_counts["injected"] == {"crash": 1}
    assert np.isfinite(res.final_loss)


def test_five_fault_chaos_run_completes(tmp_path):
    """The acceptance chaos plan: faults at 5 distinct steps, one of every
    kind, through Trainer.fit — all steps complete, the run ends on a
    memsim-valid spec, and the final loss lands near the fault-free twin."""
    plan = "oom@2,corrupt@4,crash@5,nan@8,stall@10:0.6"
    spec = _spec(tmp_path, "chaos", steps=12, inject_faults=plan,
                 straggler_factor=8.0, straggler_limit=1)
    clean = Trainer.from_spec(_spec(tmp_path, "chaos_clean", steps=12)).fit()
    res = Trainer.from_spec(spec).fit()
    assert res.history[-1].step == 12
    assert res.fault_counts["injected"] == {
        "oom": 1, "corrupt": 1, "crash": 1, "nan": 1, "stall": 1}
    assert res.fault_counts["straggler_restarts"] == 1
    assert res.fault_counts["ckpt_quarantines"] >= 1
    assert res.degradations == ["halve_batch"]
    peak = predicted_peak_mb(res.final_spec)
    if peak is not None:
        base = predicted_peak_mb(spec)
        assert base is None or peak <= base + 1e-6
    assert abs(res.final_loss - clean.final_loss) < 0.5
    # counters all surfaced in the result
    for key in ("step_failures", "oom_events", "degradations", "guard_skips",
                "straggler_restarts", "ckpt_quarantines", "steps_replayed",
                "backoff_seconds", "injected"):
        assert key in res.fault_counts


def test_chaos_cli_round_trip(tmp_path):
    spec = _spec(tmp_path, "cli", inject_faults="oom@4,nan@7",
                 straggler_limit=1, guard_budget=4)
    assert TrainSpec.from_cli_args(spec.to_cli_args()) == spec
