"""Data pipeline, optimizers, checkpointing, fault tolerance, elasticity."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, latest_step, load_checkpoint, \
    save_checkpoint
from repro.data import DataState, make_batch_iterator, synthetic_corpus
from repro.optim import adamw, compression, sgd, sgd_momentum
from repro.optim.schedules import constant, inverse_sqrt, warmup_cosine
from repro.runtime.elastic import make_mesh_from_devices, rebalance_batch, \
    reshard_tree
from repro.runtime.fault_tolerance import RestartRequired, StragglerPolicy, \
    run_resilient


# ----------------------------------------------------------------- data
def test_data_determinism_and_resume():
    it1 = make_batch_iterator(100, 8, 4, n_tokens=4096, seed=3)
    batches = [next(it1) for _ in range(5)]
    # restart from saved state after 3 batches
    it2 = make_batch_iterator(100, 8, 4, n_tokens=4096, seed=3)
    for _ in range(3):
        next(it2)
    state = DataState.from_dict(it2.state.to_dict())
    it3 = make_batch_iterator(100, 8, 4, n_tokens=4096, seed=3, state=state)
    for i in (3, 4):
        b = next(it3)
        np.testing.assert_array_equal(b["tokens"], batches[i]["tokens"])


def test_data_host_sharding_disjoint():
    full = synthetic_corpus(50, 1 << 14, seed=0)
    b0 = next(make_batch_iterator(50, 8, 8, host_index=0, host_count=2,
                                  corpus=full))
    b1 = next(make_batch_iterator(50, 8, 8, host_index=1, host_count=2,
                                  corpus=full))
    assert b0["tokens"].shape == (4, 8)
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_labels_are_shifted_tokens():
    it = make_batch_iterator(100, 16, 2, n_tokens=4096)
    b = next(it)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# ----------------------------------------------------------------- optim
def _quadratic_params():
    return {"w": {"a": jnp.array([3.0, -2.0]), "b": jnp.array([1.5])},
            "frozen": jnp.array([7.0])}


def _quadratic_grads(p):
    return {"w": {"a": 2 * p["w"]["a"], "b": 2 * p["w"]["b"]}, "frozen": None}


@pytest.mark.parametrize("opt", [sgd(0.1), sgd_momentum(0.05), adamw(0.1)])
def test_optimizers_converge_and_respect_none(opt):
    p = _quadratic_params()
    state = opt.init(p)
    for _ in range(60):
        p, state = opt.update(_quadratic_grads(p), state, p)
    assert float(jnp.abs(p["w"]["a"]).max()) < 0.2
    assert float(p["frozen"][0]) == 7.0  # None grad => untouched


@pytest.mark.parametrize("opt", [sgd(constant(0.1)), sgd_momentum(0.05),
                                 adamw(0.1)])
def test_optimizers_keep_param_dtype(opt):
    # a bf16 param updated with an f32 learning-rate array and f32 grads
    # must stay bf16: a dtype change would retrace the jitted train step
    p = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                               _quadratic_params())
    state = opt.init(p)
    for _ in range(2):
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32),
                                       _quadratic_grads(p))
        p, state = opt.update(grads, state, p)
    assert {x.dtype for x in jax.tree_util.tree_leaves(p)} == {
        jnp.dtype(jnp.bfloat16)}


def test_schedules():
    s = warmup_cosine(1.0, 10, 100)
    assert float(s(jnp.array(5))) == pytest.approx(0.5)
    assert float(s(jnp.array(10))) == pytest.approx(1.0, rel=1e-3)
    assert float(s(jnp.array(100))) == pytest.approx(0.0, abs=1e-6)
    assert float(inverse_sqrt(1.0, 16)(jnp.array(64))) == pytest.approx(0.5)
    assert float(constant(0.3)(jnp.array(9))) == pytest.approx(0.3)


def test_gradient_compression_bf16_roundtrip():
    g = {"x": jnp.linspace(-1, 1, 64), "skip": None}
    gc = compression.from_bf16(compression.to_bf16(g))
    np.testing.assert_allclose(gc["x"], g["x"], rtol=1e-2, atol=1e-2)


def test_topk_error_feedback_conserves_signal():
    g = {"x": jnp.arange(1.0, 9.0)}
    sent1, err = compression.topk_sparsify(g, 0.25)
    assert int(jnp.sum(sent1["x"] != 0)) == 2
    # error feedback: nothing is lost — sent_total + residual == n·g exactly
    total = sent1["x"]
    n = 24
    for _ in range(n - 1):
        sent, err = compression.topk_sparsify(g, 0.25, err)
        total = total + sent["x"]
    np.testing.assert_allclose(total + err["x"], n * g["x"], rtol=1e-5)
    # and the time-average converges toward g
    np.testing.assert_allclose(total / n, g["x"], atol=0.5)


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_retention(tmp_path):
    d = str(tmp_path)
    params = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((3,))}
    opt = {"step": jnp.array(4, jnp.int32)}
    for s in (10, 20, 30, 40):
        save_checkpoint(d, s, params, opt, {"cursor": s}, keep=2)
    assert latest_step(d) == 40
    # retention: only 2 newest kept
    assert sorted(int(p.split("_")[1]) for p in os.listdir(d)
                  if p.startswith("step_")) == [30, 40]
    p2, o2, ds, _ = load_checkpoint(d, 40, params, opt)
    np.testing.assert_array_equal(p2["w"], params["w"])
    assert int(o2["step"]) == 4
    assert ds["cursor"] == 40


def test_checkpoint_detects_corruption(tmp_path):
    d = str(tmp_path)
    params = {"w": jnp.ones((4,))}
    path = save_checkpoint(d, 1, params)
    # corrupt the array file
    fn = [f for f in os.listdir(path) if f.endswith(".npy")][0]
    arr = np.load(os.path.join(path, fn))
    np.save(os.path.join(path, fn), arr + 1)
    with pytest.raises(IOError, match="checksum"):
        load_checkpoint(d, 1, params)


# ---------------------------------------------------------- fault tolerance
def test_run_resilient_recovers_from_injected_failure(tmp_path):
    it = make_batch_iterator(50, 4, 2, n_tokens=2048)
    ckpt = Checkpointer(str(tmp_path), interval=2)
    calls = {"n": 0}

    def step_fn(params, opt_state, batch):
        calls["n"] += 1
        if calls["n"] == 5:  # injected failure mid-training
            raise RuntimeError("simulated device loss")
        return params + 1, opt_state, float(params)

    params, _, results = run_resilient(
        step_fn, lambda: (jnp.array(0.0), None), it, ckpt, total_steps=8)
    assert len(results) == 8 and results[-1].step == 8
    # resumed from the step-4 checkpoint: final params == 8 steps applied
    assert float(params) == 8.0


def test_straggler_policy():
    sp = StragglerPolicy(factor=2.0, consecutive_limit=2)
    assert sp.observe(1.0) == "ok"
    assert sp.observe(1.1) == "ok"
    assert sp.observe(5.0) == "slow"
    assert sp.observe(5.0) == "restart"


def test_straggler_triggers_restart_in_driver(tmp_path):
    import time as _t
    it = make_batch_iterator(50, 4, 2, n_tokens=2048)
    ckpt = Checkpointer(str(tmp_path), interval=100)
    times = iter([0.01, 0.01, 0.01, 1.0, 1.0, 1.0])

    def step_fn(params, opt_state, batch):
        _t.sleep(next(times, 0.01))
        return params, opt_state, 0.0

    with pytest.raises(RestartRequired):
        run_resilient(step_fn, lambda: (jnp.array(0.0), None), it, ckpt,
                      total_steps=6,
                      straggler=StragglerPolicy(factor=3.0,
                                                consecutive_limit=2))


# ----------------------------------------------------------------- elastic
def test_elastic_mesh_and_reshard():
    devs = jax.devices()
    mesh = make_mesh_from_devices(devs, model_parallel=1)
    from jax.sharding import PartitionSpec as P
    tree = {"w": jnp.arange(8.0), "skip": None}
    specs = {"w": P(), "skip": None}
    out = reshard_tree(tree, mesh, specs)
    np.testing.assert_array_equal(out["w"], tree["w"])
    assert rebalance_batch(256, 16, 8) == 32
    with pytest.raises(ValueError, match="cannot be kept invariant"):
        rebalance_batch(256, 16, 7)


# ------------------------------------------------------------------- quant
def test_int8_quantization_roundtrip():
    from repro.core import quant
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.2
    q, s = quant.quantize_int8(w)
    assert q.dtype == jnp.int8
    wd = quant.dequantize_int8(q, s, jnp.float32)
    np.testing.assert_allclose(wd, w, atol=float(2 * np.abs(w).max() / 127))


def test_quantize_frozen_skips_lora():
    from repro.core import quant
    params = {"attn": {"q": {"w": jnp.ones((8, 8)),
                             "a": jnp.ones((8, 2)), "b": jnp.zeros((2, 8))}}}
    qp = quant.quantize_frozen(params)
    assert "q" in qp["attn"]["q"]["w"]           # frozen weight quantized
    assert qp["attn"]["q"]["a"].dtype == jnp.float32  # LoRA untouched
    w = quant.maybe_dequant(qp["attn"]["q"]["w"], jnp.float32)
    np.testing.assert_allclose(w, params["attn"]["q"]["w"], atol=0.02)
