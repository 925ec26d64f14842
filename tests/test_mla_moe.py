"""Latent attention, sigmoid routing and the dropless expert share
(Moonlight-16B-A3B's block) on the CPU: routing against a hand count, the
flash kernels at a q·k width other than v's, the Qwen flash program left
as it was, expert shares that add up to the uncut layer, and the step's
expert counters read through ``Trainer.fit``."""
import dataclasses
import hashlib
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Trainer, TrainSpec
from repro.api.policy import ExecutionPolicy
from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.rope import rope_tables
from repro.models import layers, moe


def _tiny(n_held=0, held_lo=0, **moe_kw):
    """Moonlight's block at CPU widths: q·k 16 + 8 against v 12, 8 experts,
    top-3, sigmoid plus a correction bias, 1 shared expert, dense layer 0."""
    c = get_config("moonlight-16b-a3b")
    return dataclasses.replace(
        c, n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=32,
        vocab=256, dtype="float32",
        moe=dataclasses.replace(c.moe, n_experts=8, top_k=3, d_expert=32,
                                n_shared=1, d_dense=96, n_held=n_held,
                                held_lo=held_lo, **moe_kw),
        mla=dataclasses.replace(c.mla, kv_lora_rank=32, qk_nope_head_dim=16,
                                qk_rope_head_dim=8, v_head_dim=12),
        lora=dataclasses.replace(c.lora, rank=4, alpha=8.0))


def _moe_params(cfg, seed=0):
    from repro.models.model import init_params
    p = init_params(jax.random.PRNGKey(seed), cfg)["blocks"]["moe"]
    p = jax.tree_util.tree_map(lambda t: t[0], p)
    k = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    p["score_bias"] = jax.random.normal(k[0], (cfg.moe.n_experts,)) * 0.1
    for i, t in enumerate(("gate", "up", "down")):
        p[t]["b"] = jax.random.normal(k[i + 1], p[t]["b"].shape) * 0.1
    return p


def test_registry_entries():
    full, share = (get_config("moonlight-16b-a3b"),
                   get_config("moonlight-16b-a3b-ep8"))
    assert (full.n_layers, full.vocab, full.moe.resolved_n_held) == \
        (27, 163840, 64)
    assert (share.n_layers, share.vocab, share.moe.resolved_n_held,
            share.moe.n_experts) == (6, 20480, 8, 64)
    assert share.mla == full.mla and share.mla.qk_head_dim == 192
    assert share.moe.resolved_d_dense == 11264


def test_routing_against_a_hand_count():
    cfg = _tiny()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, 64)).astype(np.float32)
    router = (rng.normal(size=(64, 8)) / 8).astype(np.float32)
    bias = (rng.normal(size=(8,)) * 0.3).astype(np.float32)
    w, idx = moe.route({"router": jnp.asarray(router),
                        "score_bias": jnp.asarray(bias)}, jnp.asarray(x), cfg)
    scores = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ router)))
    for t in range(5):
        chosen = np.argsort(-(scores[t] + bias), kind="stable")[:3]
        top = scores[t, chosen]
        want = top / top.sum() * 2.446
        assert sorted(np.asarray(idx[t])) == sorted(chosen)
        got = dict(zip(np.asarray(idx[t]), np.asarray(w[t])))
        for e, v in zip(chosen, want):
            assert got[e] == pytest.approx(v, rel=1e-5)
    # the bias chooses, the scores weigh: without it the choice differs
    assert any(sorted(np.argsort(-scores[t])[:3])
               != sorted(np.asarray(idx[t])) for t in range(5))


def _attn_ref(q, k, v):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") / d ** 0.5
    n = q.shape[2]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                      precision="highest")


def test_flash_qk_192_v_128_matches_jnp():
    """Forward and backward of the flash kernels (interpret mode) with a
    q·k width of 192 and a v width of 128."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    B, H, N = 1, 2, 256
    q = jax.random.normal(ks[0], (B, H, N, 192))
    k = jax.random.normal(ks[1], (B, H, N, 192))
    v = jax.random.normal(ks[2], (B, H, N, 128))
    g = jax.random.normal(ks[3], (B, H, N, 128))
    out, vjp = jax.vjp(lambda q, k, v: ops.flash_attention(
        q, k, v, True, 0, True), q, k, v)
    ref, ref_vjp = jax.vjp(_attn_ref, q, k, v)
    assert out.shape == (B, H, N, 128)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    for a, b in zip(vjp(g), ref_vjp(g)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(b).max()))


# sha256 of the jaxpr of the Qwen2.5-0.5B flash call (14 q heads over 2 kv
# heads of 64, 256 positions, bf16; value and grad), plain and with rope
# fused, as the kernels were before they took a v width of their own
QWEN_FLASH_JAXPR = {
    False: "33ee13fd74d994c290676b1e30278f3f46f977adfcd8271288626a00a134f96c",
    True: "c97907f68591ca858f54c56e21b9afeda2c8fe51a6af012deff5da3ca4eecf77",
}


@pytest.mark.parametrize("fused", [False, True])
def test_qwen_flash_program_unchanged(fused):
    B, H, HKV, N, D = 1, 14, 2, 256, 64
    q = jax.ShapeDtypeStruct((B, H, N, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, HKV, N, D), jnp.bfloat16)
    rope = rope_tables(jnp.arange(N), 1e6, D) if fused else None

    def f(q, k, v):
        return ops.flash_attention(q, k, v, True, 0, False,
                                   rope).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1, 2)))(q, k, k))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        QWEN_FLASH_JAXPR[fused]


@pytest.mark.parametrize("backend", ["pallas", "structured"])
def test_four_shares_add_up_to_the_uncut_layer(backend):
    """Each of 4 shares of 2 experts run alone; their routed parts add up
    to the uncut layer's, with the shared expert counted once."""
    pol = ExecutionPolicy(backend=backend)
    uncut = _tiny()
    p = _moe_params(uncut)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 64))
    whole, st = moe.moe_mlp(p, x, uncut, policy=pol)
    shared = layers.mlp(p["shared"], x, uncut, policy=pol)
    total = shared
    routed = 0
    for lo in range(0, 8, 2):
        cfg = _tiny(n_held=2, held_lo=lo)
        part = {**p, **{t: jax.tree_util.tree_map(
            lambda a: a[lo:lo + 2], p[t]) for t in ("gate", "up", "down")}}
        y, s = moe.moe_mlp(part, x, cfg, policy=pol)
        total = total + (y - shared)
        routed += int(s["routed_rows"])
        assert int(s["dropped"]) == 0
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)
    assert routed == int(st["routed_rows"]) == 2 * 40 * 3


def test_skewed_routing_drops_nothing():
    """Every token routed to one held expert: its rows are T, far past
    any capacity bound, and the layer still equals the per-expert sum."""
    cfg = _tiny(n_held=2, held_lo=0)
    p = _moe_params(cfg)
    p["score_bias"] = p["score_bias"].at[1].set(100.0)
    part = {**p, **{t: jax.tree_util.tree_map(lambda a: a[:2], p[t])
                    for t in ("gate", "up", "down")}}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 64))
    pol = ExecutionPolicy(backend="pallas")
    y, s = moe.moe_mlp(part, x, cfg, policy=pol)
    w, idx = moe.route(p, x.reshape(80, 64), cfg)
    assert bool(jnp.all(jnp.any(idx == 1, -1)))
    want = layers.mlp(p["shared"], x, cfg).reshape(80, 64)
    xs = x.reshape(80, 64)
    for e in range(2):
        q = {t: jax.tree_util.tree_map(lambda a: a[e], p[t])
             for t in ("gate", "up", "down")}
        ye = layers.mlp(q, xs, cfg)
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        want = want + we[:, None] * ye
    np.testing.assert_allclose(y.reshape(80, 64), want, rtol=2e-5,
                               atol=2e-5)
    assert int(s["dropped"]) == 0
    assert int(s["routed_rows"]) == int(jnp.sum(idx < 2))
    assert int(s["computed_rows"]) >= int(s["routed_rows"])


def test_fit_reads_the_expert_counters_with_the_loss():
    """Trainer.fit on the reduced Moonlight, mesp_pallas: one host sync a
    step, the expert counters summed over the steps, nothing dropped, no
    kernel fallback."""
    spec = TrainSpec(arch="moonlight-16b-a3b", reduced=True,
                     engine="mesp_pallas", steps=2, batch=2, seq=64,
                     ckpt_interval=10 ** 9, log_interval=10 ** 9,
                     guard="off", degrade="off", quiet=True)
    before = dict(ops.FALLBACKS)
    with tempfile.TemporaryDirectory() as d:
        tr = Trainer.from_spec(dataclasses.replace(spec, ckpt_dir=d))
        tr.fit()
    assert tr.counters["host_syncs"] == tr.counters["steps"] == 2
    c = dict(tr.moe_counters)
    m = tr.cfg.moe
    # 2 steps × one MoE layer × 128 tokens × top_k slots, all held
    assert c["routed_rows"] == 2 * (tr.cfg.n_layers - 1) * 128 * m.top_k
    assert c["computed_rows"] >= c["routed_rows"] and c["dropped"] == 0
    assert dict(ops.FALLBACKS) == before


@pytest.mark.parametrize("backend", ["pallas", "structured", "plain"])
@pytest.mark.parametrize("quantized", [False, True])
def test_expert_linear_matches_a_per_expert_loop(backend, quantized):
    """Rows packed by expert on a device-side schedule: expert 0 two
    tiles, expert 1 none, expert 2 one, then three tiles past the routed
    rows; value and gradients against a masked loop over the experts."""
    from repro.core import quant

    E, K, N, r, bm = 3, 40, 24, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    w = jax.random.normal(ks[0], (E, K, N)) * 0.2
    a = jax.random.normal(ks[1], (E, K, r)) * 0.3
    b = jax.random.normal(ks[2], (E, r, N)) * 0.3
    gid, live = jnp.array([0, 0, 2, 2, 2, 2], jnp.int32), 3
    x = jax.random.normal(ks[3], (6 * bm, K)).at[3 * bm:].set(0.0)
    if quantized:
        q, s = quant.quantize_int8(w)
        w = {"q": q, "scale": s}
    wd = quant.maybe_dequant(w, jnp.float32)
    rows = jnp.repeat(jnp.where(jnp.arange(6) < live, gid, -1), bm)
    pol = ExecutionPolicy(backend=backend)

    def got(x, a, b):
        return jnp.sum(jnp.tanh(ops.lora_expert_linear(
            x, w, a, b, gid, live, 2.0, bm=bm, policy=pol)))

    def want(x, a, b):
        y = sum(jnp.where((rows == e)[:, None],
                          x @ wd[e] + 2.0 * (x @ a[e]) @ b[e], 0.0)
                for e in range(E))
        return jnp.sum(jnp.tanh(y))

    (lg, gg), (lw, gw) = (jax.value_and_grad(f, (0, 1, 2))(x, a, b)
                          for f in (got, want))
    assert float(lg) == pytest.approx(float(lw), rel=1e-5)
    for u, v in zip(gg, gw):
        np.testing.assert_allclose(u, v, rtol=1e-4, atol=2e-5)
