"""Grouped LoRA kernel family (``kernels/lora_grouped.py``) end-to-end.

Three layers of guarantees, mirroring test_quant_mode's structure:

1. **Equivalence**: the grouped kernel (one launch, per-tile adapter gather
   by scalar-prefetched index) matches the per-adapter Python loop it
   replaces — forward and all gradients (x, A, B) ≤1e-5 relative — across
   ragged group sizes, empty groups, a single group, non-tile-aligned
   feature dims, and int8 frozen bases.
2. **Routing**: ``lora_grouped_decode`` (the serving path: shared base +
   stacked adapters, runtime int32 tile routing) matches the gather
   reference for arbitrary — including repeated and non-contiguous —
   slot assignments, and re-routing does not retrace the jitted step.
3. **Lifecycle**: on the quantized grouped path no dense float W0-shaped
   array is ever produced outside ``pallas_call`` — dequantization happens
   tile-wise in VMEM, so MoE/multi-tenant serving never pays an HBM
   [E, K, N] float materialization. Plus the model-level contract: a
   pallas-mode MoE forward/backward (bf16-f32 and int8 bases, expert
   linears routed through the grouped kernel) matches structured mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import mesp, quant
from repro.kernels import ops, tiling
from repro.models import model as M

# deliberately non-tile-aligned: K=72, N=88 are not multiples of the 128
# lane block (nor of 8); r=6 is an odd rank
K, N, R = 72, 88, 6


def _mats(E, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    w0 = jax.random.normal(ks[0], (E, K, N)) * 0.1
    a = jax.random.normal(ks[1], (E, K, R)) * 0.3
    b = jax.random.normal(ks[2], (E, R, N)) * 0.3
    return w0, a, b


def _loop_ref(x, sizes, w0, a, b, scale=2.0):
    """The per-adapter loop the grouped kernel replaces: slice each group's
    rows, dense matmul + 2-D LoRA with its own (A, B)."""
    outs, off = [], 0
    for g, s in enumerate(sizes):
        if s == 0:
            continue
        xg = x[off:off + s]
        wg = quant.maybe_dequant(
            {"q": w0["q"][g], "scale": w0["scale"][g]}
            if quant.is_quantized(w0) else w0[g], x.dtype)
        outs.append(xg @ wg + scale * ((xg @ a[g]) @ b[g]))
        off += s
    if not outs:
        return jnp.zeros((0, b.shape[-1]), x.dtype)
    return jnp.concatenate(outs)


def _rel(u, v):
    fu = jnp.concatenate([t.reshape(-1) for t in jax.tree_util.tree_leaves(u)])
    fv = jnp.concatenate([t.reshape(-1) for t in jax.tree_util.tree_leaves(v)])
    return float(jnp.linalg.norm(fu - fv) /
                 jnp.maximum(jnp.linalg.norm(fv), 1e-30))


# ------------------------------------------------------------- equivalence


@pytest.mark.parametrize("sizes", [
    (5, 11, 3),            # ragged, nothing bm-aligned
    (8, 0, 13, 0, 2),      # empty groups interleaved
    (17,),                 # E = 1 degenerates to a plain LoRA linear
    (0, 0, 9),             # leading groups empty
])
@pytest.mark.parametrize("quantized", [False, True])
def test_ragged_matches_per_adapter_loop(sizes, quantized):
    E = len(sizes)
    w0, a, b = _mats(E)
    if quantized:
        q, s = quant.quantize_int8(w0)
        w0 = {"q": q, "scale": s}
    x = jax.random.normal(jax.random.PRNGKey(9), (sum(sizes), K)) * 0.3

    def f_grouped(x, a, b):
        y = ops.lora_grouped_ragged(x, sizes, w0, a, b, 2.0)
        return jnp.sum(jnp.tanh(y)), y

    def f_loop(x, a, b):
        y = _loop_ref(x, sizes, w0, a, b)
        return jnp.sum(jnp.tanh(y)), y

    (lg, yg), gg = jax.value_and_grad(f_grouped, (0, 1, 2),
                                      has_aux=True)(x, a, b)
    (ll, yl), gl = jax.value_and_grad(f_loop, (0, 1, 2),
                                      has_aux=True)(x, a, b)
    np.testing.assert_allclose(np.asarray(yg), np.asarray(yl),
                               rtol=1e-5, atol=1e-5)
    # The loss is a float32 sum of ~2k tanh terms that cancels to |loss| < 1
    # against a mass sum|tanh(y)| ~ 1e3, and the two programs reduce it in
    # different orders: the sums can differ by one f32 rounding of the mass
    # even when every y agrees to an ulp. The bound is the larger of 1e-6
    # relative and that one rounding.
    mass = float(jnp.sum(jnp.abs(jnp.tanh(yl))))
    tol = max(1e-6 + 1e-6 * abs(float(ll)),
              float(jnp.finfo(jnp.float32).eps) * mass)
    assert abs(float(lg) - float(ll)) <= tol, (float(lg), float(ll), tol)
    assert _rel(gg, gl) <= 1e-5
    # dA rows of empty groups are exactly zero (no tiles launched for them)
    for g, sz in enumerate(sizes):
        if sz == 0:
            assert float(jnp.abs(gg[1][g]).max()) == 0.0
            assert float(jnp.abs(gg[2][g]).max()) == 0.0


@pytest.mark.parametrize("quantized", [False, True])
def test_moe_shape_matches_loop(quantized):
    """The batched-uniform [E, C, ·] entry point (MoE expert linears)."""
    E, C = 3, 13
    w0, a, b = _mats(E, seed=2)
    if quantized:
        q, s = quant.quantize_int8(w0)
        w0 = {"q": q, "scale": s}
    x = jax.random.normal(jax.random.PRNGKey(4), (E, C, K)) * 0.3

    def f_grouped(x, a, b):
        return jnp.sum(jnp.tanh(ops.lora_grouped_linear(x, w0, a, b, 2.0)))

    def f_loop(x, a, b):
        y = _loop_ref(x.reshape(E * C, K), (C,) * E, w0, a, b)
        return jnp.sum(jnp.tanh(y))

    lg, gg = jax.value_and_grad(f_grouped, (0, 1, 2))(x, a, b)
    ll, gl = jax.value_and_grad(f_loop, (0, 1, 2))(
        x, a, b)
    np.testing.assert_allclose(float(lg), float(ll), rtol=1e-6)
    assert _rel((gg[0].reshape(E * C, K), gg[1], gg[2]),
                (gl[0], gl[1], gl[2])) <= 1e-5


def test_schedule_pack_unpack_roundtrip():
    sizes, bm = (5, 0, 11, 2), 8
    x = jax.random.normal(jax.random.PRNGKey(0), (sum(sizes), 7))
    xp = tiling.pack_ragged_rows(x, sizes, bm)
    gid, offs = tiling.grouped_schedule(sizes, bm)
    assert xp.shape[0] == int(offs[-1]) == len(gid) * bm
    assert list(gid) == [0, 2, 2, 3]          # empty group 1 launches nothing
    np.testing.assert_array_equal(
        np.asarray(tiling.unpack_ragged_rows(xp, sizes, bm)), np.asarray(x))
    stats = tiling.grouped_schedule_stats(sizes, bm)
    assert stats["live_tiles"] == 4 and stats["empty_groups"] == 1
    assert stats["dense_tiles"] == len(sizes) * 2   # cmax=11 -> 2 tiles each
    assert stats["grid_fraction"] == pytest.approx(0.5)


# ----------------------------------------------------------------- routing


@pytest.mark.parametrize("quantized", [False, True])
def test_decode_runtime_routing_matches_reference(quantized):
    """Serving path: stacked adapters + shared base, tile_gid routed at
    runtime (repeated + non-contiguous slots), pallas vs gather reference."""
    from repro.api.policy import ExecutionPolicy
    Rslots, bm, Mrows = 5, 8, 48
    w0, a, b = _mats(Rslots, seed=7)
    w0 = w0[0]                                # shared base [K, N]
    if quantized:
        q, s = quant.quantize_int8(w0)
        w0 = {"q": q, "scale": s}
    x = jax.random.normal(jax.random.PRNGKey(11), (Mrows, K)) * 0.3
    pol = ExecutionPolicy(backend="pallas")
    step = jax.jit(lambda x, g: ops.lora_grouped_decode(
        x, w0, a, b, g, None, 2.0, bm=bm, policy=pol))
    for gid in ([3, 3, 0, 4, 1, 2], [0, 0, 0, 0, 0, 0], [4, 2, 4, 2, 4, 2]):
        g = jnp.asarray(gid, jnp.int32)
        ref = ops.lora_grouped_decode(x, w0, a, b, g, None, 2.0, bm=bm,
                                      policy=None)   # jnp gather reference
        np.testing.assert_allclose(np.asarray(step(x, g)), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
    # runtime routing: all three gid vectors reused ONE compiled step
    assert step._cache_size() == 1


def test_decode_rejects_unaligned_rows():
    w0, a, b = _mats(2)
    x = jnp.zeros((10, K))
    with pytest.raises(ValueError, match="not a multiple"):
        ops.lora_grouped_decode(x, w0[0], a, b, jnp.zeros(2, jnp.int32),
                                bm=8)


# --------------------------------------------------------------- lifecycle


from tests.test_quant_mode import _float_w0_shapes  # noqa: E402


def test_no_dense_expert_w0_on_grouped_quant_path():
    """fwd+bwd of the quantized grouped op never materialize a float
    [E, K, N] (or per-expert [K, N]) array outside pallas_call — the
    per-tile dequant is the whole point of the int8 grouped kernel."""
    E, C = 3, 16
    w0, a, b = _mats(E, seed=5)
    q, s = quant.quantize_int8(w0)
    x = jax.random.normal(jax.random.PRNGKey(6), (E, C, K)) * 0.3

    def loss(x, a, b):
        y = ops.lora_grouped_linear(x, {"q": q, "scale": s}, a, b, 2.0,
                                    interpret=True)
        return jnp.sum(y * y)

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, a, b)
    hits = _float_w0_shapes(jaxpr.jaxpr, {(E, K, N), (K, N), (N, K)})
    assert not hits, f"dense W0 materialized outside kernels: {hits}"


def test_structured_moe_fallback_does_materialize_w0():
    """Sanity for the guard above: the structured dequant fallback *does*
    produce the dense [E, K, N]."""
    E = 3
    w0, a, b = _mats(E, seed=5)
    q, s = quant.quantize_int8(w0)
    x = jax.random.normal(jax.random.PRNGKey(6), (E, 16, K)) * 0.3

    def loss(x, a, b):
        w = quant.dequantize_int8(q, s, x.dtype)
        return jnp.sum(jnp.square(x @ w + 2.0 * ((x @ a) @ b)))

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, a, b)
    assert _float_w0_shapes(jaxpr.jaxpr, {(E, K, N)})


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_moe_model_pallas_matches_structured(quantize):
    """Model-level contract: pallas-mode MoE (expert linears through the
    grouped kernel, int8 dequant-in-VMEM included) reproduces structured
    mode's loss and LoRA gradients."""
    cfg = get_config("olmoe-1b-7b").reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg, quantize=quantize)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    l_s, g_s = mesp.value_and_grad(params, cfg, batch, mode="structured")
    l_p, g_p = mesp.value_and_grad(params, cfg, batch, mode="pallas")
    np.testing.assert_allclose(float(l_p), float(l_s), rtol=1e-5)
    assert _rel(g_p, g_s) <= 1e-5


def _host_mesh():
    """A (data, model) mesh over the one CPU device: enough for a policy
    whose ``act_spec`` names mesh axes, which selects the expert layer's
    capacity path (the multi-chip launchers' explicitly sharded call)."""
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


_ACT = jax.sharding.PartitionSpec("data", "model", None)


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_moe_capacity_path_pallas_matches_structured(quantize):
    """The capacity path under an explicit ``act_spec``: pallas mode (the
    grouped kernel over the [E, B·C] expert buffers) reproduces structured
    mode's loss and LoRA gradients."""
    cfg = get_config("olmoe-1b-7b").reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg, quantize=quantize)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    with _host_mesh():
        l_s, g_s = mesp.value_and_grad(params, cfg, batch, mode="structured",
                                       act_spec=_ACT)
        l_p, g_p = mesp.value_and_grad(params, cfg, batch, mode="pallas",
                                       act_spec=_ACT)
    np.testing.assert_allclose(float(l_p), float(l_s), rtol=1e-5)
    assert _rel(g_p, g_s) <= 1e-5


def test_moe_capacity_path_counts_dropped_slots_at_skew():
    """A router of zeros sends every token to experts 0 and 1 (ties go to
    the lowest ids): with capacity C = 24 rows a sequence for 32 slots,
    the capacity path drops 8 slots per expert, sequence and layer, and
    the dropless path, on the same batch, none."""
    from repro.api.policy import ExecutionPolicy

    cfg = get_config("olmoe-1b-7b").reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    params["blocks"]["moe"]["router"] = jnp.zeros_like(
        params["blocks"]["moe"]["router"])
    B, N = 2, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, N), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    m, L = cfg.moe, cfg.n_layers
    C = 24
    with _host_mesh():
        _, cap = M.loss_and_aux(params, cfg, batch, policy=ExecutionPolicy(
            backend="structured", act_spec=_ACT))
    _, drop = M.loss_and_aux(params, cfg, batch)
    routed = B * N * m.top_k * L
    assert int(cap["routed_rows"]) == routed == int(drop["routed_rows"])
    assert int(cap["dropped"]) == B * 2 * (N - C) * L
    assert int(cap["computed_rows"]) == m.n_experts * B * C * L
    assert int(drop["dropped"]) == 0
