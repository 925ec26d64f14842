"""Quantized frozen base weights (``--quantize int8|int4|nf4``) end-to-end.

Three layers of guarantees:

1. **Format**: int8 symmetric per-output-channel round-trip error is bounded
   by half a quantization step per channel; the packed 4-bit formats
   round-trip through the nibble packer at every K parity (the ragged
   odd-K boundary pads with the format's zero nibble), survive all-zero
   columns (scale guard), and the nf4 codebook is strictly monotone;
   ``quantize_frozen`` rewrites exactly the frozen ``w`` leaves and nothing
   else, for every method.
2. **Equivalence**: with the *same* quantized weights, the pallas kernel
   path (int8 dequant / int4-nf4 nibble-unpack in VMEM), the structured jnp
   path (dequantized dense W0) and plain autodiff over the explicitly
   dequantized model all produce the same loss and gradients (≤1e-5
   relative) on non-tile-aligned shapes — the quantized analogue of
   test_pallas_mode's contract.
3. **Lifecycle**: on the kernel path no dense (float) W0-shaped array is
   ever produced outside the Pallas kernels — the dequant-in-VMEM claim,
   checked on the jaxpr for int8 and both packed formats.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core import mesp, quant
from repro.kernels import ops, ref
from repro.models import model as M

# Same deliberately non-tile-aligned shape family as test_pallas_mode: none
# of d_model 160 / d_ff 192 / vocab 97 / seq 96 is a multiple of the 128
# block size. f32 so 1e-5 is meaningful.
CFG = ArchConfig(name="quant-test", family="dense", n_layers=2, d_model=160,
                 n_heads=4, n_kv_heads=2, d_ff=192, vocab=97,
                 qkv_bias=True, dtype="float32")


def _batch(seq=96, batch=2):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                CFG.vocab)
    return {"tokens": tokens, "labels": tokens}


def _flat(tree):
    return jnp.concatenate([t.reshape(-1).astype(jnp.float32)
                            for t in jax.tree_util.tree_leaves(tree)])


def _rel(a, b):
    fa, fb = _flat(a), _flat(b)
    return float(jnp.linalg.norm(fa - fb) /
                 jnp.maximum(jnp.linalg.norm(fb), 1e-30))


@pytest.fixture(scope="module")
def qparams():
    return M.init_params(jax.random.PRNGKey(0), CFG, quantize="int8")


# --------------------------------------------------------------- format


def test_roundtrip_error_bound():
    """|w − dq(q,s)| ≤ s/2 per output channel (round-to-nearest, no
    clipping beyond ±127 by construction of s = amax/127)."""
    w = jax.random.normal(jax.random.PRNGKey(3), (96, 130)) * \
        jnp.exp(jax.random.normal(jax.random.PRNGKey(4), (1, 130)))
    q, s = quant.quantize_int8(w)
    wd = quant.dequantize_int8(q, s, jnp.float32)
    err = jnp.abs(wd - w)
    assert bool(jnp.all(err <= 0.5 * s + 1e-7))
    # the bound is tight-ish: worst channel error above a quarter step
    assert float(jnp.max(err / s)) > 0.25


def test_roundtrip_exact_at_grid_points():
    """Values already on the int8 grid survive the round trip exactly."""
    s = jnp.array([[0.03]], jnp.float32)
    w = (jnp.arange(-127, 128, dtype=jnp.float32)[:, None] * s)
    q, s2 = quant.quantize_int8(w)
    np.testing.assert_allclose(quant.dequantize_int8(q, s2, jnp.float32), w,
                               rtol=0, atol=1e-7)


def test_quantize_frozen_rewrites_only_w(qparams):
    dense = M.init_params(jax.random.PRNGKey(0), CFG)
    attn = qparams["blocks"]["attn"]["q"]
    assert quant.is_quantized(attn["w"]) and attn["w"]["q"].dtype == jnp.int8
    assert attn["a"].dtype == jnp.float32        # LoRA factors untouched
    assert attn["bias"].dtype == jnp.float32     # bias untouched
    assert qparams["embed"]["tok"].dtype == jnp.float32  # embeddings too
    # trainable set identical to the dense tree's
    tm_q = M.trainable_mask(qparams)
    n_train = sum(bool(m) for m in jax.tree_util.tree_leaves(tm_q))
    tm_d = M.trainable_mask(dense)
    assert n_train == sum(bool(m) for m in jax.tree_util.tree_leaves(tm_d))


# ------------------------------------------------------- packed 4-bit fmt


@pytest.mark.parametrize("k", [1, 2, 7, 96, 97])
def test_pack_unpack_roundtrip_all_parities(k):
    """pack→unpack is the identity for every K parity; the ragged odd-K
    boundary stores the pad nibble without disturbing real rows."""
    nib = jax.random.randint(jax.random.PRNGKey(k), (k, 13), 0, 16,
                             dtype=jnp.int32).astype(jnp.uint8)
    packed = quant.pack_nibbles(nib, pad_value=quant.NF4_ZERO_NIBBLE)
    assert packed.shape == ((k + 1) // 2, 13) and packed.dtype == jnp.uint8
    np.testing.assert_array_equal(quant.unpack_nibbles(packed, k), nib)
    if k % 2:  # the pad nibble is exactly the requested value
        np.testing.assert_array_equal(
            quant.unpack_nibbles(packed)[-1], quant.NF4_ZERO_NIBBLE)


@pytest.mark.parametrize("method", ["int4", "nf4"])
@pytest.mark.parametrize("k", [97, 96])
def test_packed_roundtrip_error_bound(method, k):
    """Quantize→dequantize error per output channel is bounded by half the
    format's coarsest step (int4: s; nf4: the widest codebook gap × s)."""
    w = jax.random.normal(jax.random.PRNGKey(5), (k, 130)) * \
        jnp.exp(jax.random.normal(jax.random.PRNGKey(6), (1, 130)))
    leaf = quant.quantize_leaf(w, method)
    assert leaf["q4"].shape == ((k + 1) // 2, 130)
    assert ("kpad" in leaf) == bool(k % 2)
    assert quant.packed_k(leaf) == k
    wd = quant.dequantize_packed(leaf["q4"], leaf["scale"], method,
                                 jnp.float32, k=k)
    if method == "int4":
        step = leaf["scale"]          # grid spacing = scale (q ∈ [-7, 7])
    else:
        code = jnp.asarray(quant.NF4_CODE)
        step = float(jnp.max(jnp.diff(code))) * leaf["scale"]
    assert bool(jnp.all(jnp.abs(wd - w) <= 0.5 * step + 1e-6))


@pytest.mark.parametrize("method", ["int4", "nf4"])
def test_packed_all_zero_columns(method):
    """All-zero output channels must not divide by zero: scale is guarded
    and the round trip returns exact zeros (no NaN/Inf)."""
    w = jax.random.normal(jax.random.PRNGKey(7), (33, 6)) * 0.1
    w = w.at[:, ::2].set(0.0)
    leaf = quant.quantize_leaf(w, method)
    wd = quant.dequantize_packed(leaf["q4"], leaf["scale"], method,
                                 jnp.float32, k=33)
    assert bool(jnp.all(jnp.isfinite(wd)))
    np.testing.assert_array_equal(wd[:, ::2], 0.0)


def test_nf4_codebook_monotone_with_exact_zero():
    code = np.asarray(quant.NF4_CODE)
    assert code.shape == (16,)
    assert bool(np.all(np.diff(code) > 0))          # strictly increasing
    assert code[quant.NF4_ZERO_NIBBLE] == 0.0       # pad nibble is exact 0
    assert code[0] == -1.0 and code[-1] == 1.0


def test_nf4_quantize_picks_nearest_code():
    """searchsorted-on-midpoints must equal the brute-force nearest code."""
    w = jax.random.normal(jax.random.PRNGKey(8), (40, 9))
    leaf = quant.quantize_leaf(w, "nf4")
    nib = quant.unpack_nibbles(leaf["q4"], 40)
    code = jnp.asarray(quant.NF4_CODE)
    brute = jnp.argmin(
        jnp.abs(w[..., None] / leaf["scale"][..., None] - code), axis=-1)
    np.testing.assert_array_equal(nib, brute.astype(nib.dtype))


@pytest.mark.parametrize("method", ["int4", "nf4"])
def test_quantize_frozen_packed_rewrites_only_w(method):
    qp = M.init_params(jax.random.PRNGKey(0), CFG, quantize=method)
    attn = qp["blocks"]["attn"]["q"]
    assert quant.is_packed(attn["w"])
    assert attn["w"]["q4"].dtype == jnp.uint8
    assert quant.packed_method(attn["w"]) == method
    assert attn["a"].dtype == jnp.float32
    assert qp["embed"]["tok"].dtype == jnp.float32
    # stacked block leaves keep a uniform leading axis (scan contract)
    lead = {v.shape[0] for v in jax.tree_util.tree_leaves(qp["blocks"])}
    assert lead == {CFG.n_layers}


def test_requantize_int8_to_int4_transition():
    """The degradation ladder's int8→int4 rung is a plain re-call: already
    quantized leaves are dequantized and re-packed, not double-quantized."""
    w = jax.random.normal(jax.random.PRNGKey(9), (96, 130)) * 0.1
    tree = {"w": dict(quant.quantize_leaf(w, "int8")), "a": w[:, :4]}
    tree4 = quant.quantize_params({"x": tree}, "int4")["x"]
    assert quant.is_packed(tree4["w"])
    w8 = quant.maybe_dequant(tree["w"], jnp.float32)
    w4 = quant.maybe_dequant(tree4["w"], jnp.float32)
    # error vs the int8 stage it was re-packed from, not vs the original
    assert float(jnp.max(jnp.abs(w4 - w8))) <= \
        float(jnp.max(tree4["w"]["scale"])) * 0.5 + 1e-6
    np.testing.assert_array_equal(tree4["a"], tree["a"])  # LoRA untouched


# ----------------------------------------------------------- equivalence


@pytest.mark.parametrize("seq", [96, 48])
def test_quant_pallas_grads_match_quant_structured(qparams, seq):
    """Quantized-pallas vs quantized-structured ≤1e-5 relative; seq 96
    exercises the flash kernel, seq 48 the attention fallback."""
    batch = _batch(seq=seq)
    l_s, g_s = mesp.value_and_grad(qparams, CFG, batch, mode="structured")
    l_p, g_p = mesp.value_and_grad(qparams, CFG, batch, mode="pallas")
    np.testing.assert_allclose(float(l_p), float(l_s), rtol=1e-6)
    assert _rel(g_p, g_s) <= 1e-5


def test_quant_pallas_grads_match_dequant_oracle(qparams):
    """The unquantized-dequant oracle: plain autodiff over a dense model
    whose weights are the explicitly dequantized q·s."""
    dense = jax.tree_util.tree_map(
        lambda p: quant.maybe_dequant(p, jnp.float32) if quant.is_quantized(p)
        else p, qparams, is_leaf=quant.is_quantized)
    batch = _batch()
    _, g_oracle = mesp.value_and_grad(dense, CFG, batch, mode="plain")
    _, g_p = mesp.value_and_grad(qparams, CFG, batch, mode="pallas")
    assert _rel(g_p, g_oracle) <= 1e-5


def test_quant_train_step_descends_and_matches(qparams):
    batch = _batch()
    p_s, _ = mesp.train_step(qparams, CFG, batch, 1e-2, mode="structured")
    p_p, l0 = mesp.train_step(qparams, CFG, batch, 1e-2, mode="pallas")
    for a, b in zip(jax.tree_util.tree_leaves(p_p),
                    jax.tree_util.tree_leaves(p_s)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
    p = p_p
    for _ in range(3):
        p, l = mesp.train_step(p, CFG, batch, 5e-2, mode="pallas")
    assert float(l) < float(l0)


def test_quant_kernel_matches_ref_oracle():
    """ops-level: quantized kernel vs the jnp oracle on the dequantized W0."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (192, 160)) * 0.3
    w = jax.random.normal(jax.random.PRNGKey(1), (160, 200)) * 0.05
    a = jax.random.normal(jax.random.PRNGKey(2), (160, 8)) * 0.3
    b = jax.random.normal(jax.random.PRNGKey(3), (8, 200)) * 0.3
    q, s = quant.quantize_int8(w)
    wd = quant.dequantize_int8(q, s, jnp.float32)
    y = ops.lora_linear(x, {"q": q, "scale": s}, a, b, None, 2.0)
    np.testing.assert_allclose(y, ref.lora_fused_ref(x, wd, a, b, 2.0),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("method", ["int4", "nf4"])
def test_packed_pallas_grads_match_structured_and_oracle(method):
    """Packed-pallas ≡ packed-structured ≡ dequant-oracle (≤1e-5 relative)
    on the non-tile-aligned model — the packed analogue of the int8
    contract above, in one pass per method."""
    qp = M.init_params(jax.random.PRNGKey(0), CFG, quantize=method)
    batch = _batch()
    l_s, g_s = mesp.value_and_grad(qp, CFG, batch, mode="structured")
    l_p, g_p = mesp.value_and_grad(qp, CFG, batch, mode="pallas")
    np.testing.assert_allclose(float(l_p), float(l_s), rtol=1e-6)
    assert _rel(g_p, g_s) <= 1e-5
    dense = jax.tree_util.tree_map(
        lambda p: quant.maybe_dequant(p, jnp.float32),
        qp, is_leaf=quant.is_packed)
    _, g_oracle = mesp.value_and_grad(dense, CFG, batch, mode="plain")
    assert _rel(g_p, g_oracle) <= 1e-5


@pytest.mark.parametrize("method", ["int4", "nf4"])
def test_packed_kernel_matches_ref_oracle_odd_k(method):
    """ops-level on a ragged odd-K shape: the packed kernel vs the jnp
    oracle over the explicitly dequantized W0."""
    K, N, r = 97, 131, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (50, K)) * 0.3
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.05
    a = jax.random.normal(jax.random.PRNGKey(2), (K, r)) * 0.3
    b = jax.random.normal(jax.random.PRNGKey(3), (r, N)) * 0.3
    leaf = quant.quantize_leaf(w, method)
    wd = quant.maybe_dequant(leaf, jnp.float32)
    y = ops.lora_linear(x, leaf, a, b, None, 2.0)
    np.testing.assert_allclose(y, ref.lora_fused_ref(x, wd, a, b, 2.0),
                               rtol=2e-5, atol=2e-5)


def test_quant_dispatch_falls_back_on_moe_shapes():
    """Per-expert [E,·,·] quantized weights take the structured dequant
    path through the dispatcher, with correct LoRA gradients."""
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    E, C, d, f, r = 2, 8, 16, 12, 4
    x = jax.random.normal(keys[0], (E, C, d))
    w0 = jax.random.normal(keys[1], (E, d, f)) * 0.1
    a = jax.random.normal(keys[2], (E, d, r)) * 0.3
    b = jax.random.normal(keys[3], (E, r, f)) * 0.3
    q, s = quant.quantize_int8(w0)
    wl = {"q": q, "scale": s}
    wd = quant.dequantize_int8(q, s, jnp.float32)
    assert not ops.lora_supported(x, wl)
    f1 = lambda x, a, b: jnp.sum(jnp.tanh(ops.lora_linear(x, wl, a, b,
                                                          None, 2.0)))
    f2 = lambda x, a, b: jnp.sum(jnp.tanh(x @ wd + 2.0 * ((x @ a) @ b)))
    g1 = jax.grad(f1, (0, 1, 2))(x, a, b)
    g2 = jax.grad(f2, (0, 1, 2))(x, a, b)
    for u, w in zip(g1, g2):
        np.testing.assert_allclose(u, w, rtol=2e-5, atol=2e-5)


# -------------------------------------------------------------- lifecycle


def _sub_jaxprs(eqn):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    vals = []
    for v in eqn.params.values():
        vals += v if isinstance(v, (list, tuple)) else [v]
    for v in vals:
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v


def _float_w0_shapes(jaxpr, forbidden):
    """Float arrays of a dense-W0 shape produced OUTSIDE pallas kernels."""
    hits = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue  # inside the kernel IS VMEM — that's the design
        for sub in _sub_jaxprs(eqn):
            hits += _float_w0_shapes(sub, forbidden)
        for v in eqn.outvars:
            aval = v.aval
            if getattr(aval, "shape", None) in forbidden and \
                    jnp.issubdtype(aval.dtype, jnp.floating):
                hits.append((eqn.primitive.name, aval.shape, aval.dtype))
    return hits


def test_no_dense_w0_materialized_on_kernel_path():
    """fwd+bwd of the quantized kernel op never produce a float [K,N]/[N,K]
    array outside pallas_call — W0 exists only in VMEM. (Any jnp dequant
    happens before padding, so the exact shape is the discriminating one;
    padded shapes collide with padded activations.)"""
    K, N, r = 160, 200, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (192, K)) * 0.3
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.05
    a = jax.random.normal(jax.random.PRNGKey(2), (K, r)) * 0.3
    b = jax.random.normal(jax.random.PRNGKey(3), (r, N)) * 0.3
    q, s = quant.quantize_int8(w)

    def loss(x, a, b):
        y = ops.lora_linear(x, {"q": q, "scale": s}, a, b, None, 2.0)
        return jnp.sum(y * y)

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, a, b)
    hits = _float_w0_shapes(jaxpr.jaxpr, {(K, N), (N, K)})
    assert not hits, f"dense W0 materialized outside kernels: {hits}"


@pytest.mark.parametrize("method", ["int4", "nf4"])
def test_no_dense_w0_materialized_on_packed_kernel_path(method):
    """PR-2 invariant extended to the packed formats: fwd+bwd of the packed
    op never produce a float [K,N]/[N,K] array outside pallas_call — the
    nibble unpack happens on the VPU, in VMEM."""
    K, N, r = 160, 200, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (192, K)) * 0.3
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.05
    a = jax.random.normal(jax.random.PRNGKey(2), (K, r)) * 0.3
    b = jax.random.normal(jax.random.PRNGKey(3), (r, N)) * 0.3
    leaf = quant.quantize_leaf(w, method)

    def loss(x, a, b):
        y = ops.lora_linear(x, leaf, a, b, None, 2.0)
        return jnp.sum(y * y)

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, a, b)
    hits = _float_w0_shapes(jaxpr.jaxpr, {(K, N), (N, K)})
    assert not hits, f"dense W0 materialized outside kernels: {hits}"


def test_structured_fallback_does_materialize_w0():
    """Sanity for the guard above: the structured dequant path *does*
    materialize the dense W0 (so the check is actually discriminating)."""
    K, N, r = 160, 200, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (192, K)) * 0.3
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.05
    a = jax.random.normal(jax.random.PRNGKey(2), (K, r)) * 0.3
    b = jax.random.normal(jax.random.PRNGKey(3), (r, N)) * 0.3
    q, s = quant.quantize_int8(w)
    from repro.core import structured

    def loss(x, a, b):
        y = structured.lora_linear(x, quant.maybe_dequant({"q": q, "scale": s},
                                                          x.dtype),
                                   a, b, None, 2.0)
        return jnp.sum(y * y)

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, a, b)
    hits = _float_w0_shapes(jaxpr.jaxpr, {(K, N)})
    assert hits
