"""Hand-derived structured backward passes (paper §4.2, Appendix A).

Every op here is a ``jax.custom_vjp`` whose **residual tuple is the
tensor-lifecycle contract**: what is in the residuals is what survives the
forward pass; everything else is freed by XLA and recomputed on-demand in the
backward pass. This is the JAX-native expression of MeSP's "manually derived
backward passes with explicit control over tensor lifecycles".

The key primitive is :func:`lora_linear`, which — unlike autodiff — does NOT
save the intermediate projection ``h = x @ A`` (shape [..., r]); it recomputes
it in backward from ``x`` (which must be saved anyway, being needed for
``dA``) at cost O(b·n·d_in·r) ≪ the cost of storing h across all LoRA layers
(paper §4.1, Table 5).

All derivations follow paper Appendix A and are verified against
``jax.grad`` of the plain-jnp references in ``tests/test_structured.py``
(mathematical-equivalence claim, paper §5.5).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _flat2(x: Array) -> Array:
    """Collapse all leading dims: [..., d] -> [prod(...), d]."""
    return x.reshape(-1, x.shape[-1])


def _zero_cot(x):
    """Zero cotangent matching JAX's convention (float0 for int/bool leaves)."""
    import numpy as np

    if x is None:
        return None
    if isinstance(x, int):
        return np.zeros((), dtype=jax.dtypes.float0)
    if jnp.issubdtype(jnp.result_type(x), jnp.integer) or \
            jnp.result_type(x) == jnp.bool_:
        return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)
    return jnp.zeros_like(x)


# ---------------------------------------------------------------------------
# LoRA linear — the paper's core op (Appendix A.1)
#
#   y = x @ W0 + s * (x @ A) @ B           h := x @ A   (NOT stored)
#
#   dB = h^T (s g)          (A.1 eq 10)    <- h recomputed here
#   dh = (s g) B^T          (A.1 eq 11)
#   dA = x^T dh             (A.1 eq 12)
#   dx = dh A^T + g W0^T    (A.1 eq 13)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def lora_linear(x, w0, a, b, bias, scale: float):
    """LoRA-adapted linear: ``x @ w0 + scale * (x @ a) @ b [+ bias]``.

    ``w0``/``bias`` are frozen (their cotangents are symbolic zeros that XLA
    dead-code-eliminates); ``a``/``b`` are the trainable LoRA factors.
    """
    y = x @ w0 + scale * ((x @ a) @ b)
    if bias is not None:
        y = y + bias
    return y


def _lora_linear_fwd(x, w0, a, b, bias, scale):
    # MeSP residuals: x only — h = x@a is deliberately NOT saved.
    y = x @ w0 + scale * ((x @ a) @ b)
    if bias is not None:
        y = y + bias
    return y, (x, w0, a, b, bias is not None)


def _lora_linear_bwd(scale, res, g):
    x, w0, a, b, has_bias = res
    gx = g.astype(x.dtype)
    sg = scale * gx
    swap = lambda m: jnp.swapaxes(m, -1, -2)
    dh = sg @ swap(b)                                # (A.1 eq 11)
    h = x @ a                                        # recompute (paper §4.1)
    if w0.ndim == 2:
        # shared weight: flatten leading dims into one big contraction
        db = _flat2(h).T @ _flat2(sg)                # (A.1 eq 10)
        da = _flat2(x).T @ _flat2(dh)                # (A.1 eq 12)
    else:
        # per-expert batched weights (MoE EP): x [E,C,d], w0/a/b [E,·,·]
        db = swap(h) @ sg
        da = swap(x) @ dh
    dx = dh @ swap(a) + gx @ swap(w0)                # (A.1 eq 13)
    dw0 = jnp.zeros_like(w0)                         # frozen; DCE'd by XLA
    dbias = jnp.zeros(w0.shape[-1], w0.dtype) if has_bias else None
    return (dx, dw0, da.astype(a.dtype), db.astype(b.dtype), dbias)


lora_linear.defvjp(_lora_linear_fwd, _lora_linear_bwd)


# Ablation variant (paper §5.7 / Table 5): identical math, but h IS stored.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def lora_linear_store_h(x, w0, a, b, bias, scale: float):
    y = x @ w0 + scale * ((x @ a) @ b)
    if bias is not None:
        y = y + bias
    return y


def _lora_store_fwd(x, w0, a, b, bias, scale):
    h = x @ a
    y = x @ w0 + scale * (h @ b)
    if bias is not None:
        y = y + bias
    return y, (x, w0, a, b, h, bias is not None)   # <- h in residuals


def _lora_store_bwd(scale, res, g):
    x, w0, a, b, h, has_bias = res
    gx = g.astype(x.dtype)
    sg = scale * gx
    swap = lambda m: jnp.swapaxes(m, -1, -2)
    dh = sg @ swap(b)
    if w0.ndim == 2:
        db = _flat2(h).T @ _flat2(sg)
        da = _flat2(x).T @ _flat2(dh)
    else:
        db = swap(h) @ sg
        da = swap(x) @ dh
    dx = dh @ swap(a) + gx @ swap(w0)
    dbias = jnp.zeros(w0.shape[-1], w0.dtype) if has_bias else None
    return (dx, jnp.zeros_like(w0), da.astype(a.dtype), db.astype(b.dtype), dbias)


lora_linear_store_h.defvjp(_lora_store_fwd, _lora_store_bwd)


# ---------------------------------------------------------------------------
# RMSNorm (Appendix A.3)
#
#   rms = sqrt(mean(x^2) + eps);  xhat = x / rms;  y = xhat * w
#   dxhat = g * w
#   dx = (dxhat - xhat * mean(dxhat ⊙ xhat)) / rms     (A.3 eq 22)
#   dw = sum_batch(g ⊙ xhat)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x, w, eps: float = 1e-6):
    rms = jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True) + eps)
    return ((x.astype(jnp.float32) / rms) * w.astype(jnp.float32)).astype(x.dtype)


def _rmsnorm_fwd(x, w, eps):
    # Residual: x only. rms/xhat recomputed in backward (one reduction).
    return rmsnorm(x, w, eps), (x, w)


def _rmsnorm_bwd(eps, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    xhat = xf / rms
    dxhat = gf * w.astype(jnp.float32)
    dx = (dxhat - xhat * jnp.mean(dxhat * xhat, -1, keepdims=True)) / rms
    dw = jnp.sum(_flat2(gf) * _flat2(xhat), 0)
    return dx.astype(x.dtype), dw.astype(w.dtype)


rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


# ---------------------------------------------------------------------------
# SiLU (Appendix A.4):  silu(x) = x σ(x);  silu'(x) = σ(x)(1 + x(1-σ(x)))
# Residual: x only — σ(x) recomputed.
# ---------------------------------------------------------------------------


@jax.custom_vjp
def silu(x):
    return x * jax.nn.sigmoid(x)


def _silu_fwd(x):
    return x * jax.nn.sigmoid(x), (x,)


def _silu_bwd(res, g):
    (x,) = res
    s = jax.nn.sigmoid(x)
    return (g * s * (1 + x * (1 - s)),)


silu.defvjp(_silu_fwd, _silu_bwd)


# GeLU (tanh approx) for whisper — same recompute-from-x discipline.
@jax.custom_vjp
def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def _gelu_fwd(x):
    return jax.nn.gelu(x, approximate=True), (x,)


def _gelu_bwd(res, g):
    (x,) = res
    c = jnp.sqrt(2.0 / jnp.pi).astype(x.dtype)
    t = jnp.tanh(c * (x + 0.044715 * x**3))
    dt = (1 - t * t) * c * (1 + 3 * 0.044715 * x * x)
    return (g * (0.5 * (1 + t) + 0.5 * x * dt),)


gelu.defvjp(_gelu_fwd, _gelu_bwd)


# ---------------------------------------------------------------------------
# Scaled-dot-product attention (Appendix A.2), GQA + causal/windowed masking.
#
# Forward: probs = softmax(q k^T / sqrt(d) + mask);  out = probs v
# Residuals: (q, k, v) ONLY — the [*, n, n] probability matrix is recomputed
# in backward (FlashAttention principle, paper §2). Softmax backward:
#   dscores = probs ⊙ (dprobs − sum(dprobs ⊙ probs, -1))      (A.2 eq 19)
# ---------------------------------------------------------------------------


def _attn_mask(n_q: int, n_k: int, window: int, causal: bool, q_offset) -> Array:
    """[n_q, n_k] additive mask. q position i sits at absolute q_offset+i.
    ``q_offset`` may be a per-batch-row vector [B] (continuous-batching
    decode, every slot at its own position) — the mask then gains a leading
    batch dim: [B, n_q, n_k]."""
    off = jnp.asarray(q_offset)
    qpos = (off[..., None] if off.ndim else off) + jnp.arange(n_q)
    kpos = jnp.arange(n_k)
    d = qpos[..., :, None] - kpos
    ok = jnp.ones(d.shape, jnp.bool_)
    if causal:
        ok = ok & (d >= 0)
    if window > 0:
        ok = ok & (d < window)
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def _sdpa_mask(Nq: int, Nk: int, window: int, causal: bool, q_offset,
               kv_len) -> Array:
    """Combined positional + valid-length mask, broadcastable against
    [B, Hkv, G, Nq, Nk] scores. Scalar q_offset/kv_len keep the historical
    [Nq, Nk]-shaped mask; per-row vectors lift it to [B, 1, 1, Nq, Nk]."""
    mask = _attn_mask(Nq, Nk, window, causal, q_offset)
    if mask.ndim == 3:
        mask = mask[:, None, None]                    # [B,1,1,Nq,Nk]
    if kv_len is not None:
        kvl = jnp.asarray(kv_len)
        km = jnp.where(jnp.arange(Nk) < kvl[..., None], 0.0, -jnp.inf)
        if km.ndim == 2:                              # [B,Nk] per-row lengths
            km = km[:, None, None, None]
        mask = mask + km
    return mask


def _sdpa_ref(q, k, v, window: int, causal: bool, q_offset, kv_len):
    """Plain forward. q:[B,H,Nq,D] k:[B,Hkv,Nk,D] v:[B,Hkv,Nk,Dv] ->
    [B,H,Nq,Dv].

    Matmuls run on native (bf16) operands with f32 accumulation
    (``preferred_element_type``) — no materialized f32 copy of K/V, which for
    decode would double-read the whole KV cache (§Perf iteration 1).
    """
    B, H, Nq, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, Nq, D)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) / jnp.sqrt(D)
    # decode: only the first kv_len cache slots are valid (kv_len/q_offset
    # may be per-row vectors — continuous batching)
    scores = scores + _sdpa_mask(Nq, k.shape[2], window, causal, q_offset,
                                 kv_len)
    probs = jax.nn.softmax(scores, -1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, Nq, v.shape[-1]).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def sdpa(q, k, v, window: int = 0, causal: bool = True,
         q_offset: Array | int = 0, kv_len: Optional[Array] = None):
    return _sdpa_ref(q, k, v, window, causal, q_offset, kv_len)


def _sdpa_fwd(q, k, v, window, causal, q_offset, kv_len):
    out = _sdpa_ref(q, k, v, window, causal, q_offset, kv_len)
    return out, (q, k, v, q_offset, kv_len)  # probs NOT saved


def _sdpa_bwd(window, causal, res, g):
    q, k, v, q_offset, kv_len = res
    B, H, Nq, D = q.shape
    Hkv = k.shape[1]
    Nk = k.shape[2]
    G = H // Hkv
    f32 = dict(preferred_element_type=jnp.float32)
    qg = q.reshape(B, Hkv, G, Nq, D)
    gg = g.reshape(B, Hkv, G, Nq, v.shape[-1]).astype(q.dtype)
    # --- recompute probs (A.2 forward) ---
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k, **f32) / jnp.sqrt(D)
    mask = _sdpa_mask(Nq, Nk, window, causal, q_offset, kv_len)
    probs = jax.nn.softmax(scores + mask, -1)
    pl = probs.astype(q.dtype)
    # --- A.2 eqs 17-21 ---
    dv = jnp.einsum("bhgqk,bhgqd->bhkd", pl, gg, **f32)           # eq 17 (GQA-summed)
    dprobs = jnp.einsum("bhgqd,bhkd->bhgqk", gg, v, **f32)        # eq 18
    dscores = probs * (dprobs - jnp.sum(dprobs * probs, -1, keepdims=True))  # eq 19
    dsl = dscores.astype(q.dtype)
    dq = jnp.einsum("bhgqk,bhkd->bhgqd", dsl, k, **f32) / jnp.sqrt(D)  # eq 20
    dk = jnp.einsum("bhgqk,bhgqd->bhkd", dsl, qg, **f32) / jnp.sqrt(D)  # eq 21
    dq = dq.reshape(B, H, Nq, D).astype(q.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), _zero_cot(q_offset), _zero_cot(kv_len)


sdpa.defvjp(_sdpa_fwd, _sdpa_bwd)


# ---------------------------------------------------------------------------
# Cross-entropy with hand-derived backward: residuals are (logits-max stats),
# not the [B,N,V] softmax. For big-vocab archs this is a large saving.
# ---------------------------------------------------------------------------


@jax.custom_vjp
def softmax_xent(logits, labels):
    """Mean token cross-entropy; positions with label == -1 are ignored.

    logits [B,N,V] (any dtype), labels [B,N] int.
    """
    lf = logits.astype(jnp.float32)
    valid = (labels >= 0)
    safe = jnp.where(valid, labels, 0)
    lse = jax.scipy.special.logsumexp(lf, -1)
    ll = jnp.take_along_axis(lf, safe[..., None], -1)[..., 0]
    n = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum((lse - ll) * valid) / n


def _xent_fwd(logits, labels):
    return softmax_xent(logits, labels), (logits, labels)


def _xent_bwd(res, g):
    logits, labels = res
    lf = logits.astype(jnp.float32)
    valid = (labels >= 0)
    safe = jnp.where(valid, labels, 0)
    p = jax.nn.softmax(lf, -1)                      # recomputed
    onehot = jax.nn.one_hot(safe, logits.shape[-1], dtype=jnp.float32)
    n = jnp.maximum(jnp.sum(valid), 1)
    dlogits = (g / n) * (p - onehot) * valid[..., None]
    return dlogits.astype(logits.dtype), _zero_cot(labels)


softmax_xent.defvjp(_xent_fwd, _xent_bwd)
