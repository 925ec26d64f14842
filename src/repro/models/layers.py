"""Shared model components (LoRA-adapted linears, attention, MLP, embeddings).

All trainable-path ops take an :class:`repro.api.policy.ExecutionPolicy`
(``policy``) selecting the backward regime: with the default ``structured``
backend every backward pass is the paper's hand-derived one
(``repro.core.structured``); ``pallas`` routes through the fused Pallas
kernels instead (``repro.kernels.ops`` — same structured math, per-op
fallback to the jnp path on unsupported shapes); ``plain`` is framework
autodiff; ``store_h`` the Table 5 ablation. Parameter pytrees are plain
nested dicts; LoRA-adapted linears carry ``{"w", "a", "b" [, "bias"]}``
where ``w``/``bias`` are frozen and ``a``/``b`` are trainable.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.api.policy import STRUCTURED, ExecutionPolicy
from repro.configs.base import ArchConfig
from repro.core import structured
from repro.core.flash import flash_attention
from repro.core.quant import maybe_dequant
from repro.kernels import ops as kops
from repro.kernels import rope as krope

Array = jax.Array

# Policy defaults for the flash threshold/chunking live on ExecutionPolicy
# (flash_min_seq / flash_chunk); these module constants document the
# defaults and seed them.
FLASH_MIN_SEQ = 1024
DEFAULT_CHUNK = 1024


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _split(key, n):
    return jax.random.split(key, n)


def ambient_mesh():
    """The physical mesh installed by ``with mesh:`` at trace time, or
    ``None`` outside any mesh context (unit tests, single-device runs).

    Reads it via the public ``jax.interpreters.pxla.thread_resources``
    handle (the supported spelling of the old ``jax._src.mesh`` probe).
    """
    try:
        from jax.interpreters import pxla
        mesh = pxla.thread_resources.env.physical_mesh
    except Exception:
        return None
    return None if mesh.empty else mesh


def mesh_axis_size(axis) -> int:
    """Size of a physical-mesh axis (or axis tuple) at trace time; 1 when no
    mesh context is installed (unit tests)."""
    mesh = ambient_mesh()
    if axis is None or mesh is None:
        return 1
    try:
        if isinstance(axis, (tuple, list)):
            n = 1
            for a in axis:
                n *= mesh.shape[a]
            return n
        return mesh.shape[axis]
    except Exception:
        return 1


def _head_constrain(t, shard):
    """[B, H, N, D] → heads on the model axis when divisible, batch on DP.
    Keeps GSPMD from silently replicating k/v after the rope/transpose."""
    if shard is None:
        return t
    from jax.sharding import PartitionSpec as P
    msize = mesh_axis_size(shard["model"])
    hspec = shard["model"] if (msize > 1 and t.shape[1] % msize == 0) else None
    return jax.lax.with_sharding_constraint(
        t, P(shard["dp"], hspec, None, None))


def linear_params(key, d_in: int, d_out: int, cfg: ArchConfig, *,
                  lora: bool, bias: bool = False, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    k_w, k_a = _split(key, 2)
    p = {"w": (jax.random.normal(k_w, (d_in, d_out), dtype) * (d_in ** -0.5))}
    if bias:
        p["bias"] = jnp.zeros((d_out,), dtype)
    if lora:
        r = cfg.lora.rank
        p["a"] = jax.random.normal(k_a, (d_in, r), dtype) * (r ** -0.5)
        p["b"] = jnp.zeros((r, d_out), dtype)  # B=0: ΔW starts at 0 (LoRA std)
    return p


def apply_linear(p, x, cfg: ArchConfig, *,
                 policy: ExecutionPolicy = STRUCTURED, adapter_tiles=None):
    """LoRA linear. ``policy.backend``: "structured" (MeSP — h recomputed),
    "pallas" (MeSP via fused TPU kernels), "store_h" (Table 5 ablation),
    "plain" (MeBP — framework autodiff).

    ``p["w"]`` is either a dense frozen matrix, an int8 ``{"q", "scale"}``
    leaf or a packed 4-bit ``{"q4", "scale"}`` leaf
    (``core/quant.quantize_frozen``). The pallas path hands the quantized
    leaf to the dequant-in-VMEM kernels; the jnp paths dequantize to a dense
    matrix first (``maybe_dequant``) — same math, W0 materialized.

    Multi-tenant serving: when ``p["a"]/p["b"]`` are *stacked* adapter
    resident sets ([R, d_in, r] / [R, r, d_out] — AdapterStore), the int32
    ``adapter_tiles`` array routes each batch-slot tile to its adapter
    (``kernels/ops.lora_grouped_decode``; values may be runtime-traced so
    re-routing never recompiles). Decode only: x must be [B, 1, d].
    """
    backend = policy.backend
    bias = p.get("bias")
    if "a" in p and p["a"].ndim == 3:
        if adapter_tiles is None:
            raise ValueError("stacked adapters need adapter_tiles routing")
        if x.ndim != 2 and x.shape[-2] != 1:
            raise ValueError("grouped adapter routing is decode-only "
                             f"(got x {x.shape})")
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        bm = x2.shape[0] // adapter_tiles.shape[0]
        y = kops.lora_grouped_decode(x2, p["w"], p["a"], p["b"],
                                     adapter_tiles, bias, cfg.lora.scale,
                                     bm=bm, policy=policy)
        return y.reshape(*lead, y.shape[-1])
    if "a" in p:
        if backend == "pallas":
            return kops.lora_linear(x, p["w"], p["a"], p["b"], bias,
                                    cfg.lora.scale, policy=policy)
        w = maybe_dequant(p["w"], x.dtype)
        if backend == "plain":
            y = x @ w + cfg.lora.scale * ((x @ p["a"]) @ p["b"])
            return y + bias if bias is not None else y
        fn = structured.lora_linear_store_h if backend == "store_h" \
            else structured.lora_linear
        return fn(x, w, p["a"], p["b"], bias, cfg.lora.scale)
    y = x @ maybe_dequant(p["w"], x.dtype)
    if bias is not None:
        y = y + bias
    return y


def norm(p, x, cfg: ArchConfig, *, policy: ExecutionPolicy = STRUCTURED):
    """RMSNorm: structured (residual = x, rms recomputed), pallas (fused
    kernel, same residual contract) or plain autodiff."""
    if policy.backend == "plain":
        xf = x.astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + cfg.norm_eps)
        return ((xf / rms) * p.astype(jnp.float32)).astype(x.dtype)
    if policy.backend == "pallas":
        return kops.rmsnorm(x, p, cfg.norm_eps, policy=policy)
    return structured.rmsnorm(x, p, cfg.norm_eps)


def act_silu(x, policy: ExecutionPolicy):
    return x * jax.nn.sigmoid(x) if policy.backend == "plain" \
        else structured.silu(x)


def act_gelu(x, policy: ExecutionPolicy):
    return jax.nn.gelu(x, approximate=True) if policy.backend == "plain" \
        else structured.gelu(x)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: [B, N, H, D] (D even), positions: [N] or [B, N]."""
    D = x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [*, N, half]
    if ang.ndim == 2:  # [N, half] -> broadcast over batch
        ang = ang[None]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention block (GQA + optional sliding window + KV cache)
# ---------------------------------------------------------------------------


def attention_params(key, cfg: ArchConfig, *, cross: bool = False,
                     lora: bool = True):
    ks = _split(key, 4)
    hd = cfg.resolved_head_dim
    tg = cfg.lora.targets
    return {
        "q": linear_params(ks[0], cfg.d_model, cfg.n_heads * hd, cfg,
                           lora=lora and "q" in tg, bias=cfg.qkv_bias),
        "k": linear_params(ks[1], cfg.d_model, cfg.n_kv_heads * hd, cfg,
                           lora=lora and "k" in tg, bias=cfg.qkv_bias),
        "v": linear_params(ks[2], cfg.d_model, cfg.n_kv_heads * hd, cfg,
                           lora=lora and "v" in tg, bias=cfg.qkv_bias),
        "o": linear_params(ks[3], cfg.n_heads * hd, cfg.d_model, cfg,
                           lora=lora and "o" in tg),
    }


def attention(p, x, cfg: ArchConfig, *, window: int = 0, causal: bool = True,
              cache: Optional[dict] = None, pos: Array | int = 0,
              kv_x: Optional[Array] = None, use_rope: bool = True,
              policy: ExecutionPolicy = STRUCTURED,
              shard=None, adapter_tiles=None) -> Tuple[Array, Optional[dict]]:
    """Multi-head attention with the structured backward.

    ``cache`` (decode): {"k": [B,Hkv,S,D], "v": ..., "len": int32 — scalar,
    or [B] per-slot lengths for continuous batching (every slot at its own
    position; writes and masks then vectorize per row)}.
    ``kv_x``: source for k/v (cross-attention) — defaults to x.
    ``adapter_tiles``: multi-tenant decode routing for stacked q/k/v/o
    adapters (see :func:`apply_linear`).
    """
    B, N, _ = x.shape
    hd = cfg.resolved_head_dim
    src = x if kv_x is None else kv_x
    Nk = src.shape[1]
    lin = functools.partial(apply_linear, cfg=cfg, policy=policy,
                            adapter_tiles=adapter_tiles)

    q = lin(p["q"], x).reshape(B, N, cfg.n_heads, hd)
    k = lin(p["k"], src).reshape(B, Nk, cfg.n_kv_heads, hd)
    v = lin(p["v"], src).reshape(B, Nk, cfg.n_kv_heads, hd)

    rope_tabs = None
    if use_rope:
        parr = jnp.asarray(pos)
        off = parr[..., None] if parr.ndim else parr  # [B,1] when per-slot
        qpos = jnp.arange(N) + off
        kpos = jnp.arange(Nk) + (off if kv_x is None else 0)
        fuse = (policy.backend == "pallas" and policy.fuse_rope
                and cache is None and kv_x is None and hd % 2 == 0)
        if fuse:
            # rotation deferred into the flash kernels: the [N, D/2] cos/sin
            # tables stream per tile and q/k are rotated in VMEM — the
            # rotated copies never round-trip through HBM (kernels/rope.py)
            rope_tabs = krope.rope_tables(qpos, cfg.rope_theta, hd)
        else:
            q = rope(q, qpos, cfg.rope_theta)
            k = rope(k, kpos, cfg.rope_theta)

    q = _head_constrain(q.transpose(0, 2, 1, 3), shard)  # [B,H,N,D]
    k = _head_constrain(k.transpose(0, 2, 1, 3), shard)
    v = _head_constrain(v.transpose(0, 2, 1, 3), shard)

    new_cache = None
    if cache is not None:
        if window > 0 and cache["k"].shape[2] == window:
            # ring buffer: sliding-window layers keep only ``window`` slots
            # (long_500k decode: 512× less cache for gemma3 local layers)
            slot = cache["len"] % window
            kc = _cache_write(cache["k"], k, slot)
            vc = _cache_write(cache["v"], v, slot)
            new_cache = {"k": kc, "v": vc, "len": cache["len"] + N}
            out = _ring_attend(q, kc, vc, cache["len"], window)
        else:
            # linear cache: append k/v at ``len`` and attend over valid slots
            kc = _cache_write(cache["k"], k, cache["len"])
            vc = _cache_write(cache["v"], v, cache["len"])
            new_cache = {"k": kc, "v": vc, "len": cache["len"] + N}
            out = structured.sdpa(q, kc, vc, window, causal,
                                  cache["len"], cache["len"] + N)
    elif policy.backend == "plain":
        out = structured._sdpa_ref(q, k, v, window, causal, 0, None)
    elif policy.backend == "pallas":
        # kernel flash attention (fwd + lse-driven bwd); falls back to the
        # structured sdpa for short sequences / unsupported layouts (the
        # fallback applies any deferred rope tables via jnp first)
        out = kops.sdpa(q, k, v, causal=causal, window=window, policy=policy,
                        rope=rope_tabs)
    elif N >= policy.flash_min_seq:
        out = flash_attention(q, k, v, window, causal,
                              policy.flash_chunk, policy.flash_chunk)
    else:
        out = structured.sdpa(q, k, v, window, causal)

    out = out.transpose(0, 2, 1, 3).reshape(B, N, cfg.n_heads * hd)
    return lin(p["o"], out), new_cache


# ---------------------------------------------------------------------------
# latent attention (MLA, DeepSeek-V2/V3) — training path
# ---------------------------------------------------------------------------


def mla_params(key, cfg: ArchConfig, *, lora: bool = True):
    """q [d → H·(nope+rope)], kv_a [d → rank+rope] with an RMSNorm over the
    rank-wide latent, kv_b [rank → H·(nope+v)], o [H·v → d]."""
    m = cfg.mla
    ks = _split(key, 4)
    tg = cfg.lora.targets
    H, d = cfg.n_heads, cfg.d_model
    return {
        "q": linear_params(ks[0], d, H * m.qk_head_dim, cfg,
                           lora=lora and "q" in tg),
        "kv_a": linear_params(ks[1], d, m.kv_lora_rank + m.qk_rope_head_dim,
                              cfg, lora=lora and "kv_a" in tg),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.dtype(cfg.dtype)),
        "kv_b": linear_params(ks[2], m.kv_lora_rank,
                              H * (m.qk_nope_head_dim + m.v_head_dim), cfg,
                              lora=lora and "kv_b" in tg),
        "o": linear_params(ks[3], H * m.v_head_dim, d, cfg,
                           lora=lora and "o" in tg),
    }


def mla_attention(p, x, cfg: ArchConfig, *, policy: ExecutionPolicy =
                  STRUCTURED, shard=None, cache=None, **_):
    """Causal latent attention over x [B, N, d], training and prefill only
    (no KV cache). Rope (rotate-half, ``cfg.rope_theta``) turns only the
    rope channels of q and of the shared key; the softmax scale is
    1/√(nope + rope)."""
    if cache is not None:
        raise NotImplementedError("latent attention has no decode cache")
    m = cfg.mla
    if m.q_lora_rank is not None:
        raise NotImplementedError("MLA with a q_lora_rank is not modelled")
    B, N, _ = x.shape
    H, nope, rd, dv = (cfg.n_heads, m.qk_nope_head_dim, m.qk_rope_head_dim,
                       m.v_head_dim)
    lin = functools.partial(apply_linear, cfg=cfg, policy=policy)
    with jax.named_scope("mla"):
        q = lin(p["q"], x).reshape(B, N, H, nope + rd)
        kv = lin(p["kv_a"], x)
        c = norm(p["kv_norm"], kv[..., :m.kv_lora_rank], cfg, policy=policy)
        kvb = lin(p["kv_b"], c).reshape(B, N, H, nope + dv)
        pos = jnp.arange(N)
        q_pe = rope(q[..., nope:], pos, cfg.rope_theta)
        k_pe = rope(kv[..., None, m.kv_lora_rank:], pos, cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_pe], -1)
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(k_pe, (B, N, H, rd))], -1)
        q = _head_constrain(q.transpose(0, 2, 1, 3), shard)   # [B,H,N,D]
        k = _head_constrain(k.transpose(0, 2, 1, 3), shard)
        v = _head_constrain(kvb[..., nope:].transpose(0, 2, 1, 3), shard)
        if policy.backend == "plain":
            out = structured._sdpa_ref(q, k, v, 0, True, 0, None)
        elif policy.backend == "pallas":
            out = kops.sdpa(q, k, v, causal=True, policy=policy)
        else:
            out = structured.sdpa(q, k, v, 0, True)
        out = out.transpose(0, 2, 1, 3).reshape(B, N, H * dv)
        return lin(p["o"], out), None


def _cache_write(c, u, ln):
    """Write ``u`` into cache ``c`` ([B,Hkv,S,D]) at slot offset ``ln`` —
    a scalar (whole batch at one position, training/simple decode) or a
    [B] vector (continuous batching: every slot at its own length)."""
    if jnp.ndim(ln) == 0:
        return jax.lax.dynamic_update_slice_in_dim(c, u, ln, 2)
    row = lambda ci, ui, li: jax.lax.dynamic_update_slice_in_dim(ci, ui, li, 1)
    return jax.vmap(row)(c, u, ln)


def _ring_attend(q, kc, vc, qpos, window: int):
    """Decode attention over a ring-buffer cache (keys roped at write time).

    q: [B,H,1,D]; kc/vc: [B,Hkv,W,D]; slot s holds absolute position
    p(s) = qpos − ((qpos − s) mod W), valid when 0 ≤ p(s) and p(s) > qpos−W.
    ``qpos`` may be a [B] vector (per-slot decode).
    """
    B, H, _, D = q.shape
    Hkv, W = kc.shape[1], kc.shape[2]
    G = H // Hkv
    slots = jnp.arange(W)
    qp = qpos[..., None] if jnp.ndim(qpos) else qpos
    pos = qp - jnp.mod(qp - slots, W)
    valid = (pos >= 0) & (pos > qp - W) & (pos <= qp)
    if valid.ndim == 2:                     # [B,W] -> [B,1,1,1,W]
        valid = valid[:, None, None, None, :]
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q.reshape(B, Hkv, G, 1, D), kc,
                   preferred_element_type=jnp.float32) / jnp.sqrt(D)
    s = jnp.where(valid, s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(vc.dtype), vc,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, 1, D).astype(q.dtype)


def make_kv_cache(cfg: ArchConfig, batch: int, max_len: int, dtype, *,
                  window: int = 0, per_slot: bool = False) -> dict:
    """KV cache; sliding-window layers get a ring buffer of ``window`` slots
    when that is smaller than the full length. ``per_slot``: track a [B]
    length vector instead of one scalar, so continuous batching can hold
    every slot at its own position."""
    hd = cfg.resolved_head_dim
    slots = window if (window and window < max_len) else max_len
    return {
        "k": jnp.zeros((batch, cfg.n_kv_heads, slots, hd), dtype),
        "v": jnp.zeros((batch, cfg.n_kv_heads, slots, hd), dtype),
        "len": jnp.zeros((batch,) if per_slot else (), jnp.int32),
    }


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU) — LoRA on gate/up/down, SiLU via structured backward
# ---------------------------------------------------------------------------


def mlp_params(key, cfg: ArchConfig, d_ff: Optional[int] = None, *,
               act: str = "silu", lora: bool = True):
    ks = _split(key, 3)
    d_ff = d_ff or cfg.d_ff
    tg = cfg.lora.targets
    p = {
        "gate": linear_params(ks[0], cfg.d_model, d_ff, cfg, lora=lora and "gate" in tg),
        "up": linear_params(ks[1], cfg.d_model, d_ff, cfg, lora=lora and "up" in tg),
        "down": linear_params(ks[2], d_ff, cfg.d_model, cfg, lora=lora and "down" in tg),
    }
    if act == "gelu":  # whisper: plain (non-gated) MLP, keep 'up/down' only
        p = {
            "up": linear_params(ks[1], cfg.d_model, d_ff, cfg, lora=lora and "up" in tg),
            "down": linear_params(ks[2], d_ff, cfg.d_model, cfg, lora=lora and "down" in tg),
        }
    return p


def mlp(p, x, cfg: ArchConfig, *, policy: ExecutionPolicy = STRUCTURED,
        adapter_tiles=None):
    lin = functools.partial(apply_linear, cfg=cfg, policy=policy,
                            adapter_tiles=adapter_tiles)
    if "gate" in p:
        g = lin(p["gate"], x)
        u = lin(p["up"], x)
        return lin(p["down"], act_silu(g, policy) * u)
    u = lin(p["up"], x)
    return lin(p["down"], act_gelu(u, policy))


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------


def embed_params(key, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.dtype)
    k_e, k_h = _split(key, 2)
    p = {"tok": jax.random.normal(k_e, (cfg.vocab, cfg.d_model), dtype) * 0.02}
    if not cfg.tie_embeddings:
        p["head"] = jax.random.normal(k_h, (cfg.d_model, cfg.vocab), dtype) \
            * (cfg.d_model ** -0.5)
    return p


def embed(p, tokens, cfg: ArchConfig):
    x = jnp.take(p["tok"], tokens, axis=0)
    if cfg.name.startswith(("gemma", "recurrentgemma")):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)  # gemma convention
    return x


def unembed(p, x, cfg: ArchConfig):
    w = p["tok"].T if cfg.tie_embeddings else p["head"]
    return (x @ w).astype(jnp.float32)
