"""Model assembly: init / forward / loss / decode for all assigned families.

Layout conventions
------------------
* Homogeneous stacks (dense, moe, vlm, ssm) are ``lax.scan``-ed over stacked
  block params ``[L, ...]`` with ``jax.checkpoint`` around the block body:
  **only block inputs are stored** across the forward pass — the paper's
  block-sequential checkpointing (§4.3) expressed as scan-over-layers.
* Patterned stacks (gemma3 5:1 local:global, recurrentgemma R,R,A) scan over
  *groups* (one pattern period, params ``[n_groups, ...]``) so per-layer
  window sizes / block kinds stay static inside the group body.
* ``policy`` (:class:`repro.api.policy.ExecutionPolicy`) selects the
  backward regime (``policy.backend``: "structured" = MeSP hand-derived
  custom_vjp rules, "pallas" = MeSP via the fused TPU kernels in
  ``repro.kernels`` — sparse-grid flash attention, optionally with RoPE
  applied inside the kernels via ``policy.fuse_rope``), the activation
  sharding constraint (``policy.act_spec``) and the remat schedule
  (``policy.remat``). "plain" = MeBP framework autodiff, "store_h" =
  paper Table 5 ablation.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.api.policy import BACKENDS, STRUCTURED, ExecutionPolicy  # noqa: F401  (BACKENDS re-exported)
from repro.configs.base import ArchConfig
from repro.core import quant, structured
from repro.models import griffin, layers, moe as moe_lib, rwkv6

Array = jax.Array


# ---------------------------------------------------------------------------
# block bodies
# ---------------------------------------------------------------------------


def _attention(cfg):
    """The block's attention: latent (MLA) where the config has one."""
    return layers.mla_attention if cfg.mla is not None else layers.attention


def dense_block(bp, x, cfg, *, window=0, policy: ExecutionPolicy = STRUCTURED,
                cache=None, pos=0, shard=None, adapter_tiles=None):
    h, new_cache = _attention(cfg)(
        bp["attn"], layers.norm(bp["ln1"], x, cfg, policy=policy), cfg,
        window=window, cache=cache, pos=pos, policy=policy, shard=shard,
        adapter_tiles=adapter_tiles)
    x = x + h
    x = x + layers.mlp(bp["mlp"],
                       layers.norm(bp["ln2"], x, cfg, policy=policy),
                       cfg, policy=policy, adapter_tiles=adapter_tiles)
    return x, new_cache


def moe_block(bp, x, cfg, *, window=0, policy: ExecutionPolicy = STRUCTURED,
              cache=None, pos=0, shard=None, adapter_tiles=None):
    """Returns (x, new cache, the expert layer's counters)."""
    h, new_cache = _attention(cfg)(
        bp["attn"], layers.norm(bp["ln1"], x, cfg, policy=policy), cfg,
        window=window, cache=cache, pos=pos, policy=policy, shard=shard,
        adapter_tiles=adapter_tiles)
    x = x + h
    y, stats = moe_lib.moe_mlp(bp["moe"],
                               layers.norm(bp["ln2"], x, cfg, policy=policy),
                               cfg, policy=policy, shard=shard)
    return x + y, new_cache, stats


def _block_params(key, cfg: ArchConfig, kind: str):
    ks = jax.random.split(key, 3)
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    attn_params = (layers.mla_params if cfg.mla is not None
                   else layers.attention_params)
    if kind == "dense":
        return {"ln1": jnp.ones((d,), dtype),
                "attn": layers.attention_params(ks[0], cfg),
                "ln2": jnp.ones((d,), dtype),
                "mlp": layers.mlp_params(ks[1], cfg)}
    if kind == "moe":
        return {"ln1": jnp.ones((d,), dtype),
                "attn": attn_params(ks[0], cfg),
                "ln2": jnp.ones((d,), dtype),
                "moe": moe_lib.moe_params(ks[1], cfg)}
    if kind == "moe_dense0":  # deepseek layer 0: a dense FFN
        return {"ln1": jnp.ones((d,), dtype),
                "attn": attn_params(ks[0], cfg),
                "ln2": jnp.ones((d,), dtype),
                "mlp": layers.mlp_params(ks[1], cfg,
                                         d_ff=cfg.moe.resolved_d_dense)}
    if kind == "rwkv":
        return rwkv6.rwkv_block_params(key, cfg)
    if kind == "recurrent":
        return griffin.recurrent_block_params(key, cfg)
    if kind == "local_attn":
        return {"ln1": jnp.ones((d,), dtype),
                "attn": layers.attention_params(ks[0], cfg),
                "ln2": jnp.ones((d,), dtype),
                "mlp": layers.mlp_params(ks[1], cfg)}
    if kind == "enc":
        return {"ln1": jnp.ones((d,), dtype),
                "attn": layers.attention_params(ks[0], cfg),
                "ln2": jnp.ones((d,), dtype),
                "mlp": layers.mlp_params(ks[1], cfg, act="gelu")}
    if kind == "dec":
        return {"ln1": jnp.ones((d,), dtype),
                "attn": layers.attention_params(ks[0], cfg),
                "lnx": jnp.ones((d,), dtype),
                "xattn": layers.attention_params(ks[1], cfg, cross=True),
                "ln2": jnp.ones((d,), dtype),
                "mlp": layers.mlp_params(ks[2], cfg, act="gelu")}
    raise ValueError(kind)


def _stack_params(key, cfg, kind, n):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _block_params(k, cfg, kind))(keys)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ArchConfig, *, quantize: Optional[str] = None):
    """Init the param pytree; ``quantize`` ("int8", or packed "int4"/"nf4")
    converts every frozen ``w`` leaf to its ``core/quant`` format dict
    (``{"q", "scale"}`` int8; ``{"q4", "scale", ...}`` packed 4-bit) — LoRA
    factors, biases, norms and embeddings stay in ``cfg.dtype``."""
    k_emb, k_blk, k_tail, k_enc = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.dtype)
    p = {"embed": layers.embed_params(k_emb, cfg),
         "final_norm": jnp.ones((cfg.d_model,), dtype)}

    fam = cfg.family
    if fam in ("dense", "vlm"):
        if cfg.window_pattern:  # gemma3: group per pattern period
            gsz = len(cfg.window_pattern)
            assert cfg.n_layers % gsz == 0
            # group leaves are stacked [n_groups, gsz, ...]
            p["groups"] = jax.vmap(
                lambda k: jax.vmap(lambda kk: _block_params(kk, cfg, "dense"))(
                    jax.random.split(k, gsz)))(
                jax.random.split(k_blk, cfg.n_layers // gsz))
        else:
            p["blocks"] = _stack_params(k_blk, cfg, "dense", cfg.n_layers)
    elif fam == "moe":
        n = cfg.n_layers
        if cfg.moe.first_layer_dense:
            p["block0"] = _block_params(k_tail, cfg, "moe_dense0")
            n -= 1
        p["blocks"] = _stack_params(k_blk, cfg, "moe", n)
    elif fam == "ssm":
        p["blocks"] = _stack_params(k_blk, cfg, "rwkv", cfg.n_layers)
    elif fam == "hybrid":
        pat = cfg.hybrid.pattern
        gsz = len(pat)
        n_groups = cfg.n_layers // gsz
        n_tail = cfg.n_layers - n_groups * gsz

        def group_params(k):
            kk = jax.random.split(k, gsz)
            return {f"l{i}": _block_params(
                kk[i], cfg, "recurrent" if pat[i] == "R" else "local_attn")
                for i in range(gsz)}

        p["groups"] = jax.vmap(group_params)(jax.random.split(k_blk, n_groups))
        p["tail"] = [
            _block_params(k, cfg, "recurrent" if pat[i % gsz] == "R" else "local_attn")
            for i, k in enumerate(jax.random.split(k_tail, n_tail))]
    elif fam == "audio":
        ec = cfg.encdec
        p["enc_blocks"] = _stack_params(k_enc, cfg, "enc", ec.encoder_layers)
        p["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
        p["blocks"] = _stack_params(k_blk, cfg, "dec", cfg.n_layers)
    else:
        raise ValueError(fam)
    return quant.quantize_params(p, quantize)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _constrain(x, act_spec):
    """Apply a block-boundary activation sharding constraint (Megatron SP:
    sequence on the model axis between blocks). No-op when act_spec is None."""
    if act_spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, act_spec)


def _scan_ckpt(body, x, stacked, act_spec=None, remat=True):
    """scan over stacked block params with per-block rematerialization.

    Storing only the scan carry (= block inputs) is the paper's §4.3
    checkpoint strategy; ``act_spec`` shards those stored checkpoints.
    """
    f = jax.checkpoint(body) if remat else body

    def step(c, bp):
        return _constrain(f(c, bp), act_spec), None

    x, _ = jax.lax.scan(step, _constrain(x, act_spec), stacked)
    return x


def _encoder_forward(params, cfg, frames, policy):
    """Whisper encoder over precomputed frame embeddings [B, T, d]."""
    pos = _sinusoid(frames.shape[1], cfg.d_model, frames.dtype)
    x = frames + pos

    def body(x, bp):
        h, _ = layers.attention(bp["attn"],
                                layers.norm(bp["ln1"], x, cfg, policy=policy),
                                cfg, causal=False, use_rope=False,
                                policy=policy)
        x = x + h
        return x + layers.mlp(bp["mlp"],
                              layers.norm(bp["ln2"], x, cfg, policy=policy),
                              cfg, policy=policy)

    x = _scan_ckpt(body, x, params["enc_blocks"], remat=policy.remat)
    return layers.norm(params["enc_norm"], x, cfg, policy=policy)


def _sinusoid(n, d, dtype):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None]
    ang = pos / (10000 ** (2 * dim / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)[None]


def forward(params, cfg: ArchConfig, tokens: Array, *,
            policy: ExecutionPolicy = STRUCTURED,
            frontend_embeds: Optional[Array] = None,
            enc_frames: Optional[Array] = None) -> Array:
    """Full-sequence forward -> logits [B, N(+frontend), vocab] (fp32)."""
    return forward_and_aux(params, cfg, tokens, policy=policy,
                           frontend_embeds=frontend_embeds,
                           enc_frames=enc_frames)[0]


def forward_and_aux(params, cfg: ArchConfig, tokens: Array, *,
                    policy: ExecutionPolicy = STRUCTURED,
                    frontend_embeds: Optional[Array] = None,
                    enc_frames: Optional[Array] = None):
    """(logits, aux): aux holds the ``moe`` family's expert counters summed
    over its layers (``moe_lib.STATS``, int32 scalars), and is empty for
    every other family."""
    aux = {}
    act_spec = policy.act_spec
    remat = policy.remat
    x = layers.embed(params["embed"], tokens, cfg)
    if frontend_embeds is not None:  # vlm: precomputed patch embeddings
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)

    shard = None
    if act_spec is not None:
        shard = {"dp": act_spec[0], "model": act_spec[1],
                 "sp": layers.mesh_axis_size(act_spec[1])}

    fam = cfg.family
    if fam in ("dense", "vlm"):
        if cfg.window_pattern:
            gsz = len(cfg.window_pattern)

            def gbody(x, gp):
                for i in range(gsz):
                    bp = jax.tree_util.tree_map(lambda t: t[i], gp)
                    x, _ = dense_block(bp, x, cfg,
                                       window=cfg.window_pattern[i],
                                       policy=policy, shard=shard)
                return x

            x = _scan_ckpt(gbody, x, params["groups"], act_spec, remat)
        else:
            def body(x, bp):
                return dense_block(bp, x, cfg, policy=policy, shard=shard)[0]

            x = _scan_ckpt(body, x, params["blocks"], act_spec, remat)
    elif fam == "moe":
        if "block0" in params:
            x, _ = dense_block(params["block0"], x, cfg, policy=policy,
                               shard=shard)

        def body(carry, bp):
            x, stats = carry
            x, _, s = moe_block(bp, x, cfg, policy=policy, shard=shard)
            x = _constrain(x, act_spec)
            return (x, {k: stats[k] + s[k] for k in stats}), None

        f = jax.checkpoint(body) if remat else body
        zero = {k: jnp.int32(0) for k in moe_lib.STATS}
        (x, aux), _ = jax.lax.scan(f, (_constrain(x, act_spec), zero),
                                   params["blocks"])
    elif fam == "ssm":
        def body(x, bp):
            return rwkv6.rwkv_block(bp, x, cfg, policy=policy)[0]

        x = _scan_ckpt(body, x, params["blocks"], act_spec, remat)
    elif fam == "hybrid":
        pat = cfg.hybrid.pattern
        gsz = len(pat)

        def gbody(x, gp):
            for i in range(gsz):
                bp = gp[f"l{i}"]
                if pat[i] == "R":
                    x, _ = griffin.recurrent_block(bp, x, cfg, policy=policy)
                else:
                    x, _ = dense_block(bp, x, cfg,
                                       window=cfg.hybrid.window,
                                       policy=policy, shard=shard)
            return x

        x = _scan_ckpt(gbody, x, params["groups"], act_spec, remat)
        n_groups = jax.tree_util.tree_leaves(params["groups"])[0].shape[0]
        for i, bp in enumerate(params["tail"]):
            li = n_groups * gsz + i
            if pat[li % gsz] == "R":
                x, _ = griffin.recurrent_block(bp, x, cfg, policy=policy)
            else:
                x, _ = dense_block(bp, x, cfg, window=cfg.hybrid.window,
                                   policy=policy)
    elif fam == "audio":
        assert enc_frames is not None, "audio arch needs enc_frames"
        enc_out = _encoder_forward(params, cfg, enc_frames, policy)
        x = x + _sinusoid(x.shape[1], cfg.d_model, x.dtype)

        def body(x, bp):
            h, _ = layers.attention(bp["attn"],
                                    layers.norm(bp["ln1"], x, cfg,
                                                policy=policy),
                                    cfg, use_rope=False, policy=policy)
            x = x + h
            h, _ = layers.attention(bp["xattn"],
                                    layers.norm(bp["lnx"], x, cfg,
                                                policy=policy),
                                    cfg, causal=False, kv_x=enc_out,
                                    use_rope=False, policy=policy)
            x = x + h
            return x + layers.mlp(bp["mlp"],
                                  layers.norm(bp["ln2"], x, cfg,
                                              policy=policy),
                                  cfg, policy=policy)

        x = _scan_ckpt(body, x, params["blocks"], act_spec, remat)
    else:
        raise ValueError(fam)

    x = layers.norm(params["final_norm"], x, cfg, policy=policy)
    return layers.unembed(params["embed"], x, cfg), aux


def aux_names(cfg: ArchConfig) -> tuple:
    """Names of the counters :func:`loss_and_aux` returns beside the loss:
    the ``moe`` family's expert counters, none for every other family."""
    return moe_lib.STATS if cfg.family == "moe" else ()


def loss_fn(params, cfg: ArchConfig, batch: dict, *,
            policy: ExecutionPolicy = STRUCTURED) -> Array:
    """Mean next-token CE. batch: tokens/labels [B,N] (+frontend/frames)."""
    return loss_and_aux(params, cfg, batch, policy=policy)[0]


def loss_and_aux(params, cfg: ArchConfig, batch: dict, *,
                 policy: ExecutionPolicy = STRUCTURED):
    """(mean next-token CE, aux) — aux as :func:`forward_and_aux` gives."""
    logits, aux = forward_and_aux(
        params, cfg, batch["tokens"], policy=policy,
        frontend_embeds=batch.get("frontend_embeds"),
        enc_frames=batch.get("enc_frames"))
    labels = batch["labels"]
    if cfg.frontend_tokens and batch.get("frontend_embeds") is not None:
        # frontend prefix carries no labels
        pad = jnp.full(labels.shape[:1] + (batch["frontend_embeds"].shape[1],),
                       -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    return structured.softmax_xent(logits, labels), aux


# ---------------------------------------------------------------------------
# decode (serve_step): one new token against a cache of seq_len
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int, *,
               per_slot: bool = False):
    """Stacked per-layer decode state. ``per_slot``: [B] length vectors
    instead of one scalar (continuous batching — attention-cache families
    only)."""
    dtype = jnp.dtype(cfg.dtype)

    def stack(make, n):
        return jax.vmap(lambda _: make())(jnp.arange(n))

    fam = cfg.family
    if per_slot and fam not in ("dense", "vlm", "moe"):
        raise ValueError(f"per_slot decode caches unsupported for {fam!r}")
    if fam in ("dense", "vlm", "moe"):
        kv = lambda w=0: layers.make_kv_cache(cfg, batch, max_len, dtype,
                                              window=w, per_slot=per_slot)
        if cfg.window_pattern:
            # ring (window-sized) and linear (full-length) caches differ in
            # shape → keyed per pattern position, stacked over groups only
            gsz = len(cfg.window_pattern)

            def group_cache():
                return {f"l{i}": kv(cfg.window_pattern[i]) for i in range(gsz)}

            return {"groups": stack(group_cache, cfg.n_layers // gsz)}
        n = cfg.n_layers - (1 if (cfg.moe and cfg.moe.first_layer_dense) else 0)
        c = {"blocks": stack(kv, n)}
        if cfg.moe and cfg.moe.first_layer_dense:
            c["block0"] = kv()
        return c
    if fam == "ssm":
        return {"blocks": stack(lambda: rwkv6.make_rwkv_state(cfg, batch, dtype),
                                cfg.n_layers)}
    if fam == "hybrid":
        pat = cfg.hybrid.pattern
        gsz = len(pat)
        n_groups = cfg.n_layers // gsz
        window = cfg.hybrid.window

        def group_state():
            return {f"l{i}": (griffin.make_recurrent_state(cfg, batch, dtype)
                              if pat[i] == "R"
                              else layers.make_kv_cache(cfg, batch, max_len,
                                                        dtype, window=window))
                    for i in range(gsz)}

        tail = []
        for i in range(cfg.n_layers - n_groups * gsz):
            li = n_groups * gsz + i
            tail.append(griffin.make_recurrent_state(cfg, batch, dtype)
                        if pat[li % gsz] == "R"
                        else layers.make_kv_cache(cfg, batch, max_len, dtype,
                                                  window=window))
        return {"groups": stack(group_state, n_groups), "tail": tail}
    if fam == "audio":
        return {"blocks": stack(lambda: layers.make_kv_cache(cfg, batch, max_len, dtype),
                                cfg.n_layers),
                "enc_out": jnp.zeros((batch, cfg.encdec.encoder_seq, cfg.d_model),
                                     dtype)}
    raise ValueError(fam)


def decode_step(params, cfg: ArchConfig, cache, tokens: Array, *,
                policy: ExecutionPolicy = STRUCTURED, adapter_tiles=None):
    """One decode step. tokens: [B, 1]. Returns (logits [B,1,V], new cache).

    ``policy`` selects the forward execution regime (inference: the
    structured custom_vjp forwards == plain forwards; quantized params
    carry their format in the tree, dequantized per the policy's backend).

    ``adapter_tiles``: int32 [B // bm] per-tile adapter routing for stacked
    multi-tenant LoRA params (see :func:`layers.apply_linear`); requires a
    ``per_slot`` cache so co-batched requests sit at independent positions.
    """
    x = layers.embed(params["embed"], tokens, cfg)
    fam = cfg.family
    new_cache = dict(cache)
    if adapter_tiles is not None and fam not in ("dense", "vlm"):
        # moe: expert stacks already consume the [E, ., .] group axis —
        # per-tenant expert adapters would need (expert × tenant) grouping
        raise ValueError(f"adapter routing unsupported for {fam!r}")

    if fam in ("dense", "vlm", "moe"):
        if cfg.window_pattern:
            gsz = len(cfg.window_pattern)

            def gbody(x, gs):
                gp, gc = gs
                ncs = {}
                for i in range(gsz):
                    bp = jax.tree_util.tree_map(lambda t: t[i], gp)
                    lc = gc[f"l{i}"]
                    x, nc = dense_block(bp, x, cfg, cache=lc, pos=lc["len"],
                                        window=cfg.window_pattern[i],
                                        policy=policy,
                                        adapter_tiles=adapter_tiles)
                    ncs[f"l{i}"] = nc
                return x, ncs

            x, nc = jax.lax.scan(gbody, x, (params["groups"], cache["groups"]))
            new_cache["groups"] = nc
        else:
            blk = moe_block if fam == "moe" else dense_block
            if "block0" in params:
                x, nc0 = dense_block(params["block0"], x, cfg,
                                     cache=cache["block0"],
                                     pos=cache["block0"]["len"],
                                     policy=policy,
                                     adapter_tiles=adapter_tiles)
                new_cache["block0"] = nc0

            def body(x, bs):
                bp, lc = bs
                x, nc = blk(bp, x, cfg, cache=lc, pos=lc["len"],
                            policy=policy, adapter_tiles=adapter_tiles)[:2]
                return x, nc

            x, nc = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
            new_cache["blocks"] = nc
    elif fam == "ssm":
        def body(x, bs):
            bp, st = bs
            x, ns = rwkv6.rwkv_block(bp, x, cfg, state=st, policy=policy)
            return x, ns

        x, ns = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = ns
    elif fam == "hybrid":
        pat = cfg.hybrid.pattern
        gsz = len(pat)

        def gbody(x, gs):
            gp, gc = gs
            nstates = {}
            for i in range(gsz):
                bp, st = gp[f"l{i}"], gc[f"l{i}"]
                if pat[i] == "R":
                    x, ns = griffin.recurrent_block(bp, x, cfg, state=st,
                                                    policy=policy)
                else:
                    x, ns = dense_block(bp, x, cfg, cache=st, pos=st["len"],
                                        window=cfg.hybrid.window,
                                        policy=policy)
                nstates[f"l{i}"] = ns
            return x, nstates

        x, ng = jax.lax.scan(gbody, x, (params["groups"], cache["groups"]))
        new_cache["groups"] = ng
        n_groups = jax.tree_util.tree_leaves(params["groups"])[0].shape[0]
        ntail = []
        for i, (bp, st) in enumerate(zip(params["tail"], cache["tail"])):
            li = n_groups * gsz + i
            if pat[li % gsz] == "R":
                x, ns = griffin.recurrent_block(bp, x, cfg, state=st,
                                                policy=policy)
            else:
                x, ns = dense_block(bp, x, cfg, cache=st, pos=st["len"],
                                    window=cfg.hybrid.window, policy=policy)
            ntail.append(ns)
        new_cache["tail"] = ntail
    elif fam == "audio":
        x = x + _sinusoid_at(cache["blocks"]["len"][0], cfg.d_model, x.dtype)
        enc_out = cache["enc_out"]

        def body(x, bs):
            bp, lc = bs
            h, nc = layers.attention(bp["attn"],
                                     layers.norm(bp["ln1"], x, cfg,
                                                 policy=policy), cfg,
                                     cache=lc, pos=lc["len"], use_rope=False,
                                     policy=policy)
            x = x + h
            h, _ = layers.attention(bp["xattn"],
                                    layers.norm(bp["lnx"], x, cfg,
                                                policy=policy), cfg,
                                    causal=False, kv_x=enc_out, use_rope=False,
                                    policy=policy)
            x = x + h
            x = x + layers.mlp(bp["mlp"],
                               layers.norm(bp["ln2"], x, cfg, policy=policy),
                               cfg, policy=policy)
            return x, nc

        x, nc = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = nc
    else:
        raise ValueError(fam)

    x = layers.norm(params["final_norm"], x, cfg, policy=policy)
    return layers.unembed(params["embed"], x, cfg), new_cache


def _sinusoid_at(pos, d, dtype):
    dim = jnp.arange(d // 2, dtype=jnp.float32)
    ang = pos.astype(jnp.float32) / (10000 ** (2 * dim / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])[None, None].astype(dtype)


# ---------------------------------------------------------------------------
# trainable-parameter partitioning (LoRA A/B only)
# ---------------------------------------------------------------------------


def trainable_mask(params):
    """Pytree of bools: True for LoRA factors (keys 'a'/'b' under a linear)."""
    def mark(path, leaf):
        keys = [getattr(k, "key", None) for k in path]
        return keys[-1] in ("a", "b")

    return jax.tree_util.tree_map_with_path(mark, params)


def split_params(params):
    """(trainable, frozen) — partition by trainable_mask."""
    mask = trainable_mask(params)
    train = jax.tree_util.tree_map(lambda p, m: p if m else None, params, mask)
    frozen = jax.tree_util.tree_map(lambda p, m: None if m else p, params, mask)
    return train, frozen


def merge_params(train, frozen):
    return jax.tree_util.tree_map(
        lambda t, f: t if f is None else f, train, frozen,
        is_leaf=lambda x: x is None)
