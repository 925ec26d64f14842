"""Mixture-of-Experts MLP: routing, and two ways to run the experts.

Routing (:func:`route`) scores every one of the router's ``n_experts``
(softmax, or sigmoid with DeepSeek-V3's correction bias choosing the top-k
while the combine weights stay the plain scores), takes the top-k, and
normalises the chosen weights to sum to one and scales them by the
config's ``routed_scale``.

**Dropless, one device's share** (:func:`dropless_experts`, every call
without explicit sharding): the layer holds experts ``[held_lo, held_lo +
n_held)`` and computes their part of the result for every token slot that
routing gives them; what the absent experts would add is left out (it
comes from the other devices of an expert-parallel deployment). Slots are
sorted by held expert into a buffer of ``T·min(k, n_held)`` rows plus one
row tile per expert — the most that routing can send here, so no slot is
ever dropped — with each expert's rows padded to whole row tiles. The
expert linears run on the device-side tile schedule
(``kernels/ops.lora_expert_linear``): tiles past the routed rows are
skipped, so the work follows the routed rows and not the bound.

**Capacity, expert-parallel under GSPMD** (:func:`capacity_experts`, the
multi-chip launchers' path, with ``shard`` set): dispatch grouped by
sequence (group = batch row, already data-parallel-sharded), so routing,
capacity assignment and the scatter into per-expert buffers are per-group
operations; GSPMD keeps them on the data axis and inserts one all-to-all
pair per layer when the ``[B, E, C, d]`` buffer is resharded to
``[E, B·C, d]`` (experts on the ``model`` axis). A global sort, as the
dropless path makes, cannot be sharded by GSPMD (every device would hold
the whole ``[T·k, d]`` dispatch), so this path stays for sharded calls.
Tokens past an expert's capacity ``C = N·top_k/E · 1.25`` contribute zero
(Switch-style). It holds every expert.

Either way the expert FFN is a per-expert LoRA MLP whose backward is the
paper's structured one (per-expert ``h = x@A`` recomputed, never stored),
and the layer also returns its counters: ``routed_rows`` (slots routed to
held experts), ``computed_rows`` (rows the expert linears ran, tile
padding included) and ``dropped`` (slots not computed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.api.policy import STRUCTURED, ExecutionPolicy
from repro.configs.base import ArchConfig
from repro.models import layers

CAPACITY_FACTOR = 1.25
#: expert row tile of the dropless layout at full size
EXPERT_TILE = 512
STATS = ("routed_rows", "computed_rows", "dropped")


def moe_params(key, cfg: ArchConfig, *, lora: bool = True):
    assert cfg.moe is not None
    m = cfg.moe
    ks = jax.random.split(key, 5)
    dtype = jnp.dtype(cfg.dtype)
    d, f = cfg.d_model, m.d_expert
    E = m.resolved_n_held
    r = cfg.lora.rank
    tg = cfg.lora.targets

    def expert_stack(k, d_in, d_out, with_lora):
        kw, ka = jax.random.split(k)
        p = {"w": jax.random.normal(kw, (E, d_in, d_out), dtype) * (d_in ** -0.5)}
        if with_lora:
            p["a"] = jax.random.normal(ka, (E, d_in, r), dtype) * (r ** -0.5)
            p["b"] = jnp.zeros((E, r, d_out), dtype)
        return p

    p = {
        "router": jax.random.normal(ks[0], (d, m.n_experts), dtype) * (d ** -0.5),
        "gate": expert_stack(ks[1], d, f, lora and "gate" in tg),
        "up": expert_stack(ks[2], d, f, lora and "up" in tg),
        "down": expert_stack(ks[3], f, d, lora and "down" in tg),
    }
    if m.bias_select:
        # e_score_correction_bias: frozen, chooses experts, never weighs them
        p["score_bias"] = jnp.zeros((m.n_experts,), jnp.float32)
    if m.n_shared:
        # shared experts fused into one dense gated MLP of width n_shared·f
        p["shared"] = layers.mlp_params(ks[4], cfg, d_ff=m.n_shared * f, lora=lora)
    return p


def route(p, x, cfg: ArchConfig):
    """x [..., d] -> (weights f32 [..., k], expert ids int32 [..., k]).

    Sigmoid scoring computes the router in float32 at full precision (the
    published router runs in float32): near-tied scores decide which
    experts a token reaches."""
    m = cfg.moe
    if m.scoring == "sigmoid":
        logits = jnp.dot(x.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
    elif m.scoring == "softmax":
        scores = jax.nn.softmax((x @ p["router"]).astype(jnp.float32), -1)
    else:
        raise ValueError(f"unknown scoring {m.scoring!r}")
    if m.bias_select:
        _, idx = jax.lax.top_k(scores + p["score_bias"], m.top_k)
        weights = jnp.take_along_axis(scores, idx, -1)
    else:
        weights, idx = jax.lax.top_k(scores, m.top_k)
    weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * m.routed_scale, idx


# ---------------------------------------------------------------------------
# dropless: this device's experts, no slot dropped
# ---------------------------------------------------------------------------


def expert_tile(rows: int) -> int:
    """Row tile of the dropless layout for ``rows`` expected rows an
    expert: ``EXPERT_TILE`` at full size, one 8-aligned tile otherwise."""
    return EXPERT_TILE if rows >= EXPERT_TILE else max(8, -(-rows // 8) * 8)


def dropless_layout(idx, cfg: ArchConfig):
    """Where each token slot of the held experts goes.

    idx [T, k] expert ids -> dict: ``dest`` [T·k] buffer row of each slot
    (``rows`` for slots of absent experts), ``src`` [rows] token of each
    buffer row (``T`` for padding), ``slot`` [rows] slot of each row
    (``T·k`` for padding), ``tile_gid`` [tiles] held expert of each row
    tile, ``n_live`` the tiles that hold routed rows, ``counts`` [n_held]
    rows per held expert, and the static ``rows`` and ``bm``."""
    m = cfg.moe
    T, k = idx.shape
    nh = m.resolved_n_held
    bm = expert_tile(T * k // m.n_experts)
    rows = -(-T * min(k, nh) // bm) * bm + nh * bm
    e = idx.reshape(-1) - m.held_lo
    held = (e >= 0) & (e < nh)
    key = jnp.where(held, e, nh).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros((nh + 1,), jnp.int32).at[key].add(1)[:nh]
    padded = -(-counts // bm) * bm
    offs = jnp.cumsum(padded) - padded                  # first row of each
    start = jnp.cumsum(counts) - counts                 # first sorted slot
    sk = key[order]
    skc = jnp.minimum(sk, nh - 1)
    rank = jnp.arange(T * k, dtype=jnp.int32) - start[skc]
    dest_sorted = jnp.where(sk < nh, offs[skc] + rank, rows)
    dest = jnp.zeros((T * k,), jnp.int32).at[order].set(dest_sorted)
    slot = jnp.full((rows,), T * k, jnp.int32).at[dest].set(
        jnp.arange(T * k, dtype=jnp.int32), mode="drop")
    src = jnp.where(slot < T * k, slot // k, T)
    n_tiles = rows // bm
    ends = jnp.cumsum(padded)
    t_row = jnp.arange(n_tiles, dtype=jnp.int32) * bm
    gid = jnp.sum(ends[None, :] <= t_row[:, None], -1).astype(jnp.int32)
    n_live = (jnp.sum(padded) // bm).astype(jnp.int32)
    # tiles past the routed rows keep the last live tile's expert, so the
    # kernels fetch no new weights for them
    gid = jnp.where(jnp.arange(n_tiles) < n_live, gid,
                    gid[jnp.maximum(n_live - 1, 0)])
    gid = jnp.minimum(gid, nh - 1)
    return {"dest": dest, "src": src, "slot": slot, "tile_gid": gid,
            "n_live": n_live, "counts": counts, "held": held, "rows": rows,
            "bm": bm}


@jax.custom_vjp
def _gather_rows(x, src):
    """x [T, d] -> x[src] [rows, d], zero where ``src`` is T (padding).
    The backward sums each token's rows in float32."""
    return jnp.take(x, src, axis=0, mode="fill", fill_value=0)


def _gather_rows_fwd(x, src):
    # an empty [T, 0] array carries T and the type to the backward
    return _gather_rows(x, src), (src, x[:, :0])


def _gather_rows_bwd(res, g):
    src, proto = res
    dx = jnp.zeros((proto.shape[0], g.shape[1]), jnp.float32).at[src].add(
        g, mode="drop")
    return dx.astype(proto.dtype), None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _combine_rows(y, src, T: int):
    """Rows [rows, d] summed into their tokens [T, d] in float32 (rows
    whose ``src`` is T are padding and dropped). The backward hands each
    row its token's gradient in the rows' own type."""
    return jnp.zeros((T, y.shape[1]), jnp.float32).at[src].add(
        y, mode="drop")


def _combine_rows_fwd(y, src, T):
    return _combine_rows(y, src, T), (src, y[:0])


def _combine_rows_bwd(T, res, g):
    src, proto = res
    return jnp.take(g.astype(proto.dtype), src, axis=0, mode="fill",
                    fill_value=0), None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def dropless_experts(p, x2, weights, idx, cfg: ArchConfig,
                     policy: ExecutionPolicy):
    """x2 [T, d] -> (held experts' part of the result [T, d], counters)."""
    from repro.kernels import ops as kops

    T, d = x2.shape
    with jax.named_scope("moe/dispatch"):
        lay = dropless_layout(idx, cfg)
        xs = _gather_rows(x2, lay["src"])
        w_row = jnp.take(weights.reshape(-1), lay["slot"], mode="fill",
                         fill_value=0)

    def lin(q, z):
        return kops.lora_expert_linear(
            z, q["w"], q.get("a"), q.get("b"), lay["tile_gid"], lay["n_live"],
            cfg.lora.scale, bm=lay["bm"], policy=policy)

    with jax.named_scope("moe/experts"):
        hidden = layers.act_silu(lin(p["gate"], xs), policy) \
            * lin(p["up"], xs)
        # the combine weight scales each row before the (linear) down
        # projection, so no float32 copy of the [rows, d] output is made
        hidden = (hidden.astype(jnp.float32) * w_row[:, None]).astype(
            hidden.dtype)
        ys = lin(p["down"], hidden)
    with jax.named_scope("moe/combine"):
        out = _combine_rows(ys, lay["src"], T)
    routed = jnp.sum(lay["counts"])
    stats = {"routed_rows": routed,
             "computed_rows": lay["n_live"] * lay["bm"],
             "dropped": jnp.sum(lay["held"]
                                & (lay["dest"] >= lay["rows"])).astype(
                                    jnp.int32)}
    return out.astype(x2.dtype), stats


# ---------------------------------------------------------------------------
# capacity: every expert, per-group dispatch shardable by GSPMD
# ---------------------------------------------------------------------------


def _capacity(n_per_group: int, m) -> int:
    c = int(n_per_group * m.top_k / m.n_experts * CAPACITY_FACTOR)
    return max(8, -(-c // 8) * 8)


def _maybe_constrain(x, spec):
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def capacity_experts(p, x, weights, idx, cfg: ArchConfig,
                     policy: ExecutionPolicy, shard):
    """x [B, N, d]; weights/idx [B, N, k] -> ([B, N, d], counters).

    ``shard``: {"dp": axes, "model": axis, "sp": n} — explicit sharding
    constraints on the dispatch buffers (group dim on DP, expert dim on
    model)."""
    from jax.sharding import PartitionSpec as P
    from repro.core import structured

    m = cfg.moe
    if m.resolved_n_held != m.n_experts:
        raise NotImplementedError("the capacity path holds every expert")
    B, N, d = x.shape
    k = m.top_k
    E = m.n_experts
    # groups = (batch row × sequence shard): with the activations sharded
    # P(dp, model, ·) between blocks, routing/capacity/scatter are then
    # FULLY LOCAL to every device — zero collectives before the EP
    # all-to-all
    sp = shard.get("sp", 1)
    sp = sp if N % sp == 0 else 1
    Ng = N // sp
    C = _capacity(Ng, m)
    xg = x.reshape(B, sp, Ng, d)
    idx = idx.reshape(B, sp, Ng, k)
    weights = weights.reshape(B, sp, Ng, k).astype(x.dtype)

    # --- per-group capacity assignment (no global sort) --------------------
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)         # [B,sp,Ng,k,E]
    flat_oh = onehot.reshape(B, sp, Ng * k, E)
    pos_in_e = jnp.cumsum(flat_oh, axis=2) - flat_oh         # exclusive cumsum
    pos = jnp.sum(pos_in_e * flat_oh, -1).reshape(B, sp, Ng, k)
    keep = pos < C
    pos_c = jnp.clip(pos, 0, C - 1)

    # --- scatter into [B, sp, E, C, d] (groups stay on (dp, model)) --------
    vals = (xg[:, :, :, None, :] * keep[..., None].astype(x.dtype))
    vals = vals.reshape(B, sp, Ng * k, d)
    eid = idx.reshape(B, sp, Ng * k)
    slot = pos_c.reshape(B, sp, Ng * k)

    def scatter_group(v, e, s):
        return jnp.zeros((E, C, d), x.dtype).at[e, s].add(v)

    buf = jax.vmap(jax.vmap(scatter_group))(vals, eid, slot)  # [B,sp,E,C,d]
    dp = shard["dp"]
    buf = _maybe_constrain(buf, P(dp, shard["model"], None, None, None))

    # --- reshard to expert-parallel and run the expert LoRA MLP ------------
    ebuf = buf.transpose(2, 0, 1, 3, 4).reshape(E, B * sp * C, d)
    # expert dim on model, token rows on DP: one all-to-all pair/layer
    ebuf = _maybe_constrain(ebuf, P(shard["model"], dp, None))

    store_h = policy.backend == "store_h"

    def elin(q, z):
        # per-expert [E,·,·] weights. pallas backend: the grouped kernel
        # family (kernels/lora_grouped.py) runs all experts in one launch,
        # dequantizing int8 expert stacks tile-wise in VMEM — a dense
        # per-expert W0 never exists in HBM (jaxpr-asserted in tests).
        from repro.core.quant import maybe_dequant
        if "a" in q:
            if policy.backend == "pallas":
                from repro.kernels import ops as kops
                return kops.lora_grouped_linear(z, q["w"], q["a"], q["b"],
                                                cfg.lora.scale, policy=policy)
            w = maybe_dequant(q["w"], z.dtype)
            if policy.backend == "plain":
                return z @ w + cfg.lora.scale * ((z @ q["a"]) @ q["b"])
            fn = structured.lora_linear_store_h if store_h \
                else structured.lora_linear
            return fn(z, w, q["a"], q["b"], None, cfg.lora.scale)
        return z @ maybe_dequant(q["w"], z.dtype)

    hidden = layers.act_silu(elin(p["gate"], ebuf), policy) \
        * elin(p["up"], ebuf)
    y_ebuf = elin(p["down"], hidden)                         # [E, B·C, d]

    # --- return path: reshard back to groups, gather, combine --------------
    y_ebuf = _maybe_constrain(y_ebuf, P(shard["model"], dp, None))
    y_buf = y_ebuf.reshape(E, B, sp, C, d).transpose(1, 2, 0, 3, 4)
    y_buf = _maybe_constrain(y_buf, P(dp, shard["model"], None, None, None))

    def gather_group(yb, e, s):
        return yb[e, s]                                      # [Ng·k, d]

    out_slots = jax.vmap(jax.vmap(gather_group))(y_buf, eid, slot)
    out_slots = out_slots.reshape(B, sp, Ng, k, d) * \
        (weights * keep.astype(x.dtype))[..., None]
    out = jnp.sum(out_slots, axis=3).reshape(B, N, d)
    stats = {"routed_rows": jnp.int32(B * N * k),
             "computed_rows": jnp.int32(E * B * sp * C),
             "dropped": jnp.sum(~keep).astype(jnp.int32)}
    return out, stats


def moe_mlp(p, x, cfg: ArchConfig, *,
            policy: ExecutionPolicy = STRUCTURED, shard=None):
    """x: [B, N, d] -> ([B, N, d], counters {name: int32}).

    ``shard``: {"dp": axes, "model": axis, "sp": n} set by the production
    launchers selects the capacity path under GSPMD; None (one device's
    share, unit tests) the dropless path.
    """
    B, N, d = x.shape
    with jax.named_scope("moe/route"):
        weights, idx = route(p, x, cfg)
    if shard:
        out, stats = capacity_experts(p, x, weights, idx, cfg, policy, shard)
    else:
        out, stats = dropless_experts(p, x.reshape(B * N, d),
                                      weights.reshape(B * N, -1),
                                      idx.reshape(B * N, -1), cfg, policy)
        out = out.reshape(B, N, d)
    if "shared" in p:
        out = out + layers.mlp(p["shared"], x, cfg, policy=policy)
    return out, stats


def aux_load_balance_loss(p, x, cfg: ArchConfig):
    """Switch-style load-balance auxiliary (exposed for training configs)."""
    m = cfg.moe
    T = x.shape[0] * x.shape[1]
    logits = (x.reshape(T, -1) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    _, idx = jax.lax.top_k(probs, m.top_k)
    frac = jnp.bincount(idx.reshape(-1), length=m.n_experts) / (T * m.top_k)
    imp = jnp.mean(probs, 0)
    return m.n_experts * jnp.sum(frac * imp)
