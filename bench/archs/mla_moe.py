"""DeepSeek-V3 decoder (``model_type`` ``deepseek_v3``: Moonlight): latent
attention and sigmoid-routed experts, as one chip's expert-parallel share.

What the benchmark needs to know of this architecture, reached through
the configuration's ``architecture`` key (``bench/spec.load_arch``): the
key map and refusal, ``Widths``, the seeded weights and their program
tree, the FLOPs a step requires, the LoRA, attention and expert calls of a
step for the readers, the plain float32 reference and the int8 control.

The model. Layer 0: pre-norm latent attention and a dense SwiGLU FFN
(``intermediate_size``); layers 1..L-1: latent attention and an expert
layer — a router of ``router_experts`` sigmoid scores, the top-k chosen by
score plus a correction bias, combine weights the chosen scores (no bias)
normalised over the k and times ``routed_scaling_factor``, each routed
expert a SwiGLU of width ``moe_intermediate_size``, plus ``n_shared``
shared experts as one SwiGLU of ``n_shared`` times that width. Latent
attention (no ``q_lora_rank``): q = x·Wq, per head ``qk_nope`` + ``qk_rope``
wide; [c, k_rope] = x·Wkv_a, c RMSNorm-ed; [k_nope, v] = c·Wkv_b per head;
rotary embeddings on the rope channels of q and on k_rope, one vector per
token shared by every head; causal softmax at 1/√(nope + rope); out =
o·Wo. RMSNorm with ``rms_norm_eps``; untied LM head; mean next-token
cross-entropy; plain SGD on the LoRA factors (``y = x·W + (alpha/r)·
(x·A)·B`` on every linear of the block: q, kv_a, kv_b, o, and the gate,
up and down of the dense FFN, the shared experts and each held expert;
the router and the correction bias are frozen and carry none).

Departures from the published model, each the same in program and
reference:

* the expert share: the layer holds experts ``[experts_held_from, +
  n_routed_experts)`` of ``router_experts``; the router scores all of
  them and what the absent experts would add is left out;
* the vocabulary slice: ``vocab_size`` rows of the embedding and head,
  the traffic's token ids drawn from them, the loss over them;
* the sequence-wise balance loss (``seq_aux``) is not in the fine-tuning
  loss: it trains the router, which is frozen here;
* the rope channel pairing: rotate-half (channel i with i + 32), a fixed
  permutation of the published interleaved pairing of the rope channels
  of the random ``q`` and ``kv_a`` weights;
* the correction bias is drawn from the seed, N(0, 0.05²) in float32
  (the published one is learnt).

Weights: frozen linears (and the router and head) Student-t with 4
degrees of freedom at variance 1/K, norm scales 1 + N(0, 0.1²), the
embedding N(0, 0.02²), LoRA A N(0, 1/r) and B zero, as fine-tuning starts.

Reference: to fit beside the stored weights at 8192 positions it runs one
row of the batch at a time, each layer under rematerialisation, attention
over blocks of ``ATTN_BLOCK`` queries, the held experts as a masked loop
over all positions, and the LM head over blocks of positions. Nothing of
the program is imported.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp

from bench.flops import BYTES, lora_op
from bench.reference import F32, HIGHEST, RowReference, ce_sum, mm, \
    rmsnorm, rope
from bench.spec import CellError
from bench.weights import embedding, leaf

# config.json key -> ArchConfig field of the program's registry
HF_TO_ARCH = {
    "hidden_size": "d_model", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "num_hidden_layers": "n_layers",
    "vocab_size": "vocab", "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "intermediate_size": "moe.d_dense", "moe_intermediate_size":
    "moe.d_expert", "n_routed_experts": "moe.n_held",
    "num_experts_per_tok": "moe.top_k", "n_shared_experts": "moe.n_shared",
    "first_k_dense_replace": "moe.first_layer_dense",
    "routed_scaling_factor": "moe.routed_scale", "scoring_func":
    "moe.scoring",
    "kv_lora_rank": "mla.kv_lora_rank", "q_lora_rank": "mla.q_lora_rank",
    "qk_nope_head_dim": "mla.qk_nope_head_dim",
    "qk_rope_head_dim": "mla.qk_rope_head_dim",
    "v_head_dim": "mla.v_head_dim",
}
ASSUMED_TO_ARCH = {"dtype": "dtype", "router_experts": "moe.n_experts",
                   "experts_held_from": "moe.held_lo"}
# published keys this module reads no number from, at the only value it
# models
FIXED = {"model_type": "deepseek_v3", "hidden_act": "silu",
         "attention_bias": False, "topk_method": "noaux_tc",
         "moe_layer_freq": 1, "num_nextn_predict_layers": 0, "ep_size": 1,
         "n_group": 1, "topk_group": 1, "norm_topk_prob": True}

ATTN = ("q", "kv_a", "kv_b", "o")
FFN = ("gate", "up", "down")
ATTN_BLOCK = 512
BIAS_STD = 0.05


def refuse(cfg) -> None:
    """Registry settings this module does not model."""
    m = cfg.moe
    if (cfg.family != "moe" or m is None or cfg.mla is None
            or cfg.mla.q_lora_rank is not None or not m.first_layer_dense
            or not m.bias_select or m.scoring != "sigmoid"
            or cfg.window_pattern or cfg.tie_embeddings):
        raise CellError(f"{cfg.name} is not a DeepSeek-V3 decoder")


@dataclasses.dataclass(frozen=True)
class Widths:
    layers: int          # the dense layer and layers - 1 MoE layers
    d: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    dense_ff: int
    ff: int              # one routed expert's width
    experts: int         # the router's outputs
    held_from: int
    held: int            # experts held here
    top_k: int
    shared: int
    routed_scale: float
    vocab: int
    rank: int
    targets: Tuple[str, ...]

    @property
    def qk(self) -> int:
        return self.nope + self.rope

    @property
    def moe_layers(self) -> int:
        return self.layers - 1

    @classmethod
    def from_config(cls, conf: dict) -> "Widths":
        pub, ass = conf["published"], conf["assumed"]
        for key, want in FIXED.items():
            if pub[key] != want:
                raise CellError(f"{conf['name']}: {key} {pub[key]!r} is not "
                                f"modelled (only {want!r})")
        return cls(layers=pub["num_hidden_layers"], d=pub["hidden_size"],
                   heads=pub["num_attention_heads"],
                   kv_rank=pub["kv_lora_rank"],
                   nope=pub["qk_nope_head_dim"],
                   rope=pub["qk_rope_head_dim"], v=pub["v_head_dim"],
                   dense_ff=pub["intermediate_size"],
                   ff=pub["moe_intermediate_size"],
                   experts=ass["router_experts"],
                   held_from=ass["experts_held_from"],
                   held=pub["n_routed_experts"],
                   top_k=pub["num_experts_per_tok"],
                   shared=pub["n_shared_experts"],
                   routed_scale=float(pub["routed_scaling_factor"]),
                   vocab=pub["vocab_size"], rank=ass["lora"]["rank"],
                   targets=tuple(ass["lora"]["targets"]))


def attn_linears(w: Widths) -> List[Tuple[str, int, int]]:
    """(name, K, N) of the latent attention's linears."""
    return [("q", w.d, w.heads * w.qk), ("kv_a", w.d, w.kv_rank + w.rope),
            ("kv_b", w.kv_rank, w.heads * (w.nope + w.v)),
            ("o", w.heads * w.v, w.d)]


def ffn_linears(ff: int, d: int) -> List[Tuple[str, int, int]]:
    return [("gate", d, ff), ("up", d, ff), ("down", ff, d)]


def routed_share(w: Widths) -> float:
    """Token slots a held expert gets per token, when routing spreads
    evenly: top_k / router_experts."""
    return w.top_k / w.experts


# ------------------------------------------------------------ op counts

def _lora(k, n, r):
    return 6 * r * (k + n)


def train_flops_per_token(w: Widths, seq: int) -> int:
    """FLOPs a LoRA fine-tuning step requires per token.

    Counted, forward and input-gradient of every frozen matmul (4 FLOPs a
    weight): latent attention in every layer, the dense FFN, the router,
    the shared experts, the held experts at their expected share of the
    token (``routed_share`` slots an expert, so top_k · held / experts
    expert FFNs a token) and the LM head; the LoRA forward, dx, dA and dB
    of every linear (6·r·(K+N)), the experts' at the same share; causal
    QKᵀ (q·k width) and PV (v width) forward and a backward of twice
    that. Not counted: frozen weight gradients, recomputed work."""
    attn = sum(k * n for _, k, n in attn_linears(w))
    dense = sum(k * n for _, k, n in ffn_linears(w.dense_ff, w.d))
    shared = sum(k * n for _, k, n in ffn_linears(w.shared * w.ff, w.d))
    expert = sum(k * n for _, k, n in ffn_linears(w.ff, w.d))
    slots = w.held * routed_share(w)
    frozen = (w.layers * attn + dense + w.moe_layers
              * (shared + w.d * w.experts + slots * expert) + w.d * w.vocab)
    lora = (w.layers * sum(_lora(k, n, w.rank) for _, k, n in attn_linears(w))
            + sum(_lora(k, n, w.rank) for _, k, n in ffn_linears(w.dense_ff,
                                                                 w.d))
            + w.moe_layers * sum(
                _lora(k, n, w.rank)
                for _, k, n in ffn_linears(w.shared * w.ff, w.d))
            + w.moe_layers * slots * sum(_lora(k, n, w.rank)
                                         for _, k, n in ffn_linears(w.ff,
                                                                    w.d)))
    scores = 3 * 2 * (seq // 2) * w.heads * (w.qk + w.v)
    return int(4 * frozen + lora + w.layers * scores)


def lora_calls(w: Widths, batch: int, seq: int):
    """(target, M, K, N) of each fused LoRA linear a step calls (the latent
    attention's, the dense FFN's and the shared experts'), every one over
    all ``batch · seq`` rows. The routed experts run on kernels of their
    own (:func:`expert_calls`)."""
    m = batch * seq
    return ([(t, m, k, n) for t, k, n in attn_linears(w)]
            + [(t, m, k, n) for t, k, n in ffn_linears(w.dense_ff, w.d)]
            + [(t, m, k, n) for t, k, n in ffn_linears(w.shared * w.ff,
                                                       w.d)])


def expert_calls(w: Widths, batch: int, seq: int):
    """(target, M, K, N, experts) of each expert linear a step calls: M the
    expected rows over the held experts, ``batch · seq · top_k · held /
    router_experts`` (an even spread of the routing), in ``experts``
    equal groups."""
    m = int(round(batch * seq * w.held * routed_share(w)))
    return [(t, m, k, n, w.held) for t, k, n in ffn_linears(w.ff, w.d)]


def flash_op(kind: str, b: int, heads: int, n: int, qk: int,
             v: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of causal attention over ``b`` sequences of ``n``
    with a q·k width ``qk`` and a v width ``v`` (every head its own k and
    v). fwd: S = Q·Kᵀ and O = P·V over n/2 keys a query; bwd (dq and dkv
    together): dP, dV, dQ, dK, twice the forward's products."""
    fwd = 2 * b * heads * n * (n // 2) * (qk + v)
    q_bytes = b * heads * n * qk       # q, k, dq, dk
    v_bytes = b * heads * n * v        # v, out, g, dv
    lse = b * heads * n * 4
    if kind == "fwd":
        return fwd, BYTES * (2 * q_bytes + 2 * v_bytes) + lse
    if kind == "bwd":
        return 2 * fwd, BYTES * (4 * q_bytes + 4 * v_bytes) + lse
    raise ValueError(kind)


def flash_ops(w: Widths, batch: int, seq: int):
    """(FLOPs, bytes) of one flash attention forward call of the step and
    of one backward (dq and dkv together), at q·k width against v width."""
    shape = (batch, w.heads, seq, w.qk, w.v)
    return flash_op("fwd", *shape), flash_op("bwd", *shape)


def expert_op(kind: str, m: int, k: int, n: int, r: int,
              groups: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of one expert linear op: ``groups`` LoRA linears of
    ``m / groups`` rows each (``bench/flops.lora_op``), every group's
    weights read once."""
    f, b = lora_op(kind, m // groups, k, n, r)
    return groups * f, groups * b


# -------------------------------------------------------------- weights

def base_shapes(w: Widths) -> dict:
    L, E = w.moe_layers, w.held
    out = {"embed": (w.vocab, w.d), "head_w": (w.d, w.vocab),
           "final_norm": (w.d,), "ln1": (w.layers, w.d),
           "ln2": (w.layers, w.d), "kv_norm": (w.layers, w.kv_rank),
           "router_w": (L, w.d, w.experts), "score_bias": (L, w.experts)}
    for name, k, n in attn_linears(w):
        out[f"{name}_w"] = (w.layers, k, n)
    for name, k, n in ffn_linears(w.dense_ff, w.d):
        out[f"{name}0_w"] = (k, n)
    for name, k, n in ffn_linears(w.shared * w.ff, w.d):
        out[f"shared_{name}_w"] = (L, k, n)
    for name, k, n in ffn_linears(w.ff, w.d):
        out[f"expert_{name}_w"] = (L, E, k, n)
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def make_base(w: Widths, key, dtype: str):
    """Frozen base weights for one seed, per-layer keys ``fold_in(leaf, l)``
    so that any one layer can be made again alone."""
    out = {}
    for i, (name, shape) in enumerate(sorted(base_shapes(w).items())):
        k = jax.random.fold_in(key, i)
        if name == "embed":
            v = embedding(k, shape, dtype)
        elif name == "final_norm":
            v = leaf(k, shape, 0.1, dtype, 1.0)
        elif name in ("ln1", "ln2", "kv_norm"):
            v = leaf(k, shape[1:], 0.1, dtype, 1.0, shape[0])
        elif name == "score_bias":
            v = leaf(k, shape[1:], BIAS_STD, F32, 0.0, shape[0])
        elif len(shape) == 2:          # the dense layer's FFN and the head
            v = leaf(k, shape, shape[0] ** -0.5, dtype, heavy=True)
        else:
            v = leaf(k, shape[1:], shape[-2] ** -0.5, dtype, 0.0, shape[0],
                     heavy=True)
        out[name] = v
    return out


def lora_shapes(w: Widths) -> dict:
    """(name, layers or None, leading expert dims, K, N) of every LoRA
    target, in the canonical layout."""
    out = {t: (w.layers, (), k, n) for t, k, n in attn_linears(w)}
    out.update({f"{t}0": (None, (), k, n)
                for t, k, n in ffn_linears(w.dense_ff, w.d)})
    out.update({f"shared_{t}": (w.moe_layers, (), k, n)
                for t, k, n in ffn_linears(w.shared * w.ff, w.d)})
    out.update({f"expert_{t}": (w.moe_layers, (w.held,), k, n)
                for t, k, n in ffn_linears(w.ff, w.d)})
    return out


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def make_lora(w: Widths, key, dtype: str, b_std: float = 0.0):
    """LoRA factors of every target: A ~ N(0, 1/r); B ~ N(0, b_std²)
    (zero, as fine-tuning starts, when ``b_std`` is 0)."""
    out = {}
    for i, (name, (layers, lead, k_in, n_out)) in enumerate(
            sorted(lora_shapes(w).items())):
        ka, kb = jax.random.split(jax.random.fold_in(key, 1000 + i))
        a = leaf(ka, lead + (k_in, w.rank), w.rank ** -0.5, dtype, 0.0,
                 layers)
        b_shape = lead + (w.rank, n_out)
        b = (leaf(kb, b_shape, b_std, dtype, 0.0, layers) if b_std
             else jnp.zeros(((layers,) if layers else ()) + b_shape, dtype))
        out[name] = {"a": a, "b": b}
    return out


def _lin(w, lo):
    p = {"w": w}
    if lo is not None:
        p["a"], p["b"] = lo["a"], lo["b"]
    return p


def to_program(base: dict, lora: dict, w: Widths) -> dict:
    """The same arrays nested as the program's ``moe``-family tree
    (``block0``: layer 0; ``blocks``: the MoE layers, stacked)."""
    def attn(sl):
        p = {t: _lin(sl(base[f"{t}_w"]), jax.tree_util.tree_map(
            sl, lora.get(t))) for t in ATTN}
        p["kv_norm"] = sl(base["kv_norm"])
        return p

    first = lambda t: t[0]
    rest = lambda t: t[1:]
    return {
        "embed": {"tok": base["embed"], "head": base["head_w"]},
        "final_norm": base["final_norm"],
        "block0": {
            "ln1": base["ln1"][0], "ln2": base["ln2"][0], "attn": attn(first),
            "mlp": {t: _lin(base[f"{t}0_w"], lora.get(f"{t}0")) for t in FFN},
        },
        "blocks": {
            "ln1": base["ln1"][1:], "ln2": base["ln2"][1:],
            "attn": attn(rest),
            "moe": {
                "router": base["router_w"], "score_bias": base["score_bias"],
                **{t: _lin(base[f"expert_{t}_w"], lora.get(f"expert_{t}"))
                   for t in FFN},
                "shared": {t: _lin(base[f"shared_{t}_w"],
                                   lora.get(f"shared_{t}")) for t in FFN},
            },
        },
    }


def lora_tree(lora: dict) -> dict:
    """Only the LoRA leaves, at the program tree's paths."""
    def ab(name, sl=None):
        lo = lora[name]
        return {k: (sl(v) if sl else v) for k, v in lo.items()}

    first = lambda t: t[0]
    rest = lambda t: t[1:]
    return {
        "block0": {"attn": {t: ab(t, first) for t in ATTN},
                   "mlp": {t: ab(f"{t}0") for t in FFN}},
        "blocks": {"attn": {t: ab(t, rest) for t in ATTN},
                   "moe": {**{t: ab(f"expert_{t}") for t in FFN},
                           "shared": {t: ab(f"shared_{t}") for t in FFN}}},
    }


def lora_of(params: dict) -> dict:
    """The LoRA leaves of a program tree, in the canonical layout."""
    b0, bs = params["block0"], params["blocks"]
    out = {}
    for t in ATTN:
        out[t] = {k: jnp.concatenate([b0["attn"][t][k][None],
                                      bs["attn"][t][k]]) for k in ("a", "b")}
    for t in FFN:
        out[f"{t}0"] = {k: b0["mlp"][t][k] for k in ("a", "b")}
        out[f"expert_{t}"] = {k: bs["moe"][t][k] for k in ("a", "b")}
        out[f"shared_{t}"] = {k: bs["moe"]["shared"][t][k]
                              for k in ("a", "b")}
    return out


# ------------------------------------------------------------ reference

def linear(x, wt, lo, scale):
    y = mm(x, wt)
    if lo is not None:
        y = y + scale * mm(mm(x, lo["a"]), lo["b"])
    return y


def swiglu(x, ws, los, scale):
    g = linear(x, ws[0], los[0], scale)
    u = linear(x, ws[1], los[1], scale)
    return linear(jax.nn.silu(g) * u, ws[2], los[2], scale)


def attention(x, lw, lo, w: Widths, eps, theta, scale):
    """Causal latent attention on one sequence, x: [N, d] float32 (already
    normed); queries in blocks of ``ATTN_BLOCK``."""
    n = x.shape[0]
    q = linear(x, lw["q_w"], lo["q"], scale).reshape(n, w.heads, w.qk)
    kv = linear(x, lw["kv_a_w"], lo["kv_a"], scale)
    c = rmsnorm(kv[:, :w.kv_rank], lw["kv_norm"], eps)
    kvb = linear(c, lw["kv_b_w"], lo["kv_b"], scale).reshape(
        n, w.heads, w.nope + w.v)
    q = jnp.concatenate([q[..., :w.nope], rope(q[..., w.nope:], theta)], -1)
    k_pe = rope(kv[:, None, w.kv_rank:], theta)
    k = jnp.concatenate(
        [kvb[..., :w.nope], jnp.broadcast_to(k_pe, (n, w.heads, w.rope))], -1)
    v = kvb[..., w.nope:]
    nb = -(-n // ATTN_BLOCK)
    qb = jnp.pad(q, ((0, nb * ATTN_BLOCK - n), (0, 0), (0, 0))).reshape(
        nb, ATTN_BLOCK, w.heads, w.qk)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) \
            / jnp.sqrt(F32(w.qk))
        pos = i * ATTN_BLOCK + jnp.arange(ATTN_BLOCK)
        mask = pos[:, None] >= jnp.arange(n)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(nb), qb)).reshape(
        nb * ATTN_BLOCK, w.heads * w.v)[:n]
    return linear(o, lw["o_w"], lo["o"], scale)


def experts(x, lw, lo, w: Widths, scale):
    """The held experts' part of the expert layer, x: [N, d] normed."""
    scores = jax.nn.sigmoid(mm(x, lw["router_w"]))
    _, idx = jax.lax.top_k(scores + lw["score_bias"], w.top_k)
    top = jnp.take_along_axis(scores, idx, -1)
    top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20) * w.routed_scale
    held = w.held_from + jnp.arange(w.held)
    # [N, held]: the combine weight of each held expert (0: not chosen)
    gate = jnp.sum(top[:, :, None] * (idx[:, :, None] == held), 1)

    @jax.checkpoint
    def one(y, e):
        ws = [lw[f"expert_{t}_w"][e] for t in FFN]
        los = [jax.tree_util.tree_map(lambda a: a[e], lo[f"expert_{t}"])
               for t in FFN]
        return y + gate[:, e, None] * swiglu(x, ws, los, scale), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(w.held))
    return y


def dense_layer(x, lw, lo, w: Widths, eps, theta, scale):
    x = x + attention(rmsnorm(x, lw["ln1"], eps), lw, lo, w, eps, theta,
                      scale)
    h = rmsnorm(x, lw["ln2"], eps)
    return x + swiglu(h, [lw[f"{t}0_w"] for t in FFN],
                      [lo[f"{t}0"] for t in FFN], scale)


def moe_layer(x, lw, lo, w: Widths, eps, theta, scale):
    x = x + attention(rmsnorm(x, lw["ln1"], eps), lw, lo, w, eps, theta,
                      scale)
    h = rmsnorm(x, lw["ln2"], eps)
    shared = swiglu(h, [lw[f"shared_{t}_w"] for t in FFN],
                    [lo[f"shared_{t}"] for t in FFN], scale)
    return x + experts(h, lw, lo, w, scale) + shared


def _split_layers(base, lora, w: Widths):
    """(layer 0's weights, the MoE layers' stacked weights), each with its
    LoRA, float32 slices taken where they are used."""
    stacked = ("ln1", "ln2", "kv_norm") + tuple(f"{t}_w" for t in ATTN)
    l0 = {k: base[k][0] for k in stacked}
    l0.update({f"{t}0_w": base[f"{t}0_w"] for t in FFN})
    lo0 = {t: jax.tree_util.tree_map(lambda a: a[0], lora[t]) for t in ATTN}
    lo0.update({f"{t}0": lora[f"{t}0"] for t in FFN})
    rest = {k: base[k][1:] for k in stacked}
    rest.update({k: base[k] for k in base
                 if k.startswith(("router", "score", "shared", "expert"))})
    lo_rest = {t: jax.tree_util.tree_map(lambda a: a[1:], lora[t])
               for t in ATTN}
    lo_rest.update({k: lora[k] for k in lora
                    if k.startswith(("shared", "expert"))})
    return (l0, lo0), (rest, lo_rest)


def hidden(base, lora, tokens, w: Widths, eps, theta, scale):
    """Final-normed hidden states [N, d] of one sequence."""
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(F32), t)
    (l0, lo0), (rest, lo_rest) = _split_layers(base, lora, w)
    x = base["embed"][tokens].astype(F32)
    x = jax.checkpoint(lambda x, lw, lo: dense_layer(
        x, f32(lw), f32(lo), w, eps, theta, scale))(x, l0, lo0)

    @jax.checkpoint
    def body(x, sl):
        lw, lo = sl
        return moe_layer(x, f32(lw), f32(lo), w, eps, theta, scale), None

    x, _ = jax.lax.scan(body, x, (rest, lo_rest))
    return rmsnorm(x, base["final_norm"].astype(F32), eps)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _row_grad(base, lora, tokens, labels, w, eps, theta, scale):
    def f(lora):
        x = hidden(base, lora, tokens, w, eps, theta, scale)
        return ce_sum(x, base["head_w"].astype(F32).T, labels)
    return jax.value_and_grad(f)(lora)


class Reference(RowReference):
    """The reference for one configuration (``bench/configs/<name>.json``)."""

    def __init__(self, conf: dict):
        self.w = Widths.from_config(conf)
        self.eps = float(conf["published"]["rms_norm_eps"])
        self.theta = float(conf["published"]["rope_theta"])
        lo = conf["assumed"]["lora"]
        self.scale = float(lo["alpha"]) / int(lo["rank"])

    def row_grad(self, base, lora, tokens, labels):
        return _row_grad(base, lora, tokens, labels, self.w, self.eps,
                         self.theta, self.scale)


@functools.partial(jax.jit, static_argnums=(1,))
def quantize_int8(base: dict, dtype=None) -> dict:
    """The frozen linears (``*_w``: attention, FFNs, experts, router and
    head) rounded to int8 with one scale per output column and dequantized
    into ``dtype`` (default: the type they are stored in), one layer at a
    time: the control one precision below bf16."""
    def one(w):
        x = w.astype(F32)
        s = jnp.max(jnp.abs(x), axis=-2, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return (jnp.clip(jnp.round(x / s), -127, 127) * s).astype(
            dtype or w.dtype)

    return {k: (v if not k.endswith("_w") else one(v) if v.ndim == 2
                else jax.lax.map(one, v)) for k, v in base.items()}
