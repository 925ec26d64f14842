"""Mixture-of-experts decoder, as the program's ``moe`` family lays it out
(OLMoE: routed experts, no shared experts, no dense first layer): a
test-only architecture module. It holds what the harness reaches through
a configuration's module before a run (key map, refusal, widths, weights
and their program tree, op counts); it has no reference, so no cell can
run on it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from bench.flops import flash_op
from bench.spec import CellError
from bench.weights import embedding, leaf

HF_TO_ARCH = {
    "hidden_size": "d_model", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "num_hidden_layers": "n_layers",
    "vocab_size": "vocab", "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta", "torch_dtype": "dtype",
    "num_experts": "moe.n_experts", "num_experts_per_tok": "moe.top_k",
    "intermediate_size": "moe.d_expert",
}
ASSUMED_TO_ARCH = {"head_dim": "head_dim"}

ATTN = ("q", "k", "v", "o")
EXPERT = ("gate", "up", "down")


def refuse(cfg) -> None:
    m = cfg.moe
    if (cfg.family != "moe" or m is None or m.n_shared
            or m.first_layer_dense or cfg.window_pattern or cfg.qkv_bias):
        raise CellError(f"{cfg.name} is not a decoder of routed experts "
                        f"alone")


@dataclasses.dataclass(frozen=True)
class Widths:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    tied: bool
    experts: int
    top_k: int
    ff: int
    rank: int
    targets: Tuple[str, ...]

    @classmethod
    def from_config(cls, conf: dict) -> "Widths":
        pub, ass = conf["published"], conf["assumed"]
        return cls(layers=pub["num_hidden_layers"], d=pub["hidden_size"],
                   heads=pub["num_attention_heads"],
                   kv_heads=pub["num_key_value_heads"],
                   head_dim=ass["head_dim"], vocab=pub["vocab_size"],
                   tied=pub["tie_word_embeddings"],
                   experts=pub["num_experts"],
                   top_k=pub["num_experts_per_tok"],
                   ff=pub["intermediate_size"], rank=ass["lora"]["rank"],
                   targets=tuple(ass["lora"]["targets"]))


def linears(w: Widths):
    """(name, K, N) of the attention linears and of one expert's."""
    q, kv = w.heads * w.head_dim, w.kv_heads * w.head_dim
    return [("q", w.d, q), ("k", w.d, kv), ("v", w.d, kv), ("o", q, w.d),
            ("gate", w.d, w.ff), ("up", w.d, w.ff), ("down", w.ff, w.d)]


def train_flops_per_token(w: Widths, seq: int) -> int:
    """As the dense count, with ``top_k`` experts' linears a token, the
    router's matmul and an untied head."""
    per = {t: k * n for t, k, n in linears(w)}
    frozen = (sum(per[t] for t in ATTN) + w.top_k * sum(per[t] for t in EXPERT)
              + w.d * w.experts) * w.layers
    lora = sum(6 * w.rank * (k + n) * (w.top_k if t in EXPERT else 1)
               for t, k, n in linears(w) if t in w.targets)
    attn = 3 * 2 * 2 * (seq // 2) * w.head_dim * w.heads
    return 4 * (frozen + w.vocab * w.d) + w.layers * (lora + attn)


def lora_calls(w: Widths, batch: int, seq: int):
    """Attention targets over every row; an expert's over its share."""
    rows = batch * seq
    return [(t, rows if t in ATTN else rows * w.top_k // w.experts, k, n)
            for t, k, n in linears(w) if t in w.targets]


def flash_ops(w: Widths, batch: int, seq: int):
    shape = (batch, w.heads, w.kv_heads, seq, w.head_dim)
    return flash_op("fwd", *shape), flash_op("bwd", *shape)


def _lead(w: Widths, name: str):
    return (w.experts,) if name in EXPERT else ()


@functools.partial(jax.jit, static_argnums=(0, 2))
def make_base(w: Widths, key, dtype: str):
    ks = iter(jax.random.split(key, 16))
    out = {"embed": embedding(next(ks), (w.vocab, w.d), dtype),
           "final_norm": leaf(next(ks), (w.d,), 0.1, dtype, 1.0),
           "ln1": leaf(next(ks), (w.d,), 0.1, dtype, 1.0, w.layers),
           "ln2": leaf(next(ks), (w.d,), 0.1, dtype, 1.0, w.layers),
           "router": leaf(next(ks), (w.d, w.experts), w.d ** -0.5, dtype,
                          0.0, w.layers)}
    if not w.tied:
        out["head"] = leaf(next(ks), (w.d, w.vocab), w.d ** -0.5, dtype)
    for name, k, n in linears(w):
        out[f"{name}_w"] = leaf(next(ks), _lead(w, name) + (k, n),
                                k ** -0.5, dtype, 0.0, w.layers, heavy=True)
    return out


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def make_lora(w: Widths, key, dtype: str, b_std: float = 0.0):
    out = {}
    for i, (name, k, n) in enumerate(linears(w)):
        if name not in w.targets:
            continue
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        lead = _lead(w, name)
        out[name] = {
            "a": leaf(ka, lead + (k, w.rank), w.rank ** -0.5, dtype, 0.0,
                      w.layers),
            "b": (leaf(kb, lead + (w.rank, n), b_std, dtype, 0.0, w.layers)
                  if b_std else
                  jnp.zeros((w.layers,) + lead + (w.rank, n), dtype))}
    return out


def to_program(base: dict, lora: dict, w: Widths) -> dict:
    def lin(name):
        return {"w": base[f"{name}_w"], **lora.get(name, {})}

    embed = {"tok": base["embed"]}
    if not w.tied:
        embed["head"] = base["head"]
    return {"embed": embed, "final_norm": base["final_norm"],
            "blocks": {"ln1": base["ln1"], "ln2": base["ln2"],
                       "attn": {t: lin(t) for t in ATTN},
                       "moe": {"router": base["router"],
                               **{t: lin(t) for t in EXPERT}}}}


def lora_tree(lora: dict) -> dict:
    return {"blocks": {
        "attn": {t: dict(lora[t]) for t in ATTN if t in lora},
        "moe": {t: dict(lora[t]) for t in EXPERT if t in lora}}}


def lora_of(params: dict) -> dict:
    blocks = params["blocks"]
    return {t: {"a": p["a"], "b": p["b"]}
            for group in ("attn", "moe") for t, p in blocks[group].items()
            if isinstance(p, dict) and "a" in p}
