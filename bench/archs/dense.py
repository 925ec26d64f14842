"""Dense decoder (Qwen2 in Hugging Face ``transformers``): what the
benchmark needs to know of this architecture.

A configuration file names this module under ``architecture``; the harness
loads it by path (``bench/spec.load_arch``) and reaches every step that
depends on the architecture through it:

* which ``config.json`` keys map to which field of the program's registry
  entry, and which registry settings this module does not model;
* ``Widths``, the sizes read from the configuration file;
* the seeded weights in the benchmark's canonical layout (``base``:
  embedding, final norm and per-layer stacked ``[L, ...]`` leaves;
  ``lora``: ``{target: {"a": [L, K, r], "b": [L, r, N]}}``), and their
  mapping to and from the program's parameter tree;
* the FLOPs a LoRA step requires per token, and the LoRA and attention
  calls of a step, for the roofline readers;
* the plain float32 reference and the int8 control.

Weights: frozen linears Student-t with 4 degrees of freedom at variance
1/K (heavy tails, as trained weights have: a per-column int8 scale then
rounds most weights coarsely, so the int8 control departs from the
reference by far more than bf16 rounding), q/k/v biases N(0, 0.1²), norm
scales 1 + N(0, 0.1²), the embedding N(0, 0.02²), LoRA A N(0, 1/r) and
LoRA B zero, as a fine-tuning run starts (non-zero for serving tenants).

Reference, from the published equations: token embedding, pre-norm
RMSNorm, rotary embeddings (rotate-half, theta from the config),
grouped-query causal attention with q/k/v biases, SwiGLU MLP, a final
RMSNorm and the LM head tied to the embedding; LoRA ``y = x·W + b +
(alpha/r)·(x·A)·B`` on the configured targets; mean next-token
cross-entropy; plain SGD. To fit beside the stored weights on one chip it
runs one row of the batch at a time, layer by layer under a scan with
rematerialisation, and the LM head over blocks of positions. Nothing of
the program is imported.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp

from bench.flops import flash_op
from bench.reference import F32, HIGHEST, RowReference, ce_sum, mm, \
    rmsnorm, rope
from bench.spec import CellError
from bench.weights import embedding, leaf

# config.json key -> ArchConfig field of the program's registry
HF_TO_ARCH = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers", "vocab_size": "vocab",
    "tie_word_embeddings": "tie_embeddings", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "torch_dtype": "dtype",
}
ASSUMED_TO_ARCH = {"head_dim": "head_dim", "attention_bias": "qkv_bias"}

BIASED = ("q", "k", "v")


def refuse(cfg) -> None:
    """Registry settings this module does not model."""
    if cfg.family != "dense" or cfg.window_pattern or cfg.moe is not None:
        raise CellError(f"{cfg.name} is not a dense decoder")


@dataclasses.dataclass(frozen=True)
class Widths:
    layers: int
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    tied: bool
    rank: int
    targets: Tuple[str, ...]

    @property
    def q(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv(self) -> int:
        return self.kv_heads * self.head_dim

    @classmethod
    def from_config(cls, conf: dict) -> "Widths":
        pub, ass = conf["published"], conf["assumed"]
        return cls(layers=pub["num_hidden_layers"], d=pub["hidden_size"],
                   ff=pub["intermediate_size"],
                   heads=pub["num_attention_heads"],
                   kv_heads=pub["num_key_value_heads"],
                   head_dim=ass["head_dim"], vocab=pub["vocab_size"],
                   tied=pub["tie_word_embeddings"],
                   rank=ass["lora"]["rank"],
                   targets=tuple(ass["lora"]["targets"]))


def linears(w: Widths) -> List[Tuple[str, int, int]]:
    """(name, K, N) of every frozen linear of one decoder layer."""
    return [("q", w.d, w.q), ("k", w.d, w.kv), ("v", w.d, w.kv),
            ("o", w.q, w.d), ("gate", w.d, w.ff), ("up", w.d, w.ff),
            ("down", w.ff, w.d)]


def lora_targets(w: Widths) -> List[Tuple[str, int, int]]:
    return [t for t in linears(w) if t[0] in w.targets]


# ------------------------------------------------------------ op counts

def train_flops_per_token(w: Widths, seq: int) -> int:
    """FLOPs a LoRA fine-tuning step requires per token.

    Counted: forward and input-gradient of every frozen matmul and of the
    tied LM head; LoRA forward, dx, dA and dB (6·r·(K+N) a target, the
    shared g·Bᵀ once); causal QKᵀ and PV forward and a backward of twice
    that. Not counted: weight gradients of the frozen base (there are
    none), and work recomputed to save memory (MeSP's recomputed forward
    and h = x·A)."""
    frozen = sum(k * n for _, k, n in linears(w)) * w.layers
    head = w.vocab * w.d
    lora = sum(6 * w.rank * (k + n) for _, k, n in lora_targets(w))
    attn = 3 * 2 * 2 * (seq // 2) * w.head_dim * w.heads
    return 4 * (frozen + head) + w.layers * (lora + attn)


def lora_calls(w: Widths, batch: int, seq: int):
    """(target, M, K, N) of each LoRA linear a step calls: every target
    over all ``batch · seq`` rows."""
    return [(t, batch * seq, k, n) for t, k, n in lora_targets(w)]


def flash_ops(w: Widths, batch: int, seq: int):
    """(FLOPs, bytes) of one flash attention forward call of the step and
    of one backward (dq and dkv together): every layer's are alike."""
    shape = (batch, w.heads, w.kv_heads, seq, w.head_dim)
    return flash_op("fwd", *shape), flash_op("bwd", *shape)


# -------------------------------------------------------------- weights

def base_shapes(w: Widths) -> dict:
    out = {"embed": (w.vocab, w.d), "final_norm": (w.d,),
           "ln1": (w.layers, w.d), "ln2": (w.layers, w.d)}
    for name, k, n in linears(w):
        out[f"{name}_w"] = (w.layers, k, n)
        if name in BIASED:
            out[f"{name}_b"] = (w.layers, n)
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
        elif name in ("ln1", "ln2"):
            v = leaf(k, shape[1:], 0.1, dtype, 1.0, w.layers)
        elif name.endswith("_b"):
            v = leaf(k, shape[1:], 0.1, dtype, 0.0, w.layers)
        else:
            v = leaf(k, shape[1:], shape[1] ** -0.5, dtype, 0.0, w.layers,
                     heavy=True)
        out[name] = v
    return out


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def make_lora(w: Widths, key, dtype: str, b_std: float = 0.0):
    """LoRA factors of every target: A ~ N(0, 1/r); B ~ N(0, b_std²)
    (zero, as fine-tuning starts, when ``b_std`` is 0)."""
    out = {}
    for i, (name, k_in, n_out) in enumerate(sorted(lora_targets(w))):
        ka, kb = jax.random.split(jax.random.fold_in(key, 1000 + i))
        a = leaf(ka, (k_in, w.rank), w.rank ** -0.5, dtype, 0.0, w.layers)
        b = (leaf(kb, (w.rank, n_out), b_std, dtype, 0.0, w.layers)
             if b_std else jnp.zeros((w.layers, w.rank, n_out), dtype))
        out[name] = {"a": a, "b": b}
    return out


def to_program(base: dict, lora: dict, w: Widths) -> dict:
    """The same arrays nested as the program's dense-decoder tree."""
    def lin(name, with_lora=True):
        p = {"w": base[f"{name}_w"]}
        if name in BIASED:
            p["bias"] = base[f"{name}_b"]
        if with_lora and name in lora:
            p["a"], p["b"] = lora[name]["a"], lora[name]["b"]
        return p

    return {
        "embed": {"tok": base["embed"]},
        "final_norm": base["final_norm"],
        "blocks": {
            "ln1": base["ln1"], "ln2": base["ln2"],
            "attn": {t: lin(t) for t in ("q", "k", "v", "o")},
            "mlp": {t: lin(t) for t in ("gate", "up", "down")},
        },
    }


def lora_tree(lora: dict) -> dict:
    """Only the LoRA leaves, at the program tree's paths (what an adapter
    store takes for one tenant)."""
    return {"blocks": {
        "attn": {t: dict(lora[t]) for t in ("q", "k", "v", "o") if t in lora},
        "mlp": {t: dict(lora[t]) for t in ("gate", "up", "down")
                if t in lora}}}


def lora_of(params: dict) -> dict:
    """The LoRA leaves of a program tree, in the canonical layout."""
    blocks = params["blocks"]
    out = {}
    for group in ("attn", "mlp"):
        for t, p in blocks[group].items():
            if "a" in p:
                out[t] = {"a": p["a"], "b": p["b"]}
    return out


# ------------------------------------------------------------ reference

def linear(x, lw, name, lo, scale):
    y = mm(x, lw[f"{name}_w"])
    if f"{name}_b" in lw:
        y = y + lw[f"{name}_b"]
    if name in lo:
        y = y + scale * mm(mm(x, lo[name]["a"]), lo[name]["b"])
    return y


def layer(x, lw, lo, w: Widths, eps, theta, scale):
    """One decoder layer on one sequence, x: [N, d] float32."""
    n = x.shape[0]
    h = rmsnorm(x, lw["ln1"], eps)
    q = linear(h, lw, "q", lo, scale).reshape(n, w.heads, w.head_dim)
    k = linear(h, lw, "k", lo, scale).reshape(n, w.kv_heads, w.head_dim)
    v = linear(h, lw, "v", lo, scale).reshape(n, w.kv_heads, w.head_dim)
    q, k = rope(q, theta), rope(k, theta)
    rep = w.heads // w.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / jnp.sqrt(F32(w.head_dim))
    mask = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    x = x + linear(o.reshape(n, w.q), lw, "o", lo, scale)
    h = rmsnorm(x, lw["ln2"], eps)
    g = linear(h, lw, "gate", lo, scale)
    u = linear(h, lw, "up", lo, scale)
    return x + linear(jax.nn.silu(g) * u, lw, "down", lo, scale)


def _layer_slices(base):
    return {k: v for k, v in base.items()
            if k not in ("embed", "final_norm")}


def hidden(base, lora, tokens, w: Widths, eps, theta, scale):
    """Final-normed hidden states [N, d] of one sequence."""
    x = base["embed"][tokens].astype(F32)

    @jax.checkpoint
    def body(x, sl):
        lw, lo = sl
        lw = jax.tree_util.tree_map(lambda t: t.astype(F32), lw)
        lo = jax.tree_util.tree_map(lambda t: t.astype(F32), lo)
        return layer(x, lw, lo, w, eps, theta, scale), None

    x, _ = jax.lax.scan(body, x, (_layer_slices(base), lora))
    return rmsnorm(x, base["final_norm"].astype(F32), eps)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _row_grad(base, lora, tokens, labels, w, eps, theta, scale):
    def f(lora):
        x = hidden(base, lora, tokens, w, eps, theta, scale)
        return ce_sum(x, base["embed"].astype(F32), labels)
    return jax.value_and_grad(f)(lora)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _logits(base, lora, tokens, positions, w, eps, theta, scale):
    x = hidden(base, lora, tokens, w, eps, theta, scale)
    return mm(x[positions], base["embed"].astype(F32).T)


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

    def logits(self, base, lora, tokens, positions):
        """Float32 logits [len(positions), vocab] of one sequence at the
        given positions, each predicting the token after it."""
        with jax.default_matmul_precision("highest"):
            return _logits(base, lora, jnp.asarray(tokens),
                           jnp.asarray(positions), self.w, self.eps,
                           self.theta, self.scale)


@functools.partial(jax.jit, static_argnums=(1,))
def quantize_int8(base: dict, dtype=None) -> dict:
    """The frozen linears rounded to int8 with one scale per output column
    and dequantized, one layer at a time, into ``dtype`` (default: the type
    they are stored in, as the program's int8 path dequantizes its tiles to
    bf16; float32 where memory allows): the control one precision below
    bf16."""
    def one(w):
        x = w.astype(F32)
        s = jnp.max(jnp.abs(x), axis=-2, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return (jnp.clip(jnp.round(x / s), -127, 127) * s).astype(
            dtype or w.dtype)

    return {k: jax.lax.map(one, v) if k.endswith("_w") else v
            for k, v in base.items()}
