"""Plain float32 reference of the Qwen2.5 decoder, from the published
equations (Qwen2 in Hugging Face ``transformers``): token embedding,
pre-norm RMSNorm, rotary embeddings (rotate-half, theta from the config),
grouped-query causal attention with q/k/v biases, SwiGLU MLP, a final
RMSNorm and the LM head tied to the embedding; LoRA ``y = x·W + b +
(alpha/r)·(x·A)·B`` on the configured targets; mean next-token
cross-entropy; plain SGD.

Every matmul runs at ``Precision.HIGHEST`` in float32, on the benchmark's
own weights (``bench/weights.py``), upcast from the type they are stored
in. Nothing of the program is imported. To fit beside the stored weights
on one chip it runs one row of the batch at a time, layer by layer under
a scan with rematerialisation, and the LM head over blocks of positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.flops import Widths

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
HEAD_BLOCK = 512


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: [N, H, D]; rotate-half with frequencies theta^(-2i/D)."""
    n, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(n, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def linear(x, lw, name, lo, scale):
    y = _mm(x, lw[f"{name}_w"])
    if f"{name}_b" in lw:
        y = y + lw[f"{name}_b"]
    if name in lo:
        y = y + scale * _mm(_mm(x, lo[name]["a"]), lo[name]["b"])
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


def _ce_sum(x, emb, labels):
    """Summed cross-entropy of hidden rows x [N, d] against the tied head,
    over blocks of ``HEAD_BLOCK`` positions."""
    n = x.shape[0]
    nb = -(-n // HEAD_BLOCK)
    pad = nb * HEAD_BLOCK - n
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(nb, HEAD_BLOCK, -1)
    lb = jnp.pad(labels, (0, pad), constant_values=-1).reshape(nb, -1)

    @jax.checkpoint
    def body(tot, blk):
        xi, li = blk
        logits = _mm(xi, emb.T)
        lse = jax.scipy.special.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, jnp.maximum(li, 0)[:, None], -1)[:, 0]
        return tot + jnp.sum(jnp.where(li >= 0, lse - ll, 0.0)), None

    tot, _ = jax.lax.scan(body, F32(0), (xb, lb))
    return tot


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _row_grad(base, lora, tokens, labels, w, eps, theta, scale):
    def f(lora):
        x = hidden(base, lora, tokens, w, eps, theta, scale)
        return _ce_sum(x, base["embed"].astype(F32), labels)
    return jax.value_and_grad(f)(lora)


class Reference:
    """The reference for one configuration (``bench/configs/<name>.json``)."""

    def __init__(self, conf: dict):
        self.w = Widths.from_config(conf)
        self.eps = float(conf["published"]["rms_norm_eps"])
        self.theta = float(conf["published"]["rope_theta"])
        lo = conf["assumed"]["lora"]
        self.scale = float(lo["alpha"]) / int(lo["rank"])

    def loss_and_grads(self, base, lora, batch):
        """Mean cross-entropy of a batch and its gradient with respect to
        the LoRA factors, one row of the batch at a time."""
        with jax.default_matmul_precision("highest"):
            total, grads = None, None
            for tok, lab in zip(batch["tokens"], batch["labels"]):
                s, g = _row_grad(base, lora, jnp.asarray(tok),
                                 jnp.asarray(lab), self.w, self.eps,
                                 self.theta, self.scale)
                total = s if total is None else total + s
                grads = g if grads is None else jax.tree_util.tree_map(
                    jnp.add, grads, g)
        n = batch["labels"].size
        return (float(total) / n,
                jax.tree_util.tree_map(lambda t: t / n, grads))

    def sgd(self, base, lora, batches, lr):
        """Plain SGD over ``batches`` from ``lora`` (float32). Returns the
        losses, the gradients and the LoRA factors after each step."""
        lora = jax.tree_util.tree_map(lambda t: t.astype(F32), lora)
        losses, grads, states = [], [], []
        for batch in batches:
            loss, g = self.loss_and_grads(base, lora, batch)
            lora = jax.tree_util.tree_map(lambda p, d: p - lr * d, lora, g)
            losses.append(loss)
            grads.append(g)
            states.append(lora)
        return losses, grads, states

    def logits(self, base, lora, tokens, positions):
        """Float32 logits [len(positions), vocab] of one sequence at the
        given positions, each predicting the token after it."""
        with jax.default_matmul_precision("highest"):
            return _logits(base, lora, jnp.asarray(tokens),
                           jnp.asarray(positions), self.w, self.eps,
                           self.theta, self.scale)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _logits(base, lora, tokens, positions, w, eps, theta, scale):
    x = hidden(base, lora, tokens, w, eps, theta, scale)
    return _mm(x[positions], base["embed"].astype(F32).T)


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
