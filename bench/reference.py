"""What every architecture's plain float32 reference is built from.

Every matmul runs at ``Precision.HIGHEST`` in float32, on the benchmark's
own weights, upcast from the type they are stored in. Here: the matmul,
RMSNorm, rotary embeddings (rotate-half), the summed next-token
cross-entropy over blocks of positions, and :class:`RowReference`, which
takes a batch one row at a time and runs plain SGD. Each architecture
module (``bench/archs/<name>.py``) writes its own layers from these.
Nothing of the program is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
HEAD_BLOCK = 512


def mm(a, b):
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


def ce_sum(x, head, labels):
    """Summed cross-entropy of hidden rows x [N, d] against the LM head
    ``head`` [vocab, d], over blocks of ``HEAD_BLOCK`` positions; labels
    below 0 are left out."""
    n = x.shape[0]
    nb = -(-n // HEAD_BLOCK)
    pad = nb * HEAD_BLOCK - n
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(nb, HEAD_BLOCK, -1)
    lb = jnp.pad(labels, (0, pad), constant_values=-1).reshape(nb, -1)

    @jax.checkpoint
    def body(tot, blk):
        xi, li = blk
        logits = mm(xi, head.T)
        lse = jax.scipy.special.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, jnp.maximum(li, 0)[:, None], -1)[:, 0]
        return tot + jnp.sum(jnp.where(li >= 0, lse - ll, 0.0)), None

    tot, _ = jax.lax.scan(body, F32(0), (xb, lb))
    return tot


class RowReference:
    """Loss, gradients and SGD over a batch, one row at a time. An
    architecture's reference gives :meth:`row_grad`: the summed
    cross-entropy of one row and its gradient with respect to the LoRA
    factors."""

    def row_grad(self, base, lora, tokens, labels):
        raise NotImplementedError

    def loss_and_grads(self, base, lora, batch):
        """Mean cross-entropy of a batch and its gradient with respect to
        the LoRA factors, one row of the batch at a time."""
        with jax.default_matmul_precision("highest"):
            total, grads = None, None
            for tok, lab in zip(batch["tokens"], batch["labels"]):
                s, g = self.row_grad(base, lora, jnp.asarray(tok),
                                     jnp.asarray(lab))
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
