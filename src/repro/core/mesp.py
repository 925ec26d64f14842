"""MeSP training engines (paper §4).

Two forms, both computing *mathematically identical* gradients:

1. :func:`value_and_grad` — production engine. The model's scan-over-blocks
   already stores only block inputs (``jax.checkpoint`` per block) and every
   inner op is a hand-derived ``custom_vjp`` (``core.structured``; with the
   ``pallas`` backend the same rules fused into Pallas TPU kernels via
   ``kernels.ops``), so a single ``jax.grad`` call executes exactly the
   paper's recompute schedule.
   LoRA gradients are accumulated and applied once per step — for SGD this is
   identical to the paper's immediate per-block update because LoRA params are
   disjoint across blocks (verified in tests/test_mesp_equivalence.py).

2. :func:`sequential_train_step` — the paper's §4.3 algorithm verbatim:
   a Python reverse loop over blocks, each block recomputed from its stored
   input, gradients computed via the structured VJPs, and **the optimizer
   applied immediately** before the next block's backward. Registered as the
   first-class ``mesp_seq`` engine (``repro.api``); also used by the
   reproduction benchmarks and the convergence example (dense family).

Execution regime selection is an :class:`repro.api.policy.ExecutionPolicy`
(``policy=``). The legacy ``mode=``/``act_spec=`` string kwargs are still
accepted here — and only here — as a convenience for tests/notebooks; they
are folded into a policy at this boundary and everything below
(``models/*``, ``kernels/*``) takes the policy object exclusively.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.api.policy import ExecutionPolicy
from repro.configs.base import ArchConfig
from repro.core import structured
from repro.models import layers, model as model_lib

Array = jax.Array


def _resolve_policy(policy: Optional[ExecutionPolicy], mode: Optional[str],
                    act_spec) -> ExecutionPolicy:
    if policy is None:
        return ExecutionPolicy.from_mode(mode, act_spec=act_spec)
    if mode is not None or act_spec is not None:
        raise TypeError("pass either policy= or the legacy mode=/act_spec= "
                        "kwargs, not both")
    return policy


# ---------------------------------------------------------------------------
# production engine
# ---------------------------------------------------------------------------


def value_and_grad(params, cfg: ArchConfig, batch: dict, *,
                   policy: Optional[ExecutionPolicy] = None,
                   mode: Optional[str] = None, act_spec=None):
    """(loss, grads-over-LoRA-params). grads tree has None at frozen leaves."""
    policy = _resolve_policy(policy, mode, act_spec)
    train, frozen = model_lib.split_params(params)

    def f(train):
        p = model_lib.merge_params(train, frozen)
        return model_lib.loss_fn(p, cfg, batch, policy=policy)

    return jax.value_and_grad(f)(train)


def value_grad_and_aux(params, cfg: ArchConfig, batch: dict, *,
                       policy: ExecutionPolicy):
    """(loss, grads, aux): :func:`value_and_grad` plus the model's
    auxiliary outputs (``model.loss_and_aux``: the expert layers'
    counters), computed in the same program."""
    train, frozen = model_lib.split_params(params)

    def f(train):
        p = model_lib.merge_params(train, frozen)
        return model_lib.loss_and_aux(p, cfg, batch, policy=policy)

    (loss, aux), grads = jax.value_and_grad(f, has_aux=True)(train)
    return loss, grads, aux


def train_step(params, cfg: ArchConfig, batch: dict, lr: float, *,
               policy: Optional[ExecutionPolicy] = None,
               mode: Optional[str] = None, act_spec=None):
    """One SGD step over LoRA params. Returns (params, loss)."""
    loss, grads = value_and_grad(params, cfg, batch,
                                 policy=_resolve_policy(policy, mode,
                                                        act_spec))
    new = jax.tree_util.tree_map(
        lambda p, g: p if g is None else (p - lr * g.astype(p.dtype)),
        params, grads,
        is_leaf=lambda x: x is None)
    return new, loss


# ---------------------------------------------------------------------------
# faithful §4.3 engine: layer-by-layer with immediate optimizer update
# (dense family — the paper's Qwen2.5 models)
# ---------------------------------------------------------------------------


def _unstack(tree, n):
    return [jax.tree_util.tree_map(lambda t: t[i], tree) for i in range(n)]


def _restack(trees):
    return jax.tree_util.tree_map(lambda *ts: jnp.stack(ts), *trees)


def _sgd_lora(bp, gbp, lr):
    """Immediate SGD on the LoRA leaves of one block."""
    mask = model_lib.trainable_mask(bp)
    return jax.tree_util.tree_map(
        lambda p, g, m: (p - lr * g.astype(p.dtype)) if m else p,
        bp, gbp, mask)


def sequential_train_step(params, cfg: ArchConfig, batch: dict, lr: float,
                          *, policy: Optional[ExecutionPolicy] = None,
                          mode: Optional[str] = None):
    """Paper §4.3: forward stores only block inputs; backward walks blocks in
    reverse, recomputes each block, computes its LoRA grads and updates them
    *immediately*. Dense-family only. Returns (new_params, loss).
    """
    assert cfg.family == "dense" and not cfg.window_pattern
    policy = _resolve_policy(policy, mode, None)
    L = cfg.n_layers
    blocks = _unstack(params["blocks"], L)

    def block_f(bp, x):
        return model_lib.dense_block(bp, x, cfg, policy=policy)[0]

    # ---- Forward Phase: store only block inputs (checkpoint dict) ----------
    x = layers.embed(params["embed"], batch["tokens"], cfg)
    checkpoints = []
    for bp in blocks:
        checkpoints.append(x)
        x = block_f(bp, x)

    # ---- head: loss + gradient w.r.t. the last block output ---------------
    def head(x):
        xn = layers.norm(params["final_norm"], x, cfg, policy=policy)
        logits = layers.unembed(params["embed"], xn, cfg)
        return structured.softmax_xent(logits, batch["labels"])

    loss, head_vjp = jax.vjp(head, x)
    (g,) = head_vjp(jnp.ones((), loss.dtype))

    # ---- Backward Phase: reverse loop, recompute, update immediately ------
    new_blocks = [None] * L
    for i in reversed(range(L)):
        _, blk_vjp = jax.vjp(block_f, blocks[i], checkpoints[i])  # recompute
        gbp, g = blk_vjp(g)
        new_blocks[i] = _sgd_lora(blocks[i], gbp, lr)
        # gbp / intermediates die here — nothing from block i survives the
        # iteration (the paper's "explicitly deallocate and clear cache").

    new_params = dict(params)
    new_params["blocks"] = _restack(new_blocks)
    return new_params, loss
