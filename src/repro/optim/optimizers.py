"""Optimizers over sparse (LoRA-only) gradient trees.

Gradient trees produced by the engines have ``None`` at frozen leaves, so
optimizer state is allocated only for trainable params — for LoRA fine-tuning
the state is O(r·(d_in+d_out)) per layer, which is the property that makes
the paper's setting DP-communication-cheap at scale (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], tuple]  # (grads, state, params) -> (params, state)


def _is_none(x):
    return x is None


def _map(f, *trees):
    return jax.tree_util.tree_map(f, *trees, is_leaf=_is_none)


def sgd(lr: float | Callable[[jax.Array], jax.Array]) -> Optimizer:
    """Paper §5.1 uses plain SGD, lr 1e-4."""
    def init(params):
        return {"step": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        step = state["step"] + 1
        lr_t = lr(step) if callable(lr) else lr
        # update in f32, store in the param's dtype: a bf16 param that came
        # back f32 would change the step's input types and force a retrace
        new = _map(lambda p, g: p if g is None else
                   (p - lr_t * g.astype(jnp.float32)).astype(p.dtype),
                   params, grads)
        return new, {"step": step}

    return Optimizer(init, update)


def sgd_momentum(lr, beta: float = 0.9) -> Optimizer:
    def init(params):
        m = _map(lambda p: None, params)  # filled lazily on first step
        return {"step": jnp.zeros((), jnp.int32), "m": m}

    def update(grads, state, params):
        step = state["step"] + 1
        lr_t = lr(step) if callable(lr) else lr
        m = _map(lambda g, m_, p: None if g is None else
                 (beta * (m_ if m_ is not None else jnp.zeros_like(p, jnp.float32))
                  + g.astype(jnp.float32)),
                 grads, state["m"], params)
        new = _map(lambda p, mi: p if mi is None else
                   (p - lr_t * mi).astype(p.dtype), params, m)
        return new, {"step": step, "m": m}

    return Optimizer(init, update)


def adamw(lr, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
          weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        z = _map(lambda p: None, params)
        return {"step": jnp.zeros((), jnp.int32), "m": z, "v": z}

    def update(grads, state, params):
        step = state["step"] + 1
        lr_t = lr(step) if callable(lr) else lr

        def upd_m(g, m_, p):
            if g is None:
                return None
            m0 = m_ if m_ is not None else jnp.zeros_like(p, jnp.float32)
            return b1 * m0 + (1 - b1) * g.astype(jnp.float32)

        def upd_v(g, v_, p):
            if g is None:
                return None
            v0 = v_ if v_ is not None else jnp.zeros_like(p, jnp.float32)
            return b2 * v0 + (1 - b2) * jnp.square(g.astype(jnp.float32))

        m = _map(upd_m, grads, state["m"], params)
        v = _map(upd_v, grads, state["v"], params)
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)

        def apply(p, mi, vi):
            if mi is None:
                return p
            upd = (mi / c1) / (jnp.sqrt(vi / c2) + eps)
            if weight_decay:
                upd = upd + weight_decay * p.astype(jnp.float32)
            return (p - lr_t * upd).astype(p.dtype)

        return _map(apply, params, m, v), {"step": step, "m": m, "v": v}

    return Optimizer(init, update)


def make_optimizer(name: str, lr, **kw) -> Optimizer:
    return {"sgd": sgd, "sgd_momentum": sgd_momentum, "adamw": adamw}[name](lr, **kw)
