"""Fused RMSNorm Pallas TPU kernels (forward + backward, paper A.3).

Forward reads ``x`` once (single pass: square-mean, rsqrt, scale — no
separate mean kernel); backward recomputes rms/xhat from the saved ``x``
(the MeSP residual contract: residual = x only) and emits dx plus a
per-row-block partial dw that the wrapper sum-reduces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import block_for, pad_dim


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    o_ref[...] = (x * rms * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "bm", "interpret"))
def rmsnorm(x, w, eps: float = 1e-6, *, bm: int = 256,
            interpret: bool = False):
    """x: [M, d]; w: [d]. Row-block grid (any M — rows padded); d stays
    whole in VMEM. Padded rows normalize zeros (rsqrt(eps)) and are sliced."""
    M, d = x.shape
    bm = block_for(M, bm)
    xp = pad_dim(x, bm, 0)
    Mp = xp.shape[0]
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, d), x.dtype),
        name="rmsnorm_fwd",
        interpret=interpret,
    )(xp, w.reshape(1, d))
    return out[:M]


def _rmsnorm_bwd_kernel(x_ref, w_ref, g_ref, dx_ref, dwp_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    xhat = x * rms
    dxhat = g * w
    dx = (dxhat - xhat * jnp.mean(dxhat * xhat, -1, keepdims=True)) * rms
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dwp_ref[0] = jnp.sum(g * xhat, 0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("eps", "bm", "interpret"))
def rmsnorm_bwd(x, w, g, eps: float = 1e-6, *, bm: int = 256,
                interpret: bool = False):
    """Returns (dx, dw). Per-block dw partials reduced by the wrapper.
    Any M: padded rows carry g = 0, so they add nothing to dw."""
    M, d = x.shape
    bm = block_for(M, bm)
    xp = pad_dim(x, bm, 0)
    gp = pad_dim(g, bm, 0)
    Mp = xp.shape[0]
    dx, dwp = pl.pallas_call(
        functools.partial(_rmsnorm_bwd_kernel, eps=eps),
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            # one [1, d] partial per row block: a (1, d) block of a
            # [Mp/bm, 1, d] array spans the last two dims whole, which the
            # TPU's (8, 128) tiling needs when there is more than one block
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, d), x.dtype),
            jax.ShapeDtypeStruct((Mp // bm, 1, d), jnp.float32),
        ],
        name="rmsnorm_bwd",
        interpret=interpret,
    )(xp, w.reshape(1, d), gp)
    return dx[:M], jnp.sum(dwp, (0, 1)).astype(w.dtype)
