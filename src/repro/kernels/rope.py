"""Fused rotary-embedding Pallas kernel + the cos/sin table helpers.

Two ways RoPE runs on the kernel path:

* **Fused into flash attention** (the production path): ``models/layers.py``
  passes ``rope=(cos, sin)`` tables through ``kernels.ops.sdpa`` and the
  flash kernels rotate the q/k tiles in VMEM right after load
  (``flash_attention._rot``) — the rotated q/k never round-trip through
  HBM, and the backward counter-rotates dq/dk before the final write.
  Traffic drops from 2·[B·H, N, D] extra HBM writes+reads to one
  [N, D/2]·2 table read per tile sweep.
* **Standalone kernel** (this module): ``rope_apply`` is a drop-in for the
  jnp rotation in ``models/layers.rope`` — one pass over x with the angle
  tables streamed per row tile; the backward is the same kernel run with
  ``-sin`` (rotations are orthogonal: dx = R₋θ(dy)), so nothing but the
  tiny tables is saved as residuals.

Tables are position-indexed: ``rope_tables(positions, theta, d)`` matches
``models/layers.rope``'s frequency convention exactly (``theta ** (-i/half)``),
and ``apply_rope_tables`` is the jnp reference used by dispatch fallbacks
and the equivalence tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import block_for, pad_dim


def rope_tables(positions, theta: float, d: int):
    """(cos, sin) f32 tables [N, d//2] for 1-D ``positions`` [N]."""
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope_tables(x, cos, sin):
    """jnp reference rotation: x [..., N, D], tables [N, D//2] (f32).

    Same math as ``models/layers.rope`` (f32 compute, cast back): used by
    the dispatch fallback when the flash kernel path is not taken and as
    the oracle for the fused/standalone kernels.
    """
    half = x.shape[-1] // 2
    shape = (1,) * (x.ndim - 2) + cos.shape
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# standalone kernel: x [B, N, H, D] (the models/layers.rope layout)
# ---------------------------------------------------------------------------


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref):
    x = x_ref[0]                                   # [bn, H, D]
    half = x.shape[-1] // 2
    c = cos_ref[...][:, None, :]                   # [bn, 1, half]
    s = sin_ref[...][:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    o_ref[0] = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                               -1).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _rope_call(B: int, Np: int, H: int, D: int, dtype_name: str, bn: int,
               interpret: bool):
    return pl.pallas_call(
        _rope_kernel,
        grid=(B, Np // bn),
        in_specs=[
            pl.BlockSpec((1, bn, H, D), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((bn, D // 2), lambda b, i: (i, 0)),
            pl.BlockSpec((bn, D // 2), lambda b, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn, H, D), lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Np, H, D), jnp.dtype(dtype_name)),
        name="rope",
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def rope_fwd(x, cos, sin, *, bn: int = 256, interpret: bool = False):
    """Fused rotation kernel. x: [B, N, H, D]; tables [N, D//2] f32."""
    B, N, H, D = x.shape
    assert cos.shape == (N, D // 2), (cos.shape, x.shape)
    bn = block_for(N, bn)
    xp = pad_dim(x, bn, 1)
    cosp = pad_dim(cos.astype(jnp.float32), bn, 0)
    sinp = pad_dim(sin.astype(jnp.float32), bn, 0)
    call = _rope_call(B, xp.shape[1], H, D, jnp.dtype(x.dtype).name, bn,
                      interpret)
    return call(xp, cosp, sinp)[:, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def rope_apply(x, cos, sin, interpret: bool = False):
    """Differentiable fused RoPE: drop-in for the jnp rotation with the
    backward run as the same kernel at −θ (nothing stored but the tables)."""
    return rope_fwd(x, cos, sin, interpret=interpret)


def _rope_vjp_fwd(x, cos, sin, interpret):
    return rope_fwd(x, cos, sin, interpret=interpret), (cos, sin)


def _rope_vjp_bwd(interpret, res, g):
    cos, sin = res
    # R_θᵀ = R₋θ: same kernel, sin negated; tables are constants (zero cot)
    return (rope_fwd(g, cos, -sin, interpret=interpret),
            jnp.zeros_like(cos), jnp.zeros_like(sin))


rope_apply.defvjp(_rope_vjp_fwd, _rope_vjp_bwd)
