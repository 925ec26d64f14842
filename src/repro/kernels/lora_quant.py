"""Quantized-base-weight LoRA Pallas kernels: int8 W0 dequantized in VMEM.

The paper keeps frozen base weights quantized QLoRA-style and dequantizes on
the fly (§4.5); ``core/quant.py`` provides the int8 symmetric per-output-
channel format ``W0 = q · s`` (q int8 [K, N], s f32 [1, N]). These kernels
are the TPU execution path for that format: the int8 tile and its scale row
are the only W0 bytes that ever leave HBM — the bf16/f32 dense W0 exists
only tile-by-tile inside VMEM, never as an HBM array. Relative to the bf16
kernels in ``lora_fused.py`` this halves both the W0 HBM footprint and the
W0 HBM traffic per step.

Dequantization is split across the matmul using the per-output-channel
structure: ``(x @ (q·s))_ij = s_j · Σ_k x_ik q_kj``, so the kernels

* cast the int8 tile to the activation dtype on the VPU in front of the MXU
  (the per-element half of the dequant), and
* apply the scale row once per output tile — on the accumulator in the
  forward (``acc · s`` at the final K step), on the incoming gradient in the
  backward (``(g·s) @ qᵀ``) — instead of per K-step on the weight tile.

Only the two W0-touching ops need quantized variants: the forward and the
``dx`` backward. ``dA``/``dB`` never read W0 (paper A.1 eqs 10/12), so the
fused ``lora_dab`` kernel from ``lora_fused.py`` is reused unchanged.

Wrappers follow the ``tiling.py`` contract: every dim zero-padded to the
block grid and sliced back; padded K rows of q dequantize to zero rows,
padded N columns are sliced off (fwd) or meet zero-padded g columns (dx).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import block_for, pad_dim


def _lora_fused_q_kernel(x_ref, q_ref, s_ref, a_ref, b_ref, o_ref,
                         acc_ref, h_ref, *, scale: float, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    xb = x_ref[...]
    # int8 -> activation dtype on the VPU; the scale half of the dequant is
    # deferred to the final K step (it commutes with the K-sum).
    wb = q_ref[...].astype(x_ref.dtype)
    acc_ref[...] += jax.lax.dot(xb, wb, preferred_element_type=jnp.float32)
    h_ref[...] += jax.lax.dot(xb, a_ref[...],
                              preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        delta = jax.lax.dot(h_ref[...].astype(x_ref.dtype), b_ref[...],
                            preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] * s_ref[...] +
                      scale * delta).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _lora_fused_q_call(Mp: int, Kp: int, Np: int, r: int, dtype_name: str,
                       scale: float, bm: int, bn: int, bk: int,
                       interpret: bool):
    n_k = Kp // bk
    return pl.pallas_call(
        functools.partial(_lora_fused_q_kernel, scale=scale, n_k=n_k),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # q (int8)
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),    # scale row
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),    # a
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),    # b
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.dtype(dtype_name)),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),                # W0 accumulator
            pltpu.VMEM((bm, r), jnp.float32),                 # h tile (VMEM!)
        ],
        name="lora_q_fwd",
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "bk",
                                             "interpret"))
def lora_fused_q(x, q, s, a, b, scale: float = 2.0, *, bm: int = 128,
                 bn: int = 128, bk: int = 128, interpret: bool = False):
    """y = x@(q·s) + s_lora·(x@A)@B.  x:[M,K] q:int8[K,N] s:f32[1,N]
    a:[K,r] b:[r,N] -> [M,N]. Any M/N/K (padded)."""
    M, K = x.shape
    N = q.shape[1]
    r = a.shape[1]
    bm, bn, bk = block_for(M, bm), block_for(N, bn), block_for(K, bk)
    xp = pad_dim(pad_dim(x, bm, 0), bk, 1)
    qp = pad_dim(pad_dim(q, bk, 0), bn, 1)
    sp = pad_dim(s.astype(jnp.float32), bn, 1)
    ap = pad_dim(a, bk, 0)
    bp = pad_dim(b, bn, 1)
    Mp, Kp = xp.shape
    Np = qp.shape[1]
    out = _lora_fused_q_call(Mp, Kp, Np, r, jnp.dtype(x.dtype).name,
                             float(scale), bm, bn, bk,
                             interpret)(xp, qp, sp, ap, bp)
    return out[:M, :N]


def _lora_dx_q_kernel(g_ref, s_ref, qt_ref, dh_ref, at_ref, o_ref, acc_ref,
                      *, n_n: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # g@W0ᵀ = (g·s) @ qᵀ: scale is per-N, i.e. per contraction row of qᵀ,
    # so it folds onto the g tile (VPU) before the int8 tile hits the MXU.
    gs = g_ref[...] * s_ref[...].astype(g_ref.dtype)
    acc_ref[...] += jax.lax.dot(gs, qt_ref[...].astype(g_ref.dtype),
                                preferred_element_type=jnp.float32)

    @pl.when(n == n_n - 1)
    def _finish():
        lora_part = jax.lax.dot(dh_ref[...], at_ref[...],
                                preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + lora_part).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _lora_dx_q_call(Mp: int, Kp: int, Np: int, r: int, dtype_name: str,
                    bm: int, bk: int, bn: int, interpret: bool):
    n_n = Np // bn
    return pl.pallas_call(
        functools.partial(_lora_dx_q_kernel, n_n=n_n),
        grid=(Mp // bm, Kp // bk, n_n),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, n: (i, n)),   # g
            pl.BlockSpec((1, bn), lambda i, j, n: (0, n)),    # scale row
            pl.BlockSpec((bn, bk), lambda i, j, n: (n, j)),   # qᵀ (int8)
            pl.BlockSpec((bm, r), lambda i, j, n: (i, 0)),    # dh
            pl.BlockSpec((r, bk), lambda i, j, n: (0, j)),    # aᵀ
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, n: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Kp), jnp.dtype(dtype_name)),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        name="lora_q_dx",
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bk", "bn",
                                             "interpret"))
def lora_dx_q(g, q, s, a, b, scale: float = 2.0, *, bm: int = 128,
              bk: int = 128, bn: int = 128, interpret: bool = False):
    """dx = (s_lora·g)@Bᵀ@Aᵀ + g@(q·s)ᵀ  (A.1 eq 13).  g:[M,N] -> dx:[M,K].

    Like ``lora_dx``: the thin ``dh = s_lora·g@Bᵀ`` matmul stays in jnp; the
    kernel fuses the two large matmuls so ``g`` is read once. The transposed
    int8 table costs half the HBM of the bf16 ``w0.T`` copy in ``lora_dx``.
    """
    M, N = g.shape
    K = q.shape[0]
    bm, bk, bn = block_for(M, bm), block_for(K, bk), block_for(N, bn)
    dh = ((scale * g) @ b.T).astype(g.dtype)        # [M, r] — tiny
    gp = pad_dim(pad_dim(g, bm, 0), bn, 1)
    qtp = pad_dim(pad_dim(q.T, bn, 0), bk, 1)       # int8 [Np, Kp]
    sp = pad_dim(s.astype(jnp.float32), bn, 1)      # [1, Np]
    dhp = pad_dim(dh, bm, 0)
    atp = pad_dim(a.T, bk, 1)                       # [r, Kp]
    Mp, Np = gp.shape
    Kp = qtp.shape[1]
    r = atp.shape[0]
    out = _lora_dx_q_call(Mp, Kp, Np, r, jnp.dtype(g.dtype).name, bm, bk,
                          bn, interpret)(gp, sp, qtp, dhp, atp)
    return out[:M, :K]
