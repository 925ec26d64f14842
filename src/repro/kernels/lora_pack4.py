"""Packed 4-bit base-weight LoRA Pallas kernels: int4/nf4 W0 unpacked in VMEM.

``core/quant.py`` packs two 4-bit weights per byte along the input dimension
(``q4`` uint8 [ceil(K/2), N] + per-output-channel scale row). These kernels
are the TPU execution path for that format: the packed byte tile and its
scale row are the only W0 bytes that ever leave HBM — half the traffic of
the int8 kernels in ``lora_quant.py``, a quarter of bf16. The dense float W0
exists only tile-by-tile inside VMEM, never as an HBM array.

Per K-tile the VPU unpacks a ``[bk/2, bn]`` byte block into a ``[bk, bn]``
value block in front of the MXU:

* both formats: ``lo = v & 0xF``, ``hi = v >> 4``, each decoded to f32 in
  the byte tile's layout, then interleaved back to input order (byte row j
  holds input rows 2j/2j+1) by a stack+reshape that keeps the lane (N)
  dimension intact;
* ``int4``: two's-complement sign extension ``(nib ^ 8) - 8``;
* ``nf4``: a 16-entry codebook lookup, compiled as a chain of 16 vector
  selects against the static :data:`repro.core.quant.NF4_CODE` constants (no
  codebook operand needs to leave HBM).

The per-output-channel scale stays algebraically hoisted across the K-sum
exactly as in the int8 kernels: applied to the accumulator at the final K
step in the forward, folded onto the incoming gradient in the backward.

One structural difference from ``lora_quant.py``: the dx kernel reads the
*untransposed* packed tile. Transposing ``q4`` in HBM would break the
two-nibbles-per-K-pair byte layout, so instead ``g@W0ᵀ`` contracts the N
axis of both operands via ``dot_general`` (the same idiom as the grouped dx
kernel in ``lora_grouped.py``).

Only the two W0-touching ops need packed variants: the forward and the
``dx`` backward. ``dA``/``dB`` never read W0 (paper A.1 eqs 10/12), so the
fused ``lora_dab`` kernel from ``lora_fused.py`` is reused unchanged.

Wrappers follow the ``tiling.py`` contract: every dim zero-padded to the
block grid and sliced back. Zero *bytes* pad the packed operand; for nf4 a
zero nibble decodes to code[0] = -1, which is still harmless — padded K rows
only ever meet zero-padded x rows / are sliced off dx, and padded N columns
carry a zero scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import NF4_CODE
from repro.kernels.tiling import block_for, pad_dim


def _decode(nib, method: str):
    """int32 nibbles (0..15) -> f32 values, elementwise."""
    if method == "int4":
        return ((nib ^ 8) - 8).astype(jnp.float32)
    # nf4: 16-entry codebook gather as a static select chain on the VPU
    w = jnp.full(nib.shape, NF4_CODE[0], jnp.float32)
    for i in range(1, 16):
        w = jnp.where(nib == i, jnp.float32(NF4_CODE[i]), w)
    return w


def _unpack_tile(packed, method: str, dtype):
    """uint8 [bk/2, bn] byte tile -> [bk, bn] dequantized-value tile (no
    scale — that is hoisted out of the K-sum by the caller)."""
    v = packed.astype(jnp.int32)
    # decode each nibble plane in the byte tile's own layout: comparing the
    # interleaved array instead makes Mosaic relayout its i1 select masks,
    # which it refuses for the nf4 chain
    lo, hi = _decode(v & 0xF, method), _decode(v >> 4, method)
    # interleave to input order: row 2j <- lo[j], row 2j+1 <- hi[j]. The
    # reshape merges the sublane axes only; the lane (N) axis is untouched.
    return jnp.stack([lo, hi], axis=1).reshape(
        2 * v.shape[0], v.shape[1]).astype(dtype)


def _lora_fused_q4_kernel(x_ref, q4_ref, s_ref, a_ref, b_ref, o_ref,
                          acc_ref, h_ref, *, scale: float, n_k: int,
                          method: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    xb = x_ref[...]
    # nibble unpack on the VPU; the scale half of the dequant is deferred to
    # the final K step (it commutes with the K-sum).
    wb = _unpack_tile(q4_ref[...], method, x_ref.dtype)
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
def _lora_fused_q4_call(Mp: int, Kp: int, Np: int, r: int, dtype_name: str,
                        scale: float, bm: int, bn: int, bk: int,
                        method: str, interpret: bool):
    n_k = Kp // bk
    return pl.pallas_call(
        functools.partial(_lora_fused_q4_kernel, scale=scale, n_k=n_k,
                          method=method),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),     # x
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),  # q4 bytes
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),      # scale row
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),      # a
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),      # b
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.dtype(dtype_name)),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),                  # W0 accum
            pltpu.VMEM((bm, r), jnp.float32),                   # h tile
        ],
        name="lora_q4_fwd",
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "method", "bm", "bn",
                                             "bk", "interpret"))
def lora_fused_q4(x, q4, s, a, b, scale: float = 2.0, *,
                  method: str = "int4", bm: int = 128, bn: int = 128,
                  bk: int = 128, interpret: bool = False):
    """y = x@dequant(q4)·s + s_lora·(x@A)@B.  x:[M,K] q4:uint8[ceil(K/2),N]
    s:f32[1,N] a:[K,r] b:[r,N] -> [M,N]. Any M/N/K (padded, odd K included:
    the stray pad nibble lands on a zero-padded x row)."""
    M, K = x.shape
    N = q4.shape[1]
    r = a.shape[1]
    bm, bn, bk = block_for(M, bm), block_for(N, bn), block_for(K, bk)
    xp = pad_dim(pad_dim(x, bm, 0), bk, 1)
    q4p = pad_dim(pad_dim(q4, bk // 2, 0), bn, 1)
    sp = pad_dim(s.astype(jnp.float32), bn, 1)
    ap = pad_dim(a, bk, 0)
    bp = pad_dim(b, bn, 1)
    Mp, Kp = xp.shape
    Np = q4p.shape[1]
    out = _lora_fused_q4_call(Mp, Kp, Np, r, jnp.dtype(x.dtype).name,
                              float(scale), bm, bn, bk, method,
                              interpret)(xp, q4p, sp, ap, bp)
    return out[:M, :N]


def _lora_dx_q4_kernel(g_ref, s_ref, q4_ref, dh_ref, at_ref, o_ref, acc_ref,
                       *, n_n: int, method: str):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # g@W0ᵀ = (g·s) @ wᵀ: scale is per-N, i.e. per contraction element, so
    # it folds onto the g tile (VPU) before the unpacked tile hits the MXU.
    # The packed tile stays untransposed ([bk, bn] after unpack); the
    # transpose is expressed as a dot_general contraction over N of both.
    gs = g_ref[...] * s_ref[...].astype(g_ref.dtype)
    wb = _unpack_tile(q4_ref[...], method, g_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        gs, wb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n == n_n - 1)
    def _finish():
        lora_part = jax.lax.dot(dh_ref[...], at_ref[...],
                                preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + lora_part).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _lora_dx_q4_call(Mp: int, Kp: int, Np: int, r: int, dtype_name: str,
                     bm: int, bk: int, bn: int, method: str,
                     interpret: bool):
    n_n = Np // bn
    return pl.pallas_call(
        functools.partial(_lora_dx_q4_kernel, n_n=n_n, method=method),
        grid=(Mp // bm, Kp // bk, n_n),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, n: (i, n)),     # g
            pl.BlockSpec((1, bn), lambda i, j, n: (0, n)),      # scale row
            pl.BlockSpec((bk // 2, bn), lambda i, j, n: (j, n)),  # q4 bytes
            pl.BlockSpec((bm, r), lambda i, j, n: (i, 0)),      # dh
            pl.BlockSpec((r, bk), lambda i, j, n: (0, j)),      # aᵀ
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, n: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Kp), jnp.dtype(dtype_name)),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        name="lora_q4_dx",
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "method", "bm", "bk",
                                             "bn", "interpret"))
def lora_dx_q4(g, q4, s, a, b, scale: float = 2.0, *, method: str = "int4",
               bm: int = 128, bk: int = 128, bn: int = 128,
               interpret: bool = False):
    """dx = (s_lora·g)@Bᵀ@Aᵀ + g@dequant(q4)ᵀ·s  (A.1 eq 13).
    g:[M,N] q4:uint8[ceil(K/2),N] -> dx:[M,K].

    Like ``lora_dx_q``: the thin ``dh = s_lora·g@Bᵀ`` matmul stays in jnp;
    the kernel fuses the two large matmuls so ``g`` is read once. Unlike the
    int8 variant no HBM transpose of the table is taken — the packed byte
    layout pairs adjacent K rows, so the kernel contracts the untransposed
    tile instead (quarter the W0 HBM bytes of the bf16 ``w0.T`` copy)."""
    M, N = g.shape
    r = a.shape[1]
    K = a.shape[0]
    bm, bk, bn = block_for(M, bm), block_for(K, bk), block_for(N, bn)
    dh = ((scale * g) @ b.T).astype(g.dtype)        # [M, r] — tiny
    gp = pad_dim(pad_dim(g, bm, 0), bn, 1)
    q4p = pad_dim(pad_dim(q4, bk // 2, 0), bn, 1)   # untransposed bytes
    sp = pad_dim(s.astype(jnp.float32), bn, 1)      # [1, Np]
    dhp = pad_dim(dh, bm, 0)
    atp = pad_dim(a.T, bk, 1)                       # [r, Kp]
    Mp, Np = gp.shape
    Kp = 2 * q4p.shape[0]
    out = _lora_dx_q4_call(Mp, Kp, Np, r, jnp.dtype(g.dtype).name, bm, bk,
                           bn, method, interpret)(gp, sp, q4p, dhp, atp)
    return out[:M, :K]
