"""Fused LoRA linear Pallas TPU kernels: ``y = x@W0 + s·(x@A)@B``.

TPU-native extension of the paper's core insight (DESIGN.md §2): MeSP saves
HBM *capacity* by never storing ``h = x@A``; on TPU we also save HBM
*bandwidth* by never letting ``h`` leave VMEM — it exists only as a
``[bm, r]`` f32 scratch tile accumulated alongside the main matmul and is
consumed against ``B`` on the final K step. One kernel, one pass over
``x``/``W0``; ``A``/``B`` tiles are tiny (r ≤ 32).

Backward is split the way the paper's A.1 equations factor:

* ``lora_dx``  — dx = dh@Aᵀ + g@W0ᵀ fused so ``g`` is read once.
* ``lora_dab`` — dA = xᵀ(s·g@Bᵀ), dB = hᵀ(s·g) with ``h`` *recomputed*
  tile-wise in VMEM (paper §4.1) and both outputs produced in a single pass
  over ``x``/``g`` (previously three separate jnp matmuls re-reading both
  operands from HBM).

All wrappers zero-pad non-block-aligned dims (see ``tiling.py``) so
arbitrary ``batch×seq`` / feature sizes work; zero rows/cols contribute
nothing to the sliced-back results.

``pl.pallas_call`` closures are built through ``functools.lru_cache``
builders keyed on the static signature, so repeated non-jit calls
(benchmarks, tests, retraces under fresh outer jits) reuse the constructed
call object instead of rebuilding grid/BlockSpecs every time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import block_for, pad_dim


def _lora_fused_kernel(x_ref, w0_ref, a_ref, b_ref, o_ref, acc_ref, h_ref, *,
                       scale: float, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    xb = x_ref[...]
    acc_ref[...] += jax.lax.dot(xb, w0_ref[...],
                                preferred_element_type=jnp.float32)
    h_ref[...] += jax.lax.dot(xb, a_ref[...],
                              preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        delta = jax.lax.dot(h_ref[...].astype(x_ref.dtype), b_ref[...],
                            preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale * delta).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _lora_fused_call(Mp: int, Kp: int, Np: int, r: int, dtype_name: str,
                     scale: float, bm: int, bn: int, bk: int,
                     interpret: bool):
    n_k = Kp // bk
    return pl.pallas_call(
        functools.partial(_lora_fused_kernel, scale=scale, n_k=n_k),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # w0
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),    # a
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),    # b
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.dtype(dtype_name)),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),                # W0 accumulator
            pltpu.VMEM((bm, r), jnp.float32),                 # h tile (VMEM!)
        ],
        name="lora_fwd",
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "bk",
                                             "interpret"))
def lora_fused(x, w0, a, b, scale: float = 2.0, *, bm: int = 128,
               bn: int = 128, bk: int = 128, interpret: bool = False):
    """x:[M,K] w0:[K,N] a:[K,r] b:[r,N] -> [M,N]. Any M/N/K (padded)."""
    M, K = x.shape
    N = w0.shape[1]
    r = a.shape[1]
    bm, bn, bk = block_for(M, bm), block_for(N, bn), block_for(K, bk)
    xp = pad_dim(pad_dim(x, bm, 0), bk, 1)
    w0p = pad_dim(pad_dim(w0, bk, 0), bn, 1)
    ap = pad_dim(a, bk, 0)
    bp = pad_dim(b, bn, 1)
    Mp, Kp = xp.shape
    Np = w0p.shape[1]
    out = _lora_fused_call(Mp, Kp, Np, r, jnp.dtype(x.dtype).name,
                           float(scale), bm, bn, bk,
                           interpret)(xp, w0p, ap, bp)
    return out[:M, :N]


def _lora_dx_kernel(g_ref, w0t_ref, dh_ref, at_ref, o_ref, acc_ref, *,
                    n_n: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(g_ref[...], w0t_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(n == n_n - 1)
    def _finish():
        lora_part = jax.lax.dot(dh_ref[...], at_ref[...],
                                preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + lora_part).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _lora_dx_call(Mp: int, Kp: int, Np: int, r: int, dtype_name: str,
                  bm: int, bk: int, bn: int, interpret: bool):
    n_n = Np // bn
    return pl.pallas_call(
        functools.partial(_lora_dx_kernel, n_n=n_n),
        grid=(Mp // bm, Kp // bk, n_n),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, n: (i, n)),   # g
            pl.BlockSpec((bn, bk), lambda i, j, n: (n, j)),   # w0ᵀ
            pl.BlockSpec((bm, r), lambda i, j, n: (i, 0)),    # dh
            pl.BlockSpec((r, bk), lambda i, j, n: (0, j)),    # aᵀ
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, n: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Kp), jnp.dtype(dtype_name)),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        name="lora_dx",
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bk", "bn",
                                             "interpret"))
def lora_dx(g, w0, a, b, scale: float = 2.0, *, bm: int = 128, bk: int = 128,
            bn: int = 128, interpret: bool = False):
    """dx = (s·g)@Bᵀ@Aᵀ + g@W0ᵀ  (A.1 eq 13).  g:[M,N] -> dx:[M,K].

    The rank-r intermediate ``dh = s·g@Bᵀ`` is a thin matmul computed here
    (jnp — XLA emits it well); the kernel fuses the two large matmuls so ``g``
    is read once.
    """
    M, N = g.shape
    K = w0.shape[0]
    bm, bk, bn = block_for(M, bm), block_for(K, bk), block_for(N, bn)
    dh = ((scale * g) @ b.T).astype(g.dtype)        # [M, r] — tiny
    gp = pad_dim(pad_dim(g, bm, 0), bn, 1)
    w0tp = pad_dim(pad_dim(w0.T, bn, 0), bk, 1)     # [Np, Kp]
    dhp = pad_dim(dh, bm, 0)
    atp = pad_dim(a.T, bk, 1)                       # [r, Kp]
    Mp, Np = gp.shape
    Kp = w0tp.shape[1]
    r = atp.shape[0]
    out = _lora_dx_call(Mp, Kp, Np, r, jnp.dtype(g.dtype).name, bm, bk, bn,
                        interpret)(gp, w0tp, dhp, atp)
    return out[:M, :K]


# ---------------------------------------------------------------------------
# fused dA/dB: one pass over x and g, h recomputed tile-wise in VMEM
# ---------------------------------------------------------------------------


def _lora_dab_kernel(x_ref, g_ref, a_ref, b_ref, da_ref, db_ref, *,
                     scale: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        da_ref[...] = jnp.zeros_like(da_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    x = x_ref[...]
    sg = (scale * g_ref[...].astype(jnp.float32)).astype(g_ref.dtype)
    # h = x@A recomputed for this row tile only (paper §4.1) — never in HBM
    h = jax.lax.dot(x, a_ref[...],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    # dh = s·g @ Bᵀ  (A.1 eq 11): contract N
    dh = jax.lax.dot_general(sg, b_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32
                             ).astype(x.dtype)
    # dA += xᵀ dh  (eq 12);  dB += hᵀ s·g  (eq 10): both contract the row dim
    da_ref[...] += jax.lax.dot_general(x, dh, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    db_ref[...] += jax.lax.dot_general(h, sg, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=None)
def _lora_dab_call(Mp: int, Kp: int, Np: int, r: int, scale: float, bm: int,
                   interpret: bool):
    return pl.pallas_call(
        functools.partial(_lora_dab_kernel, scale=scale),
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, Kp), lambda i: (i, 0)),         # x
            pl.BlockSpec((bm, Np), lambda i: (i, 0)),         # g
            pl.BlockSpec((Kp, r), lambda i: (0, 0)),          # a
            pl.BlockSpec((r, Np), lambda i: (0, 0)),          # b
        ],
        out_specs=[
            pl.BlockSpec((Kp, r), lambda i: (0, 0)),
            pl.BlockSpec((r, Np), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Kp, r), jnp.float32),
            jax.ShapeDtypeStruct((r, Np), jnp.float32),
        ],
        name="lora_dab",
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "bm", "interpret"))
def lora_dab(x, g, a, b, scale: float = 2.0, *, bm: int = 256,
             interpret: bool = False):
    """(dA, dB) in one fused pass.  x:[M,K] g:[M,N] a:[K,r] b:[r,N].

    Grid is row-tiles only; ``x``/``g`` stream through VMEM once while the
    [K,r] / [r,N] outputs stay resident and accumulate in f32 (the output
    blocks are revisited every step, so they live in VMEM for the whole
    sweep). Zero-padded rows/cols contribute zero to both outputs (padded-N
    entries of g meet padded-N cols of b; padded-K cols of x meet padded-K
    rows of a). r stays unpadded — Mosaic lane-pads it like the fwd kernel.
    """
    M, K = x.shape
    N = g.shape[1]
    r = a.shape[1]
    bm = block_for(M, bm)
    xp = pad_dim(pad_dim(x, bm, 0), 128, 1)
    gp = pad_dim(pad_dim(g, bm, 0), 128, 1)
    ap = pad_dim(a, 128, 0)
    bp = pad_dim(b, 128, 1)
    Mp, Kp = xp.shape
    Np = gp.shape[1]

    da, db = _lora_dab_call(Mp, Kp, Np, r, float(scale), bm,
                            interpret)(xp, gp, ap, bp)
    return da[:K].astype(a.dtype), db[:, :N].astype(b.dtype)
