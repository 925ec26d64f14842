"""Grouped/ragged LoRA Pallas kernels: per-tile adapter gather via scalar
prefetch.

``lora_fused.py`` binds ONE (W0, A, B) triple per call. Two workloads need
many: MoE per-expert linears (``[E, ·, ·]`` weight stacks, until now a
structured-jnp fallback in pallas mode) and multi-tenant serving, where each
request in a decode batch owns a private user adapter. This family runs

    y[m] = x[m] @ W0[g(m)] + s · (x[m] @ A[g(m)]) @ B[g(m)]

in one kernel launch over all groups: rows are packed so every ``bm``-row
tile belongs to exactly one group, and an int32 ``gid[t]`` array — handed to
the kernel through ``pltpu.PrefetchScalarGridSpec``, the same idiom as the
flash kernels' tile schedules — is read by the BlockSpec index maps to
gather tile t's stack entries into VMEM. The grid size is static but the
``gid`` *values* may be runtime-traced, so the serving decode path re-routes
adapters across steps with zero recompiles.

Two W0 layouts, chosen statically by ``Ew = w0.shape[0]``:

* ``Ew == E`` — per-group base (MoE experts): W0 tile indexed by ``gid[t]``.
* ``Ew == 1`` — shared base (serving: one frozen model, many adapters):
  every tile reads stack entry 0; only A/B are per-group.

Quantized variants mirror ``lora_quant.py``/``lora_pack4.py``: the per-group
int8 tile is cast — or the packed int4/nf4 byte tile nibble-unpacked — to
the activation dtype on the VPU, and the per-output-channel scale row is
applied once per output tile (on the accumulator in the forward, folded onto
``g`` in ``dx``) — a dense per-expert W0 never exists in HBM. The packed
stack is ``[Ew, ceil(K/2), N]`` uint8: multi-tenant serving and pallas-mode
MoE experts get the same 4× W0 residency cut as single-base training.

``lora_grouped_dab`` accumulates dA/dB *per group*: its output BlockSpecs
are indexed by ``gid[t]``, so it requires the tiles of each group to be
contiguous in the schedule (the ``tiling.grouped_schedule`` contract —
group-first detection compares gid at t±1, exactly like the flash kernels'
row-boundary detection). Groups that own no tiles are zeroed by a live-group
mask after the call.

Wrappers pad K/N to the block grid per ``tiling.py``; rows arrive already
packed to ``bm`` multiples by the dispatch layer (``ops.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lora_pack4 import _unpack_tile
from repro.kernels.tiling import block_for, pad_dim


def _w_index(Ew: int):
    """Index-map factory for the W0/q/scale stacks: per-group entry when the
    stack is [E,·,·], entry 0 always when the base is shared ([1,·,·])."""
    if Ew == 1:
        return lambda t, gid: 0
    return lambda t, gid: gid[t]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _grouped_fwd_kernel(gid_ref, x_ref, w_ref, a_ref, b_ref, o_ref,
                        acc_ref, h_ref, *, scale: float, n_k: int, pid=None):
    k = pl.program_id(2) if pid is None else pid[2]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    xb = x_ref[...]
    acc_ref[...] += jax.lax.dot(xb, w_ref[0],
                                preferred_element_type=jnp.float32)
    h_ref[...] += jax.lax.dot(xb, a_ref[0],
                              preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        delta = jax.lax.dot(h_ref[...].astype(x_ref.dtype), b_ref[0],
                            preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale * delta).astype(o_ref.dtype)


def _grouped_fwd_q_kernel(gid_ref, x_ref, q_ref, s_ref, a_ref, b_ref, o_ref,
                          acc_ref, h_ref, *, scale: float, n_k: int,
                          pid=None):
    k = pl.program_id(2) if pid is None else pid[2]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    xb = x_ref[...]
    wb = q_ref[0].astype(x_ref.dtype)                 # dequant-in-VMEM
    acc_ref[...] += jax.lax.dot(xb, wb, preferred_element_type=jnp.float32)
    h_ref[...] += jax.lax.dot(xb, a_ref[0],
                              preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        delta = jax.lax.dot(h_ref[...].astype(x_ref.dtype), b_ref[0],
                            preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] * s_ref[0] +
                      scale * delta).astype(o_ref.dtype)


def _grouped_fwd_q4_kernel(gid_ref, x_ref, q4_ref, s_ref, a_ref, b_ref,
                           o_ref, acc_ref, h_ref, *, scale: float, n_k: int,
                           method: str, pid=None):
    k = pl.program_id(2) if pid is None else pid[2]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    xb = x_ref[...]
    wb = _unpack_tile(q4_ref[0], method, x_ref.dtype)  # nibble unpack (VPU)
    acc_ref[...] += jax.lax.dot(xb, wb, preferred_element_type=jnp.float32)
    h_ref[...] += jax.lax.dot(xb, a_ref[0],
                              preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        delta = jax.lax.dot(h_ref[...].astype(x_ref.dtype), b_ref[0],
                            preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] * s_ref[0] +
                      scale * delta).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _grouped_fwd_call(Mp: int, Kp: int, Np: int, Ew: int, E: int, r: int,
                      dtype_name: str, scale: float, bm: int, bn: int,
                      bk: int, interpret: bool, quant: str):
    n_k = Kp // bk
    wi = _w_index(Ew)
    packed = quant in ("int4", "nf4")
    wblk = (1, bk // 2, bn) if packed else (1, bk, bn)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda t, j, k, gid: (t, k)),          # x
        pl.BlockSpec(wblk, lambda t, j, k, gid: (wi(t, gid), k, j)),
    ]
    if quant != "none":
        in_specs.append(
            pl.BlockSpec((1, 1, bn), lambda t, j, k, gid: (wi(t, gid), 0, j)))
    in_specs += [
        pl.BlockSpec((1, bk, r), lambda t, j, k, gid: (gid[t], k, 0)),  # a
        pl.BlockSpec((1, r, bn), lambda t, j, k, gid: (gid[t], 0, j)),  # b
    ]
    if packed:
        kern = functools.partial(_grouped_fwd_q4_kernel, method=quant)
    elif quant == "int8":
        kern = _grouped_fwd_q_kernel
    else:
        kern = _grouped_fwd_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda t, j, k, gid: (t, j)),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),        # W0 accumulator
            pltpu.VMEM((bm, r), jnp.float32),         # h tile (VMEM only)
        ],
    )
    return pl.pallas_call(
        functools.partial(kern, scale=scale, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.dtype(dtype_name)),
        name="lora_grouped_fwd",
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "bk",
                                             "interpret"))
def lora_grouped(x, w0, a, b, gid, scale: float = 2.0, *, bm: int = 128,
                 bn: int = 128, bk: int = 128, interpret: bool = False):
    """x:[Mp,K] (rows packed to bm-tiles of one group each) w0:[Ew,K,N]
    a:[E,K,r] b:[E,r,N] gid:int32[Mp//bm] -> [Mp,N]."""
    Mp, K = x.shape
    Ew, _, N = w0.shape
    E, _, r = a.shape
    bn, bk = block_for(N, bn), block_for(K, bk)
    xp = pad_dim(x, bk, 1)
    w0p = pad_dim(pad_dim(w0, bk, 1), bn, 2)
    ap = pad_dim(a, bk, 1)
    bp = pad_dim(b, bn, 2)
    Kp, Np = xp.shape[1], w0p.shape[2]
    out = _grouped_fwd_call(Mp, Kp, Np, Ew, E, r, jnp.dtype(x.dtype).name,
                            float(scale), bm, bn, bk, interpret,
                            "none")(jnp.asarray(gid, jnp.int32),
                                    xp, w0p, ap, bp)
    return out[:, :N]


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "bk",
                                             "interpret"))
def lora_grouped_q(x, q, s, a, b, gid, scale: float = 2.0, *, bm: int = 128,
                   bn: int = 128, bk: int = 128, interpret: bool = False):
    """Quantized-base grouped forward. q:int8[Ew,K,N] s:f32[Ew,1,N]."""
    Mp, K = x.shape
    Ew, _, N = q.shape
    E, _, r = a.shape
    bn, bk = block_for(N, bn), block_for(K, bk)
    xp = pad_dim(x, bk, 1)
    qp = pad_dim(pad_dim(q, bk, 1), bn, 2)
    sp = pad_dim(s.astype(jnp.float32), bn, 2)
    ap = pad_dim(a, bk, 1)
    bp = pad_dim(b, bn, 2)
    Kp, Np = xp.shape[1], qp.shape[2]
    out = _grouped_fwd_call(Mp, Kp, Np, Ew, E, r, jnp.dtype(x.dtype).name,
                            float(scale), bm, bn, bk, interpret,
                            "int8")(jnp.asarray(gid, jnp.int32),
                                    xp, qp, sp, ap, bp)
    return out[:, :N]


@functools.partial(jax.jit, static_argnames=("scale", "method", "bm", "bn",
                                             "bk", "interpret"))
def lora_grouped_q4(x, q4, s, a, b, gid, scale: float = 2.0, *,
                    method: str = "int4", bm: int = 128, bn: int = 128,
                    bk: int = 128, interpret: bool = False):
    """Packed-4-bit-base grouped forward. q4:uint8[Ew,ceil(K/2),N]
    s:f32[Ew,1,N]; K is taken from x (odd K: pad nibble meets zero x)."""
    Mp, K = x.shape
    Ew, _, N = q4.shape
    E, _, r = a.shape
    bn, bk = block_for(N, bn), block_for(K, bk)
    xp = pad_dim(x, bk, 1)
    qp = pad_dim(pad_dim(q4, bk // 2, 1), bn, 2)
    sp = pad_dim(s.astype(jnp.float32), bn, 2)
    ap = pad_dim(a, bk, 1)
    bp = pad_dim(b, bn, 2)
    Kp, Np = xp.shape[1], qp.shape[2]
    out = _grouped_fwd_call(Mp, Kp, Np, Ew, E, r, jnp.dtype(x.dtype).name,
                            float(scale), bm, bn, bk, interpret,
                            method)(jnp.asarray(gid, jnp.int32),
                                    xp, qp, sp, ap, bp)
    return out[:, :N]


# ---------------------------------------------------------------------------
# dx backward
# ---------------------------------------------------------------------------


def _grouped_dx_kernel(gid_ref, g_ref, w_ref, dh_ref, a_ref, o_ref, acc_ref,
                       *, n_n: int, pid=None):
    n = pl.program_id(2) if pid is None else pid[2]

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # g @ W0[g]ᵀ: contract the shared N dim of the untransposed stack entry
    acc_ref[...] += jax.lax.dot_general(
        g_ref[...], w_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n == n_n - 1)
    def _finish():
        lora_part = jax.lax.dot_general(
            dh_ref[...], a_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + lora_part).astype(o_ref.dtype)


def _grouped_dx_q_kernel(gid_ref, g_ref, q_ref, s_ref, dh_ref, a_ref, o_ref,
                         acc_ref, *, n_n: int, pid=None):
    n = pl.program_id(2) if pid is None else pid[2]

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # g@(q·s)ᵀ = (g·s) @ qᵀ: fold the per-N scale onto g before the MXU
    gs = g_ref[...] * s_ref[0].astype(g_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        gs, q_ref[0].astype(g_ref.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n == n_n - 1)
    def _finish():
        lora_part = jax.lax.dot_general(
            dh_ref[...], a_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + lora_part).astype(o_ref.dtype)


def _grouped_dx_q4_kernel(gid_ref, g_ref, q4_ref, s_ref, dh_ref, a_ref,
                          o_ref, acc_ref, *, n_n: int, method: str, pid=None):
    n = pl.program_id(2) if pid is None else pid[2]

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # g@(dequant(q4)·s)ᵀ = (g·s) @ wᵀ: fold the per-N scale onto g, unpack
    # the untransposed byte tile, contract the shared N dim of both
    gs = g_ref[...] * s_ref[0].astype(g_ref.dtype)
    wb = _unpack_tile(q4_ref[0], method, g_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        gs, wb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n == n_n - 1)
    def _finish():
        lora_part = jax.lax.dot_general(
            dh_ref[...], a_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + lora_part).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _grouped_dx_call(Mp: int, Kp: int, Np: int, Ew: int, E: int, r: int,
                     dtype_name: str, bm: int, bk: int, bn: int,
                     interpret: bool, quant: str):
    n_n = Np // bn
    wi = _w_index(Ew)
    packed = quant in ("int4", "nf4")
    wblk = (1, bk // 2, bn) if packed else (1, bk, bn)
    in_specs = [
        pl.BlockSpec((bm, bn), lambda t, j, n, gid: (t, n)),          # g
        pl.BlockSpec(wblk, lambda t, j, n, gid: (wi(t, gid), j, n)),
    ]
    if quant != "none":
        in_specs.append(
            pl.BlockSpec((1, 1, bn), lambda t, j, n, gid: (wi(t, gid), 0, n)))
    in_specs += [
        pl.BlockSpec((bm, r), lambda t, j, n, gid: (t, 0)),           # dh
        pl.BlockSpec((1, bk, r), lambda t, j, n, gid: (gid[t], j, 0)),  # a
    ]
    if packed:
        kern = functools.partial(_grouped_dx_q4_kernel, method=quant)
    elif quant == "int8":
        kern = _grouped_dx_q_kernel
    else:
        kern = _grouped_dx_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Mp // bm, Kp // bk, n_n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bk), lambda t, j, n, gid: (t, j)),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(kern, n_n=n_n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Kp), jnp.dtype(dtype_name)),
        name="lora_grouped_dx",
        interpret=interpret,
    )


def _grouped_dh(g, b, gid, scale: float, bm: int):
    """dh = s·g @ B[g]ᵀ per row — thin [Mp, r], gathered per tile (jnp; the
    gather is r·N bytes per tile, XLA emits it well)."""
    Mp, N = g.shape
    T = Mp // bm
    gt = (scale * g).reshape(T, bm, N)
    bt = b[jnp.asarray(gid, jnp.int32)]               # [T, r, N]
    return jnp.einsum("tmn,trn->tmr", gt, bt).reshape(Mp, -1).astype(g.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bk", "bn",
                                             "interpret"))
def lora_grouped_dx(g, w0, a, b, gid, scale: float = 2.0, *, bm: int = 128,
                    bk: int = 128, bn: int = 128, interpret: bool = False):
    """dx = (s·g)@B[g]ᵀ@A[g]ᵀ + g@W0[g]ᵀ.  g:[Mp,N] -> dx:[Mp,K]."""
    Mp, N = g.shape
    Ew, K, _ = w0.shape
    E, _, r = a.shape
    bk, bn = block_for(K, bk), block_for(N, bn)
    dh = _grouped_dh(g, b, gid, scale, bm)
    gp = pad_dim(g, bn, 1)
    w0p = pad_dim(pad_dim(w0, bk, 1), bn, 2)
    ap = pad_dim(a, bk, 1)
    Np, Kp = gp.shape[1], w0p.shape[1]
    out = _grouped_dx_call(Mp, Kp, Np, Ew, E, r, jnp.dtype(g.dtype).name,
                           bm, bk, bn, interpret,
                           "none")(jnp.asarray(gid, jnp.int32),
                                   gp, w0p, dh, ap)
    return out[:, :K]


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bk", "bn",
                                             "interpret"))
def lora_grouped_dx_q(g, q, s, a, b, gid, scale: float = 2.0, *,
                      bm: int = 128, bk: int = 128, bn: int = 128,
                      interpret: bool = False):
    """Quantized-base grouped dx. q:int8[Ew,K,N] s:f32[Ew,1,N]."""
    Mp, N = g.shape
    Ew, K, _ = q.shape
    E, _, r = a.shape
    bk, bn = block_for(K, bk), block_for(N, bn)
    dh = _grouped_dh(g, b, gid, scale, bm)
    gp = pad_dim(g, bn, 1)
    qp = pad_dim(pad_dim(q, bk, 1), bn, 2)
    sp = pad_dim(s.astype(jnp.float32), bn, 2)
    ap = pad_dim(a, bk, 1)
    Np, Kp = gp.shape[1], qp.shape[1]
    out = _grouped_dx_call(Mp, Kp, Np, Ew, E, r, jnp.dtype(g.dtype).name,
                           bm, bk, bn, interpret,
                           "int8")(jnp.asarray(gid, jnp.int32),
                                   gp, qp, sp, dh, ap)
    return out[:, :K]


@functools.partial(jax.jit, static_argnames=("scale", "method", "bm", "bk",
                                             "bn", "interpret"))
def lora_grouped_dx_q4(g, q4, s, a, b, gid, scale: float = 2.0, *,
                       method: str = "int4", bm: int = 128, bk: int = 128,
                       bn: int = 128, interpret: bool = False):
    """Packed-4-bit-base grouped dx. q4:uint8[Ew,ceil(K/2),N] s:f32[Ew,1,N].
    K is taken from a ([E,K,r]); dx rows past K are sliced off."""
    Mp, N = g.shape
    Ew = q4.shape[0]
    E, K, r = a.shape
    bk, bn = block_for(K, bk), block_for(N, bn)
    dh = _grouped_dh(g, b, gid, scale, bm)
    gp = pad_dim(g, bn, 1)
    qp = pad_dim(pad_dim(q4, bk // 2, 1), bn, 2)    # untransposed bytes
    sp = pad_dim(s.astype(jnp.float32), bn, 2)
    ap = pad_dim(a, bk, 1)
    Np, Kp = gp.shape[1], 2 * qp.shape[1]
    out = _grouped_dx_call(Mp, Kp, Np, Ew, E, r, jnp.dtype(g.dtype).name,
                           bm, bk, bn, interpret,
                           method)(jnp.asarray(gid, jnp.int32),
                                   gp, qp, sp, dh, ap)
    return out[:, :K]


# ---------------------------------------------------------------------------
# fused per-group dA/dB
# ---------------------------------------------------------------------------


def _grouped_dab_kernel(gid_ref, x_ref, g_ref, a_ref, b_ref, da_ref, db_ref,
                        *, scale: float, pid=None):
    t = pl.program_id(0) if pid is None else pid[0]
    # first tile of a contiguous group run -> this (da, db) block is fresh
    first = (t == 0) | (gid_ref[t] != gid_ref[jnp.maximum(t - 1, 0)])

    @pl.when(first)
    def _init():
        da_ref[...] = jnp.zeros_like(da_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    x = x_ref[...]
    sg = (scale * g_ref[...].astype(jnp.float32)).astype(g_ref.dtype)
    # h recomputed for this tile only (paper §4.1) — never in HBM
    h = jax.lax.dot(x, a_ref[0],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    dh = jax.lax.dot_general(sg, b_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32
                             ).astype(x.dtype)
    da_ref[...] += jax.lax.dot_general(
        x, dh, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[None]
    db_ref[...] += jax.lax.dot_general(
        h, sg, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[None]


@functools.lru_cache(maxsize=None)
def _grouped_dab_call(Mp: int, Kp: int, Np: int, E: int, r: int,
                      scale: float, bm: int, interpret: bool):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, Kp), lambda t, gid: (t, 0)),            # x
            pl.BlockSpec((bm, Np), lambda t, gid: (t, 0)),            # g
            pl.BlockSpec((1, Kp, r), lambda t, gid: (gid[t], 0, 0)),  # a
            pl.BlockSpec((1, r, Np), lambda t, gid: (gid[t], 0, 0)),  # b
        ],
        out_specs=[
            pl.BlockSpec((1, Kp, r), lambda t, gid: (gid[t], 0, 0)),
            pl.BlockSpec((1, r, Np), lambda t, gid: (gid[t], 0, 0)),
        ],
        scratch_shapes=[],
    )
    return pl.pallas_call(
        functools.partial(_grouped_dab_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((E, Kp, r), jnp.float32),
            jax.ShapeDtypeStruct((E, r, Np), jnp.float32),
        ],
        name="lora_grouped_dab",
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "bm", "interpret"))
def lora_grouped_dab(x, g, a, b, gid, scale: float = 2.0, *, bm: int = 128,
                     interpret: bool = False):
    """(dA, dB) per group, one pass over x/g. x:[Mp,K] g:[Mp,N] a:[E,K,r]
    b:[E,r,N] -> (dA:[E,K,r], dB:[E,r,N]).

    REQUIRES each group's tiles contiguous in ``gid`` (the
    ``grouped_schedule`` contract): a group's output block stays resident in
    VMEM across its run and is flushed when the next group's first tile
    remaps the BlockSpec. Groups owning no tiles are zeroed by the live mask
    (their output blocks were never written — contents undefined).
    """
    Mp, K = x.shape
    N = g.shape[1]
    E, _, r = a.shape
    xp = pad_dim(x, 128, 1)
    gp = pad_dim(g, 128, 1)
    ap = pad_dim(a, 128, 1)
    bp = pad_dim(b, 128, 2)
    Kp, Np = xp.shape[1], gp.shape[1]
    gid = jnp.asarray(gid, jnp.int32)
    da, db = _grouped_dab_call(Mp, Kp, Np, E, r, float(scale), bm,
                               interpret)(gid, xp, gp, ap, bp)
    live = jnp.zeros((E,), bool).at[gid].set(True)
    da = jnp.where(live[:, None, None], da[:, :K], 0.0)
    db = jnp.where(live[:, None, None], db[:, :, :N], 0.0)
    return da.astype(a.dtype), db.astype(b.dtype)


# ---------------------------------------------------------------------------
# experts routed inside the step: the tile schedule lives on the device
# ---------------------------------------------------------------------------
#
# A MoE layer decides its routing inside the jitted step, so its row
# layout cannot be a host-side schedule. The ``expert_*`` kernels take two
# scalar-prefetched arrays: ``gid[t]``, the expert of row tile t, and
# ``live[0]``, the number of tiles that hold routed rows (the buffer is
# sized for the most rows routing can send, ``tiles ≥ live``). Tiles past
# ``live`` skip the matmuls: their index maps repeat the last live step's
# blocks, so no operand is fetched again, and the fwd/dx kernels write
# zeros there (dab leaves its per-expert blocks alone).


def _live_tile(on, t, live):
    return jnp.where(on, t, jnp.maximum(live[0] - 1, 0))


def _live_only(kern, n_in: int, zero_out: bool, n_grid: int):
    """``kern`` (prefetch ref gid first) run on live tiles only; the grid
    position is read here and handed in (``pid``)."""
    def body(gid_ref, live_ref, *refs, **kw):
        pid = tuple(pl.program_id(i) for i in range(n_grid))
        live = pid[0] < live_ref[0]

        @pl.when(live)
        def _run():
            kern(gid_ref, *refs, pid=pid, **kw)

        if zero_out:
            @pl.when(jnp.logical_not(live))
            def _zero():
                refs[n_in][...] = jnp.zeros_like(refs[n_in])
    return body


@functools.lru_cache(maxsize=None)
def _expert_fwd_call(Mp: int, Kp: int, Np: int, E: int, r: int,
                     dtype_name: str, scale: float, bm: int, bn: int,
                     bk: int, interpret: bool, quant: str):
    n_k, n_n = Kp // bk, Np // bn
    packed = quant in ("int4", "nf4")
    wblk = (1, bk // 2, bn) if packed else (1, bk, bn)

    def step(t, j, k, live):
        """(tile, n block, k block) a step reads: past the routed rows,
        those of the last live step."""
        on = t < live[0]
        return (_live_tile(on, t, live), jnp.where(on, j, n_n - 1),
                jnp.where(on, k, n_k - 1))

    def xmap(t, j, k, gid, live):
        tt, _, kk = step(t, j, k, live)
        return tt, kk

    def wmap(t, j, k, gid, live):
        tt, jj, kk = step(t, j, k, live)
        return gid[tt], kk, jj

    def smap(t, j, k, gid, live):
        tt, jj, _ = step(t, j, k, live)
        return gid[tt], 0, jj

    def amap(t, j, k, gid, live):
        tt, _, kk = step(t, j, k, live)
        return gid[tt], kk, 0

    in_specs = [pl.BlockSpec((bm, bk), xmap), pl.BlockSpec(wblk, wmap)]
    if quant != "none":
        in_specs.append(pl.BlockSpec((1, 1, bn), smap))
    in_specs += [pl.BlockSpec((1, bk, r), amap),
                 pl.BlockSpec((1, r, bn), smap)]
    if packed:
        kern = functools.partial(_grouped_fwd_q4_kernel, method=quant)
    elif quant == "int8":
        kern = _grouped_fwd_q_kernel
    else:
        kern = _grouped_fwd_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Mp // bm, n_n, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda t, j, k, gid, live: (t, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, r), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_live_only(kern, len(in_specs), True, 3),
                          scale=scale, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.dtype(dtype_name)),
        name="expert_fwd",
        interpret=interpret,
    )


def _pad_base(w, s, quant: str, bk: int, bn: int):
    """The frozen stack padded to the (K, N) block grid: dense or int8
    [E, K, N], packed 4-bit [E, ceil(K/2), N]; scale rows [E, 1, N]."""
    wp = pad_dim(pad_dim(w, bk // 2 if quant in ("int4", "nf4") else bk, 1),
                 bn, 2)
    if s is None:
        return [wp]
    return [wp, pad_dim(s.astype(jnp.float32), bn, 2)]


@functools.partial(jax.jit, static_argnames=("scale", "quant", "bm", "bn",
                                             "bk", "interpret"))
def expert_fwd(x, w, s, a, b, gid, live, scale: float = 2.0, *,
               quant: str = "none", bm: int = 256, bn: int = 512,
               bk: int = 2048, interpret: bool = False):
    """x:[Mp,K] (rows packed to bm-tiles of one expert each), w:[E,K,N]
    (or int8 / packed 4-bit with scale rows ``s``), a:[E,K,r], b:[E,r,N],
    gid:int32[Mp//bm], live:int32[1] -> [Mp,N], zero past the live tiles."""
    Mp, K = x.shape
    E, _, r = a.shape
    N = b.shape[2]
    bn, bk = block_for(N, bn), block_for(K, bk)
    xp = pad_dim(x, bk, 1)
    ap = pad_dim(a, bk, 1)
    bp = pad_dim(b, bn, 2)
    base = _pad_base(w, s, quant, bk, bn)
    Kp, Np = xp.shape[1], bp.shape[2]
    out = _expert_fwd_call(Mp, Kp, Np, E, r, jnp.dtype(x.dtype).name,
                           float(scale), bm, bn, bk, interpret, quant)(
        gid, live, xp, *base, ap, bp)
    return out[:, :N]


@functools.lru_cache(maxsize=None)
def _expert_dx_call(Mp: int, Kp: int, Np: int, E: int, r: int,
                    dtype_name: str, bm: int, bk: int, bn: int,
                    interpret: bool, quant: str):
    n_j, n_n = Kp // bk, Np // bn
    packed = quant in ("int4", "nf4")
    wblk = (1, bk // 2, bn) if packed else (1, bk, bn)

    def step(t, j, n, live):
        on = t < live[0]
        return (_live_tile(on, t, live), jnp.where(on, j, n_j - 1),
                jnp.where(on, n, n_n - 1))

    def gmap(t, j, n, gid, live):
        tt, _, nn = step(t, j, n, live)
        return tt, nn

    def wmap(t, j, n, gid, live):
        tt, jj, nn = step(t, j, n, live)
        return gid[tt], jj, nn

    def smap(t, j, n, gid, live):
        tt, _, nn = step(t, j, n, live)
        return gid[tt], 0, nn

    def dhmap(t, j, n, gid, live):
        tt, _, _ = step(t, j, n, live)
        return tt, 0

    def amap(t, j, n, gid, live):
        tt, jj, _ = step(t, j, n, live)
        return gid[tt], jj, 0

    in_specs = [pl.BlockSpec((bm, bn), gmap), pl.BlockSpec(wblk, wmap)]
    if quant != "none":
        in_specs.append(pl.BlockSpec((1, 1, bn), smap))
    in_specs += [pl.BlockSpec((bm, r), dhmap),
                 pl.BlockSpec((1, bk, r), amap)]
    if packed:
        kern = functools.partial(_grouped_dx_q4_kernel, method=quant)
    elif quant == "int8":
        kern = _grouped_dx_q_kernel
    else:
        kern = _grouped_dx_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Mp // bm, n_j, n_n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bk), lambda t, j, n, gid, live: (t, j)),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_live_only(kern, len(in_specs), True, 3), n_n=n_n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Kp), jnp.dtype(dtype_name)),
        name="expert_dx",
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "quant", "bm", "bk",
                                             "bn", "interpret"))
def expert_dx(g, w, s, a, b, gid, live, scale: float = 2.0, *,
              quant: str = "none", bm: int = 256, bk: int = 512,
              bn: int = 2048, interpret: bool = False):
    """dx = (s·g)@B[g]ᵀ@A[g]ᵀ + g@W0[g]ᵀ on live tiles. g:[Mp,N] ->
    dx:[Mp,K], zero past the live tiles."""
    Mp, N = g.shape
    E, K, r = a.shape
    bk, bn = block_for(K, bk), block_for(N, bn)
    dh = _grouped_dh(g, b, gid, scale, bm)
    gp = pad_dim(g, bn, 1)
    ap = pad_dim(a, bk, 1)
    base = _pad_base(w, s, quant, bk, bn)
    Np, Kp = gp.shape[1], ap.shape[1]
    out = _expert_dx_call(Mp, Kp, Np, E, r, jnp.dtype(g.dtype).name, bm,
                          bk, bn, interpret, quant)(gid, live, gp, *base,
                                                    dh, ap)
    return out[:, :K]


@functools.lru_cache(maxsize=None)
def _expert_dab_call(Mp: int, Kp: int, Np: int, E: int, r: int,
                     scale: float, bm: int, interpret: bool):
    def rows(t, gid, live):
        return _live_tile(t < live[0], t, live), 0

    def group(t, gid, live):
        return gid[t], 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Mp // bm,),
        in_specs=[pl.BlockSpec((bm, Kp), rows), pl.BlockSpec((bm, Np), rows),
                  pl.BlockSpec((1, Kp, r), group),
                  pl.BlockSpec((1, r, Np), group)],
        out_specs=[pl.BlockSpec((1, Kp, r), group),
                   pl.BlockSpec((1, r, Np), group)],
        scratch_shapes=[],
    )
    return pl.pallas_call(
        functools.partial(_live_only(_grouped_dab_kernel, 4, False, 1),
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((E, Kp, r), jnp.float32),
                   jax.ShapeDtypeStruct((E, r, Np), jnp.float32)],
        name="expert_dab",
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "bm", "interpret"))
def expert_dab(x, g, a, b, gid, live, scale: float = 2.0, *, bm: int = 256,
               interpret: bool = False):
    """(dA, dB) per expert over the live tiles, one pass over x/g; experts
    with no live tile get zeros. Tiles of one expert are contiguous."""
    Mp, K = x.shape
    N = g.shape[1]
    E, _, r = a.shape
    xp = pad_dim(x, 128, 1)
    gp = pad_dim(g, 128, 1)
    ap = pad_dim(a, 128, 1)
    bp = pad_dim(b, 128, 2)
    Kp, Np = xp.shape[1], gp.shape[1]
    da, db = _expert_dab_call(Mp, Kp, Np, E, r, float(scale), bm,
                              interpret)(gid, live, xp, gp, ap, bp)
    on = jnp.arange(gid.shape[0]) < live[0]
    held = jnp.zeros((E,), bool).at[gid].max(on)
    da = jnp.where(held[:, None, None], da[:, :K], 0.0)
    db = jnp.where(held[:, None, None], db[:, :, :N], 0.0)
    return da.astype(a.dtype), db.astype(b.dtype)
