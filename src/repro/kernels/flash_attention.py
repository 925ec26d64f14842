"""FlashAttention Pallas TPU kernels (paper §2's recompute principle) on
**sparse tile grids** with optional **in-kernel RoPE**.

Forward: online-softmax over KV blocks with the running (m, l, acc) state in
VMEM scratch; the [Nq, Nk] probability matrix never exists in HBM. The
per-row logsumexp is emitted alongside the output so the backward pass can
recompute probabilities tile-wise (``p = exp(s − lse)``) instead of saving
them — the same residual contract as the jnp oracle in ``core/flash.py``.

Sparse grids: causal / sliding-window / padded-length masking is known at
trace time, so instead of sweeping the dense ``n_q × n_k`` tile grid and
masking dead tiles, every kernel iterates a *flat* grid over exactly the
live tiles. The flat-step → (q_block, k_block) mapping is an int32 schedule
(``tiling.flash_schedule``) handed to the kernel via scalar prefetch; the
BlockSpec index maps read it to pick each step's HBM tiles. Tiles whose
every (q, k) pair is valid are flagged *interior* and skip mask
construction entirely; only boundary tiles (diagonal, window edge, padded
edge) build the positional mask. ``sparse=False`` runs the same kernels on
the dense schedule — the reference grid for tests and benchmarks.

Backward: two kernels factored by which operand stays resident —

* ``_bwd_dq_kernel``  — flat grid over the row-major schedule; dq
  accumulates in VMEM scratch across each q row's live k blocks.
* ``_bwd_dkv_kernel`` — flat grid over the *transposed* (k-outer) schedule
  (``tiling.flash_schedule_kv``); a K/V block stays resident while all G
  group members' live q/g rows stream past it, so GQA head-group reduction
  happens in VMEM (no H/Hkv-times K/V copy in HBM).

GQA is expressed through the schedule + BlockSpec index maps: q rows are
laid out [B·H, Nq, D], k/v stay [B·Hkv, Nk, D], and the k/v index map
divides the head program id by the group size — K/V are never repeated.

V may be narrower or wider than q·k (``Dv``; latent attention has q·k 192
against v 128): v, the output, its gradient and dv are ``Dv`` wide, q, k
and their gradients ``D``, and the softmax scale is 1/√D. With ``Dv == D``
every block shape is what it was.

Fused RoPE: with ``rope=(cos, sin)`` ([N, D/2] f32 tables), q/k tiles are
rotated in VMEM right after load — the rotated q/k never round-trip through
HBM — and the backward counter-rotates dq/dk (rotation is orthogonal:
dx = R₋θ(dy)) before the final write. Rows that attend to no key (fully
masked, e.g. causal+window with Nq > Nk+window) produce exactly 0 output
and a −∞ logsumexp in both sparse and dense modes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (LANE, block_for, flash_schedule,
                                  flash_schedule_kv, pad_dim)

NEG_INF = -1e30


def _mask(q_pos, k_pos, *, causal: bool, window: int, nq: int, nk: int):
    """Validity of (q, k) pairs incl. the padded-length guards."""
    ok = (q_pos < nq) & (k_pos < nk)
    if causal:
        ok &= q_pos >= k_pos
    if window > 0:
        ok &= q_pos - k_pos < window
    return ok


def _rot(x, cos, sin):
    """Rotate the half-split last dim: RoPE's R_θ (f32 compute).
    ``_rot(g, cos, -sin)`` is the inverse/transpose R₋θ (backward)."""
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _lanes(r):
    """[BH, N] per-row f32 -> [BH, N, LANE] lane-replicated: the kernels'
    layout for lse/delta. A (bq, LANE) block satisfies the TPU's (8, 128)
    tiling, and slicing lane 0 inside the kernel gives the (bq, 1) column a
    score tile broadcasts against, with no sublane<->lane transpose. Only
    the compact [BH, N] form is kept as a residual between fwd and bwd."""
    return jnp.broadcast_to(r[..., None], r.shape + (LANE,))


def _pad_table(t, mult: int, value: float):
    """Pad a [N, half] rope table along rows with the identity rotation
    (cos=1, sin=0) so padded q/k rows stay bit-identical to the unroped
    zero padding."""
    n = t.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return t
    return jnp.pad(t, ((0, pad), (0, 0)), constant_values=value)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(qi_ref, kj_ref, int_ref, q_ref, k_ref, v_ref, *rest,
                causal: bool, window: int, bq: int, bk: int, nq_valid: int,
                nk_valid: int, scale: float, fuse_rope: bool):
    if fuse_rope:
        (cq_ref, sq_ref, ck_ref, sk_ref,
         o_ref, lse_ref, m_ref, l_ref, acc_ref) = rest
    else:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = rest

    t = pl.program_id(1)
    T = pl.num_programs(1)
    row, col = qi_ref[t], kj_ref[t]
    first = jnp.logical_or(t == 0, row != qi_ref[jnp.maximum(t - 1, 0)])
    last = jnp.logical_or(t == T - 1,
                          row != qi_ref[jnp.minimum(t + 1, T - 1)])

    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qb, kb = q_ref[0], k_ref[0]
    if fuse_rope:
        qb = _rot(qb, cq_ref[...], sq_ref[...])
        kb = _rot(kb, ck_ref[...], sk_ref[...])
    s = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    def _accum(s):
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    interior = int_ref[t] == 1

    @pl.when(interior)
    def _interior():        # fully valid tile: no mask is ever built
        _accum(s)

    @pl.when(jnp.logical_not(interior))
    def _boundary():        # diagonal / window-edge / padded-edge tile
        q_pos = row * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = col * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = _mask(q_pos, k_pos, causal=causal, window=window,
                   nq=nq_valid, nk=nk_valid)
        _accum(jnp.where(ok, s, NEG_INF))

    @pl.when(last)
    def _finish():
        # rows that never saw an unmasked key keep m == NEG_INF: emit exact
        # zeros + a -inf-like lse (the bwd's masked p is 0 regardless)
        never = m_ref[...] <= NEG_INF * 0.5
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = jnp.where(never, 0.0,
                             acc_ref[...] / l).astype(o_ref.dtype)
        lse = jnp.where(never, NEG_INF, m_ref[...] + jnp.log(l))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


@functools.lru_cache(maxsize=None)
def _fwd_call(BH: int, Nqp: int, Nkp: int, D: int, Dv: int, dtype_name: str,
              bq: int, bk: int, causal: bool, window: int, nq: int, nk: int,
              G: int, fuse_rope: bool, sparse: bool, interpret: bool):
    """Construct (pallas_call, schedule) once per static signature — repeated
    non-jit calls (benchmarks, tests) reuse the built closure."""
    qi, kj, it = flash_schedule(Nqp // bq, Nkp // bk, bq, bk, causal,
                                window, nq, nk, sparse)
    dtype = jnp.dtype(dtype_name)
    half = D // 2
    kern = functools.partial(
        _fwd_kernel, causal=causal, window=window, bq=bq, bk=bk,
        nq_valid=nq, nk_valid=nk, scale=float(1.0 / (D ** 0.5)),
        fuse_rope=fuse_rope)
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, t, qi, kj, it: (b, qi[t], 0)),
        pl.BlockSpec((1, bk, D),
                     lambda b, t, qi, kj, it: (b // G, kj[t], 0)),
        pl.BlockSpec((1, bk, Dv),
                     lambda b, t, qi, kj, it: (b // G, kj[t], 0)),
    ]
    if fuse_rope:
        in_specs += [
            pl.BlockSpec((bq, half), lambda b, t, qi, kj, it: (qi[t], 0)),
            pl.BlockSpec((bq, half), lambda b, t, qi, kj, it: (qi[t], 0)),
            pl.BlockSpec((bk, half), lambda b, t, qi, kj, it: (kj[t], 0)),
            pl.BlockSpec((bk, half), lambda b, t, qi, kj, it: (kj[t], 0)),
        ]
    call = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(BH, len(qi)),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, bq, Dv),
                             lambda b, t, qi, kj, it: (b, qi[t], 0)),
                pl.BlockSpec((1, bq, LANE),
                             lambda b, t, qi, kj, it: (b, qi[t], 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),   # running max
                pltpu.VMEM((bq, 1), jnp.float32),   # running sum
                pltpu.VMEM((bq, Dv), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BH, Nqp, Dv), dtype),
            jax.ShapeDtypeStruct((BH, Nqp, LANE), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )
    return call, (qi, kj, it)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "q_per_kv", "interpret",
                                             "return_lse", "sparse"))
def flash_attention_fwd(q, k, v, rope=None, *, causal: bool = True,
                        window: int = 0, bq: int = 512, bk: int = 512,
                        q_per_kv: int = 1, interpret: bool = False,
                        return_lse: bool = False, sparse: bool = True):
    """q: [B·H, Nq, D]; k: [B·Hkv, Nk, D]; v: [B·Hkv, Nk, Dv] with
    H = Hkv·q_per_kv; the output is [B·H, Nq, Dv].

    Heads are pre-flattened; consecutive groups of ``q_per_kv`` q heads share
    one kv head (the BlockSpec index map does the division — K/V are never
    repeated). Any Nq/Nk (padded + masked). ``rope=(cos, sin)`` ([N, D/2]
    f32, Nq == Nk) rotates q/k tiles in VMEM. Returns out or (out, lse).
    """
    BH, Nq, D = q.shape
    Nk = k.shape[1]
    assert BH == k.shape[0] * q_per_kv, (BH, k.shape[0], q_per_kv)
    bq, bk = block_for(Nq, bq), block_for(Nk, bk)
    qp = pad_dim(q, bq, 1)
    kp = pad_dim(k, bk, 1)
    vp = pad_dim(v, bk, 1)
    Nqp, Nkp = qp.shape[1], kp.shape[1]
    call, sched = _fwd_call(BH, Nqp, Nkp, D, v.shape[-1],
                            jnp.dtype(q.dtype).name, bq, bk, causal, window,
                            Nq, Nk, q_per_kv, rope is not None, sparse,
                            interpret)
    operands = [qp, kp, vp]
    if rope is not None:
        cos, sin = rope
        assert Nq == Nk and cos.shape == (Nq, D // 2), (cos.shape, Nq, D)
        # the table is read through both (bq, ·) and (bk, ·) blocks — pad to
        # the coarser grid so every block index stays in bounds
        tb = max(bq, bk)
        cosp = _pad_table(cos.astype(jnp.float32), tb, 1.0)
        sinp = _pad_table(sin.astype(jnp.float32), tb, 0.0)
        operands += [cosp, sinp, cosp, sinp]
    out, lse = call(*sched, *operands)
    out = out[:, :Nq]
    if return_lse:
        return out, lse[:, :Nq, 0]
    return out


# ---------------------------------------------------------------------------
# backward — probabilities recomputed from the saved logsumexp
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(qi_ref, kj_ref, int_ref, q_ref, k_ref, v_ref, g_ref,
                   lse_ref, delta_ref, *rest, causal: bool, window: int,
                   bq: int, bk: int, nq_valid: int, nk_valid: int,
                   scale: float, fuse_rope: bool):
    if fuse_rope:
        cq_ref, sq_ref, ck_ref, sk_ref, dq_ref, acc_ref = rest
    else:
        dq_ref, acc_ref = rest

    t = pl.program_id(1)
    T = pl.num_programs(1)
    row, col = qi_ref[t], kj_ref[t]
    first = jnp.logical_or(t == 0, row != qi_ref[jnp.maximum(t - 1, 0)])
    last = jnp.logical_or(t == T - 1,
                          row != qi_ref[jnp.minimum(t + 1, T - 1)])

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qb, kb, vb, gb = q_ref[0], k_ref[0], v_ref[0], g_ref[0]
    if fuse_rope:
        qb = _rot(qb, cq_ref[...], sq_ref[...])
        kb = _rot(kb, ck_ref[...], sk_ref[...])
    s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    def _accum(p):
        dp = jax.lax.dot_general(gb, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # eq 18
        ds = p * (dp - delta_ref[0][:, :1]) * scale                   # eq 19
        acc_ref[...] += jax.lax.dot(ds.astype(qb.dtype), kb,
                                    preferred_element_type=jnp.float32)

    interior = int_ref[t] == 1

    @pl.when(interior)
    def _interior():
        _accum(jnp.exp(s - lse_ref[0][:, :1]))

    @pl.when(jnp.logical_not(interior))
    def _boundary():
        q_pos = row * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = col * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = _mask(q_pos, k_pos, causal=causal, window=window,
                   nq=nq_valid, nk=nk_valid)
        # p via saved lse; explicit zero on masked/padded entries (a fully-
        # masked row has lse = NEG_INF, where exp(s − lse) would blow up)
        _accum(jnp.where(ok, jnp.exp(s - lse_ref[0][:, :1]), 0.0))

    @pl.when(last)
    def _finish():
        acc = acc_ref[...]
        if fuse_rope:   # d q = R₋θ(d q_rot)  — rotation is orthogonal
            acc = _rot(acc, cq_ref[...], -sq_ref[...])
        dq_ref[0] = acc.astype(dq_ref.dtype)


def _bwd_dkv_kernel(kjs_ref, gh_ref, qis_ref, int_ref, q_ref, g_ref, lse_ref,
                    delta_ref, k_ref, v_ref, *rest, causal: bool,
                    window: int, bq: int, bk: int, nq_valid: int,
                    nk_valid: int, scale: float, fuse_rope: bool):
    if fuse_rope:
        cq_ref, sq_ref, ck_ref, sk_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest

    t = pl.program_id(1)
    T = pl.num_programs(1)
    col, row = kjs_ref[t], qis_ref[t]
    first = jnp.logical_or(t == 0, col != kjs_ref[jnp.maximum(t - 1, 0)])
    last = jnp.logical_or(t == T - 1,
                          col != kjs_ref[jnp.minimum(t + 1, T - 1)])

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    qb, kb, vb, gb = q_ref[0], k_ref[0], v_ref[0], g_ref[0]
    if fuse_rope:
        qb = _rot(qb, cq_ref[...], sq_ref[...])
        kb = _rot(kb, ck_ref[...], sk_ref[...])
    s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    def _accum(p):
        pb = p.astype(qb.dtype)
        # dv += pᵀ g  (eq 17, summed over the q heads of this kv group)
        dv_acc[...] += jax.lax.dot_general(pb, gb, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(gb, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # eq 18
        ds = (p * (dp - delta_ref[0][:, :1]) * scale).astype(qb.dtype)
        # dk += dsᵀ q  (eq 21)
        dk_acc[...] += jax.lax.dot_general(ds, qb, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    interior = int_ref[t] == 1

    @pl.when(interior)
    def _interior():
        _accum(jnp.exp(s - lse_ref[0][:, :1]))

    @pl.when(jnp.logical_not(interior))
    def _boundary():
        q_pos = row * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = col * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = _mask(q_pos, k_pos, causal=causal, window=window,
                   nq=nq_valid, nk=nk_valid)
        _accum(jnp.where(ok, jnp.exp(s - lse_ref[0][:, :1]), 0.0))

    @pl.when(last)
    def _finish():
        dk = dk_acc[...]
        if fuse_rope:   # d k = R₋θ(d k_rot)
            dk = _rot(dk, ck_ref[...], -sk_ref[...])
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=None)
def _bwd_dq_call(BH: int, Nqp: int, Nkp: int, D: int, Dv: int,
                 dtype_name: str, bq: int, bk: int, causal: bool,
                 window: int, nq: int, nk: int, G: int, fuse_rope: bool,
                 sparse: bool, interpret: bool):
    qi, kj, it = flash_schedule(Nqp // bq, Nkp // bk, bq, bk, causal,
                                window, nq, nk, sparse)
    dtype = jnp.dtype(dtype_name)
    half = D // 2
    kern = functools.partial(
        _bwd_dq_kernel, causal=causal, window=window, bq=bq, bk=bk,
        nq_valid=nq, nk_valid=nk, scale=float(1.0 / (D ** 0.5)),
        fuse_rope=fuse_rope)
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, t, qi, kj, it: (b, qi[t], 0)),
        pl.BlockSpec((1, bk, D),
                     lambda b, t, qi, kj, it: (b // G, kj[t], 0)),   # k
        pl.BlockSpec((1, bk, Dv),
                     lambda b, t, qi, kj, it: (b // G, kj[t], 0)),   # v
        pl.BlockSpec((1, bq, Dv), lambda b, t, qi, kj, it: (b, qi[t], 0)),  # g
        pl.BlockSpec((1, bq, LANE),
                     lambda b, t, qi, kj, it: (b, qi[t], 0)),        # lse
        pl.BlockSpec((1, bq, LANE),
                     lambda b, t, qi, kj, it: (b, qi[t], 0)),        # delta
    ]
    if fuse_rope:
        in_specs += [
            pl.BlockSpec((bq, half), lambda b, t, qi, kj, it: (qi[t], 0)),
            pl.BlockSpec((bq, half), lambda b, t, qi, kj, it: (qi[t], 0)),
            pl.BlockSpec((bk, half), lambda b, t, qi, kj, it: (kj[t], 0)),
            pl.BlockSpec((bk, half), lambda b, t, qi, kj, it: (kj[t], 0)),
        ]
    call = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(BH, len(qi)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, D),
                                   lambda b, t, qi, kj, it: (b, qi[t], 0)),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, Nqp, D), dtype),
        name="flash_dq",
        interpret=interpret,
    )
    return call, (qi, kj, it)


@functools.lru_cache(maxsize=None)
def _bwd_dkv_call(BHkv: int, Nqp: int, Nkp: int, D: int, Dv: int,
                  dtype_q: str, dtype_k: str, dtype_v: str, bq: int, bk: int,
                  causal: bool, window: int, nq: int, nk: int, G: int,
                  fuse_rope: bool, sparse: bool, interpret: bool):
    kjs, gh, qis, it = flash_schedule_kv(Nqp // bq, Nkp // bk, bq, bk,
                                         causal, window, nq, nk, G, sparse)
    half = D // 2
    kern = functools.partial(
        _bwd_dkv_kernel, causal=causal, window=window, bq=bq, bk=bk,
        nq_valid=nq, nk_valid=nk, scale=float(1.0 / (D ** 0.5)),
        fuse_rope=fuse_rope)
    qmap = lambda b, t, kjs, gh, qis, it: (b * G + gh[t], qis[t], 0)
    rmap = lambda b, t, kjs, gh, qis, it: (b * G + gh[t], qis[t], 0)
    kvmap = lambda b, t, kjs, gh, qis, it: (b, kjs[t], 0)
    in_specs = [
        pl.BlockSpec((1, bq, D), qmap),        # q
        pl.BlockSpec((1, bq, Dv), qmap),       # g
        pl.BlockSpec((1, bq, LANE), rmap),     # lse
        pl.BlockSpec((1, bq, LANE), rmap),     # delta
        pl.BlockSpec((1, bk, D), kvmap),       # k
        pl.BlockSpec((1, bk, Dv), kvmap),      # v
    ]
    if fuse_rope:
        in_specs += [
            pl.BlockSpec((bq, half),
                         lambda b, t, kjs, gh, qis, it: (qis[t], 0)),
            pl.BlockSpec((bq, half),
                         lambda b, t, kjs, gh, qis, it: (qis[t], 0)),
            pl.BlockSpec((bk, half),
                         lambda b, t, kjs, gh, qis, it: (kjs[t], 0)),
            pl.BlockSpec((bk, half),
                         lambda b, t, kjs, gh, qis, it: (kjs[t], 0)),
        ]
    call = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(BHkv, len(kjs)),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, bk, D), kvmap),
                pl.BlockSpec((1, bk, Dv), kvmap),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, Dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BHkv, Nkp, D), jnp.dtype(dtype_k)),
            jax.ShapeDtypeStruct((BHkv, Nkp, Dv), jnp.dtype(dtype_v)),
        ],
        name="flash_dkv",
        interpret=interpret,
    )
    return call, (kjs, gh, qis, it)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "q_per_kv", "interpret",
                                             "sparse"))
def flash_attention_bwd(q, k, v, out, lse, g, rope=None, *,
                        causal: bool = True, window: int = 0, bq: int = 512,
                        bk: int = 512, q_per_kv: int = 1,
                        interpret: bool = False, sparse: bool = True):
    """(dq, dk, dv) from the saved (out, lse) residuals.

    q: [B·H, Nq, D]; g/out: [B·H, Nq, Dv]; k: [B·Hkv, Nk, D]; v:
    [B·Hkv, Nk, Dv]; lse: [B·H, Nq] (f32).
    dk/dv come back group-summed at kv-head layout [B·Hkv, Nk, D]. With
    ``rope=(cos, sin)`` the kernels rotate q/k on load (as the forward did)
    and counter-rotate dq/dk before the final write.
    """
    BH, Nq, D = q.shape
    BHkv, Nk = k.shape[0], k.shape[1]
    assert BH == BHkv * q_per_kv
    bq, bk = block_for(Nq, bq), block_for(Nk, bk)
    G = q_per_kv

    # flash softmax correction term: delta_i = Σ_d g_i·out_i (A.2 eq 19's
    # sum(dprobs ⊙ probs) in tile-local form) — one cheap rowwise reduction
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), -1)

    qp = pad_dim(q, bq, 1)
    gp = pad_dim(g.astype(q.dtype), bq, 1)
    lsep = _lanes(pad_dim(lse, bq, 1))
    deltap = _lanes(pad_dim(delta, bq, 1))
    kp = pad_dim(k, bk, 1)
    vp = pad_dim(v, bk, 1)
    Nqp, Nkp = qp.shape[1], kp.shape[1]

    rope_ops = []
    if rope is not None:
        cos, sin = rope
        assert Nq == Nk and cos.shape == (Nq, D // 2), (cos.shape, Nq, D)
        tb = max(bq, bk)    # read through (bq, ·) and (bk, ·) blocks alike
        cosp = _pad_table(cos.astype(jnp.float32), tb, 1.0)
        sinp = _pad_table(sin.astype(jnp.float32), tb, 0.0)
        rope_ops = [cosp, sinp, cosp, sinp]

    Dv = v.shape[-1]
    dq_call, dq_sched = _bwd_dq_call(
        BH, Nqp, Nkp, D, Dv, jnp.dtype(q.dtype).name, bq, bk, causal, window,
        Nq, Nk, G, rope is not None, sparse, interpret)
    dq = dq_call(*dq_sched, qp, kp, vp, gp, lsep, deltap, *rope_ops)

    dkv_call, dkv_sched = _bwd_dkv_call(
        BHkv, Nqp, Nkp, D, Dv, jnp.dtype(q.dtype).name,
        jnp.dtype(k.dtype).name,
        jnp.dtype(v.dtype).name, bq, bk, causal, window, Nq, Nk, G,
        rope is not None, sparse, interpret)
    dk, dv = dkv_call(*dkv_sched, qp, gp, lsep, deltap, kp, vp, *rope_ops)

    return dq[:, :Nq], dk[:, :Nk], dv[:, :Nk]
