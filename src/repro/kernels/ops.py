"""Kernel dispatch layer: the single entry point for the ``pallas``
ExecutionPolicy backend.

``models/layers.py`` (and through it every model family, ``core/mesp.py``,
``launch/train.py`` and the benchmarks) routes trainable-path ops here when
``policy.backend == "pallas"`` is selected. Each public dispatcher:

* checks :func:`*_supported` for the given operands and falls back to the
  structured jnp path (``core/structured``) on unsupported shapes — per-op,
  so one unsupported op never drags the whole block off the kernel path
  (MoE per-expert [E,·,·] linears have their own grouped kernel family
  below and no longer fall back). Each fallback is counted in
  :data:`FALLBACKS` when the op is traced;
* picks block sizes from ``kernels/autotune.py`` (heuristic table, optionally
  overridden by a measured cache);
* runs the Pallas kernel with ``interpret=True`` automatically on non-TPU
  backends, so the same training code runs on CPU tests and TPU production.
  On a TPU the interpreter runs only when ``ExecutionPolicy.interpret``
  (``--pallas-interpret on``) asks for it explicitly.

The custom_vjps below compose the kernel forwards with kernel backwards that
follow the paper's structured rules: ``h``/probabilities are *recomputed* in
the backward (from ``x`` / the saved logsumexp), never stored.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import quant, structured
from repro.kernels import autotune
from repro.kernels import lora_fused as _lf
from repro.kernels import lora_grouped as _lg
from repro.kernels import lora_pack4 as _lp4
from repro.kernels import lora_quant as _lq
from repro.kernels import rmsnorm as _rn
from repro.kernels import flash_attention as _fa
from repro.kernels import rope as _rope
from repro.kernels import tiling
from repro.telemetry.metrics import CounterGroup

#: per-op jnp fallbacks of the dispatchers below ("kernels.fallback.*"),
#: counted at trace time: once per call site each time a program is traced,
#: not per execution. Module-level for the reason ``autotune.COUNTERS`` is;
#: an enabled Telemetry adopts the group.
FALLBACKS = CounterGroup("kernels.fallback",
                         ("lora_linear", "sdpa", "expert_linear"))

# Below this many query rows the dense structured sdpa beats the kernel's
# padding + grid overhead (and is easier to cross-check).
PALLAS_ATTN_MIN_SEQ = 64


def _flat(x):
    return x.reshape(-1, x.shape[-1])


def pallas_interpret() -> bool:
    """True when kernels must run under the Pallas interpreter (non-TPU)."""
    return jax.default_backend() != "tpu"


def _resolve_interpret(policy, interpret):
    """Dispatcher interpret resolution: explicit kwarg > policy override
    (``ExecutionPolicy.interpret``) > backend autodetect."""
    if interpret is not None:
        return interpret
    if policy is not None and policy.interpret is not None:
        return policy.interpret
    return pallas_interpret()


# ---------------------------------------------------------------------------
# LoRA linear: Pallas fwd (h in VMEM) + Pallas bwd (h recomputed; dx via the
# fused dx kernel; dA/dB via the fused one-pass dab kernel)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def lora_linear_kernel(x, w0, a, b, scale: float = 2.0,
                       interpret: bool = False):
    """y = x@W0 + s·(x@A)@B with [..., K] inputs. Any shapes (padded)."""
    lead = x.shape[:-1]
    x2 = _flat(x)
    blk = autotune.choose_blocks("lora_fused", x.dtype, M=x2.shape[0],
                                 K=x2.shape[1], N=w0.shape[1])
    y = _lf.lora_fused(x2, w0, a, b, scale, interpret=interpret, **blk)
    return y.reshape(*lead, w0.shape[1])


def _fwd(x, w0, a, b, scale, interpret):
    return lora_linear_kernel(x, w0, a, b, scale, interpret), (x, w0, a, b)


def _bwd(scale, interpret, res, g):
    x, w0, a, b = res
    lead = x.shape[:-1]
    g2 = _flat(g).astype(x.dtype)
    x2 = _flat(x)
    M, K = x2.shape
    N = w0.shape[1]
    dx = _lf.lora_dx(g2, w0, a, b, scale, interpret=interpret,
                     **autotune.choose_blocks("lora_dx", x.dtype,
                                              M=M, K=K, N=N))
    # one fused pass over x/g: h recomputed tile-wise in VMEM (paper §4.1)
    da, db = _lf.lora_dab(x2, g2, a, b, scale, interpret=interpret,
                          **autotune.choose_blocks("lora_dab", x.dtype,
                                                   M=M, K=K, N=N))
    return (dx.reshape(*lead, w0.shape[0]), jnp.zeros_like(w0), da, db)


lora_linear_kernel.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# Quantized-W0 LoRA linear: int8 q + per-output-channel scale dequantized in
# VMEM (kernels/lora_quant.py). Forward and dx never materialize a dense W0
# in HBM; dA/dB reuse the unquantized fused dab kernel (they don't read W0).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def lora_linear_kernel_q(x, q, s, a, b, scale: float = 2.0,
                         interpret: bool = False):
    """y = x@(q·s) + s_lora·(x@A)@B. q: int8 [K,N]; s: f32 [1,N]."""
    lead = x.shape[:-1]
    x2 = _flat(x)
    blk = autotune.choose_blocks("lora_fused_q", x.dtype, M=x2.shape[0],
                                 K=x2.shape[1], N=q.shape[1])
    y = _lq.lora_fused_q(x2, q, s, a, b, scale, interpret=interpret, **blk)
    return y.reshape(*lead, q.shape[1])


def _fwd_q(x, q, s, a, b, scale, interpret):
    return lora_linear_kernel_q(x, q, s, a, b, scale, interpret), (x, q, s,
                                                                   a, b)


def _bwd_q(scale, interpret, res, g):
    x, q, s, a, b = res
    lead = x.shape[:-1]
    g2 = _flat(g).astype(x.dtype)
    x2 = _flat(x)
    M, K = x2.shape
    N = q.shape[1]
    dx = _lq.lora_dx_q(g2, q, s, a, b, scale, interpret=interpret,
                       **autotune.choose_blocks("lora_dx_q", x.dtype,
                                                M=M, K=K, N=N))
    da, db = _lf.lora_dab(x2, g2, a, b, scale, interpret=interpret,
                          **autotune.choose_blocks("lora_dab", x.dtype,
                                                   M=M, K=K, N=N))
    # q is int8 (float0 cotangent); s is frozen alongside it
    return (dx.reshape(*lead, K), structured._zero_cot(q),
            jnp.zeros_like(s), da, db)


lora_linear_kernel_q.defvjp(_fwd_q, _bwd_q)


# ---------------------------------------------------------------------------
# Packed-4-bit-W0 LoRA linear: two nibbles per byte unpacked in VMEM
# (kernels/lora_pack4.py, int4 sign-extend / nf4 codebook). Forward and dx
# read only the packed bytes + scale row from HBM; dA/dB reuse the
# unquantized fused dab kernel (they don't read W0).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def lora_linear_kernel_p4(x, q4, s, a, b, scale: float = 2.0,
                          interpret: bool = False, method: str = "int4"):
    """y = x@dequant(q4)·s + s_lora·(x@A)@B. q4: uint8 [ceil(K/2),N]."""
    lead = x.shape[:-1]
    x2 = _flat(x)
    blk = autotune.choose_blocks("lora_fused_q4", x.dtype, M=x2.shape[0],
                                 K=x2.shape[1], N=q4.shape[1])
    y = _lp4.lora_fused_q4(x2, q4, s, a, b, scale, method=method,
                           interpret=interpret, **blk)
    return y.reshape(*lead, q4.shape[1])


def _fwd_p4(x, q4, s, a, b, scale, interpret, method):
    return (lora_linear_kernel_p4(x, q4, s, a, b, scale, interpret, method),
            (x, q4, s, a, b))


def _bwd_p4(scale, interpret, method, res, g):
    x, q4, s, a, b = res
    lead = x.shape[:-1]
    g2 = _flat(g).astype(x.dtype)
    x2 = _flat(x)
    M, K = x2.shape
    N = q4.shape[1]
    dx = _lp4.lora_dx_q4(g2, q4, s, a, b, scale, method=method,
                         interpret=interpret,
                         **autotune.choose_blocks("lora_dx_q4", x.dtype,
                                                  M=M, K=K, N=N))
    da, db = _lf.lora_dab(x2, g2, a, b, scale, interpret=interpret,
                          **autotune.choose_blocks("lora_dab", x.dtype,
                                                   M=M, K=K, N=N))
    # q4 is uint8 (float0 cotangent); s is frozen alongside it
    return (dx.reshape(*lead, K), structured._zero_cot(q4),
            jnp.zeros_like(s), da, db)


lora_linear_kernel_p4.defvjp(_fwd_p4, _bwd_p4)


def lora_supported(x, w0) -> bool:
    if quant.is_packed(w0):
        w0 = w0["q4"]
    elif quant.is_quantized(w0):
        w0 = w0["q"]
    return x.ndim >= 2 and w0.ndim == 2


def lora_linear(x, w0, a, b, bias=None, scale: float = 2.0, *,
                policy=None, interpret=None):
    """Dispatch: Pallas LoRA linear, structured fallback on unsupported
    shapes (MoE per-expert [E,·,·] weights route to
    :func:`lora_grouped_linear` instead). ``w0`` may be a dense matrix, an
    int8 ``{"q", "scale"}`` leaf or a packed 4-bit ``{"q4", "scale"}`` leaf —
    quantized weights route to the dequant-in-VMEM kernels, falling back to
    the structured jnp path on a dequantized copy
    (``core/quant.maybe_dequant``). ``policy`` (ExecutionPolicy) supplies
    kernel overrides (interpret)."""
    if not lora_supported(x, w0):
        FALLBACKS["lora_linear"] += 1
        return structured.lora_linear(x, quant.maybe_dequant(w0, x.dtype),
                                      a, b, bias, scale)
    interpret = _resolve_interpret(policy, interpret)
    if quant.is_packed(w0):
        y = lora_linear_kernel_p4(x, w0["q4"], w0["scale"], a, b, scale,
                                  interpret, quant.packed_method(w0))
    elif quant.is_quantized(w0):
        y = lora_linear_kernel_q(x, w0["q"], w0["scale"], a, b, scale,
                                 interpret)
    else:
        y = lora_linear_kernel(x, w0, a, b, scale, interpret)
    # bias is frozen (no grad needed): a plain add stores no residuals
    return y + bias if bias is not None else y


# ---------------------------------------------------------------------------
# Grouped LoRA linear: many (W0, A, B) stack entries, one kernel launch.
# Rows are packed so every bm-row tile belongs to one group and an int32
# gid[t] array (scalar-prefetched — values may be runtime-traced) routes each
# tile's stack entries into VMEM. Closes the last structured-jnp fallback in
# pallas mode (MoE per-expert [E,·,·] linears, bf16 AND int8) and powers the
# multi-tenant serving decode path (shared base, per-request adapters).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _grouped_core(x, w0, a, b, gid, scale: float, bm: int,
                  interpret: bool = False):
    """Packed-rows grouped LoRA linear. x:[Mp,K] (Mp % bm == 0, every bm-row
    tile one group), w0:[Ew,K,N] (Ew ∈ {1, E}), a:[E,K,r], b:[E,r,N],
    gid:int32[Mp//bm] -> [Mp,N]."""
    blk = autotune.choose_blocks("lora_grouped", x.dtype, M=x.shape[0],
                                 K=x.shape[1], N=w0.shape[2])
    return _lg.lora_grouped(x, w0, a, b, gid, scale, bm=bm,
                            interpret=interpret, **blk)


def _grouped_fwd(x, w0, a, b, gid, scale, bm, interpret):
    return _grouped_core(x, w0, a, b, gid, scale, bm, interpret), \
        (x, w0, a, b, gid)


def _grouped_bwd(scale, bm, interpret, res, g):
    x, w0, a, b, gid = res
    g = g.astype(x.dtype)
    M, K = x.shape
    N = w0.shape[2]
    dx = _lg.lora_grouped_dx(g, w0, a, b, gid, scale, bm=bm,
                             interpret=interpret,
                             **autotune.choose_blocks("lora_grouped_dx",
                                                      x.dtype, M=M, K=K, N=N))
    da, db = _lg.lora_grouped_dab(x, g, a, b, gid, scale, bm=bm,
                                  interpret=interpret)
    return dx, jnp.zeros_like(w0), da, db, structured._zero_cot(gid)


_grouped_core.defvjp(_grouped_fwd, _grouped_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _grouped_core_q(x, q, s, a, b, gid, scale: float, bm: int,
                    interpret: bool = False):
    """int8-base variant: q:int8[Ew,K,N], s:f32[Ew,1,N] — the per-group dense
    W0 exists only tile-wise in VMEM, never in HBM."""
    blk = autotune.choose_blocks("lora_grouped_q", x.dtype, M=x.shape[0],
                                 K=x.shape[1], N=q.shape[2])
    return _lg.lora_grouped_q(x, q, s, a, b, gid, scale, bm=bm,
                              interpret=interpret, **blk)


def _grouped_fwd_q(x, q, s, a, b, gid, scale, bm, interpret):
    return _grouped_core_q(x, q, s, a, b, gid, scale, bm, interpret), \
        (x, q, s, a, b, gid)


def _grouped_bwd_q(scale, bm, interpret, res, g):
    x, q, s, a, b, gid = res
    g = g.astype(x.dtype)
    M, K = x.shape
    N = q.shape[2]
    dx = _lg.lora_grouped_dx_q(g, q, s, a, b, gid, scale, bm=bm,
                               interpret=interpret,
                               **autotune.choose_blocks("lora_grouped_dx_q",
                                                        x.dtype, M=M, K=K,
                                                        N=N))
    da, db = _lg.lora_grouped_dab(x, g, a, b, gid, scale, bm=bm,
                                  interpret=interpret)
    return (dx, structured._zero_cot(q), jnp.zeros_like(s), da, db,
            structured._zero_cot(gid))


_grouped_core_q.defvjp(_grouped_fwd_q, _grouped_bwd_q)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _grouped_core_p4(x, q4, s, a, b, gid, scale: float, bm: int,
                     interpret: bool = False, method: str = "int4"):
    """Packed-4-bit-base variant: q4:uint8[Ew,ceil(K/2),N], s:f32[Ew,1,N] —
    only the packed bytes + scale leave HBM; the per-group dense W0 exists
    only tile-wise in VMEM."""
    blk = autotune.choose_blocks("lora_grouped_q4", x.dtype, M=x.shape[0],
                                 K=x.shape[1], N=q4.shape[2])
    return _lg.lora_grouped_q4(x, q4, s, a, b, gid, scale, method=method,
                               bm=bm, interpret=interpret, **blk)


def _grouped_fwd_p4(x, q4, s, a, b, gid, scale, bm, interpret, method):
    return (_grouped_core_p4(x, q4, s, a, b, gid, scale, bm, interpret,
                             method),
            (x, q4, s, a, b, gid))


def _grouped_bwd_p4(scale, bm, interpret, method, res, g):
    x, q4, s, a, b, gid = res
    g = g.astype(x.dtype)
    M, K = x.shape
    N = q4.shape[2]
    dx = _lg.lora_grouped_dx_q4(g, q4, s, a, b, gid, scale, method=method,
                                bm=bm, interpret=interpret,
                                **autotune.choose_blocks(
                                    "lora_grouped_dx_q4", x.dtype, M=M, K=K,
                                    N=N))
    da, db = _lg.lora_grouped_dab(x, g, a, b, gid, scale, bm=bm,
                                  interpret=interpret)
    return (dx, structured._zero_cot(q4), jnp.zeros_like(s), da, db,
            structured._zero_cot(gid))


_grouped_core_p4.defvjp(_grouped_fwd_p4, _grouped_bwd_p4)


def _grouped_bm(rows: int) -> int:
    """Row-tile granularity for a group layout: full 128-row tiles for big
    groups, one 8-row-aligned tile otherwise (8 = f32 sublane minimum —
    per-group padding cost scales with bm, so small groups get small tiles)."""
    return 128 if rows >= 128 else tiling.ceil_to(max(rows, 1), 8)


def _grouped_dispatch(xp, w0, a, b, gid, scale, bm, interpret):
    if quant.is_packed(w0):
        return _grouped_core_p4(xp, w0["q4"], w0["scale"], a, b,
                                jnp.asarray(gid, jnp.int32), scale, bm,
                                interpret, quant.packed_method(w0))
    if quant.is_quantized(w0):
        return _grouped_core_q(xp, w0["q"], w0["scale"], a, b,
                               jnp.asarray(gid, jnp.int32), scale, bm,
                               interpret)
    return _grouped_core(xp, w0, a, b, jnp.asarray(gid, jnp.int32), scale,
                         bm, interpret)


def lora_grouped_linear(x, w0, a, b, scale: float = 2.0, *, policy=None,
                        interpret=None):
    """Batched-uniform grouped LoRA linear (the MoE expert shape):
    x:[E,C,K], w0:[E,K,N] dense or quantized ``{"q","scale"}`` ([E,K,N] int8
    + [E,1,N] scale), a:[E,K,r], b:[E,r,N] -> [E,C,N]. Differentiable in
    (x, a, b); W0 is frozen (zero cotangent)."""
    E, C, K = x.shape
    bm = _grouped_bm(C)
    Cp = tiling.ceil_to(C, bm)
    xp = tiling.pad_dim(x, bm, 1).reshape(E * Cp, K)
    gid = np.repeat(np.arange(E, dtype=np.int32), Cp // bm)
    y = _grouped_dispatch(xp, w0, a, b, gid, scale, bm,
                          _resolve_interpret(policy, interpret))
    return y.reshape(E, Cp, -1)[:, :C]


def lora_grouped_ragged(x, group_sizes, w0, a, b, scale: float = 2.0, *,
                        bm: int = 8, policy=None, interpret=None):
    """Ragged grouped LoRA linear: x:[M,K] is the concatenation of per-group
    row blocks (``group_sizes[g]`` rows each, zero-size groups allowed).
    Packing/unpacking to the bm-tile layout happens here (plain jnp, so
    gradients flow through the pad/slice); the packed core carries the
    custom_vjp."""
    sizes = tuple(int(s) for s in group_sizes)
    if quant.is_packed(w0):
        N = w0["q4"].shape[-1]
    elif quant.is_quantized(w0):
        N = w0["q"].shape[-1]
    else:
        N = w0.shape[-1]
    if sum(sizes) == 0:
        return jnp.zeros((0, N), x.dtype)
    gid, _ = tiling.grouped_schedule(sizes, bm)
    xp = tiling.pack_ragged_rows(x, sizes, bm)
    y = _grouped_dispatch(xp, w0, a, b, gid, scale, bm,
                          _resolve_interpret(policy, interpret))
    return tiling.unpack_ragged_rows(y, sizes, bm)


def lora_grouped_decode(x, w0, a, b, tile_gid, bias=None, scale: float = 2.0,
                        *, bm: int = 8, policy=None, interpret=None):
    """Runtime-routed grouped linear for the serving decode path: a shared
    frozen base (w0:[K,N] dense or quantized) plus a *stack* of resident
    adapters (a:[R,K,r], b:[R,r,N]); ``tile_gid`` int32 [M//bm] holds each
    slot tile's AdapterStore slot and may be a traced array — re-routing
    adapters across steps never recompiles. Non-pallas backends use the
    gather reference (same math, jnp)."""
    M, K = x.shape
    if M % bm:
        raise ValueError(f"decode rows {M} not a multiple of tile {bm}")
    if policy is not None and policy.backend == "pallas":
        w0e = (quant.add_group_axis(w0)
               if quant.is_packed(w0) or quant.is_quantized(w0)
               else w0[None])
        # the kernel's row block must be a multiple of 8 sublanes on TPU:
        # pad every slot tile with zero rows up to that and slice them off
        bmp = tiling.ceil_to(bm, 8)
        xp = tiling.pad_dim(x.reshape(M // bm, bm, K), bmp, 1)
        y = _grouped_dispatch(xp.reshape(-1, K), w0e, a, b, tile_gid, scale,
                              bmp, _resolve_interpret(policy, interpret))
        y = y.reshape(M // bm, bmp, -1)[:, :bm].reshape(M, -1)
    else:
        row_gid = jnp.repeat(jnp.asarray(tile_gid, jnp.int32), bm)
        w = quant.maybe_dequant(w0, x.dtype)
        h = jnp.einsum("mk,mkr->mr", x, a[row_gid])
        y = (x @ w + scale * jnp.einsum("mr,mrn->mn", h, b[row_gid])
             ).astype(x.dtype)
    return y + bias if bias is not None else y


# ---------------------------------------------------------------------------
# Expert LoRA linear on a device-side tile schedule: the MoE layer routes
# inside the step, so its rows are packed on the device and the expert of
# each row tile (tile_gid) and the count of tiles holding routed rows
# (n_live) are traced arrays. Tiles past n_live are skipped by the kernels.
# ---------------------------------------------------------------------------


def _expert_base(w0):
    """(frozen operand, scale rows, quant name) of an expert stack."""
    if quant.is_packed(w0):
        return w0["q4"], w0["scale"], quant.packed_method(w0)
    if quant.is_quantized(w0):
        return w0["q"], w0["scale"], "int8"
    return w0, None, "none"


def _expert_blocks(K: int, N: int):
    """Blocks of the expert kernels: whole rows of K (up to 2048) in the
    forward and of N in dx, so a tile past the routed rows costs one grid
    step or a few."""
    fwd = {"bn": 512, "bk": 2048 if K <= 2048 else 512}
    dx = {"bk": 512, "bn": 2048 if N <= 2048 else 512}
    return fwd, dx


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _expert_core(x, w, s, a, b, gid, live, scale: float, bm: int,
                 quant_name: str, interpret: bool):
    fwd, _ = _expert_blocks(x.shape[1], b.shape[2])
    return _lg.expert_fwd(x, w, s, a, b, gid, live, scale, quant=quant_name,
                          bm=bm, interpret=interpret, **fwd)


def _expert_fwd(x, w, s, a, b, gid, live, scale, bm, quant_name, interpret):
    y = _expert_core(x, w, s, a, b, gid, live, scale, bm, quant_name,
                     interpret)
    return y, (x, w, s, a, b, gid, live)


def _expert_bwd(scale, bm, quant_name, interpret, res, g):
    x, w, s, a, b, gid, live = res
    g = g.astype(x.dtype)
    _, dxb = _expert_blocks(x.shape[1], b.shape[2])
    dx = _lg.expert_dx(g, w, s, a, b, gid, live, scale, quant=quant_name,
                       bm=bm, interpret=interpret, **dxb)
    # h = x·A recomputed tile-wise in VMEM (paper §4.1), never stored
    da, db = _lg.expert_dab(x, g, a, b, gid, live, scale, bm=bm,
                            interpret=interpret)
    dw = structured._zero_cot(w) if quant_name != "none" \
        else jnp.zeros_like(w)
    ds = None if s is None else jnp.zeros_like(s)
    return (dx, dw, ds, da, db, structured._zero_cot(gid),
            structured._zero_cot(live))


_expert_core.defvjp(_expert_fwd, _expert_bwd)


def _tile_group_sizes(tile_gid, n_live, n_groups: int, bm: int):
    """Rows of each group in the packed layout (whole live tiles)."""
    on = jnp.arange(tile_gid.shape[0]) < n_live
    return jnp.zeros((n_groups,), jnp.int32).at[tile_gid].add(
        jnp.where(on, bm, 0))


def _ragged(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ragged_lora(x, w0, a, b, sizes, scale: float):
    """Structured (MeSP) ragged LoRA linear: only x is kept; h = x·A is
    recomputed in the backward."""
    return _ragged(x, w0, sizes) + scale * _ragged(_ragged(x, a, sizes), b,
                                                   sizes)


def _ragged_lora_fwd(x, w0, a, b, sizes, scale):
    return _ragged_lora(x, w0, a, b, sizes, scale), (x, w0, a, b, sizes)


def _ragged_lora_bwd(scale, res, g):
    x, w0, a, b, sizes = res
    g = g.astype(x.dtype)
    h, h_vjp = jax.vjp(lambda x_, a_: _ragged(x_, a_, sizes), x, a)
    _, b_vjp = jax.vjp(lambda h_, b_: _ragged(h_, b_, sizes), h, b)
    dh, db = b_vjp(scale * g)
    dx_lora, da = h_vjp(dh)
    _, w_vjp = jax.vjp(lambda x_: _ragged(x_, w0, sizes), x)
    (dx,) = w_vjp(g)
    return (dx + dx_lora, jnp.zeros_like(w0), da, db,
            structured._zero_cot(sizes))


_ragged_lora.defvjp(_ragged_lora_fwd, _ragged_lora_bwd)


def lora_expert_linear(x, w0, a, b, tile_gid, n_live, scale: float = 2.0, *,
                       bm: int, policy=None, interpret=None):
    """Expert linear over rows packed by expert: x:[Mp,K] with every
    ``bm``-row tile one expert's (``tile_gid`` int32 [Mp//bm], traced), the
    first ``n_live`` tiles holding routed rows; w0:[E,K,N] dense or
    quantized, a:[E,K,r], b:[E,r,N] (None: no LoRA). Rows past the live
    tiles give zeros. Differentiable in (x, a, b).

    The pallas backend runs the ``expert_*`` kernels; the other backends
    (and a target without LoRA on the pallas backend, counted in
    ``FALLBACKS["expert_linear"]``) run ``jax.lax.ragged_dot`` over the
    tiles' group sizes — autodiff (h stored) for ``plain``/``store_h``,
    h recomputed for ``structured``."""
    backend = policy.backend if policy is not None else "structured"
    if backend == "pallas" and a is not None:
        w, s, qn = _expert_base(w0)
        live = jnp.reshape(jnp.asarray(n_live, jnp.int32), (1,))
        return _expert_core(x, w, s, a, b, jnp.asarray(tile_gid, jnp.int32),
                            live, scale, bm, qn,
                            _resolve_interpret(policy, interpret))
    if backend == "pallas":
        FALLBACKS["expert_linear"] += 1
    w = quant.maybe_dequant(w0, x.dtype)
    sizes = _tile_group_sizes(tile_gid, n_live, w.shape[0], bm)
    if a is None:
        return _ragged(x, w, sizes)
    if backend in ("plain", "store_h"):
        return _ragged(x, w, sizes) + scale * _ragged(_ragged(x, a, sizes),
                                                      b, sizes)
    return _ragged_lora(x, w, a, b, sizes, scale)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rmsnorm_kernel(x, w, eps: float = 1e-6, interpret: bool = False):
    x2 = _flat(x)
    blk = autotune.choose_blocks("rmsnorm", x.dtype, M=x2.shape[0],
                                 d=x2.shape[1])
    return _rn.rmsnorm(x2, w, eps, interpret=interpret,
                       **blk).reshape(x.shape)


def _rn_fwd(x, w, eps, interpret):
    return rmsnorm_kernel(x, w, eps, interpret), (x, w)


def _rn_bwd(eps, interpret, res, g):
    x, w = res
    x2 = _flat(x)
    blk = autotune.choose_blocks("rmsnorm_bwd", x.dtype, M=x2.shape[0],
                                 d=x2.shape[1])
    dx, dw = _rn.rmsnorm_bwd(x2, w, _flat(g), eps, interpret=interpret,
                             **blk)
    return dx.reshape(x.shape), dw


rmsnorm_kernel.defvjp(_rn_fwd, _rn_bwd)


def rmsnorm(x, w, eps: float = 1e-6, *, policy=None, interpret=None):
    """Dispatch: fused RMSNorm kernel (any row count — rows padded)."""
    return rmsnorm_kernel(x, w, eps, _resolve_interpret(policy, interpret))


# ---------------------------------------------------------------------------
# Flash attention: Pallas fwd saving per-row logsumexp + Pallas bwd that
# recomputes probabilities tile-wise from it. GQA grouped via index maps;
# causal/window grids are sparse (dead tiles never launched — see
# kernels/flash_attention.py); optional fused RoPE rotates q/k in VMEM.
# ---------------------------------------------------------------------------


def _attn_blocks(Nq, Nk, D, dtype, causal, window):
    # causal/window key the measured cache: the sparse schedule (and so the
    # best block shape) depends on the mask structure
    return autotune.choose_blocks("flash", dtype, Nq=Nq, Nk=Nk, D=D,
                                  causal=int(causal), window=window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    interpret: bool = False, rope=None):
    """q: [B,H,N,D]; k: [B,Hkv,Nk,D]; v: [B,Hkv,Nk,Dv] -> [B,H,N,Dv]
    (Dv may differ from the q·k width D). Differentiable.
    ``rope=(cos, sin)`` ([N, D/2] f32) fuses the q/k rotation into the
    kernels (tables are treated as constants — zero cotangent)."""
    out, _ = _flash_fwd_impl(q, k, v, rope, causal, window, interpret)
    return out


def _flash_fwd_impl(q, k, v, rope, causal, window, interpret):
    B, H, Nq, D = q.shape
    Hkv, Nk = k.shape[1], k.shape[2]
    blk = _attn_blocks(Nq, Nk, D, q.dtype, causal, window)
    out, lse = _fa.flash_attention_fwd(
        q.reshape(B * H, Nq, D), k.reshape(B * Hkv, Nk, D),
        v.reshape(B * Hkv, Nk, v.shape[-1]), rope, causal=causal,
        window=window, q_per_kv=H // Hkv, interpret=interpret,
        return_lse=True,
        bq=blk["bq"], bk=blk["bk"])
    return out.reshape(B, H, Nq, v.shape[-1]), lse


def _flash_vjp_fwd(q, k, v, causal, window, interpret, rope):
    out, lse = _flash_fwd_impl(q, k, v, rope, causal, window, interpret)
    # MeSP residual contract: (q, k, v, out, lse) — probs never stored
    return out, (q, k, v, rope, out, lse)


def _flash_vjp_bwd(causal, window, interpret, res, g):
    q, k, v, rope, out, lse = res
    B, H, Nq, D = q.shape
    Hkv, Nk, Dv = k.shape[1], k.shape[2], v.shape[-1]
    blk = _attn_blocks(Nq, Nk, D, q.dtype, causal, window)
    dq, dk, dv = _fa.flash_attention_bwd(
        q.reshape(B * H, Nq, D), k.reshape(B * Hkv, Nk, D),
        v.reshape(B * Hkv, Nk, Dv), out.reshape(B * H, Nq, Dv), lse,
        g.reshape(B * H, Nq, Dv), rope, causal=causal, window=window,
        q_per_kv=H // Hkv, interpret=interpret,
        bq=blk["bq"], bk=blk["bk"])
    d_rope = None if rope is None else (jnp.zeros_like(rope[0]),
                                        jnp.zeros_like(rope[1]))
    return (dq.reshape(B, H, Nq, D), dk.reshape(B, Hkv, Nk, D),
            dv.reshape(B, Hkv, Nk, Dv), d_rope)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def attention_supported(q, k) -> bool:
    if q.ndim != 4 or k.ndim != 4:
        return False
    H, Hkv = q.shape[1], k.shape[1]
    return Hkv >= 1 and H % Hkv == 0 and q.shape[2] >= PALLAS_ATTN_MIN_SEQ


def sdpa(q, k, v, *, causal: bool = True, window: int = 0, policy=None,
         interpret=None, rope=None):
    """Dispatch: flash kernel attention, structured sdpa fallback for short
    sequences / unsupported layouts. ``rope=(cos, sin)`` arrives *unapplied*
    (layers skip the jnp rotation when fusing): the kernel path rotates q/k
    tiles in VMEM; the fallback applies the same tables via jnp first."""
    if not attention_supported(q, k):
        FALLBACKS["sdpa"] += 1
        if rope is not None:
            q = _rope.apply_rope_tables(q, *rope)
            k = _rope.apply_rope_tables(k, *rope)
        return structured.sdpa(q, k, v, window, causal)
    return flash_attention(q, k, v, causal, window,
                           _resolve_interpret(policy, interpret), rope)


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           bq: int = 512, bk: int = 512,
                           interpret: bool = False):
    """Forward-only kernel entry (benchmarks/tests). q: [B,H,N,D]; k/v:
    [B,Hkv,Nk,D] -> [B,H,N,D]. GQA grouped via kernel index maps — K/V are
    never repeated in HBM."""
    B, H, Nq, D = q.shape
    Hkv, Nk = k.shape[1], k.shape[2]
    out = _fa.flash_attention_fwd(
        q.reshape(B * H, Nq, D), k.reshape(B * Hkv, Nk, D),
        v.reshape(B * Hkv, Nk, D), causal=causal, window=window,
        q_per_kv=H // Hkv, bq=bq, bk=bk, interpret=interpret)
    return out.reshape(B, H, Nq, D)
