"""Operations and bytes that the algorithm requires, from shapes alone.

Nothing here reads a compiled program or XLA's cost analysis: a kernel's
work is what its op has to compute, so the count stays the same whatever
implements it. Conventions (one token = one row of the batch):

* a matmul of an ``[M, K]`` by a ``[K, N]`` operand is ``2·M·K·N``;
* causal attention over ``n`` positions counts ``n/2`` keys per query;
* bf16 operands and results, 2 bytes an element, each read or written once.

These are the ops every architecture shares. What a step of one
architecture requires (which linears, at which rows, how many attention
calls) is counted by its module (``bench/archs/<name>.py``) from the
widths in its configuration file, never from the program's config objects.
"""
from __future__ import annotations

from typing import Tuple

BYTES = 2  # bf16


def lora_op(kind: str, m: int, k: int, n: int, r: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of one LoRA linear op over ``m`` rows.

    fwd: y = x·W + s·(x·A)·B; dx: dx = g·Wᵀ + s·(g·Bᵀ)·Aᵀ;
    dab: dA = s·xᵀ·(g·Bᵀ), dB = s·(x·A)ᵀ·g (h = x·A counted once here)."""
    lora = r * (k + n)
    if kind == "fwd":
        return 2 * m * (k * n + lora), BYTES * (m * k + k * n + lora + m * n)
    if kind == "dx":
        return 2 * m * (k * n + lora), BYTES * (m * n + k * n + lora + m * k)
    if kind == "dab":
        return (2 * m * 2 * lora,
                BYTES * (m * k + m * n + 2 * lora))
    raise ValueError(kind)


def flash_op(kind: str, b: int, heads: int, kv_heads: int, n: int,
             d: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of causal attention over ``b`` sequences of ``n``.

    fwd: S = Q·Kᵀ and O = P·V over n/2 keys a query; bwd (dq and dkv
    together): dP, dQ, dK, dV, twice the forward's products."""
    fwd = 2 * 2 * b * heads * n * (n // 2) * d
    q = b * heads * n * d
    kv = b * kv_heads * n * d
    lse = b * heads * n * 4
    if kind == "fwd":
        return fwd, BYTES * (2 * q + 2 * kv) + lse
    if kind == "bwd":
        return 2 * fwd, BYTES * (3 * q + 4 * kv + q) + lse
    raise ValueError(kind)
