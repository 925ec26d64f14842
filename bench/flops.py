"""Operations and bytes that the algorithm requires, from shapes alone.

Nothing here reads a compiled program or XLA's cost analysis: a kernel's
work is what its op has to compute, so the count stays the same whatever
implements it. Conventions (one token = one row of the batch):

* a matmul of an ``[M, K]`` by a ``[K, N]`` operand is ``2·M·K·N``;
* causal attention over ``n`` positions counts ``n/2`` keys per query;
* bf16 operands and results, 2 bytes an element, each read or written once.

``Widths`` is built from a benchmark configuration file
(``bench/configs/<name>.json``), never from the program's config objects.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

BYTES = 2  # bf16


@dataclasses.dataclass(frozen=True)
class Widths:
    layers: int
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    tied: bool
    rank: int
    targets: Tuple[str, ...]

    @property
    def q(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv(self) -> int:
        return self.kv_heads * self.head_dim

    @classmethod
    def from_config(cls, conf: dict) -> "Widths":
        pub, ass = conf["published"], conf["assumed"]
        return cls(layers=pub["num_hidden_layers"], d=pub["hidden_size"],
                   ff=pub["intermediate_size"],
                   heads=pub["num_attention_heads"],
                   kv_heads=pub["num_key_value_heads"],
                   head_dim=ass["head_dim"], vocab=pub["vocab_size"],
                   tied=pub["tie_word_embeddings"],
                   rank=ass["lora"]["rank"],
                   targets=tuple(ass["lora"]["targets"]))


def linears(w: Widths) -> List[Tuple[str, int, int]]:
    """(name, K, N) of every frozen linear of one decoder layer."""
    return [("q", w.d, w.q), ("k", w.d, w.kv), ("v", w.d, w.kv),
            ("o", w.q, w.d), ("gate", w.d, w.ff), ("up", w.d, w.ff),
            ("down", w.ff, w.d)]


def lora_targets(w: Widths) -> List[Tuple[str, int, int]]:
    return [t for t in linears(w) if t[0] in w.targets]


# ---------------------------------------------------------------- kernels

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


# -------------------------------------------------------------- training

def train_flops_per_token(w: Widths, seq: int) -> int:
    """FLOPs a LoRA fine-tuning step requires per token.

    Counted: forward and input-gradient of every frozen matmul and of the
    tied LM head; LoRA forward, dx, dA and dB (6·r·(K+N) a target, the
    shared g·Bᵀ once); causal QKᵀ and PV forward and a backward of twice
    that. Not counted: weight gradients of the frozen base (there are
    none), and work recomputed to save memory (MeSP's recomputed forward
    and h = x·A)."""
    frozen = sum(k * n for _, k, n in linears(w)) * w.layers
    head = w.vocab * w.d
    lora = sum(6 * w.rank * (k + n) for _, k, n in lora_targets(w))
    attn = 3 * 2 * 2 * (seq // 2) * w.head_dim * w.heads
    return 4 * (frozen + head) + w.layers * (lora + attn)
