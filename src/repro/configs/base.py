"""Architecture configuration system.

Every assigned architecture is expressed as an :class:`ArchConfig`. Configs are
frozen dataclasses so they can be used as static (hashable) jit arguments.

``ArchConfig.reduced()`` returns a tiny same-family config used by CPU smoke
tests; the full config is only ever lowered via the dry-run
(ShapeDtypeStruct, no allocation).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LoRAConfig:
    """Paper setup: rank 8, alpha 16, applied to q,k,v,o,gate,up,down."""

    rank: int = 8
    alpha: float = 16.0
    targets: Tuple[str, ...] = ("q", "k", "v", "o", "gate", "up", "down")

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


@dataclass(frozen=True)
class MoEConfig:
    """Routed experts of a sparse FFN.

    ``scoring`` turns router logits into scores (``softmax`` or
    ``sigmoid``); with ``bias_select`` a frozen per-expert correction bias
    is added to the scores to choose the top-k experts (DeepSeek-V3's
    ``noaux_tc``), while the combine weights are the scores without it.
    The chosen weights are divided by their sum and ``routed_scale``
    multiplies them. Group-limited routing is not modelled (one group).

    ``held_lo``/``n_held``: the experts this device holds, ``[held_lo,
    held_lo + n_held)`` (``n_held`` 0: all of them) — the expert-parallel
    share. The router scores all ``n_experts``; the layer computes its own
    experts' part of the result. ``d_dense``: width of the leading dense
    FFN (0: ``d_expert·(top_k + n_shared)``).
    """

    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 0
    first_layer_dense: bool = False  # deepseek-moe: layer 0 is a dense FFN
    scoring: str = "softmax"
    bias_select: bool = False
    routed_scale: float = 1.0
    held_lo: int = 0
    n_held: int = 0
    d_dense: int = 0

    @property
    def resolved_n_held(self) -> int:
        return self.n_held or self.n_experts

    @property
    def resolved_d_dense(self) -> int:
        return self.d_dense or self.d_expert * (self.top_k + self.n_shared)


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3): keys and values come
    from a ``kv_lora_rank``-wide latent through ``kv_b``; each head's q·k
    width is a no-rope part plus a rope part, whose key side is one vector
    per token shared by all heads; v has a width of its own. Only
    ``q_lora_rank`` None (q projected straight from the hidden state) is
    modelled."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    q_lora_rank: Optional[int] = None

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class HybridConfig:
    """RecurrentGemma-style block pattern."""

    pattern: Tuple[str, ...] = ("R", "R", "A")  # repeated; truncated to n_layers
    lru_width: int = 0  # defaults to d_model when 0
    window: int = 2048  # local attention window for 'A' blocks


@dataclass(frozen=True)
class EncDecConfig:
    """Whisper-style encoder-decoder."""

    encoder_layers: int = 4
    encoder_seq: int = 1500  # precomputed mel-frame embeddings (stub frontend)


# ---------------------------------------------------------------------------
# Main config
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "vlm", "audio", "ssm", "hybrid")


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # one of FAMILIES
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None  # d_model // n_heads unless overridden
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    # attention layout: per-layer sliding window sizes; () => all-global.
    # gemma3 uses 5 local : 1 global.
    window_pattern: Tuple[int, ...] = ()  # 0 = global, >0 = local window

    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    hybrid: Optional[HybridConfig] = None
    encdec: Optional[EncDecConfig] = None

    lora: LoRAConfig = field(default_factory=LoRAConfig)

    # frontend stub for [vlm]/[audio]: input_specs() provides precomputed
    # patch/frame embeddings of this many positions prepended to the text.
    frontend_tokens: int = 0

    # True when the arch can run the long_500k shape (sub-quadratic mixing).
    subquadratic: bool = False
    notes: str = ""

    # ---- derived -----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def q_size(self) -> int:
        return self.n_heads * self.resolved_head_dim

    @property
    def kv_size(self) -> int:
        return self.n_kv_heads * self.resolved_head_dim

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    def layer_window(self, i: int) -> int:
        if not self.window_pattern:
            return 0
        return self.window_pattern[i % len(self.window_pattern)]

    def n_params(self) -> int:
        """Approximate parameter count (for MODEL_FLOPS = 6·N·D)."""
        d, L = self.d_model, self.n_layers
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        hd = self.resolved_head_dim
        attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
            + (self.n_heads * hd) * d
        if self.moe is not None:
            ff = 3 * d * self.moe.d_expert * (self.moe.n_experts + self.moe.n_shared)
            ff += d * self.moe.n_experts  # router
        else:
            ff = 3 * d * self.d_ff
        per_layer = attn + ff
        if self.family == "ssm":
            per_layer = 5 * d * d + 3 * d * self.d_ff  # rwkv6 approx
        total = emb + L * per_layer
        if self.encdec is not None:
            total += self.encdec.encoder_layers * (4 * d * d + 2 * d * self.d_ff)
        return total

    def n_active_params(self) -> int:
        """Active params per token (MoE uses top-k + shared experts only)."""
        if self.moe is None:
            return self.n_params()
        d, L = self.d_model, self.n_layers
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        hd = self.resolved_head_dim
        attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
            + (self.n_heads * hd) * d
        ff = 3 * d * self.moe.d_expert * (self.moe.top_k + self.moe.n_shared)
        return emb + L * (attn + ff)

    # ---- reduced config for smoke tests -------------------------------------
    def reduced(self) -> "ArchConfig":
        """A tiny same-family config: runs one train/serve step on CPU."""
        changes = dict(
            n_layers=min(self.n_layers, 3 if self.hybrid else 2),
            d_model=64,
            n_heads=4,
            n_kv_heads=2 if self.n_kv_heads < self.n_heads else 4,
            d_ff=128,
            vocab=256,
            head_dim=16,
            dtype="float32",
            frontend_tokens=min(self.frontend_tokens, 4),
            lora=LoRAConfig(rank=4, alpha=8.0, targets=self.lora.targets),
        )
        if self.window_pattern:
            # keep the local:global character with a 2-layer (local, global)
            # period so the reduced model stays tiny
            changes["window_pattern"] = (8, 0)
            changes["n_layers"] = 2
        if self.moe is not None:
            changes["moe"] = dataclasses.replace(
                self.moe, n_experts=4, top_k=2, d_expert=32,
                n_shared=min(self.moe.n_shared, 1), held_lo=0,
                n_held=min(self.moe.n_held, 2),
                d_dense=64 if self.moe.d_dense else 0)
        if self.mla is not None:
            changes["mla"] = MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                                       qk_rope_head_dim=8, v_head_dim=12)
        if self.hybrid is not None:
            changes["hybrid"] = HybridConfig(
                pattern=self.hybrid.pattern, lru_width=64, window=8
            )
        if self.encdec is not None:
            changes["encdec"] = EncDecConfig(encoder_layers=2, encoder_seq=8)
        return dataclasses.replace(self, **changes)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def shape_applicable(cfg: ArchConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether an (arch, shape) cell runs; reason string when skipped."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "pure full-attention arch: long_500k needs sub-quadratic mixing (DESIGN.md §5)"
    return True, ""
