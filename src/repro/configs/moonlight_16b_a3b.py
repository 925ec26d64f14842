"""Moonlight-16B-A3B [moonshotai, 2025; hf config.json, model_type
deepseek_v3].

27L d_model=2048 16H; layer 0 a dense FFN of width 11264, then 26 MoE
layers: 64 routed experts of width 1408, top-6 by sigmoid score plus a
correction bias (``noaux_tc``, one group), chosen weights normalised and
scaled by 2.446, 2 shared experts. Latent attention: kv_lora_rank 512, no
q_lora_rank, q·k per head 128 no-rope + 64 rope (the rope key shared by
all heads), v 128. vocab 163840, untied; rope_theta 50000; context 8192.

``SHARE`` is one chip's share of a deployment that splits every layer over
8 chips: experts ``[0, 8)`` held (expert parallelism 8; the router keeps
its 64 outputs), vocabulary rows ``[0, 20480)`` (an eighth), and 6 of the
27 layers (the dense layer and 5 MoE layers; the rest lie on further
pipeline stages). Every width is as published.
"""
import dataclasses

from .base import ArchConfig, LoRAConfig, MLAConfig, MoEConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab=163840,
    rope_theta=50000.0,
    norm_eps=1e-5,
    moe=MoEConfig(
        n_experts=64, top_k=6, d_expert=1408, n_shared=2,
        first_layer_dense=True, scoring="sigmoid", bias_select=True,
        routed_scale=2.446, d_dense=11264,
    ),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    lora=LoRAConfig(targets=("q", "kv_a", "kv_b", "o", "gate", "up",
                             "down")),
    notes="deepseek_v3: MLA, sigmoid noaux_tc top-6 of 64 + 2 shared "
          "[hf moonshotai/Moonlight-16B-A3B]",
)

SHARE = dataclasses.replace(
    CONFIG, name="moonlight-16b-a3b-ep8", n_layers=6, vocab=20480,
    moe=dataclasses.replace(CONFIG.moe, n_held=8),
    notes="one chip of 8 sharing each layer: experts [0, 8), vocabulary "
          "rows [0, 20480), 6 of 27 layers")

CONFIGS = [CONFIG, SHARE]
