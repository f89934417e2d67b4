"""DeepSeek-V2-Lite (15.7B) [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite]: MLA + MoE.
27L d_model=2048 16H; MLA without query compression (kv_lora 512, nope 128 /
rope 64 / v 128), RoPE theta 1e4 with YaRN (factor 40 over 4096, beta 32/1,
mscale 0.707 both); layer 0 dense FFN d_ff=10944; layers 1..26: 64 routed
experts, softmax router, greedy top-6 without renormalisation (routed
scaling 1), d_ff_expert=1408, plus 2 shared (2x1408=2816). vocab=102400,
untied, RMSNorm eps 1e-6.

``deepseek-v2-lite`` is the whole model. ``deepseek-v2-lite-ep8`` is one
chip's share of an eight-chip expert-parallel deployment: each MoE layer's
64 routed experts are split over the 8 chips and this chip holds experts
0-7; attention, the dense layer, the router (all 64 outputs), the shared
experts and the vocabulary are on every chip, which serves its own requests.
Nothing else is cut.
"""
from dataclasses import replace
from types import SimpleNamespace

from repro.configs.base import (LayerSpec, MLAConfig, ModelConfig, MoEConfig,
                                YarnConfig)

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,                   # MLA: one latent, 16 query heads
    head_dim=128,
    d_ff=10944,                      # dense FFN of layer 0
    vocab_size=102400,
    prelayers=(LayerSpec("mla", "dense"),),
    period=(LayerSpec("mla", "moe"),),
    rope_theta=1.0e4,
    rope_scaling=YarnConfig(factor=40.0, original_max_len=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    norm_eps=1e-6,
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408, n_shared=2,
                  d_ff_shared=2816, norm_topk=False),
)

# Smoke widths keep q_lora_rank 0 and YaRN: with 8 rope dims (4 pairs) the
# ramp runs from pair 1 to pair 3, inside them.
SMOKE = CONFIG.smoke(mla=MLAConfig(q_lora_rank=0, kv_lora_rank=32,
                                   qk_nope_head_dim=16, qk_rope_head_dim=8,
                                   v_head_dim=16))

EP8 = SimpleNamespace(
    CONFIG=replace(CONFIG, name="deepseek-v2-lite-ep8",
                   moe=replace(CONFIG.moe, n_held=8, first_held=0)),
    # a strict subset at smoke size too: 2 of the 8 smoke experts
    SMOKE=replace(SMOKE, name="deepseek-v2-lite-ep8-smoke",
                  moe=replace(SMOKE.moe, n_held=2, first_held=0)),
)
