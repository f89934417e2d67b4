"""DeepSeek-V2 (236B) [arXiv:2405.04434]: MLA + MoE.
60L d_model=5120 128H; MLA kv_lora=512 q_lora=1536 (nope 128 / rope 64 /
v 128); layer 0 dense FFN d_ff=12288; layers 1..59: 160 routed experts
top-6 (d_ff_expert=1536) + 2 shared (2x1536=3072). vocab=102400.
RoPE theta 1e4 with YaRN (factor 40 over 4096, beta 32/1, mscale 0.707
both), RMSNorm eps 1e-6.

Left out of the published config: group-limited routing (the 160 experts
in 8 groups, top 3 groups per token) is plain greedy top-6 here, and the
routed experts' output is not scaled by ``routed_scaling_factor`` 16, and
the top-6 weights are renormalised (``norm_topk_prob`` is false there)."""
from repro.configs.base import (LayerSpec, MLAConfig, ModelConfig, MoEConfig,
                                YarnConfig)

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,                  # MLA is effectively MHA (kv=128 per spec)
    head_dim=128,
    d_ff=12288,                      # dense FFN of layer 0
    vocab_size=102400,
    prelayers=(LayerSpec("mla", "dense"),),
    period=(LayerSpec("mla", "moe"),),
    rope_theta=1.0e4,
    rope_scaling=YarnConfig(factor=40.0, original_max_len=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    norm_eps=1e-6,
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=160, top_k=6, d_ff_expert=1536,
                  n_shared=2, d_ff_shared=3072),
)

SMOKE = CONFIG.smoke()
