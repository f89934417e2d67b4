"""Granite-4.0-H-Small (32B-A9B) [hf:ibm-granite/granite-4.0-h-small]:
Mamba-2 / attention hybrid with MoE in every layer.
40L d_model=4096; attention at layers 5, 15, 25 and 35 (32H GQA kv=8 of 128,
no position embedding, softmax scale 1/128), Mamba-2 elsewhere (128 heads of
64, d_state 128, 1 group, conv 4 with bias, chunks of 256); after every mixer
an MoE block of 72 SwiGLU experts of 768, top-10 with a softmax over the ten
chosen logits, plus a shared SwiGLU MLP of 1536. muP multipliers: embeddings
x12, each sublayer's output x0.22 before its residual add, logits /16.
vocab=100352, tied, RMSNorm eps 1e-5.

``granite-4.0-h-small`` is the whole model. ``granite-4.0-h-small-ep8`` is
one chip's share of a sixteen-chip deployment, 8-way expert parallelism
times 2 pipeline stages: this chip holds layers 0-19 (two whole periods)
and routed experts 0-8 of 72 in each; attention, Mamba-2, the router (all
72 outputs), the shared MLP and the vocabulary are on every chip, which
serves its own requests.
"""
from dataclasses import replace
from types import SimpleNamespace

from repro.configs.base import LayerSpec, Mamba2Config, ModelConfig, MoEConfig

# layer_types of the published config, one period of 10: MMMMMAMMMM
_PERIOD = tuple(LayerSpec("attn" if i == 5 else "mamba2", "moe")
                for i in range(10))

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=0,                          # no dense FFN: MoE in every layer
    vocab_size=100352,
    period=_PERIOD,
    pos_emb="none",
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attn_scale=0.0078125,
    norm_eps=1e-5,
    mamba2=Mamba2Config(n_heads=128, head_dim=64, d_state=128, n_groups=1,
                        d_conv=4, chunk_size=256),
    moe=MoEConfig(n_experts=72, top_k=10, d_ff_expert=768, n_shared=1,
                  d_ff_shared=1536, router="softmax", norm_topk=True),
)

# Smoke: a period of three (Mamba-2, attention, Mamba-2), every layer MoE;
# chunks of 16 positions, so a smoke prompt spans several.
SMOKE = CONFIG.smoke(
    period=(LayerSpec("mamba2", "moe"), LayerSpec("attn", "moe"),
            LayerSpec("mamba2", "moe")), n_layers=6, attn_scale=1 / 16)

EP8 = SimpleNamespace(
    CONFIG=replace(CONFIG, name="granite-4.0-h-small-ep8", n_layers=20,
                   moe=replace(CONFIG.moe, n_held=9, first_held=0)),
    # a strict subset at smoke size too: 2 of the 8 smoke experts
    SMOKE=replace(SMOKE, name="granite-4.0-h-small-ep8-smoke",
                  moe=replace(SMOKE.moe, n_held=2, first_held=0)),
)
