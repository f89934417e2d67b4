"""The yardstick's arithmetic for an MLA + MoE configuration (DeepSeek-V2's
layout, a chip's share of its routed experts): the functions of
``peaks.py`` under the same names, worked out from the configuration's
sizes, not read from the program.

The routed experts' part depends on the routing, so it is not here: per
assignment that lands on a held expert, ``expert_flops_per_assignment``;
per held expert that gets a token in a step, ``expert_bytes``. The driver
takes those counts from the engine's counters. Everything else (attention,
the dense layer, the router, the shared experts, norms, embeddings) counts
from the sizes, as ``peaks.py`` counts a dense model: 2 FLOPs per
parameter and token, attention's scores not counted.
"""
from __future__ import annotations

import re
from typing import Dict

KV_BYTES = 2             # bfloat16 latents in the serving cache


def param_counts(sz: Dict) -> Dict[str, int]:
    """Parameters of the share: ``total``, ``embedding``, ``experts`` (the
    routed experts held here) and ``per_expert``."""
    m, moe = sz["mla"], sz["moe"]
    d, h, v = sz["d_model"], sz["n_heads"], sz["vocab_size"]
    dn, dr, dv, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"], m["kv_lora_rank"])
    n_dense = sz["dense_layers"]
    n_moe = sz["n_layers"] - n_dense
    attn = (d * h * (dn + dr) + d * r + d * dr + r * h * (dn + dv) + r
            + h * dv * d)
    norms = 2 * d
    dense_ffn = 3 * d * sz["d_ff"]
    per_expert = 3 * d * moe["d_ff_expert"]
    moe_rest = d * moe["n_experts"] + 3 * d * moe["d_ff_shared"]
    emb = 2 * v * d
    experts = n_moe * moe["n_held"] * per_expert
    total = (sz["n_layers"] * (attn + norms) + n_dense * dense_ffn
             + n_moe * moe_rest + experts + emb + d)
    return {"total": total, "embedding": emb, "experts": experts,
            "per_expert": per_expert}


def _n_act(sz: Dict) -> int:
    """Parameters every token uses: all but the embeddings and the routed
    experts."""
    c = param_counts(sz)
    return c["total"] - c["embedding"] - c["experts"]


def decode_flops_per_row(sz: Dict) -> float:
    """2 * N_active (routed experts aside) + unembedding per decoded row."""
    return 2 * _n_act(sz) + 2 * sz["vocab_size"] * sz["d_model"]


def prefill_flops(sz: Dict, prompt_tokens: int) -> float:
    """2 * N_active (routed experts aside) per real prompt token plus one
    unembedding row."""
    return 2 * _n_act(sz) * prompt_tokens + 2 * sz["vocab_size"] * \
        sz["d_model"]


def weight_bytes(sz: Dict, bytes_per_param: int) -> int:
    """Bytes of every weight a decode step reads whatever the routing: all
    but the routed experts (the embedding table's one row per token
    aside, the unembedding counted whole)."""
    c = param_counts(sz)
    return (c["total"] - c["experts"] - c["embedding"] // 2) \
        * bytes_per_param


def kv_bytes_per_token(sz: Dict) -> int:
    """The latent and the rope key of one position over every layer."""
    m = sz["mla"]
    return sz["n_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) \
        * KV_BYTES


def expert_flops_per_assignment(sz: Dict) -> float:
    """One token through one routed expert: three matrices of d x f."""
    return 2 * param_counts(sz)["per_expert"]


def expert_bytes(sz: Dict, bytes_per_param: int) -> int:
    """One routed expert's weights."""
    return param_counts(sz)["per_expert"] * bytes_per_param


# The grouped expert matmuls on the device trace: XLA's TPU ragged dot,
# ``%ragged-dot-none[.n] = bf16[<rows>,<cols>]``, one for the gate and up
# projections and one for the down projection of every MoE layer; rows are
# the program's batch rows times the experts per token.
_GROUPED = re.compile(r"^%?ragged-dot[\w.-]* = \w+\[(\d+),")


def grouped_expert_s(op_s: Dict[str, float], rows: int) -> float:
    """Device seconds of the grouped expert matmuls over ``rows`` rows."""
    total = 0.0
    for name, s in op_s.items():
        m = _GROUPED.match(name)
        if m and int(m.group(1)) == rows:
            total += s
    return total
