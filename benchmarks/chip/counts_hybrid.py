"""The yardstick's arithmetic for a Mamba-2 / attention hybrid with MoE in
every layer (Granite-4.0-H's layout, a chip's share of its routed experts):
the functions of ``peaks.py`` under the same names, and those of
``counts_mla_moe.py`` for the routed experts, worked out from the
configuration's sizes, not read from the program.

As for a dense model, everything but the routed experts counts 2 FLOPs per
parameter and token (attention's scores and the SSD's own products not
counted), and a decode step reads every weight but the routed experts (the
tied token table once, whole, for the unembedding) plus each occupied row's
keys and values. The routed experts' part depends on the routing: per
assignment that lands on a held expert, ``expert_flops_per_assignment``;
per held expert that gets a token in a step, ``expert_bytes``.

The Mamba-2 layers' own yardstick: ``ssm_weight_bytes`` (their weights, read
by every decode step), ``ssm_state_bytes_per_row`` (one row's conv window
and SSM state over those layers, read and written by a decode step) and
``ssm_prefill_flops_per_token`` (projections, conv and the chunked SSD as
prefill computes it: within a chunk of Q positions the whole Q x Q blocks
of C B^T and of its product with x, the state's contribution to each
position, and each position's contribution to the state).
"""
from __future__ import annotations

from typing import Dict

from counts_mla_moe import grouped_expert_s  # noqa: F401  (same trace names)

KV_BYTES = 2             # bfloat16 keys and values, conv window
STATE_BYTES = 4          # float32 SSM state


def _layers(sz: Dict):
    """(Mamba-2 layers, attention layers) of the share."""
    n = sz["n_layers"] // len(sz["period"])
    return (n * sz["period"].count("mamba2"), n * sz["period"].count("attn"))


def mamba2_params(sz: Dict) -> int:
    """Parameters of one Mamba-2 mixer."""
    m, d = sz["mamba2"], sz["d_model"]
    di = m["n_heads"] * m["head_dim"]
    conv = di + 2 * m["n_groups"] * m["d_state"]
    return (d * (di + conv + m["n_heads"]) + conv * (m["d_conv"] + 1)
            + 3 * m["n_heads"] + di + di * d)


def param_counts(sz: Dict) -> Dict[str, int]:
    """Parameters of the share: ``total``, ``embedding`` (the tied token
    table), ``experts`` (the routed experts held here), ``per_expert`` and
    ``mamba2`` (every Mamba-2 mixer)."""
    moe, d = sz["moe"], sz["d_model"]
    hq, hkv = sz["n_heads"] * sz["head_dim"], sz["n_kv_heads"] * sz["head_dim"]
    n_ssm, n_attn = _layers(sz)
    attn = d * hq + d * 2 * hkv + hq * d
    per_expert = 3 * d * moe["d_ff_expert"]
    moe_rest = d * moe["n_experts"] + 3 * d * moe["d_ff_shared"]
    layers = n_ssm + n_attn
    experts = layers * moe["n_held"] * per_expert
    ssm = n_ssm * mamba2_params(sz)
    emb = sz["vocab_size"] * d
    total = (ssm + n_attn * attn + layers * (moe_rest + 2 * d) + experts
             + emb + d)
    return {"total": total, "embedding": emb, "experts": experts,
            "per_expert": per_expert, "mamba2": ssm}


def _n_act(sz: Dict) -> int:
    """Parameters every token uses: all but the embedding and the routed
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
    but the routed experts, the tied table counted once."""
    c = param_counts(sz)
    return (c["total"] - c["experts"]) * bytes_per_param


def kv_bytes_per_token(sz: Dict) -> int:
    """Keys and values of one position over the attention layers."""
    return 2 * _layers(sz)[1] * sz["n_kv_heads"] * sz["head_dim"] * KV_BYTES


def expert_flops_per_assignment(sz: Dict) -> float:
    """One token through one routed expert: three matrices of d x f."""
    return 2 * param_counts(sz)["per_expert"]


def expert_bytes(sz: Dict, bytes_per_param: int) -> int:
    """One routed expert's weights."""
    return param_counts(sz)["per_expert"] * bytes_per_param


def ssm_weight_bytes(sz: Dict, bytes_per_param: int) -> int:
    """The Mamba-2 mixers' weights."""
    return param_counts(sz)["mamba2"] * bytes_per_param


def ssm_state_bytes_per_row(sz: Dict) -> int:
    """One row's conv window and SSM state over the Mamba-2 layers, read
    and then written whole by a decode step."""
    m = sz["mamba2"]
    di = m["n_heads"] * m["head_dim"]
    conv = di + 2 * m["n_groups"] * m["d_state"]
    one = (di * m["d_state"] * STATE_BYTES
           + (m["d_conv"] - 1) * conv * KV_BYTES)
    return 2 * _layers(sz)[0] * one


def ssm_prefill_flops_per_token(sz: Dict) -> float:
    """The Mamba-2 layers' FLOPs per prompt token: the two projections,
    the conv, and the chunked SSD (see the module's docstring)."""
    m, d = sz["mamba2"], sz["d_model"]
    h, p, n, g, q = (m["n_heads"], m["head_dim"], m["d_state"],
                     m["n_groups"], m["chunk_size"])
    di = h * p
    conv = di + 2 * g * n
    proj = 2 * d * (di + conv + h) + 2 * di * d
    ssd = 2 * q * g * n + 2 * q * h * p + 4 * h * p * n
    return _layers(sz)[0] * (proj + 2 * m["d_conv"] * conv + ssd)
