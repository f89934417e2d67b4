"""The yardstick's arithmetic: published chip peaks and model operations.

Kept with the benchmark so that no change to the program can move it.
Copied from ``benchmarks/roofline.py`` (``CHIP_PEAKS``, ``model_flops``);
the parameter count is worked out here from the configuration's sizes
(dense GQA decoder: RMSNorm, SwiGLU, untied embeddings), not read from the
program.
"""
from __future__ import annotations

from typing import Dict

# Published peak rates of one chip, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" - 197 TFLOP/s bf16,
# 819 GB/s HBM.
CHIP_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9},
}

KV_BYTES = 2             # bfloat16 keys and values in the serving cache


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of ``device_kind``; a chip missing from the table is an error."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       ) from None


def param_counts(sz: Dict) -> Dict[str, int]:
    """Total and embedding parameters of a dense GQA decoder."""
    d, h, kv, hd = sz["d_model"], sz["n_heads"], sz["n_kv_heads"], \
        sz["head_dim"]
    f, v, n = sz["d_ff"], sz["vocab_size"], sz["n_layers"]
    per_layer = (d * h * hd + d * 2 * kv * hd + h * hd * d   # wq, wkv, wo
                 + d * 2 * f + f * d                         # SwiGLU
                 + 2 * d)                                    # two norms
    emb = v * d * (1 if sz.get("tie_embeddings") else 2)
    return {"total": n * per_layer + emb + d, "embedding": emb}


def _n_act(sz: Dict) -> int:
    c = param_counts(sz)
    return c["total"] - c["embedding"]


def train_flops_per_token(sz: Dict) -> float:
    """6 * N_active + 3 * unembedding per trained token (attention not
    counted), as ``roofline.model_flops`` counts a training step."""
    return 6 * _n_act(sz) + 3 * 2 * sz["vocab_size"] * sz["d_model"]


def decode_flops_per_row(sz: Dict) -> float:
    """2 * N_active + unembedding per decoded row."""
    return 2 * _n_act(sz) + 2 * sz["vocab_size"] * sz["d_model"]


def prefill_flops(sz: Dict, prompt_tokens: int) -> float:
    """2 * N_active per real prompt token plus one unembedding row."""
    return 2 * _n_act(sz) * prompt_tokens + 2 * sz["vocab_size"] * \
        sz["d_model"]


def weight_bytes(sz: Dict, bytes_per_param: int) -> int:
    return param_counts(sz)["total"] * bytes_per_param


def kv_bytes_per_token(sz: Dict) -> int:
    """Keys and values of one position over every layer."""
    return 2 * sz["n_layers"] * sz["n_kv_heads"] * sz["head_dim"] * KV_BYTES
