"""Shared primitive layers: norms, positional embeddings, dense FFN, embeddings.

Everything is a pure function over (config, params, inputs). Param definitions
live beside the apply functions so a module is a (defs, apply) pair.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, YarnConfig
from repro.models.params import ParamDef


def scan_unroll(unroll: bool, length: int) -> int:
    """``lax.scan``'s ``unroll=`` for an unroll flag over ``length`` trips:
    1 keeps the loop; ``length + 1`` inlines every trip (``unroll=True``
    would leave a one-trip scan a loop)."""
    return length + 1 if unroll else 1


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_defs(cfg: ModelConfig, d: int) -> Dict[str, ParamDef]:
    out = {"scale": ParamDef((d,), ("norm",), init="ones")}
    if cfg.norm == "layernorm":
        out["bias"] = ParamDef((d,), ("norm",), init="zeros")
    return out


def apply_norm(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    """RMSNorm / LayerNorm in fp32, cast back to input dtype."""
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        xf = xf - mu
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    if cfg.norm == "layernorm":
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Per-head qk-norm (no mean subtraction)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary / sinusoidal position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float,
               scaling: Optional[YarnConfig] = None) -> jax.Array:
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is None:
        return freqs
    # YaRN as DeepSeek-V2 defines it: pairs below ``low`` keep their
    # frequency, pairs above ``high`` are divided by the factor, and a
    # linear ramp joins the two
    low, high = yarn_range(head_dim, theta, scaling)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return freqs * keep + freqs / scaling.factor * (1.0 - keep)


def yarn_range(head_dim: int, theta: float, s: YarnConfig):
    """The first and last rotary pair of YaRN's ramp."""
    def dim(rotations):
        return (head_dim * math.log(s.original_max_len
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim(s.beta_fast)), 0)
    high = min(math.ceil(dim(s.beta_slow)), head_dim - 1)
    return low, (high + 0.001 if high == low else high)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling: Optional[YarnConfig] = None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).
    With YaRN ``scaling``, its frequencies, and cos and sin scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta, scaling)              # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sincos_pos_emb(positions: jax.Array, d_model: int) -> jax.Array:
    """Classic transformer sinusoidal embedding; positions (..., seq)."""
    half = d_model // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Dense (SwiGLU) FFN
# ---------------------------------------------------------------------------

def ffn_defs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, ParamDef]:
    d_ff = d_ff or cfg.d_ff
    width = 2 * d_ff if cfg.ffn_gated else d_ff
    out = {
        "w_in": ParamDef((cfg.d_model, width), ("embed", "mlp")),
        "w_out": ParamDef((d_ff, cfg.d_model), ("mlp", "embed"), scale=1.0),
    }
    if cfg.use_bias:
        out["b_in"] = ParamDef((width,), ("mlp",), init="zeros")
        out["b_out"] = ParamDef((cfg.d_model,), ("embed_nofsdp",), init="zeros")
    return out


def apply_ffn(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    dt = x.dtype
    gu = x @ p["w_in"].astype(dt)
    if "b_in" in p:
        gu = gu + p["b_in"].astype(dt)
    if cfg.ffn_gated:
        g, u = jnp.split(gu, 2, axis=-1)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(gu)
    y = h @ p["w_out"].astype(dt)
    if "b_out" in p:
        y = y + p["b_out"].astype(dt)
    return y


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    out = {"tok": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed_nofsdp"),
                           init="embed", scale=0.02)}
    if not cfg.tie_embeddings:
        out["unembed"] = ParamDef((cfg.vocab_size, cfg.d_model),
                                  ("vocab", "embed_nofsdp"), init="embed", scale=0.02)
    if cfg.input_mode == "tokens+vision":
        # learned projection applied to the stubbed (precomputed) patch embeds
        out["vision_proj"] = ParamDef((cfg.d_model, cfg.d_model), ("embed", None))
    if cfg.input_mode == "embeds":
        out["frame_proj"] = ParamDef((cfg.d_model, cfg.d_model), ("embed", None))
    return out


def embed_tokens(cfg: ModelConfig, p: Dict, tokens: jax.Array,
                 extra_embeds: Optional[jax.Array] = None,
                 positions: Optional[jax.Array] = None) -> jax.Array:
    dt = jnp.dtype(cfg.dtype)
    if cfg.input_mode == "embeds":
        # modality stub: `tokens` slot carries precomputed frame embeddings
        x = extra_embeds.astype(dt) @ p["frame_proj"].astype(dt)
    else:
        x = p["tok"].astype(dt)[tokens]
        if cfg.input_mode == "tokens+vision" and extra_embeds is not None:
            v = extra_embeds.astype(dt) @ p["vision_proj"].astype(dt)
            x = jnp.concatenate([v, x], axis=1)
    x = x * jnp.asarray(cfg.embedding_multiplier, dt)
    if cfg.pos_emb == "sincos":
        if positions is None:
            positions = jnp.arange(x.shape[1])[None, :]
        x = x + sincos_pos_emb(positions, cfg.d_model).astype(dt)
    return x


def unembed(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    w = p["tok"] if cfg.tie_embeddings else p["unembed"]
    logits = jnp.einsum("...d,vd->...v", x, w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits
