"""Multi-head Latent Attention (DeepSeek-V2).

Prefill caches only the compressed latent ``c_kv`` (kv_lora_rank) plus the
shared rope key (qk_rope_head_dim) per token. Decode uses the *absorbed* form:
W_uk is folded into the query and W_uv into the output so attention runs
directly in the latent space — per-step work is O(S · (R + DR)) per head
instead of reconstructing 128 full heads of K/V. On one shard a decode step
is ``write_latent_token`` then ``latent_attention``, or, inside the decode
layer loop, ``write_kv_token`` into the carried stack of every layer's
latents; when the mesh splits the sequence,
``repro.parallel.decode_attn.sharded_mla_decode`` does both.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef
from repro.models.layers import apply_rope, yarn_mscale
from repro.models.attention import NEG_INF, flash_attention_xla, write_kv_token
from repro.parallel.decode_attn import live_seq_axes, sharded_mla_decode


def mla_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    m = cfg.mla
    H, D = cfg.n_heads, cfg.d_model
    dn, dr, dv, R, QR = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                         m.v_head_dim, m.kv_lora_rank, m.q_lora_rank)
    out = {
        "w_dkv": ParamDef((D, R), ("embed", "lora")),
        "w_kr": ParamDef((D, dr), ("embed", None)),
        "w_ukv": ParamDef((R, H, dn + dv), ("lora", "heads", None)),
        "kv_norm": ParamDef((R,), ("norm",), init="ones"),
        "w_o": ParamDef((H, dv, D), ("heads", None, "embed")),
    }
    if QR:
        out["w_dq"] = ParamDef((D, QR), ("embed", "lora"))
        out["q_norm"] = ParamDef((QR,), ("norm",), init="ones")
        out["w_uq"] = ParamDef((QR, H, dn + dr), ("lora", "heads", None))
    else:
        out["w_q"] = ParamDef((D, H, dn + dr), ("embed", "heads", None))
    return out


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    v = jnp.mean(xf * xf, -1, keepdims=True)
    return (xf * jax.lax.rsqrt(v + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _project_q(cfg: ModelConfig, p: Dict, x: jax.Array, positions: jax.Array):
    m = cfg.mla
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    dt = x.dtype
    if "w_dq" in p:
        cq = _rms(x @ p["w_dq"].astype(dt), p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhd->bshd", cq, p["w_uq"].astype(dt))
    else:
        q = jnp.einsum("bsD,Dhd->bshd", x, p["w_q"].astype(dt))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_rope


def _project_kv_latent(cfg: ModelConfig, p: Dict, x: jax.Array,
                       positions: jax.Array):
    dt = x.dtype
    ckv = _rms(x @ p["w_dkv"].astype(dt), p["kv_norm"], cfg.norm_eps)
    kr = x @ p["w_kr"].astype(dt)                       # (B,S,dr) shared head
    kr = apply_rope(kr[:, :, None, :], positions, cfg.rope_theta,
                    cfg.rope_scaling)[:, :, 0]
    return ckv, kr


def softmax_scale(cfg: ModelConfig) -> float:
    """(dn + dr) ** -0.5, times mscale(factor, mscale_all_dim) ** 2 under
    YaRN."""
    m, s = cfg.mla, cfg.rope_scaling
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if s is not None:
        scale *= yarn_mscale(s.factor, s.mscale_all_dim) ** 2
    return scale


def mla_self_attention(cfg: ModelConfig, p: Dict, x: jax.Array,
                       positions: jax.Array, *,
                       lengths: Optional[jax.Array] = None,
                       backend: str = "xla",
                       unroll: bool = False
                       ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Training / prefill. Materializes per-head K/V from the latent (flash
    path), caches only (c_kv, k_rope)."""
    m = cfg.mla
    H = cfg.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    B, S, _ = x.shape
    dt = x.dtype
    q_nope, q_rope = _project_q(cfg, p, x, positions)
    ckv, kr = _project_kv_latent(cfg, p, x, positions)
    kv = jnp.einsum("bsr,rhd->bshd", ckv, p["w_ukv"].astype(dt))
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, :, None, :],
                                                  (B, S, H, dr))], -1)
    # flash expects matching head counts (MLA is effectively MHA here)
    o = flash_attention_xla(q, k, v_pad(v, q.shape[-1]), causal=True,
                            lengths=lengths, chunk=cfg.attn_chunk,
                            max_chunks=cfg.max_attn_chunks,
                            unroll=unroll, scale=softmax_scale(cfg))[..., :dv]
    y = jnp.einsum("bshd,hdD->bsD", o, p["w_o"].astype(dt))
    return y, (ckv, kr)


def v_pad(v: jax.Array, d: int) -> jax.Array:
    """Pad value head dim up to the qk head dim for the shared flash path."""
    if v.shape[-1] == d:
        return v
    pad = [(0, 0)] * (v.ndim - 1) + [(0, d - v.shape[-1])]
    return jnp.pad(v, pad)


def write_latent_token(cache: jax.Array, new: jax.Array,
                       lengths: jax.Array) -> jax.Array:
    """Insert one new latent per sequence at its current length:
    ``cache[b, lengths[b]] = new[b]``. cache: (B, S, W); new: (B, W). A
    length at or past S writes position S-1."""
    B, S = cache.shape[:2]

    def row(cache_row, new_row, idx, in_range):
        upd = jax.lax.dynamic_update_slice_in_dim(
            cache_row, new_row[None].astype(cache_row.dtype), idx, axis=0)
        return jnp.where(in_range, upd, cache_row)

    return jax.vmap(row)(cache, new, jnp.clip(lengths, 0, S - 1),
                         jnp.ones((B, 1), bool))


def latent_attention(q_lat: jax.Array, q_rope: jax.Array, ckv: jax.Array,
                     kr: jax.Array, lengths: jax.Array,
                     sm_scale: float) -> jax.Array:
    """Absorbed MLA attention over one shard's latents. q_lat: (B, H, R),
    q_nope absorbed through W_uk; q_rope: (B, H, DR); ckv: (B, S, R); kr:
    (B, S, DR), the rope key shared across heads. ``lengths`` counts the
    tokens before this step's, which sits at position ``lengths`` and
    attends to itself. Returns the latent context (B, H, R); the caller
    applies W_uv."""
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bsd->bhs", q_rope, kr,
                      preferred_element_type=jnp.float32)) * sm_scale
    kpos = jnp.arange(ckv.shape[1])
    s = jnp.where(kpos[None, None, :] < (lengths + 1)[:, None, None], s,
                  NEG_INF)
    w = jax.nn.softmax(s, -1)
    ctx = jnp.einsum("bhs,bsr->bhr", w.astype(ckv.dtype), ckv,
                     preferred_element_type=jnp.float32)
    return ctx.astype(q_lat.dtype)


def mla_decode_attention(cfg: ModelConfig, p: Dict, x: jax.Array,
                         cache: Dict, lengths: jax.Array, *,
                         layer=None,
                         seq_axes: Tuple[str, ...] = (),
                         batch_axes: Tuple[str, ...] = ("data",)
                         ) -> Tuple[jax.Array, Dict]:
    """One decode step, absorbed form. x: (B,1,D);
    cache = {"ckv": (B,S,R), "kr": (B,S,dr)}. ``lengths`` counts tokens
    already in the cache.

    With ``layer``, ``cache`` holds every layer's latents stacked,
    {"ckv": (L,B,S,R), "kr": (L,B,S,dr)}: the token is written at
    ``[layer]`` with ``write_kv_token``, that layer attends, and the whole
    stacks are returned for the caller's layer loop to carry, bit for bit
    what the per-layer write computes."""
    dn = cfg.mla.qk_nope_head_dim
    dt = x.dtype
    sm_scale = softmax_scale(cfg)
    q_nope, q_rope = _project_q(cfg, p, x, lengths[:, None])
    ckv_new, kr_new = _project_kv_latent(cfg, p, x, lengths[:, None])
    w_uk = p["w_ukv"].astype(dt)[..., :dn]              # (R, H, dn)
    w_uv = p["w_ukv"].astype(dt)[..., dn:]              # (R, H, dv)
    # q_lat = q_nope @ W_uk  -> attention in latent space
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)       # (B,H,R)
    q_rope, ckv_new, kr_new = q_rope[:, 0], ckv_new[:, 0], kr_new[:, 0]
    seq_axes = live_seq_axes(seq_axes)
    if seq_axes:
        ctx, ckv, kr = sharded_mla_decode(
            q_lat, q_rope, cache["ckv"], cache["kr"], ckv_new, kr_new,
            lengths, sm_scale=sm_scale, seq_axes=seq_axes,
            batch_axes=batch_axes)
    elif layer is None:
        ckv = write_latent_token(cache["ckv"], ckv_new, lengths)
        kr = write_latent_token(cache["kr"], kr_new, lengths)
        ctx = latent_attention(q_lat, q_rope, ckv, kr, lengths, sm_scale)
    else:
        ckv = write_kv_token(cache["ckv"], ckv_new, lengths, layer)
        kr = write_kv_token(cache["kr"], kr_new, lengths, layer)
        ctx = latent_attention(q_lat, q_rope, ckv[layer], kr[layer], lengths,
                               sm_scale)
    o = jnp.einsum("bhr,rhd->bhd", ctx.astype(dt), w_uv)         # (B,H,dv)
    y = jnp.einsum("bhd,hdD->bD", o, p["w_o"].astype(dt))[:, None]
    return y, {"ckv": ckv, "kr": kr}
