"""Multi-head Latent Attention (DeepSeek-V2).

Prefill caches only the compressed latent ``c_kv`` (kv_lora_rank) plus the
shared rope key (qk_rope_head_dim) per token. Decode uses the *absorbed* form:
W_uk is folded into the query and W_uv into the output so attention runs
directly in the latent space — per-step work is O(S · (R + DR)) per head
instead of reconstructing 128 full heads of K/V.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef
from repro.models.layers import apply_rope, yarn_mscale
from repro.models.attention import flash_attention_xla


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


def mla_decode_attention(cfg: ModelConfig, p: Dict, x: jax.Array,
                         cache: Dict, lengths: jax.Array, *,
                         seq_axes: Optional[Tuple[str, ...]] = None,
                         batch_axes: Tuple[str, ...] = ("data",),
                         absorbed: bool = True) -> Tuple[jax.Array, Dict]:
    """One decode step, absorbed form. x: (B,1,D);
    cache = {"ckv": (B,S,R), "kr": (B,S,dr)}."""
    m = cfg.mla
    H = cfg.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    dt = x.dtype
    sm_scale = softmax_scale(cfg)
    q_nope, q_rope = _project_q(cfg, p, x, lengths[:, None])
    ckv_new, kr_new = _project_kv_latent(cfg, p, x, lengths[:, None])
    w_uk = p["w_ukv"].astype(dt)[..., :dn]              # (R, H, dn)
    w_uv = p["w_ukv"].astype(dt)[..., dn:]              # (R, H, dv)

    if not absorbed:
        # naive oracle: write latents, reconstruct all K/V, full softmax
        from repro.models.attention import NEG_INF as NI
        B = x.shape[0]
        S = cache["ckv"].shape[1]
        pos = jnp.clip(lengths, 0, S - 1)
        ckv = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
            c, n, i, axis=0))(cache["ckv"], ckv_new, pos)
        kr = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
            c, n, i, axis=0))(cache["kr"], kr_new, pos)
        kv = jnp.einsum("bsr,rhd->bshd", ckv, p["w_ukv"].astype(dt))
        k_nope, v = kv[..., :dn], kv[..., dn:]
        q = jnp.concatenate([q_nope, q_rope], -1)[:, 0]          # (B,H,dn+dr)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            kr[:, :, None, :], k_nope.shape[:3] + (dr,))], -1)
        s = jnp.einsum("bhd,bshd->bhs", q, k,
                       preferred_element_type=jnp.float32) * sm_scale
        kpos = jnp.arange(S)
        s = jnp.where(kpos[None, None, :] < (lengths + 1)[:, None, None], s, NI)
        w = jax.nn.softmax(s, -1)
        o = jnp.einsum("bhs,bshd->bhd", w.astype(dt), v,
                       preferred_element_type=jnp.float32).astype(dt)
        y = jnp.einsum("bhd,hdD->bD", o, p["w_o"].astype(dt))[:, None]
        return y, {"ckv": ckv, "kr": kr}

    # absorbed: q_lat = q_nope @ W_uk  -> attention in latent space
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)       # (B,H,R)
    from repro.parallel.decode_attn import sharded_mla_decode
    ctx, ckv, kr = sharded_mla_decode(
        q_lat, q_rope[:, 0], cache["ckv"], cache["kr"], ckv_new[:, 0],
        kr_new[:, 0], lengths, sm_scale=sm_scale,
        seq_axes=seq_axes or (), batch_axes=batch_axes)
    o = jnp.einsum("bhr,rhd->bhd", ctx.astype(dt), w_uv)         # (B,H,dv)
    y = jnp.einsum("bhd,hdD->bD", o, p["w_o"].astype(dt))[:, None]
    return y, {"ckv": ckv, "kr": kr}
