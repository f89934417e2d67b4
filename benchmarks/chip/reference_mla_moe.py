"""Plain reference of DeepSeek-V2's decoder (MLA + MoE), for a chip's share
of its routed experts.

Straightforward ``jax.numpy`` after the published description
(arXiv:2405.04434, and the model's own ``modeling_deepseek.py``): RMSNorm;
multi-head latent attention without query compression, its keys and values
reconstructed per head from the latent (not the absorbed form), the rotary
part rotated with YaRN's frequencies and the scores scaled by
``(dn + dr) ** -0.5 * mscale(factor, mscale_all_dim) ** 2``, a full causal
softmax; layer 0's dense SwiGLU; then in every MoE layer a softmax router
over all the published experts, greedy top-k without renormalisation, the
held experts' part computed densely (every held expert on every token,
weighted by its gate, zero where it was not chosen) plus the shared
experts; untied unembedding. Experts held on other chips add nothing, as in
the program. No cache, no kernels, no grouping.

It runs the layers one at a time, each layer's weights made float32 inside
the loop, so that float32 copies of all the weights never exist at once. It
imports nothing of the program; the arithmetic (``prec``: "f32" at
``Precision.HIGHEST``, or the fp8 control) and the served-token comparison
are ``reference.py``'s.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from reference import _ein, _rms, serve_gaps  # noqa: F401  (serve_gaps: reused)


def _yarn_freqs(d: int, theta: float, ys: Dict) -> jnp.ndarray:
    """Inverse frequencies of the ``d`` rotary dims under YaRN: pairs below
    ``low`` keep theta's, pairs above ``high`` are divided by the factor,
    a linear ramp between (DeepSeek-V2's ``yarn_find_correction_range``
    and ``yarn_linear_ramp_mask``)."""
    base = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def corr(rot):
        return d * math.log(ys["original_max_len"] / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = 1.0 - ramp
    return base * extra + base / ys["factor"] * (1.0 - extra)


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope(x, pos, sz):
    """Rotate-half RoPE with YaRN over (B, S, H, d)."""
    d, ys = x.shape[-1], sz["rope_scaling"]
    ang = pos[:, None].astype(jnp.float32) * _yarn_freqs(
        d, sz["rope_theta"], ys)
    m = _mscale(ys["factor"], ys["mscale"]) / _mscale(
        ys["factor"], ys["mscale_all_dim"])
    cos, sin = m * jnp.cos(ang)[:, None, :], m * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(sz, prec, h, mp):
    m, eps = sz["mla"], sz["norm_eps"]
    dn, dr = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    s = h.shape[1]
    pos = jnp.arange(s)
    q = _ein(prec, "bsd,dhe->bshe", h, mp["w_q"])
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, sz)
    ckv = _rms(_ein(prec, "bsd,dr->bsr", h, mp["w_dkv"]), mp["kv_norm"], eps)
    k_pe = _rope(_ein(prec, "bsd,de->bse", h, mp["w_kr"])[:, :, None, :],
                 pos, sz)[:, :, 0]
    kv = _ein(prec, "bsr,rhe->bshe", ckv, mp["w_ukv"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    ys = sz["rope_scaling"]
    scale = _mscale(ys["factor"], ys["mscale_all_dim"]) ** 2 / math.sqrt(
        dn + dr)
    sc = (_ein(prec, "bqhd,bshd->bhqs", q_nope, k_nope)
          + _ein(prec, "bqhd,bsd->bhqs", q_pe, k_pe)) * scale
    sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1)
    o = _ein(prec, "bhqs,bshd->bqhd", w, v)
    return _ein(prec, "bqhd,hdD->bqD", o, mp["w_o"])


def _swiglu(prec, h, w_in, w_out):
    gu = _ein(prec, "bsd,df->bsf", h, w_in)
    f = gu.shape[-1] // 2
    return _ein(prec, "bsf,fd->bsd", jax.nn.silu(gu[..., :f]) * gu[..., f:],
                w_out)


def _moe(sz, prec, h, fp):
    """The held experts' part of the routed sum plus the shared experts,
    and the experts each token chose (B, S, top_k)."""
    moe = sz["moe"]
    probs = jax.nn.softmax(_ein(prec, "bsd,de->bse", h, fp["router"]), -1)
    top_p, top_i = jax.lax.top_k(probs, moe["top_k"])
    if moe["norm_topk"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    held = fp["w_in"].shape[0]
    # one_hot of an expert held elsewhere is all zeros: it adds nothing here
    gate = jnp.sum(jax.nn.one_hot(top_i - moe["first_held"], held)
                   * top_p[..., None], -2)                   # (B, S, held)
    gu = _ein(prec, "bsd,edf->bsef", h, fp["w_in"])
    f = gu.shape[-1] // 2
    act = jax.nn.silu(gu[..., :f]) * gu[..., f:] * gate[..., None]
    routed = _ein(prec, "bsef,efd->bsd", act, fp["w_out"])
    return routed + _swiglu(prec, h, fp["shared_w_in"],
                            fp["shared_w_out"]), top_i


def _layer(sz, prec, x, lp, moe: bool):
    """(x after the layer, the MoE layer's choices or None)."""
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    eps = sz["norm_eps"]
    x = x + _attention(sz, prec, _rms(x, lp["mixer_norm"]["scale"], eps),
                       lp["mixer"])
    h = _rms(x, lp["ffn_norm"]["scale"], eps)
    if moe:
        y, top_i = _moe(sz, prec, h, lp["ffn"])
        return x + y, top_i
    return x + _swiglu(prec, h, lp["ffn"]["w_in"], lp["ffn"]["w_out"]), None


def forward(sz: Dict, params, tokens, prec: str = "f32"):
    """Final-norm hidden states (B, S, D) of ``tokens`` (B, S), and the
    experts each token chose in each MoE layer (layers, B, S, top_k)."""
    x = params["embed"]["tok"][tokens].astype(jnp.float32)
    for lp in params["prelayers"]:
        x, _ = _layer(sz, prec, x, lp, moe=False)
    x, chosen = jax.lax.scan(lambda x, lp: _layer(sz, prec, x, lp, True),
                             x, params["period"][0])
    return _rms(x, params["out_norm"]["scale"].astype(jnp.float32),
                sz["norm_eps"]), chosen


def hidden(sz: Dict, params, tokens, prec: str = "f32"):
    """Final-norm hidden states (B, S, D) of ``tokens`` (B, S)."""
    return forward(sz, params, tokens, prec)[0]


def logits(sz: Dict, params, h, prec: str = "f32"):
    return _ein(prec, "...d,vd->...v", h, params["embed"]["unembed"])


def make_gap_fn(sz: Dict, max_seq: int, max_new: int, control: bool):
    """jit (params, tokens (1, max_seq), pos (max_new,), served (max_new,))
    -> (gap of the served token, gap of the control's first token), as
    ``reference.make_gap_fn``."""
    def gaps(params, tokens, pos, served):
        ref = logits(sz, params, hidden(sz, params, tokens, "f32")[0, pos],
                     "f32")
        best = ref.max(-1)
        got = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        if not control:
            return best - got, jnp.zeros_like(got)
        low = logits(sz, params, hidden(sz, params, tokens, "fp8")[0, pos],
                     "fp8")
        pick = jnp.take_along_axis(ref, low.argmax(-1)[:, None], -1)[:, 0]
        return best - got, best - pick
    return jax.jit(gaps)
