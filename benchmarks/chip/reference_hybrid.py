"""Plain reference of Granite-4.0-H's decoder (Mamba-2 and attention, MoE in
every layer), for a chip's share of its routed experts.

Straightforward ``jax.numpy`` after the published model's own code
(``GraniteMoeHybridForCausalLM`` in ``transformers``: its Mamba layer's
``torch_forward``, ``GraniteMoeHybridRMSNormGated``, the top-k gating and
the decoder layer) and Mamba-2 (arXiv:2405.21060): token embeddings times
``embedding_multiplier``; in each layer x + r * mixer(norm(x)), then
x + r * (moe(norm(x)) + shared(norm(x))) with r the residual multiplier.
The Mamba-2 mixer: one input projection into the gate z, x B C and dt; a
causal depthwise conv with bias and SiLU over x, B and C; dt =
softplus(dt + dt_bias), A = -exp(A_log); the recurrence
h <- exp(dt A) h + dt x B^T, y = h C + D x run one position at a time in
float32 (no chunks); y * silu(z) through an RMSNorm over all of d_inner and
the output projection. Attention: GQA without position embedding, scores
scaled by ``attn_scale``, a full causal softmax, one kv head at a time. The
MoE: a softmax router over all the published experts, the top-k
renormalised (the same as a softmax over the k chosen logits), the held
experts' part computed densely (every held expert on every token, weighted
by its gate, zero where it was not chosen) plus the shared SwiGLU MLP;
experts held on other chips add nothing, as in the program. Tied
unembedding divided by ``logits_scaling``. No cache, no kernels, no
batching.

It runs the layers one at a time, each layer's weights made float32 inside
the loop and behind the previous layer's output, so that float32 copies of
all the weights never exist at once. It imports nothing of the program; the
arithmetic (``prec``: "f32" at ``Precision.HIGHEST``, or the fp8 control)
and the served-token comparison are ``reference.py``'s.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from reference import _ein, _rms, serve_gaps  # noqa: F401  (serve_gaps: reused)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _mamba2(sz, prec, h, mp):
    m = sz["mamba2"]
    nh, hp, n, g, dc = (m["n_heads"], m["head_dim"], m["d_state"],
                        m["n_groups"], m["d_conv"])
    di = nh * hp
    b, s, _ = h.shape
    proj = _ein(prec, "bsd,de->bse", h, mp["w_in"])
    z, xbc, dt = proj[..., :di], proj[..., di:-nh], proj[..., -nh:]
    padded = jnp.pad(xbc, ((0, 0), (dc - 1, 0), (0, 0)))
    conv = mp["conv_b"] + sum(padded[:, j:j + s] * mp["conv_w"][:, j]
                              for j in range(dc))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :di].reshape(b, s, nh, hp)
    bm = jnp.repeat(xbc[..., di:di + g * n].reshape(b, s, g, n), nh // g, 2)
    cm = jnp.repeat(xbc[..., di + g * n:].reshape(b, s, g, n), nh // g, 2)
    dt = jax.nn.softplus(dt + mp["dt_bias"])
    a = -jnp.exp(mp["a_log"])

    def step(state, t):
        x_t, dt_t, b_t, c_t = t
        state = state * jnp.exp(dt_t * a)[..., None, None] \
            + (x_t * dt_t[..., None])[..., None] * b_t[:, :, None, :]
        return state, _ein(prec, "bhpn,bhn->bhp", state, c_t)

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, bm, cm))
    _, ys = jax.lax.scan(step, jnp.zeros((b, nh, hp, n), jnp.float32), seq)
    y = jnp.moveaxis(ys, 0, 1) + x * mp["d_skip"][:, None]
    y = y.reshape(b, s, di) * jax.nn.silu(z)
    y = _rms(y, mp["norm"], sz["norm_eps"])
    return _ein(prec, "bse,ed->bsd", y, mp["w_out"])


def _attention(sz, prec, h, mp):
    b, s, _ = h.shape
    nh, kv, hd = sz["n_heads"], sz["n_kv_heads"], sz["head_dim"]
    q = _ein(prec, "bsd,de->bse", h, mp["wq"]).reshape(b, s, kv, nh // kv,
                                                       hd)
    kvp = _ein(prec, "bsd,de->bse", h, mp["wkv"]).reshape(b, s, 2, kv, hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(t):                     # one kv head and its query heads
        q, k, v = t
        sc = _ein(prec, "bqgd,bsd->bgqs", q, k) * sz["attn_scale"]
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return _ein(prec, "bgqs,bsd->bqgd", w, v)

    o = jax.lax.map(one_head, (jnp.moveaxis(q, 2, 0),
                               jnp.moveaxis(kvp[:, :, 0], 2, 0),
                               jnp.moveaxis(kvp[:, :, 1], 2, 0)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, s, nh * hd)
    return _ein(prec, "bse,ed->bsd", o, mp["wo"])


def _swiglu(prec, h, w_in, w_out):
    gu = _ein(prec, "bsd,df->bsf", h, w_in)
    f = gu.shape[-1] // 2
    return _ein(prec, "bsf,fd->bsd", jax.nn.silu(gu[..., :f]) * gu[..., f:],
                w_out)


def _moe(sz, prec, h, fp):
    """The held experts' part of the routed sum plus the shared MLP, and
    the experts each token chose (B, S, top_k)."""
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


def _layer(sz, prec, x, lp, kind: str):
    """(x after the layer, the experts each token chose)."""
    # the weights turn float32 only once the previous layer's output exists
    x, lp = jax.lax.optimization_barrier((x, lp))
    lp = _f32(lp)
    eps, r = sz["norm_eps"], sz["residual_multiplier"]
    mixer = _mamba2 if kind == "mamba2" else _attention
    x = x + r * mixer(sz, prec, _rms(x, lp["mixer_norm"]["scale"], eps),
                      lp["mixer"])
    y, top_i = _moe(sz, prec, _rms(x, lp["ffn_norm"]["scale"], eps),
                    lp["ffn"])
    return x + r * y, top_i


def forward(sz: Dict, params, tokens, prec: str = "f32"):
    """Final-norm hidden states (B, S, D) of ``tokens`` (B, S), and the
    experts each token chose in each layer, period by period (periods,
    layers of a period, B, S, top_k)."""
    x = params["embed"]["tok"][tokens].astype(jnp.float32) \
        * sz["embedding_multiplier"]

    def period(x, lps):
        chosen = []
        for kind, lp in zip(sz["period"], lps):
            x, top_i = _layer(sz, prec, x, lp, kind)
            chosen.append(top_i)
        return x, jnp.stack(chosen)

    x, chosen = jax.lax.scan(period, x, params["period"])
    return _rms(x, params["out_norm"]["scale"].astype(jnp.float32),
                sz["norm_eps"]), chosen


def hidden(sz: Dict, params, tokens, prec: str = "f32"):
    """Final-norm hidden states (B, S, D) of ``tokens`` (B, S)."""
    return forward(sz, params, tokens, prec)[0]


def logits(sz: Dict, params, h, prec: str = "f32"):
    return _ein(prec, "...d,vd->...v", h, params["embed"]["tok"]) \
        / sz["logits_scaling"]


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
