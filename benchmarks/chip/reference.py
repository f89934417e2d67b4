"""Plain reference of the dense GQA decoder both configurations state.

Straightforward ``jax.numpy``: RMSNorm, rotary embeddings (rotate-half),
grouped-query causal attention with a full softmax, SwiGLU, untied
unembedding; AdamW with global-norm clipping and warm-up + cosine. No
cache, no kernels, no batching beyond what the caller passes. It imports
nothing of the program: it reads the weights the benchmark made, by the
leaf names of the layout the program is handed.

``prec`` picks the arithmetic of every matrix product:
  "f32"  float32 at ``Precision.HIGHEST`` (the reference);
  "fp8"  operands rounded to float8_e4m3fn with one scale per tensor, then
         multiplied exactly: the control, one precision below the bfloat16
         the configurations compute in.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    """Scaled fp8 rounding of a matrix operand; its cotangent is rounded
    the same way on the way back, as fp8 training scales gradients."""
    return _q8(x)


_fp8.defvjp(lambda x: (_q8(x), None), lambda _, ct: (_q8(ct),))


def _ein(prec: str, spec: str, a, b):
    if prec == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(sz, prec, x, lp):
    """One decoder layer over (B, S, D); ``lp`` holds this layer's leaves."""
    b, s, d = x.shape
    h_, kv, hd = sz["n_heads"], sz["n_kv_heads"], sz["head_dim"]
    g = h_ // kv
    eps, pos = sz["norm_eps"], jnp.arange(s)
    h = _rms(x, lp["mixer_norm"]["scale"], eps)
    q = _ein(prec, "bsd,de->bse", h, lp["mixer"]["wq"]).reshape(b, s, h_, hd)
    kvp = _ein(prec, "bsd,de->bse", h, lp["mixer"]["wkv"])
    kvp = kvp.reshape(b, s, 2, kv, hd)
    k, v = kvp[:, :, 0], kvp[:, :, 1]
    q, k = _rope(q, pos, sz["rope_theta"]), _rope(k, pos, sz["rope_theta"])
    q = q.reshape(b, s, kv, g, hd)
    sc = _ein(prec, "bqkgd,bskd->bkgqs", q, k) / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1)
    o = _ein(prec, "bkgqs,bskd->bqkgd", w, v).reshape(b, s, h_ * hd)
    x = x + _ein(prec, "bse,ed->bsd", o, lp["mixer"]["wo"])
    h = _rms(x, lp["ffn_norm"]["scale"], eps)
    gu = _ein(prec, "bsd,df->bsf", h, lp["ffn"]["w_in"])
    gate, up = gu[..., :sz["d_ff"]], gu[..., sz["d_ff"]:]
    return x + _ein(prec, "bsf,fd->bsd", jax.nn.silu(gate) * up,
                    lp["ffn"]["w_out"])


def hidden(sz: Dict, params, tokens, prec: str = "f32"):
    """Final-norm hidden states (B, S, D) of ``tokens`` (B, S)."""
    x = params["embed"]["tok"].astype(jnp.float32)[tokens]
    layers = jax.tree.map(lambda a: a.astype(jnp.float32),
                          params["period"][0])
    body = jax.checkpoint(lambda x, lp: (_layer(sz, prec, x, lp), None))
    x, _ = jax.lax.scan(body, x, layers)
    return _rms(x, params["out_norm"]["scale"].astype(jnp.float32),
                sz["norm_eps"])


def logits(sz: Dict, params, h, prec: str = "f32"):
    return _ein(prec, "...d,vd->...v", h, params["embed"]["unembed"])


# ---------------------------------------------------------------------------
# Training: loss, gradients in blocks of rows, AdamW
# ---------------------------------------------------------------------------

def _block_sums(sz, prec, params, tokens, labels):
    lg = logits(sz, params, hidden(sz, params, tokens, prec), prec)
    lse = jax.nn.logsumexp(lg, -1)
    ll = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - ll), jnp.sum(lse * lse)


@functools.lru_cache(maxsize=None)
def _grad_fn(sz_items: tuple, prec: str, z_loss: float):
    sz = dict(sz_items)

    def share(params, tokens, labels, n_tokens):
        nll, lse2 = _block_sums(sz, prec, params, tokens, labels)
        return (nll + z_loss * lse2) / n_tokens
    return jax.jit(jax.value_and_grad(share))


def make_grad_fn(sz: Dict, prec: str, z_loss: float, n_tokens: int):
    """(params, tokens, labels) of one block of rows -> (its share of the
    loss, its share of the gradient); shares of all blocks add up to the
    batch's mean loss and gradient."""
    fn = _grad_fn(tuple(sorted(sz.items())), prec, z_loss)
    return lambda p, t, l: fn(p, t, l, jnp.float32(n_tokens))


def lr_at(opt: Dict, step):
    """Linear warm-up to ``lr``, then cosine down to ``min_lr_ratio``."""
    step = jnp.asarray(step, jnp.float32)
    warm = opt["lr"] * step / max(opt["warmup_steps"], 1)
    prog = jnp.clip((step - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    r = opt["min_lr_ratio"]
    cos = opt["lr"] * (r + (1 - r) * 0.5 * (1 + jnp.cos(math.pi * prog)))
    return jnp.where(step < opt["warmup_steps"], warm, cos)


def _adamw(opt, step, params, grads, m, v):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.where(gnorm > opt["clip_norm"],
                      opt["clip_norm"] / (gnorm + 1e-9), 1.0)
    lr = lr_at(opt, step)
    b1, b2 = opt["b1"], opt["b2"]
    b1c, b2c = 1 - b1 ** step, 1 - b2 ** step    # step: float32 scalar

    def upd(p, g, m, v):
        g = g * scale
        m1 = b1 * m + (1 - b1) * g
        v1 = b2 * v + (1 - b2) * g * g
        u = (m1 / b1c) / (jnp.sqrt(v1 / b2c) + opt["eps"])
        # decay every leaf of rank >= 2 as stored (stacked layers included)
        wd = opt["weight_decay"] if p.ndim >= 2 else 0.0
        return p - lr * (u + wd * p), m1, v1

    flat, tdef = jax.tree.flatten(params)
    res = [upd(*t) for t in zip(flat, jax.tree.leaves(grads),
                                jax.tree.leaves(m), jax.tree.leaves(v))]
    new = [jax.tree.unflatten(tdef, [r[i] for r in res]) for i in range(3)]
    return new[0], new[1], new[2], gnorm


@functools.lru_cache(maxsize=None)
def _adamw_fn(opt_items: tuple):
    opt = dict(opt_items)
    return jax.jit(lambda s, p, g, m, v: _adamw(opt, s, p, g, m, v))


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(_leaf_norms(tree), np.float64)


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
_delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))


def train(sz: Dict, opt: Dict, params, batches: Sequence, *,
          prec: str = "f32", rows_per_block: int = 1) -> Dict:
    """Follow the first ``len(batches)`` steps from ``params``.

    Returns each step's loss, the first step's global gradient norm before
    clipping, per-leaf norms of the first gradient and of the first moment
    after step 1, and of the parameters' change after the last step."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    p0 = params
    tokens0 = batches[0][0]
    n_tok = tokens0.shape[0] * tokens0.shape[1]
    grad_fn = make_grad_fn(sz, prec, opt["z_loss"], n_tok)
    adamw = _adamw_fn(tuple(sorted(opt.items())))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    out: Dict = {"loss": []}
    for step, (tok, lab) in enumerate(batches, start=1):
        loss, grads = 0.0, None
        for r in range(0, tok.shape[0], rows_per_block):
            l_b, g_b = grad_fn(params, jnp.asarray(tok[r:r + rows_per_block]),
                               jnp.asarray(lab[r:r + rows_per_block]))
            loss = loss + l_b
            grads = g_b if grads is None else _add(grads, g_b)
        out["loss"].append(float(loss))
        params, m, v, gnorm = adamw(jnp.float32(step), params, grads, m, v)
        if step == 1:
            out["grad_norm"] = float(gnorm)
            out["grad_leaf"] = leaf_norms(grads)
            out["m1_leaf"] = leaf_norms(m)
    out["delta_leaf"] = leaf_norms(_delta(params, p0))
    return out


# ---------------------------------------------------------------------------
# Serving: the gap of each served token below the reference's best
# ---------------------------------------------------------------------------

def make_gap_fn(sz: Dict, max_seq: int, max_new: int, control: bool):
    """jit (params, tokens (1, max_seq), pos (max_new,), served (max_new,))
    -> (gap of the served token, gap of the control's first token)."""
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


def serve_gaps(gap_fn, params, prompt: List[int], served: List[int],
               max_seq: int, max_new: int):
    """Gaps at every served token of one request: the reference reads the
    prompt and the served tokens (teacher forcing); the token served after
    position ``len(prompt) - 1 + i`` is ``served[i]``."""
    seq = list(prompt) + list(served[:-1])
    tokens = np.zeros((1, max_seq), np.int32)
    tokens[0, :len(seq)] = seq
    n = len(served)
    pos = np.zeros(max_new, np.int32)
    pos[:n] = len(prompt) - 1 + np.arange(n)
    tok = np.zeros(max_new, np.int32)
    tok[:n] = served
    g, c = gap_fn(params, jnp.asarray(tokens), jnp.asarray(pos),
                  jnp.asarray(tok))
    return np.asarray(g)[:n], np.asarray(c)[:n]
