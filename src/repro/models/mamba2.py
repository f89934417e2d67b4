"""Mamba-2 mixer (SSD, arXiv:2405.21060), as Granite-4.0-H's hybrid layers
use it.

One input projection gives the gate z (d_inner), x, B and C together (d_inner
+ 2 G N) and one dt per head. A causal depthwise conv with bias and SiLU runs
over x, B and C; dt = softplus(dt + dt_bias); each head has one scalar
A = -exp(A_log). The recurrence of each head is

    h <- exp(dt A) h + dt x B^T,    y = h C + D x

with h of (head_dim, d_state) and B, C shared by the heads of a group. Then
y * silu(z) goes through an RMSNorm over all of d_inner and the output
projection.

Prefill runs the chunked SSD algorithm: within a chunk of ``chunk_size``
positions a masked C B^T weighted by the cumulative decays (the quadratic,
attention-like form), between chunks the state is passed on, one chunk at a
time (``lax.scan``). A position whose dt is 0 is an identity step, so
``mamba2_mixer`` with ``lengths`` gives each row's state after its own
length. Decode is one recurrent step over the cached (conv window, SSM
state). The recurrence and its state are float32; projections run in the
compute dtype. ``mamba2.mix`` names the projections, the conv and the gated
norm in a trace, ``mamba2.ssd`` the recurrence.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.mamba import _causal_conv, gather_window
from repro.models.params import ParamDef

F32 = jnp.float32


def _dims(cfg: ModelConfig):
    m = cfg.mamba2
    conv_dim = m.d_inner + 2 * m.n_groups * m.d_state
    return m.n_heads, m.head_dim, m.d_state, m.n_groups, conv_dim


def mamba2_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    D, m = cfg.d_model, cfg.mamba2
    H, _, _, _, conv_dim = _dims(cfg)
    return {
        # [z | x B C | dt]
        "w_in": ParamDef((D, m.d_inner + conv_dim + H), ("embed", "dinner")),
        "conv_w": ParamDef((conv_dim, m.d_conv), ("dinner", "conv"),
                           scale=1.0),
        "conv_b": ParamDef((conv_dim,), ("dinner",), init="zeros"),
        "dt_bias": ParamDef((H,), ("heads",), init="ssm_dt"),
        "a_log": ParamDef((H,), ("heads",), init="ssm_a"),
        "d_skip": ParamDef((H,), ("heads",), init="ones"),
        "norm": ParamDef((m.d_inner,), ("dinner",), init="ones"),
        "w_out": ParamDef((m.d_inner, D), ("dinner", "embed")),
    }


def mamba2_init_cache(cfg: ModelConfig, batch: int) -> Dict:
    H, P, N, _, conv_dim = _dims(cfg)
    return {"conv": jnp.zeros((batch, cfg.mamba2.d_conv - 1, conv_dim),
                              jnp.bfloat16),
            "ssm": jnp.zeros((batch, H, P, N), F32)}


def _split(cfg: ModelConfig, zxbcdt: jax.Array):
    """(z, xBC, dt) of the input projection's output."""
    di = cfg.mamba2.d_inner
    conv_dim = _dims(cfg)[4]
    return jnp.split(zxbcdt, [di, di + conv_dim], -1)


def _xbc(cfg: ModelConfig, xbc: jax.Array):
    """x (..., H, P), B and C (..., G, N) of the conv's output, float32."""
    H, P, N, G, _ = _dims(cfg)
    di = cfg.mamba2.d_inner
    x, b, c = jnp.split(xbc.astype(F32), [di, di + G * N], -1)
    lead = xbc.shape[:-1]
    return (x.reshape(lead + (H, P)), b.reshape(lead + (G, N)),
            c.reshape(lead + (G, N)))


def _dt(p: Dict, dt_raw: jax.Array) -> jax.Array:
    return jax.nn.softplus(dt_raw.astype(F32) + p["dt_bias"].astype(F32))


def _gated_out(cfg: ModelConfig, p: Dict, y: jax.Array, z: jax.Array,
               dtype) -> jax.Array:
    """RMSNorm(y * silu(z)) over all of d_inner, then the output projection.
    y: (..., H, P) float32; z: (..., d_inner)."""
    y = y.reshape(z.shape) * jax.nn.silu(z.astype(F32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
    y = (y * p["norm"].astype(F32)).astype(dtype)
    return y @ p["w_out"].astype(dtype)


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, chunk: int,
                h0: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """The SSD recurrence over whole sequences, in chunks.

    x: (B, S, H, P); dt: (B, S, H), 0 where a position is to be skipped;
    a: (H,) (A, negative); b, c: (B, S, G, N), the H heads in G groups.
    Returns y (B, S, H, P) without the D skip, and the state after the
    last position (B, H, P, N), all float32."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    q = min(chunk, s)
    pad = -s % q
    if pad:
        def padded(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, b, c = padded(x), padded(dt), padded(b), padded(c)
    nc = (s + pad) // q
    hg = h // g

    def chunks(t):                      # (B, S, ...) -> (nc, B, q, ...)
        return jnp.moveaxis(t.reshape((bsz, nc, q) + t.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((q, q), bool))

    def step(state, xs):
        x, dt, b, c = xs
        la = jnp.moveaxis(dt * a, 1, 2)                # (B, H, q) log decay
        cum = jnp.cumsum(la, -1)
        # decay from position s to position l >= s within the chunk
        seg = jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                        -jnp.inf)
        w = jnp.einsum("blgn,bsgn->bgls", c, b)[:, :, None] * jnp.exp(
            seg).reshape(bsz, g, hg, q, q)
        xdt = (x * dt[..., None]).reshape(bsz, q, g, hg, p)
        y = jnp.einsum("bgjls,bsgjp->blgjp", w, xdt)
        # what the state passed in from earlier chunks gives each position
        st = state.reshape(bsz, g, hg, p, n)
        y = y + jnp.einsum("blgn,bgjpn->blgjp", c, st) * jnp.exp(
            cum).reshape(bsz, g, hg, q).transpose(0, 3, 1, 2)[..., None]
        to_end = jnp.exp(cum[..., -1:] - cum).reshape(bsz, g, hg, q)
        st = st * jnp.exp(cum[..., -1]).reshape(bsz, g, hg)[..., None, None] \
            + jnp.einsum("bsgjp,bgjs,bsgn->bgjpn", xdt, to_end, b)
        return st.reshape(bsz, h, p, n), y.reshape(bsz, q, h, p)

    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), F32)
    state, ys = jax.lax.scan(step, h0, tuple(map(chunks, (x, dt, b, c))))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, nc * q, h, p)[:, :s]
    return y, state


def mamba2_mixer(cfg: ModelConfig, p: Dict, x: jax.Array,
                 lengths: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Dict]:
    """Full-sequence forward. x: (B, S, D). Returns (y, cache): the cache
    (``mamba2_init_cache``'s layout) holds each row's conv window and SSM
    state after its first ``lengths`` positions (all S without)."""
    bsz, s, _ = x.shape
    m = cfg.mamba2
    if lengths is None:
        lengths = jnp.full((bsz,), s, jnp.int32)
    with jax.named_scope("mamba2.mix"):
        z, xbc, dt_raw = _split(cfg, x @ p["w_in"].astype(x.dtype))
        conv = _causal_conv(xbc.astype(F32), p["conv_w"].astype(F32),
                            p["conv_b"].astype(F32))
        xs, b, c = _xbc(cfg, jax.nn.silu(conv))
    with jax.named_scope("mamba2.ssd"):
        dt = _dt(p, dt_raw)
        dt = jnp.where((jnp.arange(s)[None, :] < lengths[:, None])[..., None],
                       dt, 0.0)
        a = -jnp.exp(p["a_log"].astype(F32))
        y, state = ssd_chunked(xs, dt, a, b, c, m.chunk_size)
        y = y + xs * p["d_skip"].astype(F32)[:, None]
    with jax.named_scope("mamba2.mix"):
        out = _gated_out(cfg, p, y, z, x.dtype)
    cache = {"conv": gather_window(xbc, lengths, m.d_conv - 1
                                   ).astype(jnp.bfloat16),
             "ssm": state}
    return out, cache


def mamba2_decode(cfg: ModelConfig, p: Dict, x: jax.Array, cache: Dict
                  ) -> Tuple[jax.Array, Dict]:
    """One-token step. x: (B, 1, D); cache: {conv (B, d_conv-1, conv_dim),
    ssm (B, H, P, N)}, read and replaced whole."""
    H, P, N, G, _ = _dims(cfg)
    bsz = x.shape[0]
    with jax.named_scope("mamba2.mix"):
        z, xbc, dt_raw = _split(cfg, x[:, 0] @ p["w_in"].astype(x.dtype))
        window = jnp.concatenate([cache["conv"].astype(xbc.dtype),
                                  xbc[:, None]], axis=1)
        conv = jnp.einsum("bkc,ck->bc", window.astype(F32),
                          p["conv_w"].astype(F32)) + p["conv_b"].astype(F32)
        xs, b, c = _xbc(cfg, jax.nn.silu(conv))
    with jax.named_scope("mamba2.ssd"):
        dt = _dt(p, dt_raw)                                     # (B, H)
        decay = jnp.exp(dt * -jnp.exp(p["a_log"].astype(F32)))
        hg = H // G
        st = cache["ssm"].reshape(bsz, G, hg, P, N)
        xdt = (xs * dt[..., None]).reshape(bsz, G, hg, P)
        st = st * decay.reshape(bsz, G, hg)[..., None, None] \
            + xdt[..., None] * b[:, :, None, None, :]
        y = jnp.einsum("bgjpn,bgn->bgjp", st, c).reshape(bsz, H, P)
        y = y + xs * p["d_skip"].astype(F32)[:, None]
    with jax.named_scope("mamba2.mix"):
        out = _gated_out(cfg, p, y, z, x.dtype)[:, None]
    return out, {"conv": window[:, 1:].astype(jnp.bfloat16),
                 "ssm": st.reshape(bsz, H, P, N)}
