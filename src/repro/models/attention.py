"""GQA attention: projections, RoPE, flash-style chunked attention (XLA path),
Pallas-kernel dispatch, and KV-cache decode: the single-shard write and
attend live here, and the sequence-sharded decode they hand over to when the
mesh splits the sequence lives in ``repro.parallel.decode_attn``.

The XLA path implements online-softmax over (q-chunk × kv-chunk) tiles, so
32k prefill never materializes an S×S score matrix: the q-chunks are
unrolled, the kv-tiles of each are a ``lax.scan``. Causal tile-skipping is
static: above-diagonal tiles are never emitted.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef
from repro.models.layers import apply_rope, rms_head_norm, scan_unroll
from repro.parallel.decode_attn import live_seq_axes, sharded_decode_attention

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter defs
# ---------------------------------------------------------------------------

def attn_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    H, KV, HD, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    out = {
        "wq": ParamDef((D, H * HD), ("embed", "heads")),
        "wkv": ParamDef((D, 2 * KV * HD), ("embed", "kv_heads")),
        "wo": ParamDef((H * HD, D), ("heads", "embed")),
    }
    if cfg.use_bias or cfg.qkv_bias:
        out["bq"] = ParamDef((H * HD,), ("heads",), init="zeros")
        out["bkv"] = ParamDef((2 * KV * HD,), ("kv_heads",), init="zeros")
    if cfg.use_bias:
        out["bo"] = ParamDef((D,), ("embed_nofsdp",), init="zeros")
    if cfg.qk_norm:
        out["q_norm"] = ParamDef((HD,), ("head_dim",), init="ones")
        out["k_norm"] = ParamDef((HD,), ("head_dim",), init="ones")
    return out


def project_qkv(cfg: ModelConfig, p: Dict, x: jax.Array, positions: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) -> q (B,S,H,HD), k/v (B,S,KV,HD), RoPE applied."""
    B, S, _ = x.shape
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    q = x @ p["wq"].astype(dt)
    kv = x @ p["wkv"].astype(dt)
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        kv = kv + p["bkv"].astype(dt)
    q = q.reshape(B, S, H, HD)
    kv = kv.reshape(B, S, 2, KV, HD)
    k, v = kv[:, :, 0], kv[:, :, 1]
    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_head_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def output_proj(cfg: ModelConfig, p: Dict, o: jax.Array) -> jax.Array:
    B, S = o.shape[:2]
    y = o.reshape(B, S, cfg.n_heads * cfg.head_dim) @ p["wo"].astype(o.dtype)
    if "bo" in p:
        y = y + p["bo"].astype(o.dtype)
    return y


# ---------------------------------------------------------------------------
# Flash-style chunked attention (XLA path; also the ref for the Pallas kernel)
# ---------------------------------------------------------------------------

def _chunk_sizes(S: int, chunk: int, max_chunks: int) -> int:
    n = -(-S // chunk)
    if n > max_chunks:
        chunk = -(-S // max_chunks)
        chunk = -(-chunk // 128) * 128 if chunk >= 128 else chunk
    return min(chunk, S)


def flash_attention_xla(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        q_offset: int = 0,
                        lengths: Optional[jax.Array] = None,
                        chunk: int = 1024,
                        max_chunks: int = 16,
                        q_chunks: int = 4,
                        unroll: bool = False,
                        scale: Optional[float] = None) -> jax.Array:
    """q: (B,Sq,H,HD); k,v: (B,Sk,H,HD) (kv already repeated to H heads).

    Online-softmax over a static (q-tile, kv-tile) grid; above-diagonal tiles
    are statically skipped (per q-tile the kv scan covers only the causal
    prefix). ``q_offset`` is the absolute position of q[0].

    The kv-tile loop is a ``lax.scan`` (one tile of temp memory);
    ``unroll=True`` unrolls it so the dry-run's roofline variants get true
    FLOP counts (a rolled scan's body is counted once).
    ``scale`` multiplies the scores (default ``HD ** -0.5``).
    """
    B, Sq, H, HD = q.shape
    Sk = k.shape[1]
    ck = _chunk_sizes(Sk, chunk, max_chunks)
    nk = -(-Sk // ck)
    if Sk % ck:                       # scan needs uniform tiles
        pad = nk * ck - Sk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if lengths is None:
            lengths = jnp.full((B,), Sk, jnp.int32)
    nq = min(q_chunks, Sq) if causal and Sq > 1 else 1
    while Sq % nq:
        nq -= 1
    cq = Sq // nq
    if scale is None:
        scale = 1.0 / math.sqrt(HD)

    def tile(q_blk, q_lo, carry, k_lo, k_blk, v_blk):
        m, l, acc = carry
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk,
                       preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jnp.arange(cq)
        kpos = k_lo + jnp.arange(k_blk.shape[1])
        mask = jnp.ones((B, 1, cq, k_blk.shape[1]), bool)
        if causal:
            mask = mask & (qpos[:, None] >= kpos[None, :])[None, None]
        if lengths is not None:
            mask = mask & (kpos[None, None, None, :]
                           < lengths[:, None, None, None])
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    outs = []
    for qi in range(nq):
        q_blk = jax.lax.slice_in_dim(q, qi * cq, (qi + 1) * cq, axis=1)
        q_lo = q_offset + qi * cq
        q_hi = q_lo + cq - 1                      # max absolute q position
        # only the causal prefix of kv tiles is visited (static skip)
        nk_q = nk if not causal else min(nk, (q_hi // ck) + 1)
        m = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        l = jnp.zeros((B, H, cq), jnp.float32)
        acc = jnp.zeros((B, H, cq, HD), jnp.float32)

        def body(carry, ki):
            k_blk = jax.lax.dynamic_slice_in_dim(k, ki * ck, ck, 1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, ki * ck, ck, 1)
            return tile(q_blk, q_lo, carry, ki * ck, k_blk, v_blk), None

        (m, l, acc), _ = jax.lax.scan(body, (m, l, acc), jnp.arange(nk_q),
                                      unroll=scan_unroll(unroll, nk_q))
        o = acc / jnp.maximum(l[..., None], 1e-30)
        outs.append(o.astype(q.dtype))
    o = jnp.concatenate(outs, axis=2) if len(outs) > 1 else outs[0]
    return o.transpose(0, 2, 1, 3)                # (B, Sq, H, HD)


def repeat_kv(x: jax.Array, q_per_kv: int) -> jax.Array:
    if q_per_kv == 1:
        return x
    B, S, KV, HD = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (B, S, KV, q_per_kv, HD)
                            ).reshape(B, S, KV * q_per_kv, HD)


def self_attention(cfg: ModelConfig, p: Dict, x: jax.Array,
                   positions: jax.Array, *,
                   lengths: Optional[jax.Array] = None,
                   backend: str = "xla",
                   unroll: bool = False
                   ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Training / prefill self-attention. Returns (output, (k, v)) so the
    caller can populate a KV cache during prefill."""
    q, k, v = project_qkv(cfg, p, x, positions)
    kf = repeat_kv(k, cfg.q_per_kv)
    vf = repeat_kv(v, cfg.q_per_kv)
    # the Pallas kernel has no ragged-length mask: ragged calls (every serve
    # prefill) take the XLA path on every backend
    if backend in ("pallas", "interpret") and lengths is None:
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, kf, vf, causal=True,
                                 interpret=(backend == "interpret"),
                                 scale=cfg.attn_scale or None)
    else:
        o = flash_attention_xla(q, kf, vf, causal=True, lengths=lengths,
                                chunk=cfg.attn_chunk,
                                max_chunks=cfg.max_attn_chunks, unroll=unroll,
                                scale=cfg.attn_scale or None)
    return output_proj(cfg, p, o), (k, v)


# ---------------------------------------------------------------------------
# Decode: write the new token, then attend. Sequence-sharded version:
# repro.parallel.decode_attn
# ---------------------------------------------------------------------------

def decode_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         lengths: jax.Array, scale: Optional[float] = None
                         ) -> jax.Array:
    """q: (B,H,HD); caches: (B,S,KV,HD); lengths (B,) = #valid positions
    (including the token just written). Grouped GQA, full softmax; the
    scores are scaled by ``scale`` (default ``HD ** -0.5``)."""
    B, H, HD = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, HD)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(HD) if scale is None else s * scale
    kpos = jnp.arange(k_cache.shape[1])
    s = jnp.where(kpos[None, None, None, :] < lengths[:, None, None, None],
                  s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", w.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, HD).astype(q.dtype)


def write_kv_cache(k_cache: jax.Array, v_cache: jax.Array,
                   k_new: jax.Array, v_new: jax.Array,
                   lengths: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Insert one new (k, v) per sequence at its current length.
    k_new/v_new: (B, KV, HD); caches (B, S, KV, HD)."""
    def one(kc, vc, kn, vn, pos):
        kc = jax.lax.dynamic_update_slice_in_dim(kc, kn[None], pos, axis=0)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, vn[None], pos, axis=0)
        return kc, vc
    return jax.vmap(one)(k_cache, v_cache, k_new, v_new, lengths)


def write_kv_token(cache: jax.Array, new: jax.Array, lengths: jax.Array,
                   layer) -> jax.Array:
    """Insert one new entry per sequence at its current length into one
    layer of a stacked cache: ``cache[layer, b, lengths[b]] = new[b]``.
    cache: (L, B, S, ...); new: (B, ...). A length at or past S writes
    position S-1, as ``write_kv_cache``'s dynamic_update_slice clamps.
    On a buffer the program owns (carried through the layer loop, donated
    by its caller) XLA scatters in place: B entries, not L layers."""
    B, S = cache.shape[1:3]
    pos = jnp.clip(lengths, 0, S - 1)
    return cache.at[layer, jnp.arange(B), pos].set(
        new.astype(cache.dtype), mode="promise_in_bounds")


def decode_self_attention(cfg: ModelConfig, p: Dict, x: jax.Array,
                          cache: Dict, lengths: jax.Array, *,
                          layer=None,
                          seq_axes: Tuple[str, ...] = (),
                          batch_axes: Tuple[str, ...] = (),
                          ) -> Tuple[jax.Array, Dict]:
    """One decode step. x: (B, 1, D). cache: {"k": (B,S,KV,HD), "v": ...}.
    ``lengths`` counts tokens already in the cache (new token goes at index
    lengths, and attends to itself).

    On one shard the token is written (``write_kv_cache``) and the layer
    attends (``decode_attention_ref``). With ``layer``, ``cache`` holds
    every layer's keys and values stacked, (L,B,S,KV,HD): the token is
    written at ``[layer]`` with ``write_kv_token``, that layer attends, and
    the whole stack is returned for the caller's layer loop to carry, bit
    for bit what the per-layer write computes. When the mesh splits the
    sequence over ``seq_axes`` (``live_seq_axes``), the sharded decode
    writes and attends instead."""
    q, k, v = project_qkv(cfg, p, x, lengths[:, None])
    q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]
    scale = cfg.attn_scale or None
    seq_axes = live_seq_axes(seq_axes)
    if seq_axes:
        o, kc, vc = sharded_decode_attention(
            q1, cache["k"], cache["v"], k1, v1, lengths, seq_axes=seq_axes,
            batch_axes=batch_axes, scale=scale)
    elif layer is None:
        kc, vc = write_kv_cache(cache["k"], cache["v"], k1, v1, lengths)
        o = decode_attention_ref(q1, kc, vc, lengths + 1, scale)
    else:
        kc = write_kv_token(cache["k"], k1, lengths, layer)
        vc = write_kv_token(cache["v"], v1, lengths, layer)
        o = decode_attention_ref(q1, kc[layer], vc[layer], lengths + 1, scale)
    y = output_proj(cfg, p, o[:, None])
    return y, {"k": kc, "v": vc}
