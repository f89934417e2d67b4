"""Sequence-sharded decode attention (TPU flash-decoding over ICI).

At decode time the KV cache is sharded along the *sequence* axis across the
``model`` mesh axis (and optionally ``data``/``pod`` for the 500k-context
cells where batch=1 cannot use the data axis). Each shard computes a partial
online-softmax over its local KV slice; partials combine with one ``pmax`` +
two ``psum`` of (B, H)-sized tensors — O(B·H·HD) bytes on the wire instead of
all-gathering the cache.

This is the TPU-idiomatic analogue of GPU flash-decoding: instead of SM-level
split-K with shared-memory reductions, we split along sequence across chips
and reduce over ICI.

``live_seq_axes`` says whether the mesh really splits the sequence. The
model's decode (``models/attention.py`` for keys and values,
``models/mla.py`` for MLA latents) calls the sharded functions here only
when it does, and otherwise writes and attends on one shard itself. This
module imports nothing from ``repro.models``.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.compat import P

NEG_INF = -1e30


def _ambient_mesh(mesh):
    if mesh is not None:
        return mesh
    m = compat.get_abstract_mesh()
    if m is None or not m.axis_names:
        raise ValueError("sharded decode attention needs a mesh "
                         "(jax.set_mesh(...) or pass mesh=)")
    return m


def _live(axes: Tuple[str, ...], mesh) -> Tuple[str, ...]:
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    return tuple(a for a in axes if sizes.get(a, 1) > 1)


def live_seq_axes(seq_axes: Tuple[str, ...], mesh=None) -> Tuple[str, ...]:
    """The axes of ``seq_axes`` that really split the sequence: those the
    mesh (``mesh``, else the ambient one) holds with more than one device.
    Empty ``seq_axes`` give () without looking for a mesh: one shard."""
    if not seq_axes:
        return ()
    return _live(seq_axes, _ambient_mesh(mesh))


def _write_row(cache_row, new_row, idx, in_range):
    upd = jax.lax.dynamic_update_slice_in_dim(
        cache_row, new_row[None].astype(cache_row.dtype), idx, axis=0)
    return jnp.where(in_range, upd, cache_row)


def _local_write(k_loc, v_loc, k_new, v_new, lengths, offset):
    """Insert each row's new (k,v) if its write position lands in this shard.
    k_loc/v_loc: (B, S_loc, KV, HD); k_new/v_new: (B, KV, HD)."""
    S_loc = k_loc.shape[1]
    idx = lengths - offset
    in_range = (idx >= 0) & (idx < S_loc)
    idx_c = jnp.clip(idx, 0, S_loc - 1)

    def one(kc, vc, kn, vn, i, ok):
        return (_write_row(kc, kn, i, ok), _write_row(vc, vn, i, ok))

    return jax.vmap(one)(k_loc, v_loc, k_new, v_new, idx_c,
                         in_range[:, None, None])


def sharded_decode_attention(q: jax.Array, k_cache: jax.Array,
                             v_cache: jax.Array, k_new: jax.Array,
                             v_new: jax.Array, lengths: jax.Array, *,
                             seq_axes: Tuple[str, ...] = ("model",),
                             batch_axes: Tuple[str, ...] = ("data",),
                             mesh=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q: (B, H, HD); caches: (B, S, KV, HD); k_new/v_new: (B, KV, HD);
    lengths: (B,) tokens already cached (new token appended, attends to self).

    Returns (o (B,H,HD), k_cache', v_cache'). ``seq_axes`` are live
    (``live_seq_axes``).
    """
    mesh = _ambient_mesh(mesh)
    axis_sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    batch_axes = _live(batch_axes, mesh)
    B, H, HD = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(HD)
    S = k_cache.shape[1]
    n_shards = math.prod(axis_sizes[a] for a in seq_axes)
    S_loc = S // n_shards
    bspec = tuple(batch_axes) if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)

    def local(q, k_loc, v_loc, k_new, v_new, lengths):
        shard = jax.lax.axis_index(seq_axes)
        offset = shard * S_loc
        k_loc, v_loc = _local_write(k_loc, v_loc, k_new, v_new, lengths, offset)
        qg = q.reshape(-1, KV, G, HD)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, k_loc,
                       preferred_element_type=jnp.float32) * scale
        kpos = offset + jnp.arange(S_loc)
        mask = kpos[None, None, None, :] < (lengths + 1)[:, None, None, None]
        s = jnp.where(mask, s, NEG_INF)
        m_loc = s.max(-1)                                     # (B,KV,G)
        m_glob = jax.lax.pmax(m_loc, seq_axes)
        p = jnp.exp(s - m_glob[..., None])
        l = jax.lax.psum(p.sum(-1), seq_axes)
        o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_loc.dtype), v_loc,
                       preferred_element_type=jnp.float32)
        o = jax.lax.psum(o, seq_axes)
        o = o / jnp.maximum(l[..., None], 1e-30)
        return o.reshape(-1, H, HD).astype(q.dtype), k_loc, v_loc

    seq_spec = tuple(seq_axes) if len(seq_axes) > 1 else seq_axes[0]
    f = compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, None), P(bspec, seq_spec, None, None),
                  P(bspec, seq_spec, None, None), P(bspec, None, None),
                  P(bspec, None, None), P(bspec)),
        out_specs=(P(bspec, None, None), P(bspec, seq_spec, None, None),
                   P(bspec, seq_spec, None, None)),
        check_vma=False)
    return f(q, k_cache, v_cache, k_new, v_new, lengths)


def sharded_mla_decode(q_lat: jax.Array, q_rope: jax.Array,
                       ckv_cache: jax.Array, kr_cache: jax.Array,
                       ckv_new: jax.Array, kr_new: jax.Array,
                       lengths: jax.Array, *,
                       sm_scale: float,
                       seq_axes: Tuple[str, ...] = ("model",),
                       batch_axes: Tuple[str, ...] = ("data",),
                       mesh=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Absorbed MLA decode over a sequence-sharded compressed cache.

    q_lat: (B, H, R)   — q_nope absorbed through W_uk into latent space
    q_rope: (B, H, DR) — rope part of the query
    ckv_cache: (B, S, R); kr_cache: (B, S, DR) (rope key, shared across heads)
    Returns (ctx (B, H, R) — latent context, caller applies W_uv —, caches').
    ``seq_axes`` are live (``live_seq_axes``).
    """
    mesh = _ambient_mesh(mesh)
    axis_sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    batch_axes = _live(batch_axes, mesh)

    def write(cache, new, lengths, offset):
        S_loc = cache.shape[1]
        idx = lengths - offset
        ok = (idx >= 0) & (idx < S_loc)
        return jax.vmap(_write_row)(cache, new, jnp.clip(idx, 0, S_loc - 1),
                                    ok[:, None])

    S = ckv_cache.shape[1]
    n_shards = math.prod(axis_sizes[a] for a in seq_axes)
    S_loc = S // n_shards
    bspec = tuple(batch_axes) if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)

    def local(q_lat, q_rope, ckv_loc, kr_loc, ckv_new, kr_new, lengths):
        shard = jax.lax.axis_index(seq_axes)
        offset = shard * S_loc
        ckv_loc = write(ckv_loc, ckv_new, lengths, offset)
        kr_loc = write(kr_loc, kr_new, lengths, offset)
        s = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv_loc,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhd,bsd->bhs", q_rope, kr_loc,
                          preferred_element_type=jnp.float32)) * sm_scale
        kpos = offset + jnp.arange(S_loc)
        mask = kpos[None, None, :] < (lengths + 1)[:, None, None]
        s = jnp.where(mask, s, NEG_INF)
        m_loc = s.max(-1)
        m_glob = jax.lax.pmax(m_loc, seq_axes)
        p = jnp.exp(s - m_glob[..., None])
        l = jax.lax.psum(p.sum(-1), seq_axes)
        ctx = jnp.einsum("bhs,bsr->bhr", p.astype(ckv_loc.dtype), ckv_loc,
                         preferred_element_type=jnp.float32)
        ctx = jax.lax.psum(ctx, seq_axes) / jnp.maximum(l[..., None], 1e-30)
        return ctx.astype(q_lat.dtype), ckv_loc, kr_loc

    seq_spec = tuple(seq_axes) if len(seq_axes) > 1 else seq_axes[0]
    f = compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, None), P(bspec, None, None),
                  P(bspec, seq_spec, None), P(bspec, seq_spec, None),
                  P(bspec, None), P(bspec, None), P(bspec)),
        out_specs=(P(bspec, None, None), P(bspec, seq_spec, None),
                   P(bspec, seq_spec, None)),
        check_vma=False)
    return f(q_lat, q_rope, ckv_cache, kr_cache, ckv_new, kr_new, lengths)
