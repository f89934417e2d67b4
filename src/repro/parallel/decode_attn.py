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

The module also carries the *paged* decode path (``paged_decode_attention``
/ ``paged_write_kv`` / ``PagedKVCache``): the KV cache lives in a shared
pool of fixed-size pages indexed through per-sequence block tables, so the
serve engine's slot lifecycle can batch sequences of wildly uneven length
without reserving (max_batch, max_seq) dense storage per slot.  Page size
routes through the kernel autotune table (``kernels/autotune.py``).
"""
from __future__ import annotations

import math
from typing import Dict, Hashable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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

    Returns (o (B,H,HD), k_cache', v_cache').
    """
    if seq_axes:
        mesh = _ambient_mesh(mesh)
        axis_sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
        seq_axes = tuple(a for a in seq_axes if axis_sizes.get(a, 1) > 1) or None
        batch_axes = tuple(a for a in batch_axes if axis_sizes.get(a, 1) > 1)
    else:
        seq_axes = None
    B, H, HD = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(HD)
    if seq_axes is None:
        # degenerate mesh: plain single-shard path
        from repro.models.attention import write_kv_cache, decode_attention_ref
        kc, vc = write_kv_cache(k_cache, v_cache, k_new, v_new, lengths)
        return decode_attention_ref(q, kc, vc, lengths + 1), kc, vc

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
    """
    if seq_axes:
        mesh = _ambient_mesh(mesh)
        axis_sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
        seq_axes = tuple(a for a in seq_axes if axis_sizes.get(a, 1) > 1) or None
        batch_axes = tuple(a for a in batch_axes if axis_sizes.get(a, 1) > 1)
    else:
        seq_axes = None
    B, H, R = q_lat.shape

    def write(cache, new, lengths, offset):
        S_loc = cache.shape[1]
        idx = lengths - offset
        ok = (idx >= 0) & (idx < S_loc)
        return jax.vmap(_write_row)(cache, new, jnp.clip(idx, 0, S_loc - 1),
                                    ok[:, None])

    if seq_axes is None:
        ckv = jax.vmap(_write_row)(ckv_cache, ckv_new,
                                   jnp.clip(lengths, 0, ckv_cache.shape[1] - 1),
                                   jnp.ones((B, 1), bool))
        kr = jax.vmap(_write_row)(kr_cache, kr_new,
                                  jnp.clip(lengths, 0, kr_cache.shape[1] - 1),
                                  jnp.ones((B, 1), bool))
        s = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhd,bsd->bhs", q_rope, kr,
                          preferred_element_type=jnp.float32)) * sm_scale
        kpos = jnp.arange(ckv.shape[1])
        s = jnp.where(kpos[None, None, :] < (lengths + 1)[:, None, None], s, NEG_INF)
        w = jax.nn.softmax(s, -1)
        ctx = jnp.einsum("bhs,bsr->bhr", w.astype(ckv.dtype), ckv,
                         preferred_element_type=jnp.float32)
        return ctx.astype(q_lat.dtype), ckv, kr

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


# ---------------------------------------------------------------------------
# Paged KV cache (block-table indexing for uneven-length decode batches)
# ---------------------------------------------------------------------------

def gather_paged_kv(k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Materialize each sequence's pages as a contiguous (B, S, KV, HD) view.

    k_pages/v_pages: (num_pages, page, KV, HD) shared pool;
    block_tables: (B, pages_per_seq) int32 page ids.  S = pages_per_seq*page.
    """
    B, n = block_tables.shape
    page, KV, HD = k_pages.shape[1:]
    k = k_pages[block_tables].reshape(B, n * page, KV, HD)
    v = v_pages[block_tables].reshape(B, n * page, KV, HD)
    return k, v


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array) -> jax.Array:
    """Grouped-GQA decode attention over a paged cache.

    q: (B, H, HD); lengths: (B,) valid tokens per sequence.  Gathers the
    block-table view and runs the exact contiguous reference math, so paged
    and dense caches produce bit-identical outputs for identical contents
    (pinned by tests/test_kernels_autotune.py); stale data in pages beyond
    ``lengths`` is masked out before the softmax.
    """
    from repro.models.attention import decode_attention_ref
    k, v = gather_paged_kv(k_pages, v_pages, block_tables)
    return decode_attention_ref(q, k, v, lengths)


def paged_write_kv(k_pages: jax.Array, v_pages: jax.Array,
                   k_new: jax.Array, v_new: jax.Array,
                   block_tables: jax.Array, lengths: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """Append one token per sequence at logical position ``lengths[b]``.

    k_new/v_new: (B, KV, HD).  The write lands in page
    ``block_tables[b, lengths[b] // page]`` at slot ``lengths[b] % page``;
    positions at or beyond capacity clamp to the last slot (the serve
    engine retires sequences before that, mirroring the dense cache's
    pinned-length contract).
    """
    page = k_pages.shape[1]
    capacity = block_tables.shape[1] * page
    pos = jnp.minimum(lengths, capacity - 1)
    page_idx = jnp.take_along_axis(block_tables,
                                   (pos // page)[:, None], axis=1)[:, 0]
    slot = pos % page
    k_pages = k_pages.at[page_idx, slot].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[page_idx, slot].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


class PagedKVCache:
    """Host-side page pool + block tables for the serve engine's slots.

    Page accounting is deterministic: the free list hands out the
    lowest-numbered pages first and released pages return in reverse order
    (LIFO), so replaying the same admit/retire sequence reproduces the
    same block tables byte-for-byte — the property every committed bench
    snapshot and chaos replay in this repo leans on.
    """

    def __init__(self, *, num_pages: int, page_size: int, num_kv_heads: int,
                 head_dim: int, pages_per_seq: int, dtype=jnp.float32):
        self.page_size = int(page_size)
        self.pages_per_seq = int(pages_per_seq)
        self.k_pages = jnp.zeros((num_pages, page_size, num_kv_heads,
                                  head_dim), dtype)
        self.v_pages = jnp.zeros_like(self.k_pages)
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self.tables: Dict[Hashable, np.ndarray] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def reserve(self, seq: Hashable) -> np.ndarray:
        """Claim ``pages_per_seq`` pages for a new sequence; returns its
        block-table row (int32)."""
        if seq in self.tables:
            raise ValueError(f"sequence {seq!r} already has pages")
        if len(self._free) < self.pages_per_seq:
            raise RuntimeError(
                f"page pool exhausted ({len(self._free)} free, "
                f"{self.pages_per_seq} needed)")
        row = np.array([self._free.pop()
                        for _ in range(self.pages_per_seq)], np.int32)
        self.tables[seq] = row
        return row

    def release(self, seq: Hashable) -> None:
        """Return a retired sequence's pages to the pool (its cache bytes
        stay in place and are masked/overwritten on reuse)."""
        row = self.tables.pop(seq)
        self._free.extend(int(p) for p in reversed(row))

    def block_tables(self, seqs: Sequence[Hashable]) -> jax.Array:
        """Stack the block-table rows for a decode batch, in batch order."""
        return jnp.asarray(np.stack([self.tables[s] for s in seqs]))

    def append(self, seqs: Sequence[Hashable], k_new: jax.Array,
               v_new: jax.Array, lengths: jax.Array) -> None:
        """Write one new token per batched sequence into the pool."""
        bt = self.block_tables(seqs)
        self.k_pages, self.v_pages = paged_write_kv(
            self.k_pages, self.v_pages, k_new, v_new, bt, lengths)

    def attend(self, seqs: Sequence[Hashable], q: jax.Array,
               lengths: jax.Array) -> jax.Array:
        """Decode attention for a batch of resident sequences."""
        bt = self.block_tables(seqs)
        return paged_decode_attention(q, self.k_pages, self.v_pages, bt,
                                      lengths)
