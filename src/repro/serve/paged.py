"""Paged KV cache: block-table indexing for uneven-length decode batches.

The KV cache lives in a shared pool of fixed-size pages indexed through
per-sequence block tables (``paged_decode_attention`` / ``paged_write_kv`` /
``PagedKVCache``), so the serve engine's slot lifecycle can batch sequences
of wildly uneven length without reserving (max_batch, max_seq) dense storage
per slot. Page size routes through the kernel autotune table
(``kernels/autotune.py``).
"""
from __future__ import annotations

from typing import Dict, Hashable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import decode_attention_ref


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
