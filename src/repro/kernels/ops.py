"""Jit'd dispatch wrappers around the Pallas kernels.

``backend`` resolution: 'pallas' (real TPU), 'interpret' (CPU validation of
the same kernel body), 'xla' (pure-jnp fallback / oracle). The model stack
calls these through RunFlags.backend.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.rmsnorm import rmsnorm_tpu, rmsnorm_residual_tpu


@partial(jax.jit, static_argnames=("causal", "interpret", "block_q",
                                   "block_k", "pad_to", "scale"))
def _flash_bhsd(q, k, v, causal: bool, interpret: bool, block_q: int,
                block_k: int, pad_to: int, scale: Optional[float] = None):
    # pad_to > S only when the autotune table chose non-dividing blocks for
    # a causal call; end-padding the keys is exact there (padded rows sit
    # strictly above the diagonal of every real query row).  The no-entry
    # path arrives with pad_to == S and the legacy fixed blocks, tracing
    # the exact pre-autotune computation.
    S = q.shape[2]
    if pad_to > S:
        cfg = ((0, 0), (0, 0), (0, pad_to - S), (0, 0))
        q, k, v = jnp.pad(q, cfg), jnp.pad(k, cfg), jnp.pad(v, cfg)
    o = flash_attention_tpu(q, k, v, causal=causal, block_q=block_q,
                            block_k=block_k, interpret=interpret, scale=scale)
    return o[:, :, :S] if pad_to > S else o


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    interpret: bool = False,
                    scale: Optional[float] = None) -> jax.Array:
    """q,k,v: (B,S,H,D) (kv pre-repeated to H heads). Returns (B,S,H,D);
    the scores are scaled by ``scale`` (default ``D ** -0.5``).

    Dense causal/full attention only: the kernel has no ragged-``lengths``
    mask, so ``models.attention.self_attention`` sends ragged calls to the
    XLA path itself.
    """
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq, bk, pad_to, _ = autotune.plan_flash(qt.shape, qt.dtype, causal=causal)
    o = _flash_bhsd(qt, kt, vt, causal, interpret, bq, bk, pad_to, scale)
    return o.transpose(0, 2, 1, 3)


def rmsnorm(x: jax.Array, w: jax.Array, *, backend: str,
            eps: float = 1e-5) -> jax.Array:
    """x: (..., D). Fused RMSNorm. ``backend``: pallas | interpret | xla."""
    if backend == "xla":
        return _ref.rmsnorm_ref(x, w, eps=eps)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    rows, _ = autotune.plan_rmsnorm(x2.shape, x2.dtype)
    y = rmsnorm_tpu(x2, w, eps=eps, block_rows=rows,
                    interpret=(backend == "interpret"))
    return y.reshape(shape)


def rmsnorm_residual(x: jax.Array, residual: jax.Array, w: jax.Array, *,
                     backend: str, eps: float = 1e-5):
    if backend == "xla":
        return _ref.rmsnorm_residual_ref(x, residual, w, eps=eps)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    rows, _ = autotune.plan_rmsnorm(x2.shape, x2.dtype)
    y, s = rmsnorm_residual_tpu(x2, residual.reshape(-1, shape[-1]), w,
                                eps=eps, block_rows=rows,
                                interpret=(backend == "interpret"))
    return y.reshape(shape), s.reshape(shape)
