"""Causal flash attention as a Pallas TPU kernel.

TPU adaptation (vs. the CUDA flash-attention design): the (block_q, block_k)
tile sizes are chosen so every matmul hits the 128x128 MXU with full tiles and
the working set (q tile + k/v tiles + f32 accumulators) stays a few MB of
VMEM; the online-softmax running max/denominator live in VMEM scratch shaped
(block_q, 128) (lane-replicated) to respect the (8, 128) vector-register
tiling; above-diagonal tiles are skipped with grid predication (``pl.when``)
rather than warp-level early exit.

Grid: (batch, heads, q_blocks, k_blocks) with the k dimension 'arbitrary'
(sequential) so the accumulator carries across k steps.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, block_q: int, block_k: int,
            n_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = iq * block_q
    k_lo = ik * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[:, 0]                           # (bq,)
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_cur[:, None])
        corr = jnp.exp(m_prev - m_cur)
        l_ref[...] = (l_ref[...] * corr[:, None]
                      + jnp.broadcast_to(p.sum(axis=1)[:, None],
                                         l_ref.shape))
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)

    if causal:
        # skip tiles strictly above the diagonal (grid predication)
        pl.when(k_lo <= q_lo + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_q: int = 512,
                        block_k: int = 512, interpret: bool = False,
                        scale: Optional[float] = None) -> jax.Array:
    """q, k, v: (B, H, S, D) with matching head counts. Returns (B, H, S, D).
    The scores are scaled by ``scale`` (default ``D ** -0.5``).

    S must divide by the chosen block sizes (ops.py pads otherwise).
    """
    B, H, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    while S % block_q:
        block_q //= 2
    while S % block_k:
        block_k //= 2
    n_q = S // block_q
    n_k = S // block_k
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_k=n_k)
    grid = (B, H, n_q, n_k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running denom
            pltpu.VMEM((block_q, D), jnp.float32),        # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out
