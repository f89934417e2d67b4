"""Attention equivalences: chunked online-softmax (XLA flash path) vs naive
softmax; GQA decode reference; MLA absorbed vs naive decode (the naive
form lives here, as the reference)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.ref import attention_ref
from repro.models.attention import (NEG_INF, decode_attention_ref,
                                    flash_attention_xla, repeat_kv,
                                    write_kv_cache)
from repro.models import mla as MLA
from repro.models import model_defs, init_params

# ~42s of wall time: excluded from the default tier-1 run (pytest.ini
# deselects `slow`); run explicitly via `pytest -m slow` / `-m ""`.
pytestmark = pytest.mark.slow


@pytest.mark.parametrize("S,chunk,qc", [(64, 16, 4), (128, 32, 2), (96, 64, 1)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("unroll", [False, True])
def test_flash_xla_matches_naive(S, chunk, qc, causal, unroll):
    key = jax.random.PRNGKey(0)
    B, H, D = 2, 4, 32
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    o = flash_attention_xla(q, k, v, causal=causal, chunk=chunk,
                            max_chunks=64, q_chunks=qc, unroll=unroll)
    r = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=causal
                      ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


def test_flash_xla_ragged_lengths():
    key = jax.random.PRNGKey(1)
    B, S, H, D = 2, 64, 2, 16
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    lengths = jnp.asarray([40, 64], jnp.int32)
    o = flash_attention_xla(q, k, v, causal=True, lengths=lengths, chunk=16)
    # row 1 (full length) must equal the unmasked result
    o_full = flash_attention_xla(q, k, v, causal=True, chunk=16)
    np.testing.assert_allclose(np.asarray(o[1]), np.asarray(o_full[1]),
                               atol=2e-5)
    # row 0 positions < 40 only attend within the first 40 tokens
    o_trunc = flash_attention_xla(q[:, :40], k[:, :40], v[:, :40],
                                  causal=True, chunk=16)
    np.testing.assert_allclose(np.asarray(o[0, :40]), np.asarray(o_trunc[0]),
                               atol=2e-5)


def test_gqa_decode_ref_matches_flash_row():
    """decode_attention_ref at position t == full flash at row t."""
    key = jax.random.PRNGKey(2)
    B, S, KV, G, D = 2, 32, 2, 3, 16
    H = KV * G
    q = jax.random.normal(key, (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.split(key)[0], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(jax.random.split(key)[1], (B, S, KV, D), jnp.float32)
    kf, vf = repeat_kv(k, G), repeat_kv(v, G)
    full = flash_attention_xla(q, kf, vf, causal=True, chunk=8)
    t = S - 1
    o = decode_attention_ref(q[:, t], k, v, jnp.full((B,), S, jnp.int32))
    np.testing.assert_allclose(np.asarray(o), np.asarray(full[:, t]),
                               atol=2e-5)


def test_write_kv_cache_positions():
    B, S, KV, D = 2, 8, 1, 4
    kc = jnp.zeros((B, S, KV, D))
    vc = jnp.zeros((B, S, KV, D))
    kn = jnp.ones((B, KV, D))
    vn = 2 * jnp.ones((B, KV, D))
    lens = jnp.asarray([0, 5])
    kc, vc = write_kv_cache(kc, vc, kn, vn, lens)
    assert float(kc[0, 0].sum()) == KV * D and float(kc[0, 1:].sum()) == 0
    assert float(kc[1, 5].sum()) == KV * D and float(vc[1, 5].sum()) == 2 * KV * D


def naive_mla_decode(cfg, p, x, cache, lengths):
    """MLA decode without absorption: write the latents, rebuild every
    head's K/V from them, full softmax."""
    dn, dr = cfg.mla.qk_nope_head_dim, cfg.mla.qk_rope_head_dim
    dt = x.dtype
    q_nope, q_rope = MLA._project_q(cfg, p, x, lengths[:, None])
    ckv_new, kr_new = MLA._project_kv_latent(cfg, p, x, lengths[:, None])
    S = cache["ckv"].shape[1]
    pos = jnp.clip(lengths, 0, S - 1)
    ckv = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
        c, n, i, axis=0))(cache["ckv"], ckv_new, pos)
    kr = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
        c, n, i, axis=0))(cache["kr"], kr_new, pos)
    kv = jnp.einsum("bsr,rhd->bshd", ckv, p["w_ukv"].astype(dt))
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q_nope, q_rope], -1)[:, 0]          # (B,H,dn+dr)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        kr[:, :, None, :], k_nope.shape[:3] + (dr,))], -1)
    s = jnp.einsum("bhd,bshd->bhs", q, k,
                   preferred_element_type=jnp.float32) * MLA.softmax_scale(cfg)
    kpos = jnp.arange(S)
    s = jnp.where(kpos[None, None, :] < (lengths + 1)[:, None, None], s,
                  NEG_INF)
    w = jax.nn.softmax(s, -1)
    o = jnp.einsum("bhs,bshd->bhd", w.astype(dt), v,
                   preferred_element_type=jnp.float32).astype(dt)
    y = jnp.einsum("bhd,hdD->bD", o, p["w_o"].astype(dt))[:, None]
    return y, {"ckv": ckv, "kr": kr}


def test_mla_absorbed_matches_naive_decode():
    cfg = get_config("deepseek-v2-236b", smoke=True)
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    p = params["prelayers"][0]["mixer"]
    B, S = 2, 16
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (B, 1, cfg.d_model), jnp.float32)
    m = cfg.mla
    cache = {"ckv": jax.random.normal(key, (B, S, m.kv_lora_rank), jnp.float32),
             "kr": jax.random.normal(key, (B, S, m.qk_rope_head_dim),
                                     jnp.float32)}
    lens = jnp.asarray([5, 9], jnp.int32)
    y_abs, c_abs = MLA.mla_decode_attention(cfg, p, x, dict(cache), lens)
    y_naive, c_naive = naive_mla_decode(cfg, p, x, dict(cache), lens)
    np.testing.assert_allclose(np.asarray(y_abs), np.asarray(y_naive),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(c_abs["ckv"]),
                               np.asarray(c_naive["ckv"]), atol=1e-5)
