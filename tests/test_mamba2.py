"""Mamba-2 (SSD) mixer: the chunked prefill against the recurrence run one
token at a time, with padded rows, and prefill then decode against the
full-sequence mixer. Float32 throughout, so the tolerances only cover the
different order of float32 sums (chunked sums against a running sum)."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.models import mamba2 as MB2

S = 32


def _cfg(chunk):
    cfg = get_config("granite-4.0-h-small-ep8", smoke=True)
    return replace(cfg, dtype="float32",
                   mamba2=replace(cfg.mamba2, chunk_size=chunk, n_groups=2))


def _inputs(cfg, seed=0, b=2):
    """x, dt, a, B, C as the mixer hands them to the SSD, drawn so that the
    decays span fast and slow heads."""
    m = cfg.mamba2
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (b, S, m.n_heads, m.head_dim))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, S, m.n_heads)) - 1.0)
    a = -jnp.exp(jax.random.uniform(k[2], (m.n_heads,), minval=-2.0,
                                    maxval=1.0))
    bm = jax.random.normal(k[3], (b, S, m.n_groups, m.d_state))
    cm = jax.random.normal(k[4], (b, S, m.n_groups, m.d_state))
    return x, dt, a, bm, cm


def _recurrence(x, dt, a, bm, cm):
    """h <- exp(dt A) h + dt x B^T, y = h C, one position at a time."""
    b, s, h, p = x.shape
    g = bm.shape[2]
    bh = jnp.repeat(bm, h // g, axis=2)
    ch = jnp.repeat(cm, h // g, axis=2)
    state = jnp.zeros((b, h, p, bm.shape[-1]))
    ys = []
    for t in range(s):
        state = state * jnp.exp(dt[:, t] * a)[..., None, None] + (
            x[:, t] * dt[:, t, :, None])[..., None] * bh[:, t, :, None, :]
        ys.append(jnp.einsum("bhpn,bhn->bhp", state, ch[:, t],
                             precision="highest"))
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("n_chunks", [1, 2, 4])
def test_chunked_ssd_matches_the_token_recurrence(n_chunks):
    cfg = _cfg(S // n_chunks)
    x, dt, a, bm, cm = _inputs(cfg)
    with jax.default_matmul_precision("highest"):
        y, state = MB2.ssd_chunked(x, dt, a, bm, cm, S // n_chunks)
    want_y, want_state = _recurrence(x, dt, a, bm, cm)
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state, want_state, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n_chunks", [1, 2, 4])
def test_padded_rows_keep_the_state_of_their_own_length(n_chunks):
    """dt = 0 past a row's length is an identity step: the state the
    chunked SSD hands on is the recurrence's after that row's last real
    position, whichever chunk it falls in, and the real positions'
    outputs do not change."""
    cfg = _cfg(S // n_chunks)
    x, dt, a, bm, cm = _inputs(cfg, seed=1)
    lengths = np.asarray([S, 13])
    keep = (np.arange(S)[None, :] < lengths[:, None])[..., None]
    with jax.default_matmul_precision("highest"):
        y, state = MB2.ssd_chunked(x, jnp.where(keep, dt, 0.0), a, bm, cm,
                                   S // n_chunks)
    for row, n in enumerate(lengths):
        sl = slice(row, row + 1)
        want_y, want_state = _recurrence(x[sl, :n], dt[sl, :n], a,
                                         bm[sl, :n], cm[sl, :n])
        np.testing.assert_allclose(y[sl, :n], want_y, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(state[sl], want_state, rtol=1e-4,
                                   atol=1e-4)


def test_uneven_tail_chunk_is_padded_not_dropped():
    cfg = _cfg(12)                      # 32 = 2 chunks of 12 and 8 left
    x, dt, a, bm, cm = _inputs(cfg, seed=2)
    with jax.default_matmul_precision("highest"):
        y, state = MB2.ssd_chunked(x, dt, a, bm, cm, 12)
    want_y, want_state = _recurrence(x, dt, a, bm, cm)
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state, want_state, rtol=1e-4, atol=1e-4)


def test_prefill_cache_then_decode_matches_the_full_mixer():
    """Each row's cache after its own prompt length, then one decode step
    per further token, gives the full-sequence mixer's outputs at those
    positions (conv window and SSM state both carried). The conv window is
    kept in bfloat16, as served, which puts the decoded outputs up to 0.008
    off (outputs reach 4); 0.02 bounds that, while a decode step that
    drops the carried SSM state is 0.16 off and one that drops the window
    2.5."""
    cfg = _cfg(8)
    m = cfg.mamba2
    defs = MB2.mamba2_defs(cfg)
    p = init_params(defs, jax.random.PRNGKey(3))
    # a conv bias and weights of the published init's size, so the conv
    # window matters
    p["conv_b"] = 0.3 * jax.random.normal(jax.random.PRNGKey(4),
                                          p["conv_b"].shape)
    p["conv_w"] = 0.5 * jax.random.normal(jax.random.PRNGKey(5),
                                          p["conv_w"].shape)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, S, cfg.d_model))
    lengths = jnp.asarray([11, 20], jnp.int32)
    with jax.default_matmul_precision("highest"):
        full, _ = MB2.mamba2_mixer(cfg, p, x)
        _, cache = MB2.mamba2_mixer(cfg, p, x, lengths)
        assert cache["ssm"].shape == (2, m.n_heads, m.head_dim, m.d_state)
        assert cache["conv"].shape[1] == m.d_conv - 1
        cache["conv"] = cache["conv"].astype(jnp.float32)
        for row, n in enumerate(np.asarray(lengths)):
            c = jax.tree.map(lambda t: t[row:row + 1], cache)
            for t in range(n, n + 6):
                y, c = MB2.mamba2_decode(cfg, p, x[row:row + 1, t:t + 1], c)
                c["conv"] = c["conv"].astype(jnp.float32)
                np.testing.assert_allclose(y[0, 0], full[row, t],
                                           rtol=2e-2, atol=2e-2)
