"""The one-device expert layer that knows its share (``moe.moe_held``) and
YaRN rotary scaling, on the CPU at smoke size with seeded random weights.

The layer is held to the dense oracle (every expert on every token) with the
experts held elsewhere zeroed, so the oracle computes exactly this device's
part; four disjoint shares add up to the uncut layer; the counts it returns
are checked against the routing the oracle's router makes.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import YarnConfig
from repro.models import init_params
from repro.models.layers import apply_rope, rope_freqs, yarn_mscale
from repro.models.mla import softmax_scale
from repro.models.moe import _route, moe_defs, moe_dense_oracle, moe_held

SMOKE = get_config("deepseek-v2-lite", smoke=True)      # 8 experts, top-2


def _cfg(n_held=0, first_held=0, dtype="float32"):
    return replace(SMOKE, dtype=dtype, moe=replace(
        SMOKE.moe, n_held=n_held, first_held=first_held))


def _params(cfg, seed=0):
    p = init_params(moe_defs(cfg), jax.random.PRNGKey(seed))
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def _share(full, first, n):
    """The weights a device holding experts [first, first + n) holds."""
    return dict(full, w_in=full["w_in"][first:first + n],
                w_out=full["w_out"][first:first + n])


def _oracle_here(cfg_full, full, x, first, n):
    """The dense oracle over all experts with those held elsewhere zeroed:
    exactly the held experts' part of the routed sum, plus the shared."""
    keep = (jnp.arange(full["w_in"].shape[0]) >= first) & \
        (jnp.arange(full["w_in"].shape[0]) < first + n)
    p = dict(full, w_in=full["w_in"] * keep[:, None, None],
             w_out=full["w_out"] * keep[:, None, None])
    return moe_dense_oracle(cfg_full, p, x)[0]


def _x(seed=1, b=2, s=24):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, SMOKE.d_model),
                             jnp.float32)


def _force(full, expert, others=None):
    """Router weights under which every token puts ``expert`` first (and,
    with ``others``, its remaining choices among them)."""
    r = full["router"] * 0.01
    r = r.at[:, expert].add(50.0 / SMOKE.d_model ** 0.5)
    if others is not None:
        r = r.at[:, others].add(20.0 / SMOKE.d_model ** 0.5)
    return dict(full, router=r)


# routing cases: as drawn; every token's first choice the held expert 3;
# every choice among experts held elsewhere (none routed here)
@pytest.mark.parametrize("case", ["drawn", "all_to_one_held", "none_here"])
def test_held_layer_matches_oracle_restricted_to_held(case):
    first, n = 2, 2
    full_cfg, cfg = _cfg(), _cfg(n, first)
    full = _params(full_cfg)
    x = 1.0 + 0.5 * _x()           # positive features: forcing holds
    if case == "all_to_one_held":
        full = _force(full, 3)
    elif case == "none_here":
        full = _force(full, 6, others=[7])
    y, _, counts = jax.jit(lambda p, x: moe_held(cfg, p, x))(
        _share(full, first, n), x)
    want = _oracle_here(full_cfg, full, x, first, n)
    # float32 on both sides, same arithmetic but the grouping: rounding only
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    idx, _, _ = _route(full_cfg, x.reshape(-1, SMOKE.d_model),
                       full["router"])
    here = (np.asarray(idx) >= first) & (np.asarray(idx) < first + n)
    assert int(counts["assignments_here"]) == here.sum()
    touched = len(set(np.asarray(idx)[here].tolist()))
    assert int(counts["experts_touched"]) == touched
    if case == "all_to_one_held":
        assert here[:, 0].all() and touched >= 1
    if case == "none_here":
        assert here.sum() == 0
        # nothing routed here: the shared experts alone
        shared = moe_dense_oracle(full_cfg, dict(
            full, w_in=full["w_in"] * 0, w_out=full["w_out"] * 0), x)[0]
        np.testing.assert_allclose(np.asarray(y), np.asarray(shared),
                                   rtol=1e-6, atol=1e-6)


def test_padding_routes_nowhere():
    cfg = _cfg(2, 0)
    p = _share(_params(_cfg()), 0, 2)
    x = _x()
    valid = jnp.arange(x.shape[1])[None, :] < jnp.asarray([[10], [24]])
    y, _, counts = moe_held(cfg, p, x, valid)
    y0, _, c0 = moe_held(cfg, p, x[:1, :10])
    np.testing.assert_allclose(np.asarray(y[:1, :10]), np.asarray(y0),
                               rtol=1e-6, atol=1e-6)
    _, _, c1 = moe_held(cfg, p, x[1:])
    assert int(counts["assignments_here"]) == int(
        c0["assignments_here"]) + int(c1["assignments_here"])


def test_four_disjoint_shares_add_up_to_the_uncut_layer():
    """Each of 4 devices holds 2 of the 8 experts; their routed parts, with
    the shared experts (computed on every device alike) counted once, are
    the whole layer."""
    full_cfg = _cfg()
    full = _params(full_cfg, seed=3)
    x = _x(seed=4)
    whole, _, c_whole = moe_held(full_cfg, full, x)
    shared = moe_dense_oracle(full_cfg, dict(
        full, w_in=full["w_in"] * 0, w_out=full["w_out"] * 0), x)[0]
    parts, assigned = [], 0
    for first in (0, 2, 4, 6):
        y, _, c = moe_held(_cfg(2, first), _share(full, first, 2), x)
        parts.append(y - shared)
        assigned += int(c["assignments_here"])
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    n_tok = x.shape[0] * x.shape[1]
    assert assigned == int(c_whole["assignments_here"]) == \
        n_tok * SMOKE.moe.top_k


def test_yarn_frequencies_and_scale():
    """DeepSeek-V2-Lite's YaRN at 64 rope dims: pairs 0-10 keep theta's
    frequency, pairs 23-31 are divided by 40, a ramp joins them; the
    softmax scale gains mscale(40, 0.707)**2."""
    cfg = get_config("deepseek-v2-lite")
    ys = cfg.rope_scaling
    plain = np.asarray(rope_freqs(64, 1e4))
    yarn = np.asarray(rope_freqs(64, 1e4, ys))
    np.testing.assert_allclose(yarn[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(yarn[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all(yarn[11:23] < plain[11:23]) and \
        np.all(yarn[11:23] > plain[11:23] / 40)
    assert softmax_scale(cfg) == pytest.approx(
        yarn_mscale(40, 0.707) ** 2 / 192 ** 0.5)
    assert yarn_mscale(40, 0.707) ** 2 == pytest.approx(1.5896, abs=1e-4)
    # mscale and mscale_all_dim equal: cos and sin unscaled
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 64))
    pos = jnp.arange(5)[None]
    y = apply_rope(x, pos, 1e4, ys)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    # unequal ones scale both by their ratio
    y2 = apply_rope(x, pos, 1e4, replace(ys, mscale=1.0))
    ratio = yarn_mscale(40, 1.0) / yarn_mscale(40, 0.707)
    np.testing.assert_allclose(np.asarray(y2), ratio * np.asarray(y),
                               rtol=1e-5, atol=1e-6)
    assert apply_rope(x, pos, 1e4).shape == x.shape
    assert YarnConfig() == ys


@pytest.mark.parametrize("where", ["prelayer", "period"])
def test_decode_layer_counts_only_occupied_rows(where):
    """``apply_layer_decode`` returns its layer's counts: None for the dense
    layer 0, and for an MoE layer the assignments of the rows whose length
    is above 0 (the engine's free slots sit at length 0 and route nowhere)."""
    from repro.models import model_defs
    from repro.models import transformer as T
    cfg = _cfg()
    params = init_params(model_defs(cfg), jax.random.PRNGKey(5))
    lengths = jnp.asarray([0, 3, 0, 5], jnp.int32)
    cache = T.init_cache(cfg, 4, 16)
    if where == "prelayer":
        spec, p, c = cfg.prelayers[0], params["prelayers"][0], \
            cache["prelayers"][0]
    else:
        spec = cfg.period[0]
        p, c = jax.tree.map(lambda a: a[0], (params["period"][0],
                                             cache["period"][0]))
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 1, cfg.d_model),
                          jnp.float32)
    _, _, counts = T.apply_layer_decode(cfg, spec, p, x, c, lengths,
                                        T.RunFlags())
    if where == "prelayer":
        assert spec.ffn != "moe" and counts is None
        return
    assert int(counts["assignments_here"]) == 2 * cfg.moe.top_k
    assert 1 <= int(counts["experts_touched"]) <= 2 * cfg.moe.top_k
