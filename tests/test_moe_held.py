"""The one-device expert layer that knows its share (``moe.moe_held``) and
YaRN rotary scaling, on the CPU at smoke size with seeded random weights.

The layer is held to the dense oracle (every expert on every token) with the
experts held elsewhere zeroed, so the oracle computes exactly this device's
part; four disjoint shares add up to the uncut layer; the counts it returns
are checked against the routing the oracle's router makes.
"""
import functools
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


N_LAYERS = 5          # a stack of five period layers' held experts


def _rows(kind, shift):
    """A decode step's 16 rows (every third empty) or two prefill rows of
    24 positions (17 real, then padding; the second row empty), with the
    ``valid`` mask the serving programs pass; features ``shift`` + N(0, 1)."""
    if kind == "decode":
        x = shift + _x(seed=7, b=16, s=1)
        return x, (jnp.arange(16) % 3 != 0)[:, None]
    x = shift + _x(seed=8, b=2, s=24)
    return x, jnp.arange(24)[None, :] < jnp.asarray([[17], [0]])


def _whole(p, seed):
    """Whole-number expert weights under which every sum the grouped
    matmuls make is exact in float32, whatever its order: gates of 1-2 over
    inputs of 1-2 sum to at least 64, where silu is the identity in
    float32; up and down projections of -1, 0, 1."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    F = p["w_out"].shape[1]
    ints = lambda k, shape, lo, hi: jax.random.randint(
        k, shape, lo, hi + 1).astype(jnp.float32)
    gate = ints(k1, p["w_in"].shape[:2] + (F,), 1, 2)
    up = ints(k2, p["w_in"].shape[:2] + (F,), -1, 1)
    return dict(p, w_in=jnp.concatenate([gate, up], -1),
                w_out=ints(k3, p["w_out"].shape, -1, 1))


@functools.lru_cache(maxsize=None)
def _held_programs(first):
    """``moe_held`` jitted on a layer's slice and on the stack with a
    traced layer index, for a device holding experts [first, first + 2)."""
    cfg = _cfg(2, first)
    return (jax.jit(lambda p, x, v: moe_held(cfg, p, x, v)),
            jax.jit(lambda p, x, v, i: moe_held(cfg, p, x, v, i)))


@pytest.mark.parametrize("first", [0, 3])
@pytest.mark.parametrize("layer", [0, 2, N_LAYERS - 1])
@pytest.mark.parametrize("routing", ["drawn", "none_here"])
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_stacked_experts_match_the_layer_slice(kind, routing, layer, first):
    """Given the held experts of every period layer stacked and a layer's
    index (traced, as the serving programs' layer loop passes it), the
    layer gives what it gives on that layer's slice: the grouped matmuls
    read the same rows against the same experts, and the other layers'
    groups are empty. ``none_here``: every token chooses only experts held
    elsewhere.

    Bit for bit on whole-number inputs and weights, where every sum is
    exact: on the CPU jax's ragged dot is one dense contraction over the
    groups and the features together, so the empty groups reorder its
    float sums. On drawn weights the two agree to float32 rounding."""
    layers = [_share(_params(_cfg(), seed=10 + i), first, 2)
              for i in range(N_LAYERS)]
    if routing == "none_here":      # the held experts' logits sink
        layers = [dict(p, router=p["router"].at[:, first:first + 2].add(
            -50.0 / SMOKE.d_model ** 0.5)) for p in layers]
    # positive features for the sunk logits to hold; centred ones route
    # the drawn tokens over every expert
    x, valid = _rows(kind, 2.0 if routing == "none_here" else 0.0)
    sliced, stacked = _held_programs(first)

    def both(layers, x):
        stack = dict(layers[layer],
                     w_in=jnp.stack([p["w_in"] for p in layers]),
                     w_out=jnp.stack([p["w_out"] for p in layers]))
        y, _, c = sliced(layers[layer], x, valid)
        ys, _, cs = stacked(stack, x, valid, jnp.asarray(layer))
        assert {k: int(v) for k, v in cs.items()} == \
            {k: int(v) for k, v in c.items()}
        return np.asarray(ys), np.asarray(y), cs

    ys, y, _ = both([_whole(p, 20 + i) for i, p in enumerate(layers)],
                    jnp.clip(jnp.round(1.5 + 0.5 * x), 1, 2))
    np.testing.assert_array_equal(ys, y)
    ys, y, counts = both(layers, x)
    np.testing.assert_allclose(ys, y, rtol=1e-6, atol=1e-6)
    if routing == "none_here":
        assert int(counts["assignments_here"]) == 0
    else:
        assert int(counts["assignments_here"]) > 0


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


def _scans(jaxpr):
    """Every scan equation in a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def test_training_slices_each_layers_experts():
    """``train_logits`` keeps cutting each layer's held experts out of the
    stacks as the layer loop's scanned input: in its gradient's program the
    (L, E, D, 2F) ``w_in`` stack enters every layer loop, forward and
    backward, as a scanned input, and its gradient leaves as a scanned
    output, never a loop constant or carried value (a carried stack would
    sum a whole-stack gradient on every layer). The gradient equals the
    one through the unrolled layer loop's static slices."""
    from repro.models import model_defs
    from repro.models import transformer as T
    cfg = replace(get_config("deepseek-v2-lite-ep8", smoke=True),
                  dtype="float32")
    params = init_params(model_defs(cfg), jax.random.PRNGKey(9))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(10), (2, 12), 0,
                                          cfg.vocab_size)}
    stack = params["period"][0]["ffn"]["w_in"].shape
    assert stack[:2] == (cfg.n_periods, cfg.moe.n_held) and cfg.n_periods > 1

    def grad_w_in(p, flags):
        def loss(p):
            logits, _ = T.train_logits(cfg, p, batch, flags=flags)
            return jnp.mean(jax.nn.log_softmax(logits) ** 2)
        return jax.grad(loss)(p)["period"][0]["ffn"]["w_in"]

    jaxpr = jax.make_jaxpr(lambda p: grad_w_in(p, T.RunFlags()))(
        params).jaxpr
    scans = list(_scans(jaxpr))
    scanned_in = scanned_out = 0
    for eqn in scans:
        n_c, n_k = eqn.params["num_consts"], eqn.params["num_carry"]
        shapes = [v.aval.shape for v in eqn.invars]
        assert stack not in shapes[:n_c + n_k]
        assert stack not in [v.aval.shape for v in eqn.outvars[:n_k]]
        scanned_in += stack in shapes[n_c + n_k:]
        scanned_out += stack in [v.aval.shape for v in eqn.outvars[n_k:]]
    assert scanned_in >= 2 and scanned_out >= 1
    grad = jax.jit(grad_w_in, static_argnums=1)
    got = grad(params, T.RunFlags())
    want = grad(params, T.RunFlags(unroll_layers=True, remat="none"))
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-7)
