"""Weights of a Mamba-2 / attention hybrid with MoE in every layer (Granite-
4.0-H's layout, a chip's share of its routed experts), made by the benchmark
from the seed, on the device, in the dtype the configuration serves them in.

The tree has the layout the program is handed: ``embed`` (tied: the token
table alone), ``out_norm``, no prelayers and one stacked ``period`` entry
per layer of the period, Mamba-2 or attention, each with routed experts
(those this chip holds), router and shared MLP; the harness checks it
against the program's own shapes before use. Each leaf is made by a jitted
call of its own, directly in the serving dtype and, for stacked leaves, one
layer at a time, as ``weights_mla_moe.py`` makes them. Initialisation:
matrices normal with standard deviation 1/sqrt(fan-in) (the conv's fan-in
is its width), norm scales and D one; Mamba-2's published A_log =
log(1..heads) and dt_bias with softplus(dt_bias) log-uniform in
[0.001, 0.1]. The token table is drawn at 0.02 / ``embedding_multiplier``,
so that the multiplied embeddings enter the residual stream at 0.02. The
table is also the unembedding (tied), and a table drawn at 0.02 gives the
input token's own logit a lead of about twelve standard deviations at
d_model 4096 (the multiplier 12 times sqrt(d_model) times 0.02 over the
stream's rms): every served token then repeats its input, whatever the
layers compute, and the check could not tell the program from its control.

Routers so drawn route unevenly: each layer adds to the residual stream
a part that every token shares (the SiLU outputs' positive means, carried
by the Mamba-2 state), so the rows of a decode step give the same few
experts their largest logits, and how few follows the seed (on a TPU
v5e, 32 to 66 of the 180 held experts touched a step at 5.6 rows, where
tokens routed evenly touch about 102). A trained router spreads its
load (the published model trains with a load-balancing loss,
``router_aux_loss_coef``). So each layer's router is made blind to that
shared part, layer by layer in order (``balance_routers``): a
calibration batch of random tokens runs through the plain reference up
to the layer's router, the mean direction of the router's input is taken
out of the router's weights, and the batch goes on through the layer
with the router so made. The shared part then adds nothing to any
expert's logit, and tokens route by what sets them apart. On a TPU v5e
six draws in seven so balanced touch 88 to 90 held experts a step at 5.7
rows and one 64, so the cell draws its weights once, from a fixed key
(the driver's ``weights_key``).
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

import reference_hybrid
from reference import _rms
from weights import _is_leaf

# the calibration batch of ``balance_routers``: rows of random tokens
CALIBRATION_ROWS, CALIBRATION_SEQ = 4, 512


def _a_log(key, shape):
    return jnp.log(jnp.broadcast_to(
        jnp.arange(1, shape[-1] + 1, dtype=jnp.float32), shape))


def _dt_bias(key, shape):
    lo, hi = math.log(0.001), math.log(0.1)
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(key, shape))
    return dt + jnp.log(-jnp.expm1(-dt))         # softplus's inverse


SPECIAL = {"a_log": _a_log, "dt_bias": _dt_bias}


def layout(sz: Dict) -> Dict:
    """Leaf -> (shape, standard deviation; None for ones, or the name of a
    ``SPECIAL`` draw). Stacked leaves have the period count first."""
    m, moe = sz["mamba2"], sz["moe"]
    d, v = sz["d_model"], sz["vocab_size"]
    n = sz["n_layers"] // len(sz["period"])
    nh, hd, kv = sz["n_heads"], sz["head_dim"], sz["n_kv_heads"]
    di = m["n_heads"] * m["head_dim"]
    conv = di + 2 * m["n_groups"] * m["d_state"]
    fe, fs, held = moe["d_ff_expert"], moe["d_ff_shared"], moe["n_held"]
    mixers = {
        "mamba2": {"w_in": ((n, d, di + conv + m["n_heads"]), d ** -0.5),
                   "conv_w": ((n, conv, m["d_conv"]), m["d_conv"] ** -0.5),
                   "conv_b": ((n, conv), m["d_conv"] ** -0.5),
                   "dt_bias": ((n, m["n_heads"]), "dt_bias"),
                   "a_log": ((n, m["n_heads"]), "a_log"),
                   "d_skip": ((n, m["n_heads"]), None),
                   "norm": ((n, di), None),
                   "w_out": ((n, di, d), di ** -0.5)},
        "attn": {"wq": ((n, d, nh * hd), d ** -0.5),
                 "wkv": ((n, d, 2 * kv * hd), d ** -0.5),
                 "wo": ((n, nh * hd, d), (nh * hd) ** -0.5)}}
    ffn = {"router": ((n, d, moe["n_experts"]), d ** -0.5),
           "w_in": ((n, held, d, 2 * fe), d ** -0.5),
           "w_out": ((n, held, fe, d), fe ** -0.5),
           "shared_w_in": ((n, d, 2 * fs), d ** -0.5),
           "shared_w_out": ((n, fs, d), fs ** -0.5)}
    period = tuple({"mixer_norm": {"scale": ((n, d), None)},
                    "mixer": mixers[kind],
                    "ffn_norm": {"scale": ((n, d), None)},
                    "ffn": ffn} for kind in sz["period"])
    return {"embed": {"tok": ((v, d), 0.02 / sz["embedding_multiplier"])},
            "out_norm": {"scale": ((d,), None)},
            "prelayers": (), "period": period}


def make_params_fn(sz: Dict, dtype: str):
    """``key -> params`` for the sizes ``sz`` (flat sizes plus ``mamba2``,
    ``moe`` and ``period``), in ``dtype``: drawn, then the routers
    balanced on a calibration batch drawn from the same key."""
    draw = draw_params_fn(sz, dtype)

    def make(key):
        return balance_routers(sz, draw(key), calibration_tokens(sz, key))

    return make


def calibration_tokens(sz: Dict, key):
    """``balance_routers``' batch of random tokens, from the weights' key."""
    return jax.random.randint(jax.random.fold_in(key, 2 ** 31 - 1),
                              (CALIBRATION_ROWS, CALIBRATION_SEQ), 1,
                              sz["vocab_size"])


def draw_params_fn(sz: Dict, dtype: str):
    """``key -> params`` as drawn, the routers not yet balanced."""
    spec, dt = layout(sz), jnp.dtype(dtype)
    leaves, tdef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    def one(shape, kind, stacked):
        if kind is None:
            return jax.jit(lambda key: jnp.ones(shape, dt))

        def draw(key, shp):
            if isinstance(kind, str):
                return SPECIAL[kind](key, shp).astype(dt)
            return (kind * jax.random.normal(key, shp, dt)).astype(dt)
        if not stacked:
            return jax.jit(lambda key: draw(key, shape))
        return jax.jit(lambda key: jax.lax.map(
            lambda i: draw(jax.random.fold_in(key, i), shape[1:]),
            jnp.arange(shape[0])))

    def make(key):
        out = []
        for i, (shape, kind) in enumerate(leaves):
            out.append(one(shape, kind, len(shape) >= 3)(
                jax.random.fold_in(key, i)))
        return jax.tree.unflatten(tdef, out)

    return make


def balance_routers(sz: Dict, params, tokens):
    """``params`` with each layer's router made orthogonal to the mean
    direction of its input over ``tokens`` (B, S), the layers taken in
    order through the plain reference in float32, each with the routers
    before it already made so."""
    eps, r = sz["norm_eps"], sz["residual_multiplier"]
    mixers = {"mamba2": reference_hybrid._mamba2,
              "attn": reference_hybrid._attention}

    @functools.partial(jax.jit, static_argnames="kind")
    def layer(x, lp, kind):
        f = reference_hybrid._f32(lp)
        x = x + r * mixers[kind](sz, "f32",
                                 _rms(x, f["mixer_norm"]["scale"], eps),
                                 f["mixer"])
        h = _rms(x, f["ffn_norm"]["scale"], eps)
        u = jnp.mean(h.reshape(-1, h.shape[-1]), 0)
        u = u / jnp.linalg.norm(u)
        w = f["ffn"]["router"]
        f["ffn"]["router"] = w - jnp.outer(u, jnp.dot(
            u, w, precision=jax.lax.Precision.HIGHEST))
        y, _ = reference_hybrid._moe(sz, "f32", h, f["ffn"])
        return x + r * y, f["ffn"]["router"].astype(lp["ffn"]["router"].dtype)

    x = params["embed"]["tok"][tokens].astype(jnp.float32) \
        * sz["embedding_multiplier"]
    period = jax.tree.map(lambda a: a, params["period"])    # new containers
    for i in range(sz["n_layers"] // len(sz["period"])):
        for lps, kind in zip(period, sz["period"]):
            x, w = layer(x, jax.tree.map(lambda a: a[i], lps), kind)
            lps["ffn"]["router"] = lps["ffn"]["router"].at[i].set(w)
    return dict(params, period=period)
