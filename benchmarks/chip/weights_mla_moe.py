"""Weights of an MLA + MoE configuration (DeepSeek-V2's layout), made by the
benchmark from the seed, on the device, in the dtype the configuration
serves them in.

The tree has the layout the program is handed: ``embed``, ``out_norm``, the
dense ``prelayers`` and one stacked ``period`` layer of MLA and routed plus
shared experts, the routed experts being those this chip holds; the harness
checks it against the program's own shapes before use. Each leaf is made
by a jitted call of its own, directly in the serving dtype and, for stacked
leaves, one layer at a time: a float32 copy of the stacked expert leaf alone
would take 4.8 GB. Initialisation as ``weights.py``: matrices normal with
standard deviation 1/sqrt(fan-in), embeddings 0.02, norm scales one.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from weights import _is_leaf


def layout(sz: Dict) -> Dict:
    """Leaf -> (shape, standard deviation; None for a norm scale of ones).
    A leaf of a stacked layer has the layer count first."""
    m, moe = sz["mla"], sz["moe"]
    d, v, h = sz["d_model"], sz["vocab_size"], sz["n_heads"]
    dn, dr, dv, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"], m["kv_lora_rank"])
    if m["q_lora_rank"]:
        raise ValueError("this layout has no query compression")
    n_pre = sz["dense_layers"]
    n = sz["n_layers"] - n_pre
    fe, fs, held = moe["d_ff_expert"], moe["d_ff_shared"], moe["n_held"]

    def mla(*lead):
        return {"w_dkv": (lead + (d, r), d ** -0.5),
                "w_kr": (lead + (d, dr), d ** -0.5),
                "w_ukv": (lead + (r, h, dn + dv), r ** -0.5),
                "kv_norm": (lead + (r,), None),
                "w_o": (lead + (h, dv, d), (h * dv) ** -0.5),
                "w_q": (lead + (d, h, dn + dr), d ** -0.5)}

    def norms(*lead):
        return {"mixer_norm": {"scale": (lead + (d,), None)},
                "ffn_norm": {"scale": (lead + (d,), None)}}

    dense = dict(norms(), mixer=mla(), ffn={
        "w_in": ((d, 2 * sz["d_ff"]), d ** -0.5),
        "w_out": ((sz["d_ff"], d), sz["d_ff"] ** -0.5)})
    routed = dict(norms(n), mixer=mla(n), ffn={
        "router": ((n, d, moe["n_experts"]), d ** -0.5),
        "w_in": ((n, held, d, 2 * fe), d ** -0.5),
        "w_out": ((n, held, fe, d), fe ** -0.5),
        "shared_w_in": ((n, d, 2 * fs), d ** -0.5),
        "shared_w_out": ((n, fs, d), fs ** -0.5)})
    return {"embed": {"tok": ((v, d), 0.02), "unembed": ((v, d), 0.02)},
            "out_norm": {"scale": ((d,), None)},
            "prelayers": (dense,) * n_pre, "period": (routed,)}


def make_params_fn(sz: Dict, dtype: str):
    """``key -> params`` for the sizes ``sz`` (flat sizes plus ``mla``,
    ``moe`` and ``dense_layers``), in ``dtype``."""
    spec, dt = layout(sz), jnp.dtype(dtype)
    leaves, tdef = jax.tree.flatten(spec, is_leaf=_is_leaf)
    n_stacked = sz["n_layers"] - sz["dense_layers"]

    def one(shape, std, stacked):
        if std is None:
            return jax.jit(lambda key: jnp.ones(shape, dt))

        def draw(key, shp):
            return (std * jax.random.normal(key, shp, dt)).astype(dt)
        if not stacked:
            return jax.jit(lambda key: draw(key, shape))
        return jax.jit(lambda key: jax.lax.map(
            lambda i: draw(jax.random.fold_in(key, i), shape[1:]),
            jnp.arange(shape[0])))

    def make(key):
        out = []
        for i, (shape, std) in enumerate(leaves):
            stacked = len(shape) >= 3 and shape[0] == n_stacked
            out.append(one(shape, std, stacked)(jax.random.fold_in(key, i)))
        return jax.tree.unflatten(tdef, out)

    return make
