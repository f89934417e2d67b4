"""Weights made by the benchmark from the seed, on the device, in one jitted
call, in the dtype the configuration serves or trains them in.

The tree has the layout the program is handed (``embed``, ``out_norm``,
``prelayers``, one stacked ``period`` layer); the harness checks it against
the program's own shapes before use. Initialisation: matrices normal with
standard deviation 1/sqrt(fan-in), embeddings normal with 0.02, norm scales
one.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def layout(sz: Dict) -> Dict:
    """Leaf -> (shape, standard deviation; None for a norm scale of ones)."""
    n, d, f, v = sz["n_layers"], sz["d_model"], sz["d_ff"], sz["vocab_size"]
    hq = sz["n_heads"] * sz["head_dim"]
    hkv = 2 * sz["n_kv_heads"] * sz["head_dim"]
    emb = {"tok": ((v, d), 0.02)}
    if not sz.get("tie_embeddings"):
        emb["unembed"] = ((v, d), 0.02)
    layer = {
        "mixer_norm": {"scale": ((n, d), None)},
        "mixer": {"wq": ((n, d, hq), d ** -0.5),
                  "wkv": ((n, d, hkv), d ** -0.5),
                  "wo": ((n, hq, d), hq ** -0.5)},
        "ffn_norm": {"scale": ((n, d), None)},
        "ffn": {"w_in": ((n, d, 2 * f), d ** -0.5),
                "w_out": ((n, f, d), f ** -0.5)},
    }
    return {"embed": emb, "out_norm": {"scale": ((d,), None)},
            "prelayers": (), "period": (layer,)}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_params_fn(sz: Dict, dtype: str):
    """A jitted ``key -> params`` for the sizes ``sz``, in ``dtype``."""
    spec, dt = layout(sz), jnp.dtype(dtype)

    def make(key):
        leaves, tdef = jax.tree.flatten(spec, is_leaf=_is_leaf)
        out = []
        for i, (shape, std) in enumerate(leaves):
            if std is None:
                out.append(jnp.ones(shape, dt))
            else:
                out.append((std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                            ).astype(dt))
        return jax.tree.unflatten(tdef, out)

    return jax.jit(make)
