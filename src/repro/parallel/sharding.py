"""Sharding plans: activation specs, KV-cache specs, and per-cell plan logic.

Train/prefill layout (baseline, Megatron+ZeRO3):
  - weights: d_model dim over ``data`` (FSDP), heads/FFN-hidden/experts over
    ``model`` (TP/EP); optimizer state sharded like params.
  - activations: batch over (pod, data); optional sequence-parallel constraint
    (seq over ``model``) on the residual stream between layers.

Decode layout (TPU flash-decoding):
  - weights: same 2D sharding (reads stay fully distributed);
  - activations replicated within a pod (tiny at S=1);
  - KV cache sharded along *sequence* over ("data","model") — and over "pod"
    too when the batch cannot split across pods (long_500k, batch=1);
  - recurrent state (mamba/xlstm): d_inner over ``model`` (Mamba-2: heads).
"""
from __future__ import annotations

from typing import Tuple

import jax

from repro.compat import NamedSharding, P
from repro.configs.base import LayerSpec, ModelConfig, ShapeConfig


def _lead(axes: Tuple[str, ...]):
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def layer_cache_specs(cfg: ModelConfig, spec: LayerSpec,
                      batch_axes: Tuple[str, ...],
                      seq_axes: Tuple[str, ...], stacked: bool):
    b = _lead(batch_axes)
    s = _lead(seq_axes)
    pre = (None,) if stacked else ()
    if spec.mixer == "attn":
        kv = P(*pre, b, s, None, None)
        return {"k": kv, "v": kv}
    if spec.mixer == "mla":
        return {"ckv": P(*pre, b, s, None), "kr": P(*pre, b, s, None)}
    if spec.mixer == "mamba":
        return {"conv": P(*pre, b, None, "model"),
                "ssm": P(*pre, b, "model", None)}
    if spec.mixer == "mamba2":
        return {"conv": P(*pre, b, None, "model"),
                "ssm": P(*pre, b, "model", None, None)}
    if spec.mixer == "mlstm":
        return {"C": P(*pre, b, None, None, None),
                "n": P(*pre, b, None, None),
                "m": P(*pre, b, None),
                "conv": P(*pre, b, None, "model")}
    if spec.mixer == "slstm":
        e = P(*pre, b, None, None)
        return {"c": e, "n": e, "h": e, "m": e}
    raise ValueError(spec.mixer)


def cache_specs(cfg: ModelConfig, batch_axes: Tuple[str, ...],
                seq_axes: Tuple[str, ...]):
    """PartitionSpec pytree matching ``init_cache``'s structure."""
    return {
        "prelayers": tuple(layer_cache_specs(cfg, s, batch_axes, seq_axes,
                                             stacked=False)
                           for s in cfg.prelayers),
        "period": tuple(layer_cache_specs(cfg, s, batch_axes, seq_axes,
                                          stacked=True)
                        for s in cfg.period),
        "lengths": P(_lead(batch_axes)),
    }


def to_shardings(tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def decode_plan(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """(batch_axes, seq_axes) for a decode cell on this mesh."""
    names = mesh.axis_names
    sizes = dict(zip(names, mesh.axis_sizes))
    batch_axes: Tuple[str, ...] = ()
    if "pod" in names and shape.global_batch % sizes["pod"] == 0 \
            and shape.global_batch > 1:
        batch_axes = ("pod",)
    seq_axes = tuple(a for a in names if a not in batch_axes and a != "pod")
    if "pod" in names and not batch_axes:
        seq_axes = ("pod",) + seq_axes           # long-context: shard seq 3-way
    return batch_axes, seq_axes


def train_batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
