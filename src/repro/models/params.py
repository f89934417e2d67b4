"""Parameter definition / initialization / sharding machinery.

Modules describe their parameters as pytrees of :class:`ParamDef` (shape,
dtype, *logical axes*, initializer). A single definition drives:

- ``init_params``  — materialize real arrays (works under ``jax.eval_shape``
  too, which is how the dry-run builds ShapeDtypeStruct state without ever
  allocating);
- ``param_specs``  — map logical axes to mesh axes through a *rules table*
  (MaxText-style), producing a ``PartitionSpec`` pytree.  Swapping the rules
  table is the main §Perf lever for re-sharding experiments.

Divisibility fallback: if a logical axis maps to a mesh axis whose size does
not divide the dimension, the dimension is left unsharded (replicated). This
keeps e.g. GQA KV-head projections valid when n_kv_heads < |model|.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.compat import Mesh, NamedSharding, P

AxisName = Union[str, Tuple[str, ...], None]


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]      # logical axis name per dim (None = replicated)
    init: str = "normal"                 # normal | zeros | ones | scaled | ssm_a | ssm_dt | embed
    dtype: Any = jnp.float32
    scale: float = 1.0                   # stddev multiplier for normal/scaled

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")


# ---------------------------------------------------------------------------
# Sharding rules: logical axis -> mesh axis (or tuple of mesh axes)
# ---------------------------------------------------------------------------

# Baseline rules for the ("pod", "data", "model") production mesh:
#   - FSDP (ZeRO-3) over the data axis on the embed dim of weight matrices,
#   - Megatron TP over the model axis on heads / FFN hidden / experts / vocab,
#   - layer (scan) axis never sharded.
DEFAULT_RULES: Dict[str, AxisName] = {
    "layers": None,
    "vocab": "model",
    "embed": "data",            # FSDP shard of the d_model dim of matrices
    "embed_nofsdp": None,
    "heads": "model",
    "kv_heads": "model",        # falls back to replicated when not divisible
    "head_dim": None,
    "mlp": "model",
    "experts": "model",         # EP
    "dinner": "model",          # mamba / xlstm inner dim
    "state": None,
    "lora": None,
    "conv": None,
    "norm": None,
}

# Rules variant that additionally shards FSDP over the pod axis (ZeRO across
# pods; cheaper memory, pays inter-pod all-gathers).
POD_FSDP_RULES = dict(DEFAULT_RULES, embed=("pod", "data"))


def logical_to_spec(axes: Sequence[Optional[str]], rules: Mapping[str, AxisName],
                    shape: Sequence[int], mesh_axis_sizes: Mapping[str, int]) -> P:
    """Resolve logical axes to a PartitionSpec with divisibility fallback."""
    used: set = set()
    out = []
    for dim, name in zip(shape, axes):
        if name is None:
            out.append(None)
            continue
        mesh_axis = rules.get(name)
        if mesh_axis is None:
            out.append(None)
            continue
        parts = (mesh_axis,) if isinstance(mesh_axis, str) else tuple(mesh_axis)
        # drop mesh axes already used by an earlier dim or absent from the mesh
        parts = tuple(p for p in parts if p in mesh_axis_sizes and p not in used)
        total = math.prod(mesh_axis_sizes[p] for p in parts) if parts else 1
        if not parts or dim % total != 0:
            out.append(None)
            continue
        used.update(parts)
        out.append(parts[0] if len(parts) == 1 else parts)
    return P(*out)


def param_specs(defs: Any, mesh: Mesh,
                rules: Optional[Mapping[str, AxisName]] = None) -> Any:
    rules = DEFAULT_RULES if rules is None else rules
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(d: ParamDef) -> P:
        return logical_to_spec(d.axes, rules, d.shape, sizes)

    return jax.tree.map(one, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def param_shardings(defs: Any, mesh: Mesh,
                    rules: Optional[Mapping[str, AxisName]] = None) -> Any:
    specs = param_specs(defs, mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _fan_in(d: ParamDef) -> int:
    # convention: last dim is fan-out; everything except the last (and a
    # leading stacked-layer dim, named "layers") is fan-in.
    dims = [s for s, a in zip(d.shape, d.axes) if a != "layers"]
    if len(dims) <= 1:
        return max(dims[0] if dims else 1, 1)
    return max(math.prod(dims[:-1]), 1)


def init_one(d: ParamDef, key: jax.Array) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init == "ssm_a":
        # mamba A: -log-spaced state matrix, stored as log(-A)
        d_state = d.shape[-1]
        a = jnp.tile(jnp.arange(1, d_state + 1, dtype=jnp.float32), d.shape[:-1] + (1,))
        return jnp.log(a).astype(d.dtype)
    if d.init == "ssm_dt":
        # Mamba-2 dt bias: softplus(bias) log-uniform in [0.001, 0.1]
        u = jax.random.uniform(key, d.shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(d.dtype)
    if d.init == "embed":
        return (jax.random.normal(key, d.shape, jnp.float32) * d.scale).astype(d.dtype)
    if d.init in ("normal", "scaled"):
        std = d.scale / math.sqrt(_fan_in(d))
        return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(d.dtype)
    raise ValueError(f"unknown init {d.init}")


def init_params(defs: Any, key: jax.Array) -> Any:
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    vals = [init_one(d, k) for d, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(defs: Any) -> Any:
    """ShapeDtypeStruct pytree — used by the dry-run (no allocation)."""
    return jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def param_count(defs: Any) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    return sum(math.prod(d.shape) for d in leaves)


def param_bytes(defs: Any) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    return sum(math.prod(d.shape) * jnp.dtype(d.dtype).itemsize for d in leaves)
