"""Train-step builder: microbatched gradient accumulation (lax.scan), remat,
mixed precision, buffer donation, sharding-annotated state.

The accumulation scan performs a single logical gradient all-reduce per step
(XLA fuses the FSDP reduce-scatters into the backward); ``accum_dtype``
selects the accumulation buffer precision (bf16 halves the grad-buffer HBM,
the standard 'gradient compression' lever on TPU — see DESIGN.md §7).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.compat import NamedSharding, P
from repro.configs.base import ModelConfig
from repro.models import model_defs, init_params
from repro.models.layers import scan_unroll
from repro.models.transformer import RunFlags, train_logits
from repro.train.loss import cross_entropy
from repro.train.optimizer import OptConfig, adamw_update, init_opt


@dataclass(frozen=True)
class TrainConfig:
    n_microbatches: int = 1
    accum_dtype: Any = jnp.float32
    z_loss: float = 1e-4
    aux_scale: float = 1.0        # scale on MoE aux losses
    # unroll the accumulation scan, even over one microbatch (dry-run
    # roofline variants: cost_analysis counts a rolled scan's body once)
    unroll_accum: bool = False


def init_train_state(cfg: ModelConfig, ocfg: OptConfig, key) -> Dict[str, Any]:
    params = init_params(model_defs(cfg), key)
    return {"params": params, "opt": init_opt(params, ocfg)}


def abstract_train_state(cfg: ModelConfig, ocfg: OptConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct state for the dry-run (no allocation)."""
    from repro.models import abstract_params
    defs = model_defs(cfg)
    params = abstract_params(defs)
    opt = jax.eval_shape(lambda p: init_opt(p, ocfg), params)
    return {"params": params, "opt": opt}


def _split_micro(batch: Dict[str, jax.Array], m: int) -> Dict[str, jax.Array]:
    def f(x):
        return x.reshape((m, x.shape[0] // m) + x.shape[1:])
    return jax.tree.map(f, batch)


def build_train_step(cfg: ModelConfig, ocfg: OptConfig,
                     tcfg: TrainConfig = TrainConfig(),
                     flags: RunFlags = RunFlags()):
    """Returns train_step(state, batch) -> (state, metrics)."""

    def loss_fn(params, micro):
        logits, aux = train_logits(cfg, params, micro, flags=flags)
        loss, stats = cross_entropy(logits, micro["labels"],
                                    z_loss=tcfg.z_loss)
        aux_total = sum(aux.values())
        loss = loss + tcfg.aux_scale * aux_total
        stats = dict(stats, **aux, loss=loss)
        return loss, stats

    grad_fn = jax.grad(loss_fn, has_aux=True)

    def train_step(state, batch):
        params, opt = state["params"], state["opt"]
        m = tcfg.n_microbatches
        if m > 1 or tcfg.unroll_accum:
            micros = _split_micro(batch, m)

            def acc_body(carry, micro):
                grads, stats_acc = carry
                g, stats = grad_fn(params, micro)
                grads = jax.tree.map(
                    lambda a, b: a + b.astype(tcfg.accum_dtype), grads, g)
                stats_acc = jax.tree.map(lambda a, b: a + b, stats_acc, stats)
                return (grads, stats_acc), None

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, tcfg.accum_dtype), params)
            zero_s = {k: jnp.zeros((), jnp.float32) for k in
                      ("ce", "z_loss", "accuracy", "tokens", "loss",
                       "moe_load_balance", "moe_router_z")}
            (grads, stats), _ = jax.lax.scan(
                acc_body, (zero_g, zero_s), micros,
                unroll=scan_unroll(tcfg.unroll_accum, m))
            grads = jax.tree.map(lambda g: (g / m).astype(jnp.float32), grads)
            stats = jax.tree.map(lambda s: s / m, stats)
            stats["tokens"] = stats["tokens"] * m
        else:
            grads, stats = grad_fn(params, batch)
        new_params, new_opt, opt_stats = adamw_update(grads, opt, params, ocfg)
        metrics = dict(stats, **opt_stats, step=new_opt["step"])
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def state_shardings(cfg: ModelConfig, mesh, rules=None):
    """NamedSharding pytree matching init_train_state's structure."""
    from repro.models import param_shardings
    defs = model_defs(cfg)
    pshard = param_shardings(defs, mesh, rules)
    scalar = NamedSharding(mesh, P())
    return {"params": pshard,
            "opt": {"m": pshard, "v": pshard, "step": scalar}}


def batch_shardings(mesh, batch_axes=("data",), batch_example=None):
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    lead = axes if len(axes) > 1 else (axes[0] if axes else None)

    def one(x):
        nd = len(x.shape)
        return NamedSharding(mesh, P(*([lead] + [None] * (nd - 1))))

    if batch_example is None:
        return lambda tree: jax.tree.map(one, tree)
    return jax.tree.map(one, batch_example)
