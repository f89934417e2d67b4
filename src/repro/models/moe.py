"""Mixture-of-Experts: token-choice top-k routing with expert parallelism.

Three execution paths, validated against each other in tests:

- ``held`` (one device, no mesh): the device holds a share of the routed
  experts (``MoEConfig.n_held`` from ``first_held``; all by default). Every
  token is routed over all experts; each held expert then runs on exactly
  the tokens routed to it, grouped by a sort (``lax.ragged_dot``), with no
  capacity and no dropped token. Assignments to experts held elsewhere add
  nothing here: their part lies on other devices.
- ``dense oracle``: every expert applied to every token, combined with the
  sparse top-k weights. O(E) compute — the tests' oracle.
- ``EP path``: experts sharded over the ``model`` mesh axis (``shard_map``).
  Each rank owns a strided subset of its data-shard's tokens, packs
  fixed-capacity per-destination buffers, exchanges them with
  ``lax.all_to_all``, runs its local experts as one grouped einsum, sends
  results back, and combines with the gate weights (capacity overflow drops,
  GShard-style). Routed experts pad up to a multiple of the EP degree
  (e.g. Qwen2's 60 -> 64) with -inf router logits.

Aux outputs: switch-style load-balance loss and router z-loss.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.compat import P
from repro.configs.base import ModelConfig, MoEConfig
from repro.models.params import ParamDef
from repro.models.layers import ffn_defs, apply_ffn


def padded_experts(moe: MoEConfig) -> int:
    return max(moe.pad_to, moe.n_experts)


def held_experts(moe: MoEConfig) -> int:
    """Routed experts whose weights this device holds."""
    return moe.n_held or padded_experts(moe)


def moe_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    moe = cfg.moe
    E = held_experts(moe)
    D, F = cfg.d_model, moe.d_ff_expert
    out = {
        "router": ParamDef((D, padded_experts(moe)), (None, "experts"),
                           scale=1.0),
        "w_in": ParamDef((E, D, 2 * F), ("experts", "embed", "mlp")),
        "w_out": ParamDef((E, F, D), ("experts", "mlp", "embed")),
    }
    if moe.n_shared:
        shared = ffn_defs(cfg, d_ff=moe.d_ff_shared)
        out.update({f"shared_{k}": v for k, v in shared.items()})
    return out


def _router_probs(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    moe = cfg.moe
    E = padded_experts(moe)
    if E > moe.n_experts:
        pad_mask = jnp.arange(E) >= moe.n_experts
        logits = jnp.where(pad_mask, -1e30, logits)
    if moe.router == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def _route(cfg: ModelConfig, x: jax.Array, router_w: jax.Array
           ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x: (N, D) -> (idx (N,k), weights (N,k), aux-loss terms)."""
    moe = cfg.moe
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = _router_probs(cfg, logits)
    top_p, top_i = jax.lax.top_k(probs, moe.top_k)
    if moe.norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    E = padded_experts(moe)
    one_hot = jax.nn.one_hot(top_i, E, dtype=jnp.float32)        # (N,k,E)
    f_sum = one_hot.sum((0, 1))                                  # tokens per expert
    p_sum = probs.sum(0)
    z_sum = jnp.square(jax.nn.logsumexp(logits, -1)).sum()
    aux = {"f_sum": f_sum, "p_sum": p_sum, "z_sum": z_sum,
           "n": jnp.asarray(x.shape[0], jnp.float32)}
    return top_i, top_p.astype(x.dtype), aux


def _aux_loss(cfg: ModelConfig, aux: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    moe = cfg.moe
    n = jnp.maximum(aux["n"], 1.0)
    f = aux["f_sum"] / (n * moe.top_k)       # fraction of assignments per expert
    p = aux["p_sum"] / n                      # mean router prob per expert
    lb = moe.n_experts * jnp.sum(f * p)
    return {"moe_load_balance": moe.aux_loss_coef * lb,
            "moe_router_z": 1e-3 * aux["z_sum"] / n}


def _expert_ffn(cfg: ModelConfig, w_in: jax.Array, w_out: jax.Array,
                x: jax.Array) -> jax.Array:
    """Grouped FFN. x: (E, C, D); w_in: (E, D, 2F); w_out: (E, F, D)."""
    dt = x.dtype
    gu = jnp.einsum("ecd,edf->ecf", x, w_in.astype(dt))
    g, u = jnp.split(gu, 2, axis=-1)
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, w_out.astype(dt))


# ---------------------------------------------------------------------------
# Held experts, dropless (one device)
# ---------------------------------------------------------------------------

def moe_held(cfg: ModelConfig, p: Dict, x: jax.Array,
             valid: Optional[jax.Array] = None, layer=None
             ) -> Tuple[jax.Array, Dict[str, jax.Array], Dict[str, jax.Array]]:
    """x: (B, S, D); ``valid`` (broadcastable to (B, S)) marks the tokens
    that route (padding and empty rows route to no expert). Returns (this
    device's part of the layer, aux losses, counts): the held experts'
    part of each token's routed sum plus the shared experts, and the
    assignments that landed on held experts and the held experts that got
    at least one token.

    With ``layer``, ``w_in`` and ``w_out`` are the stacks of every period
    layer's held experts, (L, E, D, 2F) and (L, E, F, D), and this is layer
    ``layer`` of them. The grouped matmuls then read the stack in place as
    L·E groups, all empty but this layer's E: a slice of the stack would be
    copied whole before the TPU's ragged-dot kernel, which fuses no slice
    into its operands."""
    moe = cfg.moe
    B, S, D = x.shape
    n, k, E = B * S, moe.top_k, held_experts(moe)
    dt = x.dtype
    flat = x.reshape(n, D)
    with jax.named_scope("moe.route"):
        idx, w, aux = _route(cfg, flat, p["router"])
        local = idx - moe.first_held
        here = (local >= 0) & (local < E)
        if valid is not None:
            here = here & jnp.broadcast_to(valid, (B, S)).reshape(n, 1)
        group = jnp.where(here, local, E).reshape(-1)          # (n*k,)
        order = jnp.argsort(group, stable=True)                # held first
        sizes = jnp.sum(jax.nn.one_hot(group, E, dtype=jnp.int32), 0)
        rows = flat[order // k]
    w_in, w_out, groups = p["w_in"], p["w_out"], sizes
    if layer is not None:
        G = w_in.shape[0] * E
        w_in = w_in.reshape((G,) + w_in.shape[2:])
        w_out = w_out.reshape((G,) + w_out.shape[2:])
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((G,), sizes.dtype), sizes, (layer * E,))
    with jax.named_scope("moe.experts"):
        gu = jax.lax.ragged_dot(rows, w_in.astype(dt), groups)
        g, u = jnp.split(gu, 2, axis=-1)
        out = jax.lax.ragged_dot(jax.nn.silu(g) * u, w_out.astype(dt), groups)
        # back to (token, choice) order; rows past the groups are not here
        out = out[jnp.argsort(order)].reshape(n, k, D)
        gate = jnp.where(here, w.astype(jnp.float32), 0.0)
        y = jnp.einsum("nk,nkd->nd", gate,
                       jnp.where(here[..., None], out, 0).astype(jnp.float32))
    y = y.astype(dt).reshape(B, S, D) + _shared(cfg, p, x)
    counts = {"assignments_here": jnp.sum(sizes),
              "experts_touched": jnp.sum(sizes > 0).astype(jnp.int32)}
    return y, _aux_loss(cfg, aux), counts


# ---------------------------------------------------------------------------
# Dense oracle
# ---------------------------------------------------------------------------

def moe_dense_oracle(cfg: ModelConfig, p: Dict, x: jax.Array
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D). Computes every expert on every token (tests only)."""
    moe = cfg.moe
    B, S, D = x.shape
    E = padded_experts(moe)
    flat = x.reshape(B * S, D)
    idx, w, aux = _route(cfg, flat, p["router"])
    combine = jnp.zeros((B * S, E), x.dtype)
    combine = jax.vmap(lambda c, i, v: c.at[i].add(v))(combine, idx, w)
    all_out = _expert_ffn(cfg, p["w_in"], p["w_out"],
                          jnp.broadcast_to(flat, (E,) + flat.shape))
    y = jnp.einsum("ne,end->nd", combine, all_out)
    y = y.reshape(B, S, D)
    y = y + _shared(cfg, p, x)
    return y, _aux_loss(cfg, aux)


def _shared(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    if not cfg.moe.n_shared:
        return jnp.zeros_like(x)
    sp = {k[len("shared_"):]: v for k, v in p.items() if k.startswith("shared_")}
    return apply_ffn(cfg, sp, x)


# ---------------------------------------------------------------------------
# Expert-parallel path
# ---------------------------------------------------------------------------

def moe_ep(cfg: ModelConfig, p: Dict, x: jax.Array, *,
           ep_axis: str = "model",
           token_axes: Tuple[str, ...] = ("data",),
           combine: str = "psum",
           mesh=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D) sharded (token_axes, None, None); experts over ep_axis.

    ``combine``: how per-rank (owner-partitioned) outputs reassemble across
    the EP axis — "psum" (baseline: f32-width all-reduce of a mostly-zero
    buffer) or "allgather" (contiguous ownership blocks, bf16 all-gather;
    ~4x less wire traffic — see EXPERIMENTS.md §Perf)."""
    moe = cfg.moe
    E = padded_experts(moe)
    if mesh is None:
        mesh = compat.get_abstract_mesh()
    if mesh is None:                      # no ambient mesh: single-rank path
        return moe_dense_oracle(cfg, p, x)
    axis_sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    n_ranks = axis_sizes.get(ep_axis, 1)
    if n_ranks <= 1 or E % n_ranks != 0:
        return moe_dense_oracle(cfg, p, x)
    token_axes = tuple(a for a in token_axes if axis_sizes.get(a, 1) > 1)
    E_loc = E // n_ranks
    B, S, D = x.shape
    n_tok_shards = math.prod(axis_sizes[a] for a in token_axes) if token_axes else 1
    N_loc = (B // n_tok_shards) * S
    k = moe.top_k
    cf = moe.capacity_factor
    # per-destination send capacity; each rank owns ~N_loc/n_ranks tokens
    c_send = max(int(math.ceil(N_loc * k * cf / (n_ranks * n_ranks))), k, 4)
    c_loc = max(int(math.ceil(n_ranks * c_send * cf / E_loc)), 4)
    bspec = (tuple(token_axes) if len(token_axes) > 1
             else (token_axes[0] if token_axes else None))
    blk = -(-N_loc // n_ranks)            # contiguous ownership block size

    def local(x_blk, router_w, w_in, w_out):
        # x_blk: (B_loc, S, D) replicated over ep_axis
        r = jax.lax.axis_index(ep_axis)
        flat = x_blk.reshape(-1, D)
        n = flat.shape[0]
        idx, w, aux = _route(cfg, flat, router_w)
        if combine == "allgather":
            # contiguous ownership blocks (gatherable)
            owner = jnp.arange(n) // blk
        else:
            # strided token ownership across the EP axis
            owner = jnp.arange(n) % n_ranks
        owned = owner == r
        a_idx = idx.reshape(-1)                                   # (n*k,)
        a_w = w.reshape(-1)
        a_src = jnp.repeat(jnp.arange(n), k)
        a_valid = jnp.repeat(owned, k)
        dst = a_idx // E_loc
        e_loc = a_idx % E_loc
        # position within each destination bucket (among valid assignments)
        oh = (jax.nn.one_hot(dst, n_ranks, dtype=jnp.int32)
              * a_valid[:, None].astype(jnp.int32))
        pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - oh,
                                  dst[:, None], axis=1)[:, 0]
        keep = a_valid & (pos < c_send)
        pos_c = jnp.where(keep, pos, c_send)                      # drop slot
        send_x = jnp.zeros((n_ranks, c_send + 1, D), x.dtype)
        send_x = send_x.at[dst, pos_c].set(flat[a_src], mode="drop")
        send_e = jnp.full((n_ranks, c_send + 1), E_loc, jnp.int32)
        send_e = send_e.at[dst, pos_c].set(e_loc, mode="drop")
        send_slot = jnp.full((n_ranks, c_send + 1), -1, jnp.int32)
        send_slot = send_slot.at[dst, pos_c].set(jnp.arange(n * k), mode="drop")
        send_x, send_e, send_slot = jax.tree.map(
            lambda a: a[:, :c_send], (send_x, send_e, send_slot))

        recv_x = jax.lax.all_to_all(send_x, ep_axis, 0, 0, tiled=True)
        recv_e = jax.lax.all_to_all(send_e, ep_axis, 0, 0, tiled=True)
        recv_x = recv_x.reshape(-1, D)                            # (M, D)
        recv_e = recv_e.reshape(-1)
        M = recv_x.shape[0]
        # group received tokens by local expert (second fixed-capacity scatter)
        ohe = jax.nn.one_hot(recv_e, E_loc, dtype=jnp.int32)
        gpos = jnp.take_along_axis(jnp.cumsum(ohe, 0) - ohe,
                                   jnp.minimum(recv_e, E_loc - 1)[:, None],
                                   axis=1)[:, 0]
        gvalid = (recv_e < E_loc) & (gpos < c_loc)
        gpos_c = jnp.where(gvalid, gpos, c_loc)
        grp = jnp.zeros((E_loc, c_loc + 1, D), x.dtype)
        grp = grp.at[jnp.minimum(recv_e, E_loc - 1), gpos_c].set(
            recv_x, mode="drop")[:, :c_loc]
        out_grp = _expert_ffn(cfg, w_in, w_out, grp)
        # ungroup -> recv layout (rows that were dropped contribute zeros)
        out_recv = jnp.where(
            gvalid[:, None],
            out_grp[jnp.minimum(recv_e, E_loc - 1),
                    jnp.minimum(gpos, c_loc - 1)],
            0.0).astype(x.dtype)
        back = jax.lax.all_to_all(out_recv.reshape(n_ranks, c_send, D),
                                  ep_axis, 0, 0, tiled=True).reshape(-1, D)
        # combine at source using the original slot numbering
        flat_y = jnp.zeros((n * k, D), x.dtype)
        slot = send_slot.reshape(-1)
        flat_y = flat_y.at[jnp.maximum(slot, 0)].add(
            jnp.where(slot[:, None] >= 0, back, 0.0), mode="drop")
        y = (flat_y.reshape(n, k, D) * w[..., None]).sum(1)
        if combine == "allgather":
            # owner blocks are contiguous: gather the bf16 blocks instead of
            # all-reducing a mostly-zero f32-width buffer
            pad = blk * n_ranks - n
            y_pad = jnp.pad(y, ((0, pad), (0, 0))) if pad else y
            mine = jax.lax.dynamic_slice_in_dim(y_pad, r * blk, blk, axis=0)
            y = jax.lax.all_gather(mine, ep_axis, axis=0, tiled=True)
            y = y[:n] if pad else y
        else:
            # each token's y is nonzero on exactly one rank -> psum
            y = jax.lax.psum(y, ep_axis)
        # aux terms: identical across EP ranks, partial across token shards
        all_axes = (ep_axis,) + token_axes
        aux = {kk: jax.lax.psum(v, all_axes) / n_ranks for kk, v in aux.items()}
        return y.reshape(x_blk.shape), aux

    specs_in = (P(bspec, None, None), P(None, None), P(ep_axis, None, None),
                P(ep_axis, None, None))
    specs_out = (P(bspec, None, None), {kk: P() for kk in
                                        ("f_sum", "p_sum", "z_sum", "n")})
    f = compat.shard_map(local, mesh=mesh, in_specs=specs_in,
                         out_specs=specs_out, check_vma=False)
    y, aux = f(x, p["router"], p["w_in"], p["w_out"])
    y = y + _shared(cfg, p, x)
    return y, _aux_loss(cfg, aux)


def moe_apply(cfg: ModelConfig, p: Dict, x: jax.Array, *,
              valid: Optional[jax.Array] = None, layer=None,
              distributed: bool = False,
              ep_axis: str = "model",
              token_axes: Tuple[str, ...] = ("data",),
              combine: str = "psum",
              ) -> Tuple[jax.Array, Dict[str, jax.Array],
                         Optional[Dict[str, jax.Array]]]:
    """(y, aux losses, counts); counts (``moe_held``'s) are None on the
    expert-parallel path, which holds every expert over the mesh and routes
    padding too. ``layer``: ``moe_held``'s, for stacked held experts."""
    if distributed:
        if held_experts(cfg.moe) != padded_experts(cfg.moe):
            raise ValueError(f"{cfg.name}: a device's share of the experts "
                             "runs on one device, not over a mesh")
        y, aux = moe_ep(cfg, p, x, ep_axis=ep_axis, token_axes=token_axes,
                        combine=combine)
        return y, aux, None
    return moe_held(cfg, p, x, valid, layer)
