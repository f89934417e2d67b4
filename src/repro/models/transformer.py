"""Model assembly: layer dispatch, scan-over-layers stack, train/prefill/decode.

A model is ``prelayers`` (unscanned, e.g. DeepSeek-V2's dense layer 0) plus
``n_periods`` repetitions of a ``period`` (tuple of LayerSpec). Period
parameters are stacked on a leading axis and the stack is evaluated with
``lax.scan``, keeping HLO size independent of depth (126-layer models compile
in seconds at 512 devices).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models.params import ParamDef
from repro.models import layers as L
from repro.models import attention as A
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import mamba as MB
from repro.models import xlstm as XL


@dataclass(frozen=True)
class RunFlags:
    """Runtime execution options (distribution / kernel backend / remat)."""
    distributed: bool = False
    backend: str = "xla"                   # attention backend: xla|pallas|interpret
    ep_axis: str = "model"
    token_axes: Tuple[str, ...] = ("data",)
    decode_seq_axes: Tuple[str, ...] = ()  # () -> single-shard reference path
    act_spec: Optional[Any] = None         # PartitionSpec for (B,S,D) activations
    remat: str = "full"                    # full | none
    # unroll the layer scan (and flash attention's kv-tile scan): used by
    # the dry-run's roofline variants so cost_analysis counts every layer
    # (a rolled scan's body is counted once regardless of trip count)
    unroll_layers: bool = False
    moe_combine: str = "psum"              # psum | allgather (§Perf)
    # cast weight matrices to the compute dtype BEFORE their use-site, so the
    # ZeRO-3 all-gather moves bf16 instead of fp32 (halves FSDP gather volume;
    # §Perf). Norm scales / biases / SSM A-matrices stay fp32.
    cast_params_early: bool = False


AUX_KEYS = ("moe_load_balance", "moe_router_z")


def zero_aux() -> Dict[str, jax.Array]:
    return {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}


def _add_aux(a: Dict, b: Dict) -> Dict:
    return {k: a[k] + b.get(k, 0.0) for k in AUX_KEYS}


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

_MIXER_DEFS = {
    "attn": A.attn_defs,
    "mla": MLA.mla_defs,
    "mamba": MB.mamba_defs,
    "mlstm": XL.mlstm_defs,
    "slstm": XL.slstm_defs,
}


def layer_defs(cfg: ModelConfig, spec: LayerSpec) -> Dict[str, Any]:
    out: Dict[str, Any] = {
        "mixer_norm": L.norm_defs(cfg, cfg.d_model),
        "mixer": _MIXER_DEFS[spec.mixer](cfg),
    }
    if spec.ffn != "none":
        if not spec.parallel:
            out["ffn_norm"] = L.norm_defs(cfg, cfg.d_model)
        out["ffn"] = MOE.moe_defs(cfg) if spec.ffn == "moe" else L.ffn_defs(cfg)
    return out


def _stack_def(d: ParamDef, n: int) -> ParamDef:
    return dataclasses.replace(d, shape=(n,) + d.shape, axes=("layers",) + d.axes)


def model_defs(cfg: ModelConfig) -> Dict[str, Any]:
    defs: Dict[str, Any] = {
        "embed": L.embed_defs(cfg),
        "out_norm": L.norm_defs(cfg, cfg.d_model),
        "prelayers": tuple(layer_defs(cfg, s) for s in cfg.prelayers),
    }
    period = tuple(layer_defs(cfg, s) for s in cfg.period)
    defs["period"] = jax.tree.map(lambda d: _stack_def(d, cfg.n_periods), period,
                                  is_leaf=lambda x: isinstance(x, ParamDef))
    return defs


# ---------------------------------------------------------------------------
# KV / state cache
# ---------------------------------------------------------------------------

def layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, s_max: int):
    if spec.mixer == "attn":
        KV, HD = cfg.n_kv_heads, cfg.head_dim
        return {"k": jnp.zeros((batch, s_max, KV, HD), jnp.bfloat16),
                "v": jnp.zeros((batch, s_max, KV, HD), jnp.bfloat16)}
    if spec.mixer == "mla":
        m = cfg.mla
        return {"ckv": jnp.zeros((batch, s_max, m.kv_lora_rank), jnp.bfloat16),
                "kr": jnp.zeros((batch, s_max, m.qk_rope_head_dim), jnp.bfloat16)}
    if spec.mixer == "mamba":
        return MB.mamba_init_cache(cfg, batch)
    if spec.mixer == "mlstm":
        return XL.mlstm_init_cache(cfg, batch)
    if spec.mixer == "slstm":
        return XL.slstm_init_cache(cfg, batch)
    raise ValueError(spec.mixer)


def init_cache(cfg: ModelConfig, batch: int, s_max: int):
    pre = tuple(layer_cache(cfg, s, batch, s_max) for s in cfg.prelayers)
    def stack(c):
        return jax.tree.map(
            lambda a: jnp.zeros((cfg.n_periods,) + a.shape, a.dtype), c)
    period = tuple(stack(layer_cache(cfg, s, batch, s_max)) for s in cfg.period)
    return {"prelayers": pre, "period": period, "lengths":
            jnp.zeros((batch,), jnp.int32)}


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------

def _apply_mixer_seq(cfg, spec, p, x, positions, lengths, flags, want_cache):
    """Full-sequence mixer (train / prefill). Returns (y, cache_or_None)."""
    if spec.mixer == "attn":
        y, (k, v) = A.self_attention(cfg, p, x, positions, lengths=lengths,
                                     backend=flags.backend,
                                     unroll=flags.unroll_layers)
        cache = {"k": k.astype(jnp.bfloat16),
                 "v": v.astype(jnp.bfloat16)} if want_cache else None
        return y, cache
    if spec.mixer == "mla":
        y, (ckv, kr) = MLA.mla_self_attention(cfg, p, x, positions,
                                              lengths=lengths,
                                              backend=flags.backend,
                                              unroll=flags.unroll_layers)
        cache = {"ckv": ckv.astype(jnp.bfloat16),
                 "kr": kr.astype(jnp.bfloat16)} if want_cache else None
        return y, cache
    if spec.mixer == "mamba":
        y = MB.mamba_mixer(cfg, p, x)
        cache = None
        if want_cache:
            lens = lengths if lengths is not None else \
                jnp.full((x.shape[0],), x.shape[1], jnp.int32)
            cache = MB.mamba_prefill_cache(cfg, p, x, lens)
        return y, cache
    if spec.mixer in ("mlstm", "slstm"):
        mix = XL.mlstm_mixer if spec.mixer == "mlstm" else XL.slstm_mixer
        y = mix(cfg, p, x)
        cache = None
        if want_cache:
            lens = lengths if lengths is not None else \
                jnp.full((x.shape[0],), x.shape[1], jnp.int32)
            cache = XL.xlstm_prefill_cache(cfg, spec.mixer, p, x, lens)
        return y, cache
    raise ValueError(spec.mixer)


def apply_layer_seq(cfg: ModelConfig, spec: LayerSpec, p: Dict, x: jax.Array,
                    positions, lengths, flags: RunFlags, want_cache: bool,
                    expert_layer=None):
    """One full layer over a whole sequence. Returns (x, cache, aux,
    counts); counts (an MoE layer's, else None) leave padding out. With
    ``expert_layer``, the MoE weights ``w_in`` and ``w_out`` are the stacks
    over the periods (``held_stacks``) and this layer is that period."""
    aux, counts = zero_aux(), None
    h = L.apply_norm(cfg, p["mixer_norm"], x)
    y_mix, cache = _apply_mixer_seq(cfg, spec, p["mixer"], h, positions,
                                    lengths, flags, want_cache)
    valid = None
    if spec.ffn == "moe" and lengths is not None:
        valid = positions < lengths[:, None]
    if spec.parallel and spec.ffn != "none":
        y_ffn, aux, counts = _apply_ffn(cfg, spec, p["ffn"], h, flags, valid,
                                        expert_layer)
        x = x + y_mix + y_ffn
        return x, cache, aux, counts
    x = x + y_mix
    if spec.ffn != "none":
        h = L.apply_norm(cfg, p["ffn_norm"], x)
        y_ffn, aux, counts = _apply_ffn(cfg, spec, p["ffn"], h, flags, valid,
                                        expert_layer)
        x = x + y_ffn
    return x, cache, aux, counts


def _apply_ffn(cfg, spec, p, h, flags, valid=None, expert_layer=None):
    """(y, aux, counts); counts only from an MoE layer that reports them."""
    if spec.ffn == "moe":
        y, aux_losses, counts = MOE.moe_apply(
            cfg, p, h, valid=valid, layer=expert_layer,
            distributed=flags.distributed,
            ep_axis=flags.ep_axis, token_axes=flags.token_axes,
            combine=flags.moe_combine)
        aux = zero_aux()
        aux.update({k: jnp.asarray(v, jnp.float32)
                    for k, v in aux_losses.items()})
        return y, aux, counts
    return L.apply_ffn(cfg, p, h), zero_aux(), None


def apply_layer_decode(cfg: ModelConfig, spec: LayerSpec, p: Dict,
                       x: jax.Array, cache: Dict, lengths: jax.Array,
                       flags: RunFlags, layer=None, expert_layer=None):
    """One layer, one decode token. Returns (x, new_cache, counts): an MoE
    layer's counts, else None. With ``layer``, ``cache`` is the stacked
    cache of every period layer (see ``carried_layers``) and the whole
    stack comes back; ``expert_layer`` is ``apply_layer_seq``'s. An MoE
    layer routes no token of a row whose length is 0: the engine keeps its
    free slots there."""
    h = L.apply_norm(cfg, p["mixer_norm"], x)
    if spec.mixer == "attn":
        y_mix, new_cache = A.decode_self_attention(
            cfg, p["mixer"], h, cache, lengths, layer=layer,
            seq_axes=flags.decode_seq_axes, batch_axes=flags.token_axes)
    elif spec.mixer == "mla":
        y_mix, new_cache = MLA.mla_decode_attention(
            cfg, p["mixer"], h, cache, lengths, layer=layer,
            seq_axes=flags.decode_seq_axes, batch_axes=flags.token_axes)
    elif spec.mixer == "mamba":
        y_mix, new_cache = MB.mamba_decode(cfg, p["mixer"], h, cache)
    elif spec.mixer == "mlstm":
        y_mix, new_cache = XL.mlstm_decode(cfg, p["mixer"], h, cache)
    elif spec.mixer == "slstm":
        y_mix, new_cache = XL.slstm_decode(cfg, p["mixer"], h, cache)
    else:
        raise ValueError(spec.mixer)
    valid = (lengths > 0)[:, None] if spec.ffn == "moe" else None
    counts = None
    if spec.parallel and spec.ffn != "none":
        y_ffn, _, counts = _apply_ffn(cfg, spec, p["ffn"], h, flags, valid,
                                      expert_layer)
        x = x + y_mix + y_ffn
    else:
        x = x + y_mix
        if spec.ffn != "none":
            h = L.apply_norm(cfg, p["ffn_norm"], x)
            y_ffn, _, counts = _apply_ffn(cfg, spec, p["ffn"], h, flags,
                                          valid, expert_layer)
            x = x + y_ffn
    return x, new_cache, counts


def moe_counts(per_layer) -> Dict[str, jax.Array]:
    """Per-layer counts of the MoE layers as one int32 vector per count
    (``assignments_here``, ``experts_touched``): prelayers first, then each
    position of the period over the periods. ``per_layer`` holds scalars
    (prelayers) and (n_periods,) vectors (period layers), or None for a
    layer without counts."""
    per_layer = [c for c in per_layer if c is not None]
    if not per_layer:
        return {}
    return {k: jnp.concatenate([jnp.reshape(c[k], (-1,)) for c in per_layer])
            for k in per_layer[0]}


# ---------------------------------------------------------------------------
# Full model: train / prefill forward
# ---------------------------------------------------------------------------

def _embed_input(cfg: ModelConfig, params, batch: Dict[str, jax.Array]):
    extra = batch.get("vision_embeds", batch.get("frame_embeds"))
    x = L.embed_tokens(cfg, params["embed"], batch.get("tokens"), extra)
    return x


def _constrain(x, flags):
    if flags.act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, flags.act_spec)
    return x


# numerics-sensitive weights stay fp32: SSM A / dt projection (exp/softplus)
# and the MoE router (top-k selection must not flip under bf16 logits)
_PRECAST_EXCLUDE = ("a_log", "dt_w", "router")


def _precast(pp, cfg: ModelConfig, flags: RunFlags):
    """Cast >=2-D weights to the compute dtype while still sharded, so SPMD
    gathers bf16 (downstream ``.astype`` calls become no-ops)."""
    if not flags.cast_params_early:
        return pp
    dt = jnp.dtype(cfg.dtype)

    def f(path, a):
        name = getattr(path[-1], "key", None) if path else None
        if a.ndim >= 2 and name not in _PRECAST_EXCLUDE:
            return a.astype(dt)
        return a

    return jax.tree_util.tree_map_with_path(f, pp)


def held_stacks(cfg: ModelConfig, period, flags: RunFlags):
    """Take each MoE position's held experts (``w_in`` and ``w_out``,
    stacked over the periods) out of the period parameters the layer loop
    slices. Returns (what the loop slices, per position of the period the
    stacks, ``_precast`` once here, or None). Nothing is taken on the
    expert-parallel path, which shards the experts itself.

    The serving programs hand the stacks whole to every layer, so no layer's
    experts are copied out of them (``moe_held``). Training keeps the
    slice: the gradient of a stack the loop closes over is a whole stack
    summed on every layer."""
    if flags.distributed or not cfg.n_periods:
        return period, (None,) * len(cfg.period)
    sliced, stacks = [], []
    for spec, p in zip(cfg.period, period):
        if spec.ffn != "moe":
            sliced.append(p)
            stacks.append(None)
            continue
        ffn = dict(p["ffn"])
        stacks.append(_precast({k: ffn.pop(k) for k in ("w_in", "w_out")},
                               cfg, flags))
        sliced.append(dict(p, ffn=ffn))
    return tuple(sliced), tuple(stacks)


def _with_stack(p, stack):
    """A layer's parameters with its held-expert stacks put back."""
    return p if stack is None else dict(p, ffn=dict(p["ffn"], **stack))


def forward(cfg: ModelConfig, params: Dict, batch: Dict[str, jax.Array], *,
            flags: RunFlags = RunFlags(), want_cache: bool = False,
            lengths: Optional[jax.Array] = None, with_counts: bool = False):
    """Full-sequence forward. Returns (hidden (B,S,D), caches, aux), with
    ``with_counts`` also the MoE layers' counts (``moe_counts``)."""
    x = _embed_input(cfg, params, batch)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    aux = zero_aux()
    x = _constrain(x, flags)

    pre_caches, pre_counts = [], []
    for spec, p in zip(cfg.prelayers, params["prelayers"]):
        x, c, a, n = apply_layer_seq(cfg, spec, _precast(p, cfg, flags), x,
                                     positions, lengths, flags, want_cache)
        pre_caches.append(c)
        pre_counts.append(n)
        aux = _add_aux(aux, a)

    # a forward that builds a cache serves: its held experts are read in
    # place in their stacks, with each period's index beside its slice
    period, stacks = params["period"], (None,) * len(cfg.period)
    if want_cache:
        period, stacks = held_stacks(cfg, period, flags)
    stacked = any(st is not None for st in stacks)

    def period_body(carry, xs):
        x, aux = carry
        pp, i = xs if stacked else (xs, None)
        x = _constrain(x, flags)
        pp = _precast(pp, cfg, flags)
        caches, counts = [], []
        for spec, p, st in zip(cfg.period, pp, stacks):
            x, c, a, n = apply_layer_seq(
                cfg, spec, _with_stack(p, st), x, positions, lengths, flags,
                want_cache, expert_layer=None if st is None else i)
            caches.append(c)
            counts.append(n)
            aux = _add_aux(aux, a)
        if with_counts:
            return (x, aux), (tuple(caches), tuple(counts))
        return (x, aux), tuple(caches)

    body = period_body
    if flags.remat == "full":
        body = jax.remat(period_body)
    xs = (period, jnp.arange(cfg.n_periods)) if stacked else period
    (x, aux), period_caches = jax.lax.scan(
        body, (x, aux), xs,
        unroll=L.scan_unroll(flags.unroll_layers, cfg.n_periods))
    period_counts = ()
    if with_counts:
        period_caches, period_counts = period_caches
    x = L.apply_norm(cfg, params["out_norm"], x)
    caches = None
    if want_cache:
        caches = {"prelayers": tuple(pre_caches), "period": period_caches}
    if with_counts:
        return x, caches, aux, moe_counts(pre_counts + list(period_counts))
    return x, caches, aux


def train_logits(cfg: ModelConfig, params, batch, *, flags=RunFlags()):
    x, _, aux = forward(cfg, params, batch, flags=flags)
    return L.unembed(cfg, params["embed"], x), aux


def prefill(cfg: ModelConfig, params, batch, lengths, *, flags=RunFlags(),
            with_counts: bool = False):
    """Prompt ingestion. Returns (last-position logits (B,V), cache), with
    ``with_counts`` also the MoE layers' counts over the prompt tokens
    (``moe_counts``; positions from ``lengths`` on route nowhere)."""
    out = forward(cfg, params, batch, flags=flags, want_cache=True,
                  lengths=lengths, with_counts=with_counts)
    x, caches = out[0], out[1]
    B = x.shape[0]
    idx = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = L.unembed(cfg, params["embed"], last)
    caches["lengths"] = lengths
    if with_counts:
        return logits, caches, out[3]
    return logits, caches


def carried_layers(cfg: ModelConfig, flags: RunFlags) -> Tuple[bool, ...]:
    """For each layer of the period, whether ``decode_step`` carries its
    cache through the layer loop whole and writes it in place, one token
    per row (attention keys and values, MLA latents, on one shard), rather
    than reading and replacing it whole as the loop's per-layer input and
    output (recurrent state, the sequence-sharded path). Only a carried
    cache gains from being donated to the decode program: one the loop
    reads and replaces per layer, XLA copies whole on every step if it is
    donated."""
    return tuple(s.mixer in ("attn", "mla") and not flags.decode_seq_axes
                 for s in cfg.period)


def decode_step(cfg: ModelConfig, params, cache, tokens, *,
                flags: RunFlags = RunFlags(), with_counts: bool = False):
    """One token for every sequence. tokens: (B,) or (B,1) int32 (or
    (B,1,D) frame embeds for input_mode=embeds). Returns (logits, cache),
    with ``with_counts`` also the MoE layers' counts (``moe_counts``; rows
    of length 0 route nowhere)."""
    lengths = cache["lengths"]
    if cfg.input_mode == "embeds":
        x = tokens.astype(jnp.dtype(cfg.dtype)) @ \
            params["embed"]["frame_proj"].astype(jnp.dtype(cfg.dtype))
    else:
        tok = tokens if tokens.ndim == 2 else tokens[:, None]
        x = params["embed"]["tok"].astype(jnp.dtype(cfg.dtype))[tok]
        x = x * jnp.asarray(cfg.embedding_multiplier, jnp.dtype(cfg.dtype))
    if cfg.pos_emb == "sincos":
        x = x + L.sincos_pos_emb(lengths[:, None], cfg.d_model
                                 ).astype(x.dtype)

    new_pre, pre_counts = [], []
    for spec, p, c in zip(cfg.prelayers, params["prelayers"],
                          cache["prelayers"]):
        x, c2, n = apply_layer_decode(cfg, spec, p, x, c, lengths, flags)
        new_pre.append(c2)
        pre_counts.append(n)

    # append-only caches ride the layer loop's carry and take one token per
    # row in place; the rest pass through as scanned inputs and outputs
    carried = carried_layers(cfg, flags)
    kv = tuple(c if k else None for c, k in zip(cache["period"], carried))
    state = tuple(None if k else c for c, k in zip(cache["period"], carried))
    period, stacks = held_stacks(cfg, params["period"], flags)

    def body(carry, xs):
        x, kv = carry
        pp, st, i = xs
        pp = _precast(pp, cfg, flags)
        kv, st, counts = list(kv), list(st), []
        for j, (spec, p) in enumerate(zip(cfg.period, pp)):
            p = _with_stack(p, stacks[j])
            at = None if stacks[j] is None else i
            if carried[j]:
                x, kv[j], n = apply_layer_decode(cfg, spec, p, x, kv[j],
                                                 lengths, flags, layer=i,
                                                 expert_layer=at)
            else:
                x, st[j], n = apply_layer_decode(cfg, spec, p, x, st[j],
                                                 lengths, flags,
                                                 expert_layer=at)
            counts.append(n)
        if with_counts:
            return (x, tuple(kv)), (tuple(st), tuple(counts))
        return (x, tuple(kv)), tuple(st)

    (x, kv), state = jax.lax.scan(
        body, (x, kv),
        (period, state, jnp.arange(cfg.n_periods)),
        unroll=L.scan_unroll(flags.unroll_layers, cfg.n_periods))
    period_counts = ()
    if with_counts:
        state, period_counts = state
    new_period = tuple(c if k else s
                       for c, s, k in zip(kv, state, carried))
    x = L.apply_norm(cfg, params["out_norm"], x)
    logits = L.unembed(cfg, params["embed"], x[:, 0])
    new_cache = {"prelayers": tuple(new_pre), "period": new_period,
                 "lengths": lengths + 1}
    if with_counts:
        return logits, new_cache, moe_counts(pre_counts + list(period_counts))
    return logits, new_cache
