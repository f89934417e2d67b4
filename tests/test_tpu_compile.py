"""Compile the main path's kernels at real widths for a described TPU v5e.

Nothing runs: the TPU compiler that ships with jax compiles for a chip that
is described, not attached, and refuses what the chip would refuse — a
tiling the hardware cannot take, a VMEM budget, HBM — which interpret-mode
tests cannot see. Describing the topology loads libtpu, which one process
holds at a time, so it happens only inside the module fixture below, and
all such compiles stay in this one file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import autotune, ops
from repro.models import abstract_params, model_defs
from repro.models import transformer as T
from repro.models.transformer import init_cache, prefill
from repro.serve.engine import decode_program, split_cache
from repro.serve.paged import paged_decode_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # else the compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("heads,head_dim", [(12, 64), (8, 128)])
def test_flash_attention_compiles_for_v5e(one_chip, heads, head_dim):
    q = jax.ShapeDtypeStruct((1, 4096, heads, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    hlo = _hlo(lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
               q, q, q)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("residual", [False, True])
def test_rmsnorm_compiles_for_v5e(one_chip, residual):
    x = jax.ShapeDtypeStruct((8192, 768), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((768,), jnp.float32, sharding=one_chip)
    if residual:
        hlo = _hlo(lambda x, r, w: ops.rmsnorm_residual(x, r, w,
                                                        backend="pallas"),
                   x, x, w)
    else:
        hlo = _hlo(lambda x, w: ops.rmsnorm(x, w, backend="pallas"), x, w)
    assert "tpu_custom_call" in hlo


def test_paged_decode_compiles_for_v5e(one_chip):
    """tacc-100m decode widths: 8 sequences, 12 query / 4 kv heads of 64,
    a 1024-token cache in autotuned pages. The path is XLA (gather, then
    the dense reference math), so no kernel is expected."""
    B, H, KV, HD, S = 8, 12, 4, 64, 1024
    page, _ = autotune.plan_decode_page((B, H, S, HD), jnp.bfloat16)
    n = S // page

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = _hlo(paged_decode_attention, spec((B, H, HD)),
               spec((B * n, page, KV, HD)), spec((B * n, page, KV, HD)),
               spec((B, n), jnp.int32), spec((B,), jnp.int32))
    assert "gather" in hlo


def _decode_args(cfg, B, S, sharding):
    """Abstract params (bf16), cache and tokens of the engine's decode
    program, placed on ``sharding``."""
    def spec(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype,
                                    sharding=sharding)

    params = jax.tree.map(lambda a: spec(a, jnp.bfloat16),
                          abstract_params(model_defs(cfg)))
    cache = jax.tree.map(spec, jax.eval_shape(lambda: init_cache(cfg, B, S)))
    tokens = spec(jax.ShapeDtypeStruct((B,), jnp.int32))
    return params, cache, tokens


def _nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def test_serve_decode_owns_its_cache_for_v5e(one_chip):
    """ServeEngine's decode program at InternLM2-1.8B widths (d_model 2048,
    16 query / 8 kv heads of 128, vocab 92544) over 16 rows of 2048
    positions, depth cut to 2 layers to keep the compile short. The program
    aliases the whole KV cache from input to output, and its temporaries
    stay under one layer's keys: each step writes one token per row and
    layer, and copies no layer.

    Fails without either half of the mechanism: with the cache neither
    donated nor carried through the layer loop, the alias is 0 and the
    output is a fresh cache; donated but still passed through the loop as
    per-layer input and output, the compiler adds whole-cache copies and
    the temporaries exceed the cache."""
    cfg = dataclasses.replace(get_config("internlm2-1.8b"), n_layers=2)
    B, S = 16, 2048
    params, cache, tokens = _decode_args(cfg, B, S, one_chip)
    owned, kept = split_cache(cfg, cache)
    mem = decode_program(cfg).lower(params, owned, kept,
                                    tokens).compile().memory_analysis()
    one_layer_k = B * S * cfg.n_kv_heads * cfg.head_dim * 2
    assert _nbytes(owned) == 2 * cfg.n_layers * one_layer_k
    assert _nbytes(kept) == B * 4                    # the lengths alone
    assert mem.alias_size_in_bytes >= _nbytes(owned)
    assert mem.temp_size_in_bytes < one_layer_k


def test_mla_serve_decode_owns_its_latents_for_v5e(one_chip):
    """ServeEngine's decode program of the DeepSeek-V2-Lite share at its
    published widths (kv_lora_rank 512, rope key 64, YaRN, 8 held
    experts), depth cut to 3 layers, over 16 rows of 4096 positions. The
    program aliases the period layers' latent and rope-key stacks from
    input to output, and its temporaries stay under one layer's latents:
    each step writes one latent per row and layer, and copies no layer.

    Fails without the mechanism: with the latents replaced whole as the
    layer loop's per-layer input and output, nothing is donated and the
    alias is 0."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-ep8"), n_layers=3)
    B, S = 16, 4096
    params, cache, tokens = _decode_args(cfg, B, S, one_chip)
    owned, kept = split_cache(cfg, cache)
    mem = decode_program(cfg, with_counts=True).lower(
        params, owned, kept, tokens).compile().memory_analysis()
    period_layers = cfg.n_layers - len(cfg.prelayers)
    one_layer_ckv = B * S * cfg.mla.kv_lora_rank * 2
    latents = period_layers * B * S * (
        cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim) * 2
    assert one_layer_ckv == 64 * 2 ** 20
    assert _nbytes(cache["period"]) == latents
    assert mem.alias_size_in_bytes >= latents
    assert mem.temp_size_in_bytes < one_layer_ckv


@pytest.mark.parametrize("arch,n_layers,smoke", [
    ("deepseek-v2-236b", 3, False),     # MLA latents, an unscanned prelayer
    ("xlstm-125m", 8, False),           # mlstm and slstm state
    ("jamba-1.5-large-398b", 0, True),  # mamba state beside one attention
])
def test_serve_decode_donates_only_what_it_writes_in_place_for_v5e(
        one_chip, arch, n_layers, smoke):
    """Caches the decode program reads and replaces whole stay out of the
    donation: donated, the compiler copies each of them whole in
    temporaries on every step (xLSTM-125M's state: 0 B of temp become 233
    MB at 16 x 2048). So the engine's program aliases exactly the caches
    its layer loop carries (``carried_layers``: attention keys and values,
    the period's MLA latents, not DeepSeek-V2's prelayer), and its
    temporaries are no larger than those of the same step with nothing
    donated. DeepSeek-V2 keeps its published widths but 2 routed experts,
    so 3 layers fit one chip; Jamba runs at smoke widths, 16 x 2048."""
    cfg = get_config(arch, smoke=smoke)
    cfg = dataclasses.replace(cfg, n_layers=n_layers or cfg.n_layers)
    if cfg.moe is not None and not smoke:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, n_experts=cfg.moe.top_k))
    params, cache, tokens = _decode_args(cfg, 16, 2048, one_chip)
    args = (params, *split_cache(cfg, cache), tokens)
    mem = decode_program(cfg).lower(*args).compile().memory_analysis()
    undonated = jax.jit(decode_program(cfg).__wrapped__)
    base = undonated.lower(*args).compile().memory_analysis()
    carried_bytes = sum(_nbytes(c) for c, k in zip(
        cache["period"], T.carried_layers(cfg, T.RunFlags())) if k)
    assert mem.alias_size_in_bytes == carried_bytes
    assert mem.temp_size_in_bytes <= base.temp_size_in_bytes


def test_held_expert_layer_compiles_for_v5e(one_chip):
    """The dropless held-expert layer at DeepSeek-V2-Lite's widths (d_model
    2048, 8 of 64 experts of 1408 held, top-6, 2 shared), over a decode
    step's 16 rows and a prefill's 4096: the grouped matmuls are the TPU's
    ragged-dot kernel, and the temporaries stay under what computing all 8
    held experts on every assignment would take."""
    from repro.models.moe import moe_held
    cfg = get_config("deepseek-v2-lite-ep8")
    p = abstract_params(model_defs(cfg))["period"][0]["ffn"]
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape[1:], jnp.bfloat16, sharding=one_chip), p)
    moe = cfg.moe
    for rows in (16, 4096):
        x = jax.ShapeDtypeStruct((1, rows, cfg.d_model), jnp.bfloat16,
                                 sharding=one_chip)
        valid = jax.ShapeDtypeStruct((1, rows), jnp.bool_, sharding=one_chip)
        c = jax.jit(lambda p, x, v: moe_held(cfg, p, x, v)).lower(
            p, x, valid).compile()
        assert "ragged-dot" in c.as_text()
        dense = moe.n_held * rows * moe.top_k * 2 * moe.d_ff_expert * 2
        assert c.memory_analysis().temp_size_in_bytes < dense


# a layer's 8 held experts copied out of the (26, 8, ...) stacks
_EXPERT_COPY = re.compile(r"= bf16\[8,(2048,2816|1408,2048)\]")
_GROUPED_ROWS = re.compile(r"%ragged-dot-none[.\d]* = bf16\[(\d+),")


def _docs_programs(sharding):
    """The DeepSeek-V2-Lite share at the docs cell's full size (27 layers,
    16 rows of 4096 positions, one 4096-long prefill row), with its MoE
    counts: ``{name: (program, args)}`` for ServeEngine's decode and
    prefill programs."""
    cfg = get_config("deepseek-v2-lite-ep8")
    params, cache, tokens = _decode_args(cfg, 16, 4096, sharding)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return cfg, cache, {
        "decode": (decode_program(cfg, with_counts=True),
                   (params, *split_cache(cfg, cache), tokens)),
        "prefill": (jax.jit(lambda p, b, n: prefill(cfg, p, b, n,
                                                    with_counts=True)),
                    (params, {"tokens": spec((1, 4096))}, spec((1,))))}


def _experts_sliced_per_layer(monkeypatch):
    """Serve as a layer loop that slices each layer's held experts out of
    the stacks, as training does."""
    monkeypatch.setattr(T, "held_stacks", lambda cfg, period, flags: (
        period, (None,) * len(cfg.period)))


@pytest.mark.parametrize("name,rows", [("decode", 16 * 6),
                                       ("prefill", 4096 * 6)])
def test_yarn_mla_serving_reads_held_experts_in_place_for_v5e(
        one_chip, monkeypatch, name, rows):
    """The docs cell's decode and prefill programs hand the TPU's ragged-dot
    kernel the whole (26·8, d, ·) expert stacks: no op copies a layer's 8
    held experts out of them (sliced per layer, the program does, since the
    kernel is a custom call that fuses no slice), the grouped matmuls keep
    their names and row counts (16 or 4096 rows times 6 experts per token),
    and the temporaries hold no such copy. The prefill's sliced form keeps
    its copy in HBM temporaries, so the stacked form's are no larger. The
    decode's keeps it in fast memory (its temporaries read 0 B) now that
    the latents are carried, so there the stacked form's temporaries stay
    under one layer's held ``w_out``, the smaller of the two copies."""
    cfg, _, programs = _docs_programs(one_chip)
    program, args = programs[name]
    c = program.lower(*args).compile()
    text = c.as_text()
    assert not _EXPERT_COPY.search(text)
    assert set(_GROUPED_ROWS.findall(text)) == {str(rows)}
    _experts_sliced_per_layer(monkeypatch)
    program, args = _docs_programs(one_chip)[2][name]    # traced anew
    sliced = program.lower(*args).compile()
    assert _EXPERT_COPY.search(sliced.as_text())
    temp = c.memory_analysis().temp_size_in_bytes
    if name == "prefill":
        assert temp <= sliced.memory_analysis().temp_size_in_bytes
    else:
        assert temp < cfg.moe.n_held * cfg.moe.d_ff_expert * cfg.d_model * 2


def test_yarn_mla_decode_compiles_for_v5e(one_chip):
    """ServeEngine's decode program of the DeepSeek-V2-Lite share, YaRN on,
    with its MoE counts, at the cell's full size, 16 rows of 4096
    positions: it compiles, aliases the 26 period layers' latents it
    writes in place (the prelayer's it replaces whole), its temporaries
    stay under a tenth of the latent cache, and no op copies a layer's
    held experts."""
    cfg, cache, programs = _docs_programs(one_chip)
    program, args = programs["decode"]
    c = program.lower(*args).compile()
    mem = c.memory_analysis()
    latents = _nbytes(cache["period"]) + _nbytes(cache["prelayers"])
    assert latents == cfg.n_layers * 16 * 4096 * (512 + 64) * 2
    assert mem.alias_size_in_bytes == _nbytes(cache["period"])
    assert mem.temp_size_in_bytes < latents / 10
    assert "ragged-dot" in c.as_text()
    assert not _EXPERT_COPY.search(c.as_text())
