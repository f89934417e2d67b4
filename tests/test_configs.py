"""Config registry: exact assigned configs, plausible parameter counts."""
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config, list_archs
from repro.configs.base import SHAPES, shape_applicable
from repro.models import model_defs, param_count

# nominal sizes from the assignment (tolerances cover vocab/glu conventions)
NOMINAL = {
    "starcoder2-15b": 15e9,
    "internlm2-1.8b": 1.8e9,
    "llama3-405b": 405e9,
    "command-r-plus-104b": 104e9,
    "internvl2-2b": 1.8e9,          # backbone only (frontend stubbed)
    "xlstm-125m": 125e6,
    "qwen2-moe-a2.7b": 14.3e9,      # total (A2.7B is the *active* count)
    "deepseek-v2-236b": 236e9,
    "deepseek-v2-lite": 15.7e9,
    # one chip's share of 8-chip expert parallelism: 8 of 64 experts held
    "deepseek-v2-lite-ep8": 3.1e9,
    "jamba-1.5-large-398b": 398e9,
    "granite-4.0-h-small": 32.21e9,
    # one chip's share of 16: layers 0-19 and 9 of 72 experts held
    "granite-4.0-h-small-ep8": 4.418e9,
    "musicgen-medium": 1.5e9,
}


def test_registry_has_all_assigned():
    assert set(ASSIGNED_ARCHS) == set(NOMINAL)
    assert "tacc-100m" in list_archs()


@pytest.mark.parametrize("arch", sorted(NOMINAL))
def test_exact_config_fields(arch):
    cfg = get_config(arch)
    total = len(cfg.prelayers) + len(cfg.period) * cfg.n_periods
    assert total == cfg.n_layers
    assert cfg.n_heads % cfg.n_kv_heads == 0


@pytest.mark.parametrize("arch", sorted(NOMINAL))
def test_param_count_matches_nominal(arch):
    cfg = get_config(arch)
    n = param_count(model_defs(cfg))
    lo, hi = 0.75 * NOMINAL[arch], 1.35 * NOMINAL[arch]
    assert lo <= n <= hi, f"{arch}: {n:.3e} params, expected ~{NOMINAL[arch]:.3e}"


@pytest.mark.parametrize("arch", sorted(NOMINAL))
def test_smoke_variant_is_small(arch):
    cfg = get_config(arch, smoke=True)
    n = param_count(model_defs(cfg))
    assert n < 5e6, f"smoke config too big: {n}"


def test_long_context_applicability():
    long = SHAPES["long_500k"]
    runs = {a for a in NOMINAL if shape_applicable(get_config(a), long)}
    assert runs == {"xlstm-125m", "jamba-1.5-large-398b"}
    # every other (arch, shape) cell runs
    for a in NOMINAL:
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert shape_applicable(get_config(a), SHAPES[s])


def test_exact_dims_spotcheck():
    c = get_config("llama3-405b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (126, 16384, 128, 8, 53248, 128256)
    c = get_config("deepseek-v2-236b")
    assert c.mla.kv_lora_rank == 512 and c.moe.n_experts == 160
    assert c.moe.top_k == 6 and c.moe.n_shared == 2
    c = get_config("qwen2-moe-a2.7b")
    assert c.moe.n_experts == 60 and c.moe.pad_to == 64 and c.moe.top_k == 4
    c = get_config("jamba-1.5-large-398b")
    assert sum(1 for s in c.period if s.mixer == "attn") == 1
    assert sum(1 for s in c.period if s.ffn == "moe") == 4
    assert len(c.period) == 8 and c.n_periods == 9


def test_granite_counts_its_published_size():
    """Granite-4.0-H-Small is published as 32B: the full model counts
    32.21e9 parameters (within 0.5 %), and the share this chip serves,
    layers 0-19 with 9 of each layer's 72 experts, 4.418e9."""
    full = param_count(model_defs(get_config("granite-4.0-h-small")))
    assert abs(full / 32.21e9 - 1) < 0.005, full
    share = param_count(model_defs(get_config("granite-4.0-h-small-ep8")))
    assert round(share / 1e6) == 4418, share
    c = get_config("granite-4.0-h-small")
    assert [s.mixer for s in c.period] == ["mamba2"] * 5 + ["attn"] + \
        ["mamba2"] * 4
    assert all(s.ffn == "moe" for s in c.period) and c.n_periods == 4
