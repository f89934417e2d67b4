"""How the model step's programs are built.

Each loop over layers, microbatches or kv-tiles is one ``lax.scan``; the
dry-run's roofline variants unroll it through the existing flags
(``RunFlags.unroll_layers``, ``TrainConfig.unroll_accum``,
``flash_attention_xla(unroll=)``) so that ``cost_analysis`` counts every
iteration, and the lowered text then holds no loop at all.

``repro.parallel`` holds the sequence-sharded code and depends on nothing in
``repro.models``: the model's decode calls into it, never the other way.
"""
import ast
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import abstract_params, model_defs
from repro.models import transformer as T
from repro.models.attention import flash_attention_xla
from repro.train import OptConfig, TrainConfig, build_train_step
from repro.train.step import abstract_train_state

B, S = 2, 32


def _lower(case: str, unroll: bool):
    cfg = get_config("internlm2-1.8b", smoke=True)
    params = abstract_params(model_defs(cfg))
    flags = T.RunFlags(unroll_layers=unroll)
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if case == "forward":
        return jax.jit(lambda p, b: T.forward(cfg, p, b, flags=flags)).lower(
            params, {"tokens": tokens})
    if case == "decode_step":
        cache = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
        return jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t,
                                                     flags=flags)).lower(
            params, cache, jax.ShapeDtypeStruct((B,), jnp.int32))
    if case == "flash_attention_xla":
        q = jax.ShapeDtypeStruct((B, S, 2, 16), jnp.float32)
        return jax.jit(lambda q, k, v: flash_attention_xla(
            q, k, v, chunk=8, max_chunks=64, unroll=unroll)).lower(q, q, q)
    ocfg = OptConfig()
    step = build_train_step(
        cfg, ocfg, TrainConfig(n_microbatches=2, unroll_accum=unroll), flags)
    return jax.jit(step).lower(abstract_train_state(cfg, ocfg),
                               {"tokens": tokens, "labels": tokens})


@pytest.mark.parametrize("case", ["forward", "decode_step",
                                  "flash_attention_xla", "train_step"])
def test_unroll_flags_leave_no_loop(case):
    assert "stablehlo.while" in _lower(case, False).as_text()
    assert "stablehlo.while" not in _lower(case, True).as_text()


def _imported_modules(path: Path, package: str):
    """Every module an ``import`` in ``path`` names, relative ones resolved
    against ``package``, function-level imports included."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                parent = package.rsplit(".", node.level - 1)[0] \
                    if node.level > 1 else package
                base = f"{parent}.{base}" if base else parent
            yield base
            yield from (f"{base}.{a.name}" for a in node.names)


def test_parallel_imports_nothing_from_models():
    root = Path(__file__).resolve().parents[1] / "src" / "repro" / "parallel"
    paths = sorted(root.glob("*.py"))
    assert paths
    seen = set()
    for path in paths:
        for mod in _imported_modules(path, "repro.parallel"):
            seen.add(mod)
            assert mod != "repro.models" and \
                not mod.startswith("repro.models."), (path.name, mod)
    assert "repro.compat" in seen          # the walk does see imports
