"""ServeEngine slot lifecycle regressions: freed slots must stop decoding —
their cache rows must not keep advancing ``lengths`` (which walked past
``max_seq`` on long workloads pre-fix) and an idle engine must not burn a
decode step at all."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params, model_defs
from repro.serve import ServeEngine


@pytest.fixture(scope="module")
def model():
    cfg = get_config("tacc-100m", smoke=True)
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def test_freed_slot_lengths_pinned(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=32)
    assert eng.add_request([1, 2, 3], max_new=2) is not None      # slot 0
    assert eng.add_request([4, 5, 6, 7], max_new=24) is not None  # slot 1
    finished = []
    for _ in range(4):
        finished += eng.step()
        if finished:
            break
    assert [r.request_id for r in finished] == [0]
    assert int(eng.cache["lengths"][0]) == 0          # freed slot reset
    for _ in range(6):                                # slot 1 keeps decoding
        eng.step()
    assert int(eng.cache["lengths"][0]) == 0          # ...and 0 stays pinned
    assert int(eng.cache["lengths"][1]) <= eng.max_seq


def test_idle_engine_step_is_a_noop(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=16)
    before = eng.counters["decode_steps"]
    assert eng.step() == []
    assert eng.counters["decode_steps"] == before     # no decode was paid
    assert int(np.max(np.asarray(eng.cache["lengths"]))) == 0


def test_long_workload_never_exceeds_max_seq(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=24)
    res = eng.run([[1, 2, 3]] * 6, max_new=8)
    assert len(res) == 6 and all(r.done for r in res)
    assert all(len(r.tokens) == 8 for r in res)
    assert int(np.max(np.asarray(eng.cache["lengths"]))) <= 24


def test_bench_serving_smoke_keeps_slot_invariants(model):
    """One short ``bench_serving`` pass stays true to the slot lifecycle:
    every request finishes with exactly max_new tokens, freed slots end
    reset to length 0, and nothing walks past max_seq.  Pins the bench
    driver itself against serve-engine API drift."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "benchmarks"))
    from bench_serving import run_bench
    cfg, params = model
    out = run_bench(n_requests=3, max_new=2, max_seq=24,
                    cfg=cfg, params=params)
    for eng, res, _wall in out.values():
        assert len(res) == 3 and all(r.done for r in res)
        assert all(len(r.tokens) == 2 for r in res)
        assert all(s.request is None for s in eng._slots)
        assert int(np.max(np.asarray(eng.cache["lengths"]))) == 0
        assert eng.counters["decode_steps"] > 0
    # batching must not serve in more decode steps than sequential
    assert out["batched"][0].counters["decode_steps"] <= \
        out["sequential"][0].counters["decode_steps"]


# sha256 of ``.lower(...).as_text()`` of each benchmark configuration's two
# serving programs at smoke size (max_batch 4, max_seq 64; jax 0.9.0).
# InternLM2's are as they were before the engine learned the MoE counters (a
# dense model's programs must not change when an MoE model's do);
# DeepSeek-V2-Lite-EP8's prefill as it was before its MLA decode moved into
# ``models/mla.py``, its decode since the period latents ride the layer
# loop's carry. A change that rightly alters them updates these.
SERVING_PROGRAMS = {
    "internlm2-1.8b": {
        "jit_serve_prefill":
            "6be2f4b1cf383d3f65a58c26e925d3816340865ac5e40646c2b73a15bbec9ca4",
        "jit_serve_decode":
            "a1b53a379198c852c4d51149980cfc6cefc28dd038d3818bd926c730933b2a2e",
    },
    "deepseek-v2-lite-ep8": {
        "jit_serve_prefill":
            "a39e96a4fc827db08236ed3f7477bf963c52c77955279a821862d95ac95d2df1",
        "jit_serve_decode":
            "17fe4ec8e594a0aea72a42840e18d2347a64f71175c6be8f563ed462a03b7896",
    },
}


@pytest.mark.parametrize("arch", sorted(SERVING_PROGRAMS))
def test_serving_programs_lower_unchanged(arch):
    import hashlib

    import jax.numpy as jnp

    from repro.models import abstract_params
    from repro.serve.engine import split_cache
    cfg = get_config(arch, smoke=True)
    params = abstract_params(model_defs(cfg))
    eng = ServeEngine(cfg, jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype), params), max_batch=4,
        max_seq=64)
    owned, kept = split_cache(cfg, eng.cache)
    texts = {
        "jit_serve_prefill": eng._prefill1.lower(
            params, {"tokens": jax.ShapeDtypeStruct((1, 64), jnp.int32)},
            jax.ShapeDtypeStruct((1,), jnp.int32)).as_text(),
        "jit_serve_decode": eng._decode.lower(
            params, owned, kept,
            jax.ShapeDtypeStruct((4,), jnp.int32)).as_text()}
    for name, text in texts.items():
        assert name in text
        assert hashlib.sha256(text.encode()).hexdigest() == \
            SERVING_PROGRAMS[arch][name], name


def test_moe_counters_add_up_the_programs_counts():
    """For an MoE model the engine's counters add up what its programs
    count: every real prompt token's top-k assignments when all experts are
    held, and none for a free slot's row."""
    cfg = get_config("deepseek-v2-lite", smoke=True)     # every expert held
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=3, max_seq=32)
    eng.run([[1, 2, 3, 4, 5], [6, 7]], max_new=4)
    c = eng.counters
    n_moe = cfg.n_layers - len(cfg.prelayers)
    k = cfg.moe.top_k
    assert c["moe_prefill_assignments_here"] == 7 * k * n_moe
    assert c["moe_assignments_here"] == c["decode_rows"] * k * n_moe
    assert 0 < c["moe_experts_touched"] <= c["decode_steps"] * n_moe * \
        cfg.moe.n_experts
    dense = ServeEngine(get_config("internlm2-1.8b", smoke=True), init_params(
        model_defs(get_config("internlm2-1.8b", smoke=True)),
        jax.random.PRNGKey(0)), max_batch=2, max_seq=16)
    assert not any(name.startswith("moe_") for name in dense.counters)
