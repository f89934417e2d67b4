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
