"""CPU tests of ``benchmarks/chip/attribute.py``: the reduction of the serving
engine's spans and programs from a trace, on a small hand-worked trace, the
four metrics that read it, and one smoke-size run of the serving driver with
the engine's spans on."""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

import attribute  # noqa: E402
import harness  # noqa: E402
import tracefold  # noqa: E402
from repro import obs  # noqa: E402

SEED = 2 ** 33 + 7
SERVE_CELLS = [w["name"] for w in harness.load_bench()["workloads"]
               if w["traffic"].startswith("serve.")]


@pytest.fixture(autouse=True)
def tracing_off():
    obs.enable(False)
    obs.reset()
    yield
    obs.enable(False)
    obs.reset()


def _trace(t):
    return {"ops": {p: [tuple(e) for e in v] for p, v in t["ops"].items()},
            "modules": {p: [tuple(e) for e in v]
                        for p, v in t["modules"].items()},
            "spans": [tuple(e) for e in t["spans"]],
            **({"program_spans": [tuple(e) for e in t["program_spans"]]}
               if "program_spans" in t else {})}


def _fixture(name):
    with open(os.path.join(BENCH, "fixtures", name)) as f:
        return json.load(f)


def _approx_dict(got, want):
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k] == pytest.approx(v), k


@pytest.mark.parametrize("key", ["module_n", "idle_s_by_program_span",
                                 "program_span_n"])
def test_program_reduction_on_a_small_trace(key):
    fx = _fixture("program_trace.json")
    got = attribute.reduce_program(_trace(fx["trace"]))
    _approx_dict(got[key], fx["expected"][key])


def test_program_gaps_are_the_harness_gaps_named_anew():
    fx = _fixture("program_trace.json")
    trace = _trace(fx["trace"])
    harness_idle = tracefold.reduce(trace)["idle_s_by_span"]
    _approx_dict(harness_idle, fx["expected"]["idle_s_by_span"])
    program_idle = attribute.reduce_program(trace)["idle_s_by_program_span"]
    assert sum(program_idle.values()) == pytest.approx(
        sum(harness_idle.values()))


def test_trace_without_program_spans_keeps_the_harness_names():
    trace = _trace(_fixture("small_trace.json")["trace"])
    got = attribute.reduce_program(trace)
    _approx_dict(got["idle_s_by_program_span"],
                 tracefold.reduce(trace)["idle_s_by_span"])
    assert got["program_span_n"] == {}
    assert got["module_n"] == {"jit_train_step(12)": 1, "jit__lambda(3)": 1}


@pytest.mark.parametrize("name", [m["name"]
                                  for m in attribute.PROGRAM_METRICS])
def test_program_metric_reads_the_small_trace(monkeypatch, name):
    fx = _fixture("program_trace.json")
    trace = _trace(fx["trace"])
    rec = harness.RunRecord(trace={**tracefold.reduce(trace),
                                   **attribute.reduce_program(trace)})
    monkeypatch.setattr(obs, "spans", lambda: [
        obs.Span(n, s, e, None, {}) for s, e, n in fx["obs_spans"]])
    reader = harness.load_module(
        os.path.join(BENCH, "metrics", name + ".py"), "m_" + name)
    assert reader.read(rec, None) == pytest.approx(
        fx["expected"]["metrics"][name])


@pytest.mark.parametrize("name", [m["name"]
                                  for m in attribute.PROGRAM_METRICS])
def test_program_metric_reads_nothing_without_its_source(name):
    trace = _trace(_fixture("small_trace.json")["trace"])
    reader = harness.load_module(
        os.path.join(BENCH, "metrics", name + ".py"), "m_" + name)
    for rec in (harness.RunRecord(),
                harness.RunRecord(trace=tracefold.reduce(trace))):
        assert reader.read(rec, None) is None


def _smoke_serve_cell(name):
    cell = harness.load_cell(name, harness.load_bench())
    t = cell.traffic
    t.update(max_seq=96, rate_per_s=30.0, drain_s=20, check_tokens=60)
    t["prompt"] = {"dist": "uniform", "min": 8, "max": 48}
    t["output"] = {"dist": "lognormal", "median": 6, "sigma": 0.5,
                   "min": 2, "max": 12}
    cell.limits = {"served_gap": 0.01}
    return cell


def test_spanned_serve_driver_counts_what_the_driver_counts():
    import jax
    out, rec = attribute.run_cell(
        _smoke_serve_cell(SERVE_CELLS[0]), SEED, 0.3, False,
        t_start=time.perf_counter(), devices=jax.devices()[:1], smoke=True)
    c = rec.counters
    assert out["correct"], out["checks"]
    assert c["decode_steps"] > 0
    assert c["engine.decode_steps"] == c["decode_steps"]
    assert c["engine.decode_rows"] == c["decode_rows"]
    assert c["engine.admitted"] == out["attempted"]
    assert out["counters"] == {k: v for k, v in c.items()
                               if k.startswith("engine.")}
    steps = [s for s in obs.spans() if s.name == "serve.step"]
    assert len(steps) == c["decode_steps"]
    assert set(out["metrics"]) == {"ttft_p90_s", "itl_p50_s", "itl_p90_s",
                                   "serve_tokens_per_s", "setup_s",
                                   "sample_ms.serve"}
    assert out["compiles_by_span"] is not None
    assert "breakdown" not in out
    json.dumps(out)
