"""CPU tests of the chip benchmark (``benchmarks/chip``) at smoke size.

They skip the harness's look for a chip and drive the rest of a run
(``run.run_cell``) on the program's smoke configurations with shortened
traffic: each driver end to end, the result line's keys, the control and
the faults a cell can have, the cell files, and the trace reduction on a
small hand-worked trace. No TPU topology is described here.

The training drivers have no cell in ``BENCHMARK.json`` yet; they run here
as the job a training cell of InternLM2-1.8B would be, on its smoke
configuration with float32 master weights.
"""
import copy
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import run  # noqa: E402
import tracefold  # noqa: E402

BENCHMARK = harness.load_bench()
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
SEED = 2 ** 33 + 7

# Limits at smoke size, set as the cells' own are: above the program's
# largest reading and below the control's or a fault's least, over seeds
# 5, 123456789 and 2**33 + 7 on a CPU. Program: loss 7.2e-5, gradient
# norm 1.0e-3, gradient leaf 1.2e-3, change leaf 1.9e-3, served token
# 3.2e-4. Control (fp8): gradient leaf 0.0106, change leaf 0.0059, served
# token 0.055; half the batch: gradient norm 0.39.
SMOKE_LIMITS = {"loss_gap": 1.5e-4, "grad_norm_gap": 0.01,
                "grad_leaf_gap": 0.004, "update_leaf_gap": 0.0035,
                "restore_mismatch": 0, "served_gap": 0.01}
TRAIN_MIXES = {"steady": "train.steady", "preempt": "train.preempt"}


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def train_cell(kind: str) -> harness.Cell:
    """A training job of InternLM2-1.8B under ``traffic/train.<kind>``,
    cut to what a CPU test can hold, with ``train_tokens_per_s`` as its
    end-to-end metric."""
    name = f"internlm2-1.8b.train.{kind}"
    config = dict(_json("configs", "internlm2-1.8b.json"),
                  weights_dtype="float32")
    mix = _json("traffic", TRAIN_MIXES[kind] + ".json")
    mix.update(global_batch=4, seq_len=32, quantum_steps=2, check_steps=2)
    if mix["preempt_every_steps"]:
        mix["preempt_every_steps"] = 2
    bench = copy.deepcopy(BENCHMARK)
    bench["end_to_end"].append({"name": "train_tokens_per_s",
                                "unit": "tokens/s", "workloads": [name]})
    return harness.Cell(name=name, chips=1, config=config, traffic=mix,
                        limits=SMOKE_LIMITS, bench=bench)


def smoke_cell(name: str) -> harness.Cell:
    """The cell with its traffic cut to what a CPU test can hold."""
    if name in ("steady", "preempt"):
        return train_cell(name)
    cell = harness.load_cell(name, BENCHMARK)
    t = cell.traffic
    t.update(max_seq=96, rate_per_s=30.0, drain_s=20, check_tokens=60)
    t["prompt"] = {"dist": "uniform", "min": 8, "max": 48}
    t["output"] = {"dist": "lognormal", "median": 6, "sigma": 0.5,
                   "min": 2, "max": 12}
    cell.limits = SMOKE_LIMITS
    return cell


def drive(name: str, *, seconds: float = 0.3, control: bool = False):
    import jax
    return run.run_cell(smoke_cell(name), SEED, seconds, False,
                        t_start=time.perf_counter(),
                        devices=jax.devices()[:1], smoke=True,
                        control=control)


# -- the files of every cell ------------------------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = harness.load_cell(name, BENCHMARK)
    assert os.path.exists(os.path.join(BENCH, "drivers",
                                       cell.driver + ".py"))
    for traced in (False, True):
        for m in harness.cell_metrics(BENCHMARK, name, traced):
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               m["name"] + ".py"))
    assert set(cell.config["sizes"]) >= {"n_layers", "d_model", "vocab_size"}
    assert set(cell.limits) == {"served_gap"}
    assert cell.limits, "every number the check compares needs a limit"


def test_benchmark_json_shape():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    cells = set(CELLS)
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert name.match(m["name"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
    for w in BENCHMARK["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        reported = harness.cell_metrics(BENCHMARK, w["name"], False)
        assert len(reported) >= 2
        assert harness.cell_metrics(BENCHMARK, w["name"], True)
    for c in BENCHMARK["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))


# -- the run as the driver sees it ------------------------------------------

def test_run_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("kind", ["steady", "preempt"])
def test_train_driver_end_to_end(kind):
    out, rec = drive(kind, control=kind == "steady")
    assert set(out) - {"others_correct"} == LINE_KEYS
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["attempted"] == rec.counters["steps"] > 0
    assert out["device"]["count"] == 1
    json.dumps(out)
    c = {k: v["value"] for k, v in out["checks"].items()}
    assert out["correct"], c
    if kind == "preempt":
        assert rec.counters["round_trips"] >= 1
        assert c["restore_mismatch"] == 0
    else:
        # the control (fp8 reference) and half the batch, judged by the
        # comparison that decides correct, both come out not correct
        assert out["others_correct"] == {"control": False,
                                         "half_batch": False}, c
        assert c["control.grad_leaf_gap"] >= 3 * c["grad_leaf_gap"]


def test_serve_driver_end_to_end():
    out, rec = drive("internlm2-1.8b.serve.chat", control=True)
    assert set(out) - {"others_correct"} == LINE_KEYS
    assert set(out["metrics"]) == {"ttft_p90_s", "itl_p50_s", "itl_p90_s",
                                   "serve_tokens_per_s", "setup_s"}
    assert out["attempted"] == len(rec.requests) > 0 and out["failed"] == 0
    assert rec.counters["checked_tokens"] > 0
    c = {k: v["value"] for k, v in out["checks"].items()}
    assert out["correct"], c
    assert out["others_correct"] == {"control": False}, c
    assert c["control.served_gap"] >= 3 * c["served_gap"]
    json.dumps(out)


@pytest.mark.parametrize("who,checks,verdict", [
    ("", {"served_gap": 0.005}, True),
    ("control", {"served_gap": 0.05}, False),
    ("half_batch", {"loss_gap": 1e-5, "grad_norm_gap": 0.4}, False),
])
def test_every_reading_is_judged_alike(who, checks, verdict):
    named = {(f"{who}.{k}" if who else k): v for k, v in checks.items()}
    got = harness.split_checks(named)
    assert got == {who: checks}
    assert harness.passed(harness.judge(got[who], SMOKE_LIMITS)) is verdict


# -- the timed path broken underneath: correct must come out false ---------

def _broken_step(monkeypatch, how):
    import repro.train
    real = repro.train.build_train_step

    def build(*a, **k):
        step = real(*a, **k)
        if how == "unchanged":
            return lambda state, batch: (state, step(state, batch)[1])
        return lambda state, batch: step(state, {
            key: v[: v.shape[0] // 2] for key, v in batch.items()})
    monkeypatch.setattr(repro.train, "build_train_step", build)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_makes_run_incorrect(monkeypatch, fault):
    if fault == "token_altered":
        from repro.serve.engine import ServeEngine
        calls = [0]

        def pick(self, logits, temperature=0.0):
            calls[0] += 1
            return int(np.argmin(logits) if calls[0] % 5 == 0
                       else np.argmax(logits))
        monkeypatch.setattr(ServeEngine, "_pick", pick)
        out, _ = drive("internlm2-1.8b.serve.chat")
    else:
        _broken_step(monkeypatch, "unchanged" if fault == "state_unchanged"
                     else "half")
        out, _ = drive("steady")
    assert out["correct"] is False, out["checks"]


# -- the trace reduction -----------------------------------------------------

def test_trace_reduction_on_a_small_trace():
    with open(os.path.join(BENCH, "fixtures", "small_trace.json")) as f:
        fx = json.load(f)
    t = fx["trace"]
    trace = {"ops": {p: [tuple(e) for e in v] for p, v in t["ops"].items()},
             "modules": {p: [tuple(e) for e in v]
                         for p, v in t["modules"].items()},
             "spans": [tuple(e) for e in t["spans"]]}
    got = tracefold.reduce(trace)
    for key, want in fx["expected"].items():
        if isinstance(want, dict):
            assert set(got[key]) == set(want), key
            for k, v in want.items():
                assert got[key][k] == pytest.approx(v), (key, k)
        else:
            assert got[key] == pytest.approx(want), key
