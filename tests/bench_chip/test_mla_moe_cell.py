"""CPU tests of the MLA + MoE cell (``deepseek-v2-lite-ep8.serve.docs``) at
smoke size: the serving engine against the plain reference
(``reference_mla_moe``) on logits, its MoE counters against the reference's
routing, the cell end to end through ``run.run_cell`` with its control, the
configuration's widths and counts against the program, and the two expert
readers on a recorded trace. Smoke size: DeepSeek-V2-Lite's smoke widths
(d_model 64, 4 heads, 8 rope dims, YaRN on), 8 experts, top-2, 2 held."""
import json
import os
import sys
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

import counts_mla_moe  # noqa: E402
import harness  # noqa: E402
import reference_mla_moe  # noqa: E402
import run  # noqa: E402
import weights_mla_moe  # noqa: E402

CELL = "deepseek-v2-lite-ep8.serve.docs"
SEED = 2 ** 33 + 7
DRIVER = harness.load_module(
    os.path.join(BENCH, "drivers", "serve_open_loop_mla_moe.py"),
    "test_driver_mla_moe")


def _cell(smoke=True):
    cell = harness.load_cell(CELL, harness.load_bench())
    if smoke:
        t = cell.traffic
        t.update(max_seq=96, rate_per_s=30.0, drain_s=20, check_tokens=60)
        t["prompt"] = {"dist": "uniform", "min": 8, "max": 48}
        t["output"] = {"dist": "lognormal", "median": 6, "sigma": 0.5,
                       "min": 2, "max": 12}
        # set as the other cells' smoke limits are, between the program's
        # largest reading and the control's least over seeds 5, 123456789
        # and 2**33 + 7 on a CPU: program 0.058, 0.046, 0.006 (bfloat16
        # routing near ties: 0.0009 at seed 5 computing in float32);
        # control 0.103, 0.099, 0.116
        cell.limits = {"served_gap": 0.08}
    return cell


def _ctx(cell, smoke, cfg=None):
    cfg_, sizes = run.program_config(cell, smoke)
    return harness.Context(cell=cell, sizes=sizes, program_cfg=cfg or cfg_,
                           seeds=harness.sub_seeds(SEED), seconds=0.0,
                           traced=False, t_start=0.0, workdir="",
                           spans=harness.Spans(False), smoke=smoke)


def _smoke_model(dtype="bfloat16"):
    """The smoke configuration (computing in ``dtype``), its sizes with
    widths, and the benchmark's weights for it in bfloat16."""
    ctx = _ctx(_cell(), True)
    cfg = replace(ctx.program_cfg, dtype=dtype)
    sz = DRIVER.sizes_with_widths(ctx)
    params = weights_mla_moe.make_params_fn(sz, "bfloat16")(
        jax.random.PRNGKey(3))
    DRIVER.BASE._check_layout(params, cfg)
    return cfg, sz, params


PROMPTS = [[5, 9, 200, 17, 3, 3, 41, 77, 120, 9, 8, 1], [33, 2, 250, 6],
           list(range(1, 30, 2))]
MAX_NEW = 8


def _serve(cfg, params):
    """Serve PROMPTS on one engine (three rows, spliced at once) and keep
    every logits row the engine sampled from, by request."""
    from repro.serve import ServeEngine
    engine = ServeEngine(cfg, params, max_batch=4, max_seq=64)
    seen = []
    pick = engine._pick
    engine._pick = lambda lg, temperature=0.0: (seen.append(lg), pick(lg))[1]
    out = engine.run(PROMPTS, max_new=MAX_NEW)
    # prefill rows come in request order, then each step in slot order
    n = len(PROMPTS)
    by_req = [[seen[i]] + seen[n + i::n] for i in range(n)]
    return out, by_req, engine


def test_engine_prefill_and_decode_match_reference_logits():
    """Prefill, then decode through the latent cache with YaRN and 2 of 8
    experts held, against the reference's full forward pass over prompt and
    served tokens. The program computes in bfloat16 (as served): bfloat16
    activations through 3 layers put its logits at most 1.9 % of their
    range off the float32 reference here, so 4 % of the range bounds every
    position; the same engine without YaRN reads 61 %, with YaRN's softmax
    scale but not its mscale 47 %, and the reference itself computed in
    fp8 25 %."""
    cfg, sz, params = _smoke_model()
    out, by_req, _ = _serve(cfg, params)
    for g, rows in zip(out, by_req):
        seq = g.prompt + g.tokens[:-1]
        h = reference_mla_moe.hidden(sz, params, jnp.asarray([seq]))
        ref = np.asarray(reference_mla_moe.logits(sz, params, h))[0]
        ref = ref[len(g.prompt) - 1:]
        got = np.stack(rows)
        assert got.shape == ref.shape == (MAX_NEW, sz["vocab_size"])
        err = np.max(np.abs(got - ref), -1) / np.ptp(ref, -1)
        assert err.max() < 0.04, err


def test_engine_counters_match_reference_routing():
    """The engine's MoE counters, against the experts the reference's
    router chooses for the same tokens: assignments that land on held
    experts over the prompt (prefill) and over the decoded rows (decode),
    and held experts that got a token in each step. The program computes
    in float32 here, so that its routing is the reference's to rounding."""
    cfg, sz, params = _smoke_model("float32")
    out, _, engine = _serve(cfg, params)
    moe = sz["moe"]
    first, held = moe["first_held"], moe["n_held"]
    prefill = 0
    step_sets = {}          # step -> held experts of each MoE layer
    step_assign = 0
    for g in out:
        seq = g.prompt + g.tokens[:-1]
        _, chosen = reference_mla_moe.forward(sz, params, jnp.asarray([seq]))
        here = np.asarray(chosen)[:, 0] - first        # (layers, S, k)
        ok = (here >= 0) & (here < held)
        p = len(g.prompt)
        prefill += int(ok[:, :p].sum())
        step_assign += int(ok[:, p:].sum())
        for t in range(p, len(seq)):      # decode step t - p of this row
            for layer in range(here.shape[0]):
                step_sets.setdefault((t - p, layer), set()).update(
                    here[layer, t][ok[layer, t]].tolist())
    c = engine.counters
    assert c["moe_prefill_assignments_here"] == prefill > 0
    assert c["moe_assignments_here"] == step_assign > 0
    assert c["moe_experts_touched"] == sum(len(s)
                                           for s in step_sets.values())


def test_cell_end_to_end_with_its_control():
    out, rec = run.run_cell(_cell(), SEED, 0.3, False,
                            t_start=time.perf_counter(),
                            devices=jax.devices()[:1], smoke=True,
                            control=True)
    c = {k: v["value"] for k, v in out["checks"].items()}
    assert out["correct"], c
    assert out["others_correct"] == {"control": False}, c
    assert c["control.served_gap"] >= 3 * c["served_gap"]
    assert out["failed"] == 0 and rec.counters["checked_tokens"] > 0
    assert set(out["metrics"]) == {"ttft_p90_s", "itl_p50_s", "itl_p90_s",
                                   "serve_tokens_per_s", "setup_s"}
    json.dumps(out)


def test_widths_are_checked_against_the_program():
    from repro.configs import get_config
    cell = _cell(smoke=False)
    ctx = _ctx(cell, False)
    sz = DRIVER.sizes_with_widths(ctx)
    assert sz["moe"]["n_held"] == 8 and sz["mla"]["kv_lora_rank"] == 512
    cfg = get_config("deepseek-v2-lite-ep8")
    wrong = replace(cfg, moe=replace(cfg.moe, first_held=8))
    with pytest.raises(ValueError, match="moe.first_held"):
        DRIVER.sizes_with_widths(_ctx(cell, False, wrong))
    with pytest.raises(ValueError, match="rope_scaling.factor"):
        DRIVER.sizes_with_widths(_ctx(cell, False, replace(
            cfg, rope_scaling=replace(cfg.rope_scaling, factor=1.0))))


def test_counts_match_the_program_parameter_count():
    from repro.configs import get_config
    from repro.models import model_defs, param_count
    sz = DRIVER.sizes_with_widths(_ctx(_cell(smoke=False), False))
    c = counts_mla_moe.param_counts(sz)
    assert c["total"] == param_count(model_defs(
        get_config("deepseek-v2-lite-ep8"))) == 3_110_989_312
    assert c["experts"] == 26 * 8 * 3 * 2048 * 1408
    # the 2.2 GB a decode step reads whatever the routing, 31,104 B a
    # position of latent cache
    assert counts_mla_moe.weight_bytes(sz, 2) == 2_203_835_392
    assert counts_mla_moe.kv_bytes_per_token(sz) == 27 * 576 * 2


# -- the expert readers on a recorded trace ----------------------------------

def _fixture():
    with open(os.path.join(BENCH, "fixtures", "moe_trace.json")) as f:
        return json.load(f)


def _reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                               "m_" + name.replace(".", "_"))


@pytest.mark.parametrize("name", ["hbm_roofline.moe.decode",
                                  "mfu.moe.prefill"])
def test_expert_reader_on_a_recorded_trace(name):
    fx = _fixture()
    cell = _cell(smoke=False)
    ctx = _ctx(cell, False)
    ctx.sizes = DRIVER.sizes_with_widths(ctx)
    ctx.device_kind = "TPU v5 lite"
    rec = harness.RunRecord(counters=fx["counters"],
                            trace={"op_s": fx["op_s"]})
    assert _reader(name).read(rec, ctx) == pytest.approx(
        fx["expected"][name])
    # nothing to read: no trace, no counter, no grouped op
    assert _reader(name).read(harness.RunRecord(), ctx) is None
    assert _reader(name).read(harness.RunRecord(
        counters=fx["counters"], trace={"op_s": {}}), ctx) is None
    assert _reader(name).read(harness.RunRecord(
        trace={"op_s": fx["op_s"]}), ctx) is None
