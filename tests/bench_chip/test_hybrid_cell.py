"""CPU tests of the Mamba-2 hybrid cell (``granite-4.0-h-small-ep8.serve.
reason``) at smoke size: the serving engine against the plain reference
(``reference_hybrid``) on logits, the cell end to end through
``run.run_cell`` with its control, the configuration's widths and counts
against the program, the share of the experts against the uncut MoE layer,
and the fold of the Mamba-2 layers' device time and its readers. Smoke size: Granite-4.0-H's smoke
widths (d_model 64, a period of Mamba-2, attention, Mamba-2 with 4 heads of
16 and d_state 8, chunks of 16), 8 experts, top-2, 2 held."""
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

import counts_hybrid  # noqa: E402
import harness  # noqa: E402
import reference_hybrid  # noqa: E402
import run  # noqa: E402
import shapefold  # noqa: E402
import weights_hybrid  # noqa: E402

CELL = "granite-4.0-h-small-ep8.serve.reason"
SEED = 2 ** 33 + 7
DRIVER = harness.load_module(
    os.path.join(BENCH, "drivers", "serve_open_loop_hybrid.py"),
    "test_driver_hybrid")


def _cell(smoke=True):
    cell = harness.load_cell(CELL, harness.load_bench())
    if smoke:
        t = cell.traffic
        t.update(max_seq=96, rate_per_s=30.0, drain_s=20, check_tokens=200,
                 check_max_requests=40)
        t["prompt"] = {"dist": "uniform", "min": 8, "max": 48}
        t["output"] = {"dist": "lognormal", "median": 6, "sigma": 0.5,
                       "min": 2, "max": 12}
        # set as the other cells' smoke limits are, between the program's
        # largest reading and the control's least over seeds 5, 123456789
        # and 2**33 + 7 on a CPU, 30 requests each: program 0.00023,
        # 0.00038, 0.000059; control 0.00089, 0.00121, 0.00108 (the
        # logits are divided by 16 and the tied table is drawn at
        # 0.02 / 12)
        cell.limits = {"served_gap": 0.00065}
    return cell


def _ctx(cell, smoke, cfg=None):
    cfg_, sizes = run.program_config(cell, smoke)
    return harness.Context(cell=cell, sizes=sizes, program_cfg=cfg or cfg_,
                           seeds=harness.sub_seeds(SEED), seconds=0.0,
                           traced=False, t_start=0.0, workdir="",
                           spans=harness.Spans(False), smoke=smoke)


PROMPTS = [[5, 9, 200, 17, 3, 3, 41, 77, 120, 9, 8, 1], [33, 2, 250, 6],
           list(range(1, 40, 2))]
MAX_NEW = 8


def test_engine_prefill_and_decode_match_reference_logits():
    """Prefill (chunked SSD over 16-position chunks, ragged rows spliced at
    once), then decode through the Mamba-2 state and the attention cache,
    against the reference's full forward pass (token-by-token recurrence)
    over prompt and served tokens. The program computes in float32 here,
    so that the comparison checks the equations rather than rounding: its
    logits are at most 0.19 % of their range off the reference, so 1 %
    bounds every position; the same engine computing in bfloat16, as
    served, reads up to 5 % (a near tie in the routing), and the reference
    itself computed in fp8 7 % to 12 %."""
    ctx = _ctx(_cell(), True)
    cfg = replace(ctx.program_cfg, dtype="float32")
    sz = DRIVER.sizes_with_widths(ctx)
    params = weights_hybrid.make_params_fn(sz, "bfloat16")(
        jax.random.PRNGKey(3))
    DRIVER.BASE._check_layout(params, cfg)
    from repro.serve import ServeEngine
    engine = ServeEngine(cfg, params, max_batch=4, max_seq=64)
    seen = []
    pick = engine._pick
    engine._pick = lambda lg, temperature=0.0: (seen.append(lg), pick(lg))[1]
    out = engine.run(PROMPTS, max_new=MAX_NEW)
    n = len(PROMPTS)
    # prefill rows come in request order, then each step in slot order
    for i, g in enumerate(out):
        seq = g.prompt + g.tokens[:-1]
        h = reference_hybrid.hidden(sz, params, jnp.asarray([seq]))
        ref = np.asarray(reference_hybrid.logits(sz, params, h))[0]
        ref = ref[len(g.prompt) - 1:]
        got = np.stack([seen[i]] + seen[n + i::n])
        assert got.shape == ref.shape == (MAX_NEW, sz["vocab_size"])
        err = np.max(np.abs(got - ref), -1) / np.ptp(ref, -1)
        assert err.max() < 0.01, err


def test_cell_end_to_end_with_its_control():
    out, rec = run.run_cell(_cell(), SEED, 1.0, False,
                            t_start=time.perf_counter(),
                            devices=jax.devices()[:1], smoke=True,
                            control=True)
    c = {k: v["value"] for k, v in out["checks"].items()}
    assert out["correct"], c
    assert out["others_correct"] == {"control": False}, c
    assert c["control.served_gap"] >= 3 * c["served_gap"]
    assert out["failed"] == 0 and rec.counters["checked_tokens"] > 0
    assert set(out["metrics"]) == {"ttft_p90_s", "itl_p50_s", "itl_p90_s",
                                   "setup_s"}
    json.dumps(out)


def test_weights_are_the_same_whatever_the_run_seed():
    """The weights come from the configuration's ``weights_seed``, so two
    runs of other seeds serve the same weights, and their traffic
    differs."""
    cell = _cell()
    ctxs = [_ctx(cell, True), _ctx(cell, True)]
    ctxs[1].seeds = harness.sub_seeds(SEED + 1)
    assert ctxs[0].seeds["traffic"] != ctxs[1].seeds["traffic"]
    keys = [np.asarray(DRIVER.weights_key(c)) for c in ctxs]
    np.testing.assert_array_equal(keys[0], keys[1])
    np.testing.assert_array_equal(
        keys[0], jax.random.PRNGKey(cell.config["weights_seed"]))


def test_widths_are_checked_against_the_program():
    from repro.configs import get_config
    cell = _cell(smoke=False)
    sz = DRIVER.sizes_with_widths(_ctx(cell, False))
    assert sz["moe"]["n_held"] == 9 and sz["mamba2"]["d_state"] == 128
    assert sz["period"] == ["mamba2"] * 5 + ["attn"] + ["mamba2"] * 4
    cfg = get_config("granite-4.0-h-small-ep8")
    with pytest.raises(ValueError, match="mamba2.chunk_size"):
        DRIVER.sizes_with_widths(_ctx(cell, False, replace(
            cfg, mamba2=replace(cfg.mamba2, chunk_size=128))))
    with pytest.raises(ValueError, match="period"):
        DRIVER.sizes_with_widths(_ctx(cell, False, replace(
            cfg, period=cfg.period[1:] + cfg.period[:1])))


def test_counts_match_the_program_parameter_count():
    from repro.configs import get_config
    from repro.models import model_defs, param_count
    sz = DRIVER.sizes_with_widths(_ctx(_cell(smoke=False), False))
    c = counts_hybrid.param_counts(sz)
    assert c["total"] == param_count(model_defs(
        get_config("granite-4.0-h-small-ep8"))) == 4_418_340_096
    assert c["experts"] == 20 * 9 * 3 * 4096 * 768
    assert c["mamba2"] == 18 * 102_286_976
    # a decode step reads the 3.68 GB of Mamba-2 weights, and per row
    # 2 x 18 x (4 MiB of float32 state + a 3 x 8448 bfloat16 window)
    assert counts_hybrid.ssm_weight_bytes(sz, 2) == 3_682_331_136
    assert counts_hybrid.ssm_state_bytes_per_row(sz) == 2 * 18 * (
        128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert counts_hybrid.kv_bytes_per_token(sz) == 2 * 2 * 8 * 128 * 2
    assert counts_hybrid.weight_bytes(sz, 2) == 2 * (
        4_418_340_096 - c["experts"])


def test_eight_shares_add_up_to_the_uncut_moe_layer():
    """The share ties to the model: at a small width with Granite's 72
    experts and top-10, the program's held-expert layer on each of eight
    chips holding 9 experts, the shared MLP counted once, adds up to the
    reference's uncut layer (every expert held). Float32 throughout: the
    sums differ only in order, 1e-5 of the routed part's scale."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.models.moe import moe_defs, moe_held
    full = get_config("granite-4.0-h-small")
    cfg = replace(full, d_model=32, dtype="float32", moe=replace(
        full.moe, d_ff_expert=16, d_ff_shared=24))
    p = init_params(moe_defs(cfg), jax.random.PRNGKey(0))
    # experts of the benchmark's scale (1/sqrt(fan-in) of one expert), so
    # that the routed part is as large as the shared MLP's
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    p["w_in"] = jax.random.normal(k1, p["w_in"].shape) * 32 ** -0.5
    p["w_out"] = jax.random.normal(k2, p["w_out"].shape) * 16 ** -0.5
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.d_model))
    sz = {"moe": {"top_k": 10, "norm_topk": True, "first_held": 0}}
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference_hybrid._moe(sz, "f32", x, p)
        shared = reference_hybrid._swiglu("f32", x, p["shared_w_in"],
                                          p["shared_w_out"])
        shares = []
        for i in range(8):
            part = replace(cfg, moe=replace(cfg.moe, n_held=9,
                                            first_held=9 * i))
            held = dict(p, w_in=p["w_in"][9 * i:9 * i + 9],
                        w_out=p["w_out"][9 * i:9 * i + 9])
            shares.append(moe_held(part, held, x)[0])
    routed = uncut - shared
    scale = float(jnp.max(jnp.abs(routed)))
    np.testing.assert_allclose(sum(shares) - 7 * shared, uncut,
                               atol=1e-5 * scale)
    # one share alone is not the layer
    assert float(jnp.max(jnp.abs(shares[0] - uncut))) > 0.1 * scale


def test_routers_are_blind_to_the_direction_their_inputs_share():
    """Each drawn router loses the mean direction of its input over the
    calibration batch, so that direction adds nothing to any expert's
    logit: the first layer's router, as drawn and as balanced, against the
    mean of its input computed here; and every layer's router a fixed
    point of a second balancing on the same batch (to bfloat16's rounding
    of the router, 2**-8 of its largest weight)."""
    sz = DRIVER.sizes_with_widths(_ctx(_cell(), True))
    key = jax.random.PRNGKey(11)
    drawn = weights_hybrid.draw_params_fn(sz, "bfloat16")(key)
    tokens = weights_hybrid.calibration_tokens(sz, key)
    params = weights_hybrid.balance_routers(sz, drawn, tokens)
    lp = jax.tree.map(lambda a: a[0].astype(jnp.float32), drawn["period"][0])
    eps = sz["norm_eps"]
    x = drawn["embed"]["tok"][tokens].astype(jnp.float32) \
        * sz["embedding_multiplier"]
    x = x + sz["residual_multiplier"] * reference_hybrid._mamba2(
        sz, "f32", weights_hybrid._rms(x, lp["mixer_norm"]["scale"], eps),
        lp["mixer"])
    h = weights_hybrid._rms(x, lp["ffn_norm"]["scale"], eps)
    u = jnp.mean(h.reshape(-1, h.shape[-1]), 0)
    u = u / jnp.linalg.norm(u)

    def shared_logits(p):
        w = p["period"][0]["ffn"]["router"][0].astype(jnp.float32)
        return float(jnp.max(jnp.abs(u @ w))), float(jnp.max(jnp.abs(w)))

    before, scale = shared_logits(drawn)
    after, _ = shared_logits(params)
    assert before > 2 ** -4 * scale and after < 2 ** -7 * scale
    again = weights_hybrid.balance_routers(sz, params, tokens)
    for lps, kept in zip(again["period"], params["period"]):
        np.testing.assert_allclose(
            np.asarray(lps["ffn"]["router"], np.float32),
            np.asarray(kept["ffn"]["router"], np.float32),
            atol=2 ** -8 * scale)


# -- the Mamba-2 layers' device time: its fold and its readers -----------------

def test_shape_fold_counts_self_time_by_span():
    """Two decode steps and one admission inside the traced window, on two
    chips: a matched loop of 10 us holds a matched op of 4 us and an
    unmatched one of 2 us (self time 4 us); an op outside the window is
    cut to it; an unmatched op counts nowhere."""
    spans = [(0, 100_000, "bench.traced"), (10_000, 30_000, "bench.step"),
             (40_000, 60_000, "bench.add_request"),
             (70_000, 90_000, "bench.step")]
    chip = [(11_000, 21_000, True), (12_000, 16_000, True),
            (16_000, 18_000, False), (41_000, 46_000, True),
            (50_000, 59_000, False), (71_000, 73_000, True),
            (95_000, 105_000, True)]
    got = shapefold.matched_s_by_span({"/device:TPU:0": chip,
                                      "/device:TPU:1": chip}, spans)
    assert got == pytest.approx({"bench.step": 10e-6,
                                 "bench.add_request": 5e-6,
                                 "window": 5e-6})
    assert shapefold.matched_s_by_span({}, spans) == {}


def test_shape_fold_reads_the_device_ops_from_the_xplane(tmp_path,
                                                         monkeypatch):
    """``from_xplane`` takes the device planes' "XLA Ops" events, matched by
    their HLO text, and the host's ``bench.`` spans from the newest trace
    file; other lines and host events count nowhere."""
    from types import SimpleNamespace as NS
    ev = lambda name, s, e: NS(name=name, start_ns=s, end_ns=e)  # noqa: E731
    planes = [
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=[
                ev("%fusion.3 = bf16[16,16768]{1,0} fusion(...)", 11, 15),
                ev("%fusion.2 = f32[16,100352]{1,0} fusion(...)", 15, 19),
                ev("%fusion.7 = f32[1,128,256,256] fusion(...)", 41, 44)]),
            NS(name="XLA Modules", events=[
                ev("jit_serve_decode(16768)", 10, 30)])]),
        NS(name="/host:CPU", lines=[NS(name="python3", events=[
            ev("bench.traced", 0, 100), ev("bench.step", 10, 30),
            ev("bench.add_request", 40, 60), ev("serve.fetch", 11, 15)])])]
    (tmp_path / "a").mkdir()
    (tmp_path / "a" / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(jax.profiler, "ProfileData", NS(
        from_file=lambda path: NS(planes=planes)))
    sz = DRIVER.sizes_with_widths(_ctx(_cell(smoke=False), False))
    got = shapefold.from_xplane(str(tmp_path), DRIVER.mamba2_dims(sz))
    assert got == pytest.approx({"bench.step": 4e-9,
                                 "bench.add_request": 3e-9})
    assert shapefold.from_xplane(str(tmp_path / "none"), "x") == {}


def test_mamba2_dims_pick_the_mixers_ops():
    """The TPU profiler's op events carry no named scope, so the Mamba-2
    layers' own dimensions pick their ops: the input projection (16768),
    the conv (8448), d_inner (8192), a state's heads by channels, a chunk's
    block; no attention, expert, shared-MLP or unembedding shape matches."""
    import re
    sz = DRIVER.sizes_with_widths(_ctx(_cell(smoke=False), False))
    pattern = re.compile(DRIVER.mamba2_dims(sz))
    mamba2 = ["%fusion.3 = bf16[16,16768]{1,0}", "%fusion.9 = f32[16,8448]",
              "%convert.2 = bf16[1,4096,8192]", "%fusion = f32[16,128,64,128]",
              "%fusion.7 = f32[1,128,256,256]"]
    others = ["%fusion.1 = bf16[16,4096]", "%fusion.2 = f32[16,100352]",
              "%ragged-dot-none = bf16[160,1536]", "%copy = bf16[16,81920]",
              "%fusion.5 = bf16[2,16,4096,8,128]", "%fusion.6 = f32[16,32,4096]"]
    assert all(pattern.search(n) for n in mamba2)
    assert not any(pattern.search(n) for n in others)


def _reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                               "m_" + name.replace(".", "_"))


@pytest.mark.parametrize("name,counter,device_s,peak", [
    ("hbm_roofline.ssm.decode", "traced_ssm_decode_bytes",
     "ssm_decode_device_s", 819e9),
    ("mfu.ssm.prefill", "traced_ssm_prefill_flops", "ssm_prefill_device_s",
     197e12)])
def test_ssm_reader(name, counter, device_s, peak):
    ctx = _ctx(_cell(smoke=False), False)
    ctx.device_kind = "TPU v5 lite"
    rec = harness.RunRecord(counters={counter: 0.5 * peak, device_s: 2.0},
                            trace={"op_s": {}})
    assert _reader(name).read(rec, ctx) == pytest.approx(25.0)
    # nothing to read: no trace, no counter, no device time under the scope
    assert _reader(name).read(harness.RunRecord(), ctx) is None
    for missing in (counter, device_s):
        c = {k: v for k, v in rec.counters.items() if k != missing}
        assert _reader(name).read(harness.RunRecord(
            counters=c, trace={"op_s": {}}), ctx) is None
