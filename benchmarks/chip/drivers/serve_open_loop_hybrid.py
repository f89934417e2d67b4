"""serve_open_loop_hybrid: ``serve_open_loop`` for a Mamba-2 / attention
hybrid with MoE in every layer (Granite-4.0-H's layout, a chip's share of
its routed experts).

The loop, the window, the schedule and the check are ``serve_open_loop``'s,
loaded here as a private copy of that module and given this configuration's
plain reference (``reference_hybrid``), weights (``weights_hybrid``) and
FLOP and byte counts (``counts_hybrid``). The configuration file's
``widths`` (Mamba-2, MoE and the period's layer kinds) are checked field by
field against the program's configuration and passed on with the flat
sizes.

The weights are drawn once, from the configuration file's
``weights_seed``, the same in every run, as a deployment serves one set of
weights; the run's seed draws the traffic and the check. A draw per run
made each run's routing, and so its step time, follow its draw
(``weights_seed_why``).

The engine is handed to the loop wrapped, and each call's work is added,
while the profiler is on, to the run's traced counters: the routed
experts' share from the engine's MoE counters, as
``serve_open_loop_mla_moe`` adds it; and the Mamba-2 layers' own, from the
sizes: their weights and each occupied row's conv window and SSM state,
read and written by every decode step (``traced_ssm_decode_bytes``, also
part of ``traced_decode_bytes``), and their FLOPs per real prompt token
(``traced_ssm_prefill_flops``). In a traced run the device time of the
Mamba-2 layers' operations, picked by the dimensions only those layers
have (``mamba2_dims``), is folded from the profiler trace by harness span
(``shapefold``) before the harness reduces the trace, as
``ssm_decode_device_s`` and ``ssm_prefill_device_s``.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import numpy as np

import counts_hybrid
import harness
import reference_hybrid
import shapefold
import traffic
import weights_hybrid

HERE = os.path.dirname(os.path.abspath(__file__))
BASE = harness.load_module(os.path.join(HERE, "serve_open_loop.py"),
                           "serve_open_loop_for_hybrid")
BASE.reference = reference_hybrid
BASE.peaks = counts_hybrid
BASE.make_params_fn = weights_hybrid.make_params_fn
MLA_MOE = harness.load_module(os.path.join(HERE,
                                           "serve_open_loop_mla_moe.py"),
                              "serve_open_loop_mla_moe_for_hybrid")


def program_widths(cfg) -> dict:
    """The program configuration's widths, as the configuration file's
    ``widths`` states them."""
    return {"mamba2": dataclasses.asdict(cfg.mamba2),
            "moe": {k: v for k, v in dataclasses.asdict(cfg.moe).items()
                    if k not in ("capacity_factor", "aux_loss_coef",
                                 "pad_to")},
            "period": [s.mixer for s in cfg.period]}


def sizes_with_widths(ctx) -> dict:
    """The flat sizes plus the widths; at full size the file's widths,
    checked field by field against the program's, at smoke size the
    program's."""
    got = program_widths(ctx.program_cfg)
    if not ctx.smoke:
        want = MLA_MOE._dotted(ctx.cell.config["widths"])
        have = MLA_MOE._dotted(got)
        differ = {k: (want.get(k), have.get(k)) for k in set(want) | set(have)
                  if want.get(k) != have.get(k)}
        if differ:
            raise ValueError(f"program widths differ from "
                             f"{ctx.cell.config['name']}: {differ}")
    return dict(ctx.sizes, **got)


class CountedEngine(MLA_MOE.CountedEngine):
    """``serve_open_loop_mla_moe.CountedEngine`` with the Mamba-2 layers'
    work added too."""

    def __init__(self, engine, ctx, rec, sz):
        self.engine, self.ctx, self.rec = engine, ctx, rec
        self.max_batch = engine.max_batch
        dtype = np.dtype(ctx.cell.config["weights_dtype"])
        self.flops = counts_hybrid.expert_flops_per_assignment(sz)
        self.bytes = counts_hybrid.expert_bytes(sz, dtype.itemsize)
        self.ssm_weights = counts_hybrid.ssm_weight_bytes(sz, dtype.itemsize)
        self.ssm_row = counts_hybrid.ssm_state_bytes_per_row(sz)
        self.ssm_token = counts_hybrid.ssm_prefill_flops_per_token(sz)

    def add_request(self, prompt, max_new):
        g = super().add_request(prompt, max_new)
        self.ctx.count(self.rec, traced_ssm_prefill_flops=self.ssm_token
                       * len(g.prompt))
        return g

    def step(self):
        rows = self.engine.active()
        out = super().step()
        state = self.ssm_row * rows
        self.ctx.count(self.rec, traced_decode_bytes=state,
                       traced_ssm_decode_bytes=self.ssm_weights + state)
        return out


def mamba2_dims(sz) -> str:
    """``shapefold.dims_pattern`` of the dimensions only the Mamba-2 layers
    have: the input projection's width, the conv's channels, d_inner, a
    state's heads by channels, a chunk's positions by positions."""
    m = sz["mamba2"]
    h, p, g, n = m["n_heads"], m["head_dim"], m["n_groups"], m["d_state"]
    conv = h * p + 2 * g * n
    return shapefold.dims_pattern([h * p + conv + h, conv, h * p, (h, p),
                                   (m["chunk_size"], m["chunk_size"])])


def fold_mamba2(ctx, rec) -> None:
    """Stop the profiler if it is still on, and add the device time of the
    Mamba-2 layers' ops (``mamba2_dims``) in decode steps and in admissions
    to the run's counters, from the trace the harness reduces next."""
    if ctx.tracing:
        ctx._stop_trace()
    if not ctx.traced or not ctx._trace.get("done"):
        return
    by_span = shapefold.from_xplane(os.path.join(ctx.workdir, "trace"),
                                    mamba2_dims(ctx.sizes))
    rec.counters["ssm_decode_device_s"] = by_span.get("bench.step", 0.0)
    rec.counters["ssm_prefill_device_s"] = by_span.get("bench.add_request",
                                                       0.0)
    harness.log(f"device time of the Mamba-2 ops by span: {by_span}")


def weights_key(ctx):
    """The weights' key: the configuration's ``weights_seed``, whatever the
    run's seed."""
    return jax.random.PRNGKey(ctx.cell.config["weights_seed"])


def run(ctx: harness.Context, devices) -> harness.RunRecord:
    """As ``serve_open_loop.run``, with this configuration's widths,
    weights (from ``weights_key``) and counts, and the engine wrapped in
    ``CountedEngine``."""
    from repro.serve import ServeEngine
    ctx.sizes = sizes_with_widths(ctx)
    mix = ctx.cell.traffic
    params = weights_hybrid.make_params_fn(
        ctx.sizes, ctx.cell.config["weights_dtype"])(weights_key(ctx))
    BASE._check_layout(params, ctx.program_cfg)
    engine = ServeEngine(ctx.program_cfg, params, max_batch=mix["max_batch"],
                         max_seq=mix["max_seq"])
    warm = engine.add_request([1] * 8, max_new=2)
    while engine.active():
        engine.step()
    harness.log(f"warm-up request served: {warm.tokens}")
    rng = np.random.RandomState(ctx.seeds["traffic"])
    schedule = traffic.open_loop(mix, ctx.seconds, ctx.sizes["vocab_size"],
                                 rng)
    rec = harness.RunRecord()
    with ctx.window(rec):
        recs = BASE.serve_window(CountedEngine(engine, ctx, rec, ctx.sizes),
                                 schedule, ctx, rec, ctx.sizes)
        rec.window_s = rec.counters["served_s"]
        fold_mamba2(ctx, rec)
    rec.requests = [{k: v for k, v in r.items() if k != "result"}
                    for r in recs]
    rec.attempted = len(recs)
    rec.failed = sum(not r["done"] for r in recs)
    c = engine.counters
    harness.log(f"{rec.attempted} requests due, {rec.failed} failed, "
                f"{rec.counters['decode_steps']} decode steps, served in "
                f"{rec.counters['served_s']:.3f} s; held experts touched "
                f"per decode step {c['moe_experts_touched'] / max(c['decode_steps'], 1):.2f} "
                f"at {c['decode_rows'] / max(c['decode_steps'], 1):.2f} rows; "
                f"engine counters {c}")
    rec.memory_peak_bytes = harness.memory_peak(devices)
    del engine
    BASE.check(ctx, params, recs, rec)
    return rec
