"""serve_open_loop_mla_moe: ``serve_open_loop`` for an MLA + MoE
configuration (DeepSeek-V2's layout, a chip's share of its routed experts).

The loop, the window, the schedule and the check are ``serve_open_loop``'s,
loaded here as a private copy of that module and given this configuration's
plain reference (``reference_mla_moe``), weights (``weights_mla_moe``) and
FLOP and byte counts (``counts_mla_moe``). The configuration file's
``widths`` (MLA, MoE, YaRN and the dense layers) are checked field by field
against the program's configuration and passed on with the flat sizes.

The routed experts' work depends on the routing, so the engine's own MoE
counters say how much there was: the engine is handed to the loop wrapped,
and each call's counter deltas are added, while the profiler is on, to the
traced FLOPs and bytes of decode and prefill and to the expert layer's own
(``traced_moe_decode_bytes``, ``traced_moe_prefill_flops``).
"""
from __future__ import annotations

import dataclasses
import os

import jax
import numpy as np

import counts_mla_moe
import harness
import reference_mla_moe
import traffic
import weights_mla_moe

BASE = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "serve_open_loop.py"), "serve_open_loop_for_mla_moe")
BASE.reference = reference_mla_moe
BASE.peaks = counts_mla_moe
BASE.make_params_fn = weights_mla_moe.make_params_fn


def program_widths(cfg) -> dict:
    """The program configuration's widths, as the configuration file's
    ``widths`` states them."""
    return {"mla": dataclasses.asdict(cfg.mla),
            "moe": {k: v for k, v in dataclasses.asdict(cfg.moe).items()
                    if k not in ("capacity_factor", "aux_loss_coef",
                                 "pad_to")},
            "rope_scaling": dataclasses.asdict(cfg.rope_scaling),
            "dense_layers": len(cfg.prelayers)}


def _dotted(widths: dict) -> dict:
    return {f"{g}.{k}" if isinstance(w, dict) else g: v
            for g, w in widths.items()
            for k, v in (w.items() if isinstance(w, dict) else [("", w)])}


def sizes_with_widths(ctx) -> dict:
    """The flat sizes plus the widths; at full size the file's widths,
    checked field by field against the program's, at smoke size the
    program's."""
    got = program_widths(ctx.program_cfg)
    if not ctx.smoke:
        want, have = _dotted(ctx.cell.config["widths"]), _dotted(got)
        differ = {k: (want.get(k), have.get(k)) for k in set(want) | set(have)
                  if want.get(k) != have.get(k)}
        if differ:
            raise ValueError(f"program widths differ from "
                             f"{ctx.cell.config['name']}: {differ}")
    return dict(ctx.sizes, **got)


class CountedEngine:
    """``ServeEngine`` as the loop drives it (``max_batch``, ``active``,
    ``add_request``, ``step``), adding the routed experts' share of each
    call's work, from the engine's MoE counters, to the run's traced
    counters."""

    def __init__(self, engine, ctx, rec, sz):
        self.engine, self.ctx, self.rec = engine, ctx, rec
        self.max_batch = engine.max_batch
        dtype = np.dtype(ctx.cell.config["weights_dtype"])
        self.flops = counts_mla_moe.expert_flops_per_assignment(sz)
        self.bytes = counts_mla_moe.expert_bytes(sz, dtype.itemsize)

    def active(self) -> int:
        return self.engine.active()

    def _delta(self, call, *args, **kw):
        before = dict(self.engine.counters)
        out = call(*args, **kw)
        c = self.engine.counters
        return out, {k: c[k] - before[k] for k in c}

    def add_request(self, prompt, max_new):
        g, d = self._delta(self.engine.add_request, prompt, max_new=max_new)
        flops = self.flops * d["moe_prefill_assignments_here"]
        self.ctx.count(self.rec, traced_prefill_flops=flops,
                       traced_moe_prefill_flops=flops)
        return g

    def step(self):
        out, d = self._delta(self.engine.step)
        nbytes = self.bytes * d["moe_experts_touched"]
        self.ctx.count(self.rec,
                       traced_decode_flops=self.flops
                       * d["moe_assignments_here"],
                       traced_decode_bytes=nbytes,
                       traced_moe_decode_bytes=nbytes)
        return out


def run(ctx: harness.Context, devices) -> harness.RunRecord:
    """As ``serve_open_loop.run``, with this configuration's widths,
    weights and counts, and the engine wrapped in ``CountedEngine``."""
    from repro.serve import ServeEngine
    ctx.sizes = sizes_with_widths(ctx)
    mix = ctx.cell.traffic
    params = weights_mla_moe.make_params_fn(
        ctx.sizes, ctx.cell.config["weights_dtype"])(
        jax.random.PRNGKey(ctx.seeds["weights"]))
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
    rec.requests = [{k: v for k, v in r.items() if k != "result"}
                    for r in recs]
    rec.attempted = len(recs)
    rec.failed = sum(not r["done"] for r in recs)
    harness.log(f"{rec.attempted} requests due, {rec.failed} failed, "
                f"{rec.counters['decode_steps']} decode steps, served in "
                f"{rec.counters['served_s']:.3f} s; engine counters "
                f"{engine.counters}")
    rec.memory_peak_bytes = harness.memory_peak(devices)
    del engine
    BASE.check(ctx, params, recs, rec)
    return rec
