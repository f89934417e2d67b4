"""serve_open_loop: ``ServeEngine``, the ``jax_serve`` runtime's engine,
under open-loop traffic.

Requests fall due on the mix's schedule whatever the engine is doing. The
loop admits every due request while a slot is free (``add_request``:
one-row prefill, splice into the batch cache, first token), then runs one
``step`` (a decode for every occupied slot), as ``ServeEngine.run`` does;
with nothing to do it sleeps until the next request falls due. Each
request is timed from when it was due. Requests still running when the
window closes are served to completion; one not finished ``drain_s``
after the close has failed.

Set-up makes the weights on the device and builds the engine, then serves
one short request to completion, which compiles the prefill (always one
row padded to ``max_seq``), the decode step and the splice: the only
shapes the window uses.

The check samples, from the seed, finished requests with the longest
among them until ``check_tokens`` served tokens, and runs the reference
over each prompt with its served tokens: the widest gap by which a served
token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List

import jax
import numpy as np

import harness
import peaks
import reference
import traffic
from weights import make_params_fn


def _check_layout(params, cfg) -> None:
    """Same tree and shapes as the program's parameters (the dtype is the
    configuration's serving dtype)."""
    from repro.models import abstract_params, model_defs
    want = abstract_params(model_defs(cfg))
    if jax.tree.structure(want) != jax.tree.structure(params) or any(
            w.shape != p.shape for w, p in
            zip(jax.tree.leaves(want), jax.tree.leaves(params))):
        raise ValueError("benchmark weights do not have the program's "
                         "parameter layout")


def serve_window(engine, schedule: List[traffic.Request], ctx, rec,
                 sizes: Dict) -> List[Dict]:
    """Serve ``schedule`` open loop; returns one record per request."""
    sp, mix = ctx.spans, ctx.cell.traffic
    recs = [{"due": r.due_s, "prompt_len": len(r.prompt),
             "max_new": r.max_new, "times": [], "done": False,
             "result": None} for r in schedule]
    pending, live = collections.deque(), {}
    n, i = len(schedule), 0
    steps = rows = 0
    w_bytes = peaks.weight_bytes(
        sizes, np.dtype(ctx.cell.config["weights_dtype"]).itemsize)
    kv_tok = peaks.kv_bytes_per_token(sizes)
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds + mix["drain_s"]
    while True:
        paused = ctx.trace_poll(time.perf_counter() - t0)
        t0, deadline = t0 + paused, deadline + paused
        now = time.perf_counter()
        while i < n and t0 + schedule[i].due_s <= now:
            pending.append(i)
            i += 1
        while pending and engine.active() < engine.max_batch:
            j = pending.popleft()
            with sp("bench.add_request"):
                g = engine.add_request(schedule[j].prompt,
                                       max_new=schedule[j].max_new)
            if g is None:
                raise RuntimeError("engine refused a request with a slot "
                                   "free")
            recs[j]["times"].append(time.perf_counter() - t0)
            recs[j]["result"] = g
            live[j] = g
            ctx.count(rec, traced_prefill_flops=peaks.prefill_flops(
                sizes, len(g.prompt)))
        if engine.active():
            occupied = [(j, len(g.prompt) + len(g.tokens))
                        for j, g in live.items()]
            with sp("bench.step"):
                engine.step()
            t = time.perf_counter() - t0
            steps += 1
            rows += len(occupied)
            ctx.count(rec, traced_decode_flops=len(occupied)
                      * peaks.decode_flops_per_row(sizes),
                      traced_decode_bytes=w_bytes + kv_tok * sum(
                          c for _, c in occupied))
            for j, _ in occupied:
                g = live[j]
                if len(g.tokens) > len(recs[j]["times"]):
                    recs[j]["times"].append(t)
                if g.done:
                    recs[j]["done"] = True
                    del live[j]
        elif i < n:
            with sp("bench.wait"):
                time.sleep(max(0.0, t0 + schedule[i].due_s
                               - time.perf_counter()))
        elif not pending:
            break
        if time.perf_counter() > deadline:
            break
    end = time.perf_counter() - t0
    rec.counters.update(decode_steps=steps, decode_rows=rows, served_s=end)
    return recs


def sample_for_check(recs: List[Dict], ctx) -> List[Dict]:
    """Finished requests drawn from the seed, the longest first, until
    ``check_tokens`` served tokens or ``check_max_requests`` requests."""
    mix = ctx.cell.traffic
    done = [r for r in recs if r["done"]]
    if not done:
        return []
    rng = np.random.RandomState(ctx.seeds["check"])
    longest = max(range(len(done)),
                  key=lambda k: len(done[k]["result"].tokens))
    order = [longest] + [k for k in rng.permutation(len(done))
                         if k != longest]
    out, total = [], 0
    for k in order:
        if total >= mix["check_tokens"] or len(out) >= mix[
                "check_max_requests"]:
            break
        out.append(done[k])
        total += len(done[k]["result"].tokens)
    return out


def check(ctx, params, recs: List[Dict], rec) -> None:
    mix = ctx.cell.traffic
    gap_fn = reference.make_gap_fn(ctx.sizes, mix["max_seq"],
                                   mix["output"]["max"], ctx.control)
    served, low = [], []
    for r in sample_for_check(recs, ctx):
        g = r["result"]
        a, b = reference.serve_gaps(gap_fn, params, g.prompt, g.tokens,
                                    mix["max_seq"], mix["output"]["max"])
        served.extend(a)
        low.extend(b)
    rec.counters["checked_tokens"] = len(served)
    harness.log(f"check: {len(served)} served tokens against the "
                f"reference")
    # nothing to compare reads as a gap no limit admits
    rec.checks["served_gap"] = float(np.max(served)) if served else 1e9
    if ctx.control:
        rec.checks["control.served_gap"] = float(np.max(low)) if low \
            else 1e9


def run(ctx: harness.Context, devices) -> harness.RunRecord:
    from repro.serve import ServeEngine
    mix = ctx.cell.traffic
    dtype = ctx.cell.config["weights_dtype"]
    params = make_params_fn(ctx.sizes, dtype)(
        jax.random.PRNGKey(ctx.seeds["weights"]))
    _check_layout(params, ctx.program_cfg)
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
        recs = serve_window(engine, schedule, ctx, rec, ctx.sizes)
        rec.window_s = rec.counters["served_s"]
    rec.requests = [{k: v for k, v in r.items() if k != "result"}
                    for r in recs]
    rec.attempted = len(recs)
    rec.failed = sum(not r["done"] for r in recs)
    harness.log(f"{rec.attempted} requests due, {rec.failed} failed, "
                f"{rec.counters['decode_steps']} decode steps, served in "
                f"{rec.counters['served_s']:.3f} s")
    rec.memory_peak_bytes = harness.memory_peak(devices)
    del engine
    check(ctx, params, recs, rec)
    return rec
