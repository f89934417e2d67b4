#!/usr/bin/env python3
"""Find a serving cell's knee once: its open loop at several fixed rates.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 2,3,4

One process, one engine: each rate serves its own schedule for
``--seconds`` and drains. A rate the engine sustains finishes soon after
the window closes and keeps time to first token flat from the first third
of its requests to the last; above the knee the backlog grows through the
window. Prints one JSON line per rate. Not part of a benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_bench()
    cell = harness.load_cell(args.workload, bench)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro import runtime
    runtime.init_from_env()
    import jax
    import run
    import traffic
    from repro.serve import ServeEngine
    from weights import make_params_fn
    if jax.devices()[0].platform != "tpu":
        harness.log("no TPU: no sweep")
        return 2
    drv = harness.load_module(os.path.join(HERE, "drivers",
                                           "serve_open_loop.py"), "drv")
    cfg, sizes = run.program_config(cell, False)
    mix = cell.traffic
    params = make_params_fn(sizes, cell.config["weights_dtype"])(
        jax.random.PRNGKey(args.seed))
    engine = ServeEngine(cfg, params, max_batch=mix["max_batch"],
                         max_seq=mix["max_seq"])
    engine.add_request([1] * 8, max_new=2)
    while engine.active():
        engine.step()
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        c = harness.Cell(cell.name, cell.chips, cell.config, m, {}, bench)
        ctx = harness.Context(cell=c, sizes=sizes, program_cfg=cfg,
                              seeds=harness.sub_seeds(args.seed),
                              seconds=args.seconds, traced=False,
                              t_start=T_START, workdir="",
                              spans=harness.Spans(False))
        sched = traffic.open_loop(m, args.seconds, sizes["vocab_size"],
                                  np.random.RandomState(args.seed))
        rec = harness.RunRecord()
        recs = drv.serve_window(engine, sched, ctx, rec, sizes)
        ttft = [r["times"][0] - r["due"] for r in recs if r["times"]]
        third = max(1, len(ttft) // 3)
        print(json.dumps({
            "rate": rate, "requests": len(recs),
            "done": sum(r["done"] for r in recs),
            "drain_s": rec.counters["served_s"] - args.seconds,
            "ttft_p50_first_third": float(np.median(ttft[:third])),
            "ttft_p50_last_third": float(np.median(ttft[-third:])),
            "ttft_p90": float(np.percentile(ttft, 90)),
            "decode_step_ms": 1e3 * float(np.mean(
                ctx.spans.durations("bench.step"))),
            "prefill_ms": 1e3 * float(np.mean(
                ctx.spans.durations("bench.add_request"))),
            "tokens_per_s": sum(len(r["times"]) for r in recs)
            / rec.counters["served_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
