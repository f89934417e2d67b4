#!/usr/bin/env python3
"""Put a serving cell's device time and idle time down to the serving
engine's own spans and programs, over one traced run.

    python3 benchmarks/chip/attribute.py --workload <cell> --seed <n> \
        --seconds <s>

One run of the cell as ``run.py --trace 1`` makes it, with the engine's
spans (``repro.obs``) on through the window. When the profiler stops, it
keeps the profile; once the run is over, it reads from it what the
harness's reduction leaves out: the engine's ``serve.*`` spans on the host
line and each program's executions. It adds to the reduced trace

- ``module_n``: executions per program inside the traced part;
- ``idle_s_by_program_span``: each idle gap under the innermost engine
  span open at its midpoint, or, where none is, under the name
  ``tracefold.reduce`` gives it (the same gaps as ``idle_s_by_span``);
- ``program_span_n``: engine spans per name inside the traced part;

and to the record's counters the window's deltas of ``engine.counters``
under ``engine.*`` names. It prints one JSON line: the cell's metrics and
breakdown as ``run.py --trace 1`` prints them, with the four metrics that
read the engine's spans and programs (``PROGRAM_METRICS``), the breakdown
lists ``idle_gaps_program`` and ``modules``, the engine's counters and the
compilations in the window by engine span. Not part of a benchmark run:
the metrics it adds have no entry in ``BENCHMARK.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import Counter, defaultdict  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import run  # noqa: E402
import tracefold  # noqa: E402

PROGRAM_METRICS = [{"name": "decode_device_ms.serve", "unit": "ms"},
                   {"name": "prefill_device_ms.serve", "unit": "ms"},
                   {"name": "step_idle_ms.serve", "unit": "ms"},
                   {"name": "sample_ms.serve", "unit": "ms"}]


def newest_profile(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def program_spans(log_dir: str) -> List[tracefold.Interval]:
    """The engine's ``serve.*`` spans on the host line of the newest
    profile under ``log_dir``, as ``(start_ns, end_ns, name)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(newest_profile(log_dir))
    return sorted((e.start_ns, e.end_ns, e.name) for plane in pd.planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith("serve."))


def innermost(spans: List[tracefold.Interval], times: List[float]
              ) -> List[Optional[str]]:
    """The innermost of nested ``spans`` open at each of ``times``
    (None where none is)."""
    order = sorted(spans, key=lambda s: (s[0], -s[1]))
    found: Dict[float, Optional[str]] = {}
    stack: List[tuple] = []           # (end, name), outermost first
    i = 0
    for t in sorted(set(times)):
        while i < len(order) and order[i][0] <= t:
            s, e, name = order[i]
            while stack and stack[-1][0] <= s:
                stack.pop()
            stack.append((e, name))
            i += 1
        while stack and stack[-1][0] <= t:
            stack.pop()
        found[t] = stack[-1][1] if stack else None
    return [found[t] for t in times]


def reduce_program(trace: Dict[str, object]) -> Dict[str, object]:
    """``module_n``, ``idle_s_by_program_span`` and ``program_span_n`` of
    a trace as ``tracefold.from_xplane`` reads it, with the engine's spans
    under ``program_spans`` (none where the key is missing)."""
    w0, w1 = next(s[:2] for s in trace["spans"]
                  if s[2] == tracefold.WINDOW)
    index = tracefold.SpanIndex(trace["spans"])
    prog = [s for s in trace.get("program_spans", [])
            if s[1] > w0 and s[0] < w1]
    n_chips = len(trace["ops"])
    idle: Dict[str, float] = defaultdict(float)
    for ops in trace["ops"].values():
        u = tracefold.union([(max(s, w0), min(e, w1)) for s, e, _ in ops
                             if e > w0 and s < w1])
        edges = [w0] + [t for iv in u for t in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        mids = [(a + b) / 2 for a, b in gaps]
        for (a, b), m, name in zip(gaps, mids, innermost(prog, mids)):
            idle[name or index.at(m)] += (b - a) / 1e9 / n_chips
    module_n: Dict[str, float] = defaultdict(float)
    for mods in trace["modules"].values():
        for s, e, name in mods:
            if e > w0 and s < w1:
                module_n[name] += 1 / n_chips
    return {"module_n": dict(module_n),
            "idle_s_by_program_span": dict(idle),
            "program_span_n": dict(Counter(n for _, _, n in prog))}


@dataclass
class SpanContext(harness.Context):
    """The harness's context, which also keeps the profile that the harness
    removes once it has reduced it (a hard link: nothing is read or copied
    while the window runs)."""
    compiles: Optional[Dict[str, int]] = None     # in the window, by span

    @property
    def kept(self) -> str:
        return os.path.join(self.workdir, "kept")

    def _stop_trace(self) -> None:
        super()._stop_trace()
        os.makedirs(self.kept)
        os.link(newest_profile(os.path.join(self.workdir, "trace")),
                os.path.join(self.kept, "trace.xplane.pb"))


def spanned_driver():
    """The serving driver, its window served with the engine's spans on
    and its counters' deltas kept."""
    from repro import obs
    drv = harness.load_module(os.path.join(HERE, "drivers",
                                           "serve_open_loop.py"),
                              "driver_serve_spans")
    window = drv.serve_window

    def serve_window(engine, schedule, ctx, rec, sizes):
        before = dict(engine.counters)
        obs.reset()
        obs.enable(True)
        try:
            return window(engine, schedule, ctx, rec, sizes)
        finally:
            obs.enable(False)
            rec.counters.update({"engine." + k: v - before[k]
                                 for k, v in engine.counters.items()})
            ctx.compiles = obs.compiles()
            harness.log(f"compilations in the window by engine span: "
                        f"{ctx.compiles}")

    drv.serve_window = serve_window
    return drv


def run_cell(cell: harness.Cell, seed: int, seconds: float, traced: bool,
             *, t_start: float, devices, smoke: bool = False) -> tuple:
    """One run of a serving cell with the engine's spans on; returns
    (result dict, record)."""
    cfg, sizes = run.program_config(cell, smoke)
    work = harness.workdir()
    ctx = SpanContext(cell=cell, sizes=sizes, program_cfg=cfg,
                      seeds=harness.sub_seeds(seed), seconds=seconds,
                      traced=traced, t_start=t_start, workdir=work,
                      spans=harness.Spans(traced), smoke=smoke,
                      device_kind=devices[0].device_kind,
                      cache_events=run._COMPILES)
    run.count_compiles()
    try:
        rec = spanned_driver().run(ctx, devices)
        if rec.trace is not None:
            trace = tracefold.from_xplane(ctx.kept)
            trace["program_spans"] = program_spans(ctx.kept)
            rec.trace.update(reduce_program(trace))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    judged = harness.judge(rec.checks, cell.limits)
    metrics = harness.read_metrics(
        harness.cell_metrics(cell.bench, cell.name, traced)
        + PROGRAM_METRICS, rec, ctx)
    d0 = devices[0]
    out = {"correct": harness.passed(judged) and rec.failed == 0,
           "attempted": rec.attempted, "failed": rec.failed,
           "metrics": metrics,
           "device": {"platform": d0.platform, "kind": d0.device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": rec.memory_peak_bytes},
           "counters": {k: v for k, v in rec.counters.items()
                        if k.startswith("engine.")},
           "compiles_by_span": ctx.compiles}
    if rec.trace is not None:
        t = rec.trace
        out["device"].update(busy_s=t["busy_s"], window_s=t["window_s"])
        out["breakdown"] = {
            "device_ops": tracefold.top(t["op_s"]),
            "idle_gaps": tracefold.top(t["idle_s_by_span"]),
            "idle_gaps_program": tracefold.top(
                t.get("idle_s_by_program_span", {})),
            "modules": tracefold.top(t["module_s"])}
    out["checks"] = judged
    return out, rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = harness.load_bench()
    cell = harness.load_cell(args.workload, bench)
    if cell.driver != "serve_open_loop":
        harness.log(f"{cell.name} is not a serving cell")
        return 2
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro import runtime
    runtime.init_from_env()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log("no TPU: no trace")
        return 2
    out, _ = run_cell(cell, args.seed, args.seconds, True,
                      t_start=T_START, devices=devices[:cell.chips])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
