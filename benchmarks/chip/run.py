#!/usr/bin/env python3
"""SING's chip benchmark: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` at the checkout's root and its
configuration, traffic mix and limits from files named after it (see
``harness.py``). Sets up the platform and the compile cache through
``repro.runtime.init_from_env``, refuses any platform but TPU, makes the
weights on the device from ``--seed``, warms up the cell's own shapes,
measures for ``--seconds`` and checks what the timed path produced against
the plain reference. Progress goes to stderr, ending with each number
compared beside its limit; the last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and ``checks`` last.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
from harness import log  # noqa: E402


def program_config(cell: harness.Cell, smoke: bool):
    """The program's configuration of the cell's model, checked key by key
    against the configuration file (the file holds what is run)."""
    from repro.configs import get_config
    cfg = get_config(cell.config["arch"], smoke=smoke)
    sizes = dict(cell.config["sizes"])
    if smoke:
        return cfg, {k: getattr(cfg, k) for k in sizes}
    differ = {k: (v, getattr(cfg, k)) for k, v in sizes.items()
              if getattr(cfg, k) != v}
    if differ:
        raise ValueError(f"program configuration differs from "
                         f"{cell.config['name']}: {differ}")
    return cfg, sizes


_COMPILES = {"compiles": 0}


def _on_duration(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["compiles"] += 1


def _on_event(event, **_):
    # a persistent-cache hit is timed as a compile too: it is a load
    if event == "/jax/compilation_cache/cache_hits":
        _COMPILES["compiles"] -= 1


def count_compiles() -> None:
    """Count XLA compilations, not loads from the compile cache."""
    import jax
    if not getattr(count_compiles, "registered", False):
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        count_compiles.registered = True


def run_cell(cell: harness.Cell, seed: int, seconds: float, traced: bool,
             *, t_start: float, devices, smoke: bool = False,
             control: bool = False) -> tuple:
    """Drive one run; returns (result dict, record)."""
    cfg, sizes = program_config(cell, smoke)
    work = harness.workdir()
    ctx = harness.Context(cell=cell, sizes=sizes, program_cfg=cfg,
                          seeds=harness.sub_seeds(seed), seconds=seconds,
                          traced=traced, t_start=t_start, workdir=work,
                          spans=harness.Spans(traced), smoke=smoke,
                          control=control,
                          device_kind=devices[0].device_kind,
                          cache_events=_COMPILES)
    count_compiles()
    driver = harness.load_module(
        os.path.join(HERE, "drivers", cell.driver + ".py"),
        "driver_" + cell.driver)
    try:
        rec = driver.run(ctx, devices)
        log(f"run and check done {time.perf_counter() - t_start:.3f} s "
            f"after start")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    by_whom = harness.split_checks(rec.checks)
    judged = harness.judge(by_whom.pop("", {}), cell.limits)
    correct = harness.passed(judged) and rec.failed == 0
    others = {}        # the control's and the faults' verdicts, if read
    for who, checks in by_whom.items():
        theirs = harness.judge(checks, cell.limits)
        others[who] = harness.passed(theirs)
        judged.update({f"{who}.{k}": c for k, c in theirs.items()})
    metrics = harness.read_metrics(
        harness.cell_metrics(cell.bench, cell.name, traced), rec, ctx)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": rec.memory_peak_bytes}
    out = {"correct": correct,
           "attempted": rec.attempted, "failed": rec.failed,
           "metrics": metrics, "device": device}
    if traced and rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        from tracefold import top
        out["breakdown"] = {"device_ops": top(rec.trace["op_s"]),
                            "idle_gaps": top(rec.trace["idle_s_by_span"])}
    if others:
        out["others_correct"] = others
    out["checks"] = judged
    return out, rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.load_bench()
    cell = harness.load_cell(args.workload, bench)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro import runtime
    log(f"compile cache {runtime.init_from_env()}")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform!r} device(s): no result")
        return 2
    devices = devices[:cell.chips]
    out, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, devices=devices)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
