#!/usr/bin/env python3
"""Read a cell's check numbers, and its control's, over several seeds in
one process: the readings its limits are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,3 --seconds <s>

Each seed is one whole run of the cell at its own load (``run.run_cell``)
with a short window; besides the program's numbers it reads the control
(the reference in the cell's place, computed in scaled fp8, one precision
below the configuration's bfloat16) and, for training cells, the
half-batch fault (the reference on half of each batch, the mean over the
rest). Each reading is judged by the comparison that decides ``correct``
(``harness.judge`` against the cell's limits, then ``harness.passed``):
the program has to come out correct, the control and every fault not.
Prints one JSON line per seed with those verdicts. Not part of a
benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = harness.load_bench()
    cell = harness.load_cell(args.workload, bench)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro import runtime
    runtime.init_from_env()
    import jax
    import run
    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log("no TPU: no readings")
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        out, rec = run.run_cell(cell, seed, args.seconds, False,
                                t_start=time.perf_counter(),
                                devices=devices[:cell.chips], control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
            "others_correct": out.get("others_correct", {}), "checks": {
                k: v["value"] for k, v in out["checks"].items()},
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
