"""Serving engine benchmark: continuous batching vs sequential service on the
smoke model — requests served per decode step and total steps (CPU wall time
is reported for regression tracking only)."""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import init_params, model_defs
from repro.serve import ServeEngine


def run_bench(n_requests: int = 12, max_new: int = 8, *, max_seq: int = 48,
              cfg=None, params=None):
    """One bench pass (importable so tier-1 can smoke it): serve the same
    prompt set with continuous batching and sequentially, returning both
    engines and result lists for invariant checks."""
    if cfg is None:
        cfg = get_config("tacc-100m", smoke=True)
    if params is None:
        params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, cfg.vocab_size, rng.randint(2, 10)))
               for _ in range(n_requests)]

    # continuous batching
    eng = ServeEngine(cfg, params, max_batch=4, max_seq=max_seq)
    t0 = time.time()
    res = eng.run(prompts, max_new=max_new)
    t_cb = time.time() - t0

    # sequential (batch=1)
    eng1 = ServeEngine(cfg, params, max_batch=1, max_seq=max_seq)
    t0 = time.time()
    res1 = eng1.run(prompts, max_new=max_new)
    t_seq = time.time() - t0

    return {"batched": (eng, res, t_cb), "sequential": (eng1, res1, t_seq)}


def main(n_requests: int = 12, max_new: int = 8):
    out = run_bench(n_requests, max_new)
    eng, _res, t_cb = out["batched"]
    eng1, _res1, t_seq = out["sequential"]
    steps_cb, steps_seq = (e.counters["decode_steps"] for e in (eng, eng1))

    tok = n_requests * max_new
    print("name,us_per_call,derived")
    print(f"serve_continuous_batch4,{t_cb/tok*1e6:.0f},"
          f"decode_steps={steps_cb};tokens={tok}")
    print(f"serve_sequential_batch1,{t_seq/tok*1e6:.0f},"
          f"decode_steps={steps_seq};tokens={tok}")
    print(f"serve_speedup,%.2f,steps_ratio=%.2f" %
          (t_seq / max(t_cb, 1e-9), steps_seq / max(steps_cb, 1)))


if __name__ == "__main__":
    main()
