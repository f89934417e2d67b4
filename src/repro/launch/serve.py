"""Serving driver: load (or init) params and serve a synthetic request
stream through the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch tacc-100m --smoke \
      --requests 8

:func:`serve` is the whole run; ``main`` and ``chip_smoke.py`` both call it.
"""
import argparse
import time
from dataclasses import dataclass
from typing import List

import jax
import numpy as np

from repro import runtime
from repro.ckpt import restore_checkpoint
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.models import init_params, model_defs
from repro.serve import GenerationResult, ServeEngine


def synthetic_prompts(cfg: ModelConfig, n: int, min_len: int, max_len: int,
                      seed: int) -> List[List[int]]:
    """``n`` prompts of uniform random tokens, lengths in [min_len, max_len)."""
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, cfg.vocab_size, rng.randint(min_len, max_len)))
            for _ in range(n)]


@dataclass
class ServeResult:
    results: List[GenerationResult]
    engine: ServeEngine
    warmup_s: float               # one request that compiles prefill+decode
    wall_s: float                 # from the first admission to the last token

    @property
    def tokens(self) -> int:
        return sum(len(r.tokens) for r in self.results)

    def ttft_s(self) -> List[float]:
        """Time to first token per request, queueing included."""
        return [r.first_token_s - r.arrived_s for r in self.results]

    def tpot_s(self) -> List[float]:
        """Mean time per output token after the first, per request."""
        return [(r.finished_s - r.first_token_s) / (len(r.tokens) - 1)
                for r in self.results if len(r.tokens) > 1]


def serve(cfg: ModelConfig, params, prompts: List[List[int]], *,
          max_batch: int, max_seq: int, max_new: int) -> ServeResult:
    """Serve ``prompts`` to completion through one :class:`ServeEngine`,
    after one warm-up request that compiles its prefill and decode steps
    (prefill is padded to ``max_seq``, so no later prompt compiles)."""
    engine = ServeEngine(cfg, params, max_batch=max_batch, max_seq=max_seq)
    t0 = time.perf_counter()
    engine.run(prompts[:1], max_new=2)
    t1 = time.perf_counter()
    results = engine.run(prompts, max_new=max_new)
    return ServeResult(results, engine, t1 - t0, time.perf_counter() - t1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tacc-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    # before the first jax computation: device count / platform lock in at
    # backend init, and the compile cache at the first compilation
    runtime.init_from_env()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.ckpt_dir:
        state, _ = restore_checkpoint(args.ckpt_dir)
        params = jax.tree.map(jax.numpy.asarray, state["params"])
    else:
        params = init_params(model_defs(cfg), jax.random.PRNGKey(args.seed))
    r = serve(cfg, params,
              synthetic_prompts(cfg, args.requests, 2, 10, args.seed),
              max_batch=args.max_batch, max_seq=args.max_seq,
              max_new=args.max_new)
    for g in r.results:
        print(f"req {g.request_id}: {g.prompt} -> {g.tokens}")
    print(f"{len(r.results)} requests, {r.tokens} tokens in {r.wall_s:.1f}s "
          f"({r.engine._steps} decode steps; warm-up {r.warmup_s:.1f}s)")


if __name__ == "__main__":
    main()
