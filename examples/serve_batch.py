"""Batched serving example: a trained small model served with continuous
batching — requests arrive while others are mid-generation; slots refill
without stalling the batch.

  PYTHONPATH=src python examples/serve_batch.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import SyntheticLM
from repro.models import init_params, model_defs
from repro.serve import ServeEngine
from repro.train import OptConfig, TrainConfig, build_train_step, init_train_state


def main():
    cfg = get_config("tacc-100m", smoke=True)
    # quick train so generations follow the synthetic pattern
    ocfg = OptConfig(lr=2e-3, warmup_steps=10, total_steps=80)
    state = init_train_state(cfg, ocfg, jax.random.PRNGKey(0))
    step = jax.jit(build_train_step(cfg, ocfg, TrainConfig()), donate_argnums=0)
    data = SyntheticLM(cfg, 8, 64, seed=0)
    for i in range(80):
        state, m = step(state, jax.tree.map(jnp.asarray, data.batch(i)))
    print(f"trained to loss {float(m['loss']):.3f}")

    engine = ServeEngine(cfg, state["params"], max_batch=4, max_seq=64)
    rng = np.random.RandomState(0)
    v = cfg.vocab_size
    # prompts that follow the learned pattern x -> (5x+17) % V
    prompts = []
    for _ in range(10):
        x = int(rng.randint(0, v))
        seq = [x]
        for _ in range(5):
            seq.append((5 * seq[-1] + 17) % v)
        prompts.append(seq)

    t0 = time.time()
    results = engine.run(prompts, max_new=6)
    dt = time.time() - t0
    correct = 0
    total = 0
    for r in results:
        expect = []
        x = r.prompt[-1]
        for _ in range(6):
            x = (5 * x + 17) % v
            expect.append(x)
        hit = sum(a == b for a, b in zip(r.tokens, expect))
        correct += hit
        total += len(expect)
        print(f"  prompt tail {r.prompt[-2:]} -> {r.tokens} "
              f"(expected {expect}, {hit}/6 match)")
    print(f"\npattern accuracy {correct/total:.0%}; "
          f"{len(results)} requests in {dt:.1f}s with continuous batching "
          f"({engine.counters['decode_steps']} decode steps)")


if __name__ == "__main__":
    main()
