"""Fleet training driver.

On a real TPU fleet each host runs this under its own process with
``jax.distributed.initialize()``; here it runs the same code over the local
devices (``--mesh local``: every chip of the host) or a forced-device tiny
mesh. Platform knobs (REPRO_PLATFORM / REPRO_X64 / REPRO_HOST_DEVICES) and
the persistent compilation cache come from ``repro.runtime``.

  PYTHONPATH=src python -m repro.launch.train --arch tacc-100m --smoke \
      --steps 100 --global-batch 8 --seq-len 64 --ckpt-dir /tmp/run1

:func:`train` is the whole loop; ``main`` and ``chip_smoke.py`` both call it.
"""
import argparse
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax

from repro import compat, runtime
from repro.compat import NamedSharding, P
from repro.ckpt import Checkpointer, latest_step
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.data import SyntheticLM
from repro.models.transformer import RunFlags
from repro.train import (OptConfig, TrainConfig, build_train_step,
                         init_train_state)
from repro.train.step import state_shardings


@dataclass
class TrainResult:
    """What one :func:`train` call ran, and the compiled step to run more."""
    state: Any                       # final state, placed on the mesh
    step_fn: Callable                # compiled: (state, batch) -> (state, m)
    batch: Callable[[int], Any]      # step -> batch placed on the mesh
    state_shardings: Any
    compile_s: float
    first_step_s: float              # first call, block_until_ready
    steady_step_s: Optional[float]   # mean of the later steps
    tokens_per_step: int
    metrics: List[Dict[str, float]] = field(default_factory=list)


def train(cfg: ModelConfig, mesh, *, steps: int, global_batch: int,
          seq_len: int, lr: float = 1e-3, seed: int = 0,
          microbatches: int = 1, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 50, log_every: int = 10,
          log: Callable[[str], None] = print) -> TrainResult:
    """Train ``cfg`` on synthetic data over ``mesh`` up to step ``steps``.

    Resumes from the latest checkpoint in ``ckpt_dir`` when there is one,
    saves every ``ckpt_every`` steps and at the end. The step is compiled
    ahead of the loop, so ``compile_s`` is compilation alone; step times
    end in ``block_until_ready``."""
    ocfg = OptConfig(lr=lr, warmup_steps=max(steps // 10, 1),
                     total_steps=steps)
    tcfg = TrainConfig(n_microbatches=microbatches)
    b_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    flags = RunFlags(distributed=True, token_axes=b_axes)
    st_sh = state_shardings(cfg, mesh)
    b_sh = NamedSharding(mesh, P(b_axes))      # every batch leaf: rows split
    step_fn = jax.jit(build_train_step(cfg, ocfg, tcfg, flags),
                      in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None),
                      donate_argnums=0)
    data = SyntheticLM(cfg, global_batch, seq_len, seed=seed,
                       host_id=jax.process_index(),
                       n_hosts=jax.process_count())
    ck = Checkpointer(ckpt_dir, keep=3) if ckpt_dir else None

    def batch(i: int):
        return {k: jax.make_array_from_process_local_data(b_sh, v)
                for k, v in data.batch(i).items()}

    with compat.set_mesh(mesh):
        start = 0
        if ck and latest_step(ckpt_dir) is not None:
            state, man = ck.restore(shardings=st_sh)
            start = man["step"]
            log(f"restored step {start}")
        else:
            state = init_train_state(cfg, ocfg, jax.random.PRNGKey(seed))
            state = jax.device_put(state, st_sh)
        t0 = time.perf_counter()
        compiled = step_fn.lower(state, batch(start)).compile()
        compile_s = time.perf_counter() - t0

        tok = global_batch * seq_len
        metrics: List[Dict[str, float]] = []
        first_s, t_steady = 0.0, time.perf_counter()
        last_log, t_log = start, t_steady
        for i in range(start, steps):
            state, m = compiled(state, batch(i))
            metrics.append(m)
            if i == start:
                jax.block_until_ready(state)
                t_steady = t_log = time.perf_counter()
                first_s = t_steady - t0 - compile_s
            elif (i + 1) % log_every == 0 or i + 1 == steps:
                loss = float(m["loss"])          # waits for step i
                dt = time.perf_counter() - t_log
                log(f"step {int(m['step']):5d} loss {loss:.4f} "
                    f"gnorm {float(m['grad_norm']):.3f} "
                    f"lr {float(m['lr']):.2e} "
                    f"tok/s {(i - last_log) * tok / dt:,.0f}")
                last_log, t_log = i, time.perf_counter()
            if ck and (i + 1) % ckpt_every == 0 and i + 1 < steps:
                ck.save(i + 1, state)
        jax.block_until_ready(state)
        steady = None
        if steps - start > 1:
            steady = (time.perf_counter() - t_steady) / (steps - start - 1)
        if ck:
            ck.save(steps, state, block=True)
    metrics = [{k: float(v) for k, v in m.items()}
               for m in jax.device_get(metrics)]
    return TrainResult(state, compiled, batch, st_sh, compile_s, first_s,
                       steady, tok, metrics)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tacc-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["local", "tiny", "pod", "multipod"],
                    default="local")
    args = ap.parse_args()
    # before the first jax computation: device count / platform lock in at
    # backend init, and the compile cache at the first compilation
    runtime.init_from_env()

    from repro.launch.mesh import (make_local_mesh, make_production_mesh,
                                   make_tiny_mesh)
    mesh = {"local": make_local_mesh, "tiny": make_tiny_mesh,
            "pod": make_production_mesh,
            "multipod": lambda: make_production_mesh(multi_pod=True)}[
        args.mesh]()
    r = train(get_config(args.arch, smoke=args.smoke), mesh,
              steps=args.steps, global_batch=args.global_batch,
              seq_len=args.seq_len, lr=args.lr, seed=args.seed,
              microbatches=args.microbatches, ckpt_dir=args.ckpt_dir,
              ckpt_every=args.ckpt_every)
    print(f"compile {r.compile_s:.1f}s, first step {r.first_step_s:.3f}s")
    print("done")


if __name__ == "__main__":
    main()
