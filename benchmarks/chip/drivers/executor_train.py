"""executor_train: a ``jax_train`` job driven as SING drives it.

The job is compiled by ``TaskCompiler`` from a ``TaskSpec``, provisioned
by ``LocalExecutor.provision`` and advanced by ``LocalExecutor.tick``,
which calls ``JaxTrainRuntime.run_quantum``: the runtime builds each batch
on the host and runs the jitted train step. The benchmark hands the
provisioned runtime its own initial state (weights from the seed, zero
moments) so that the reference starts from weights the program never made.

Set-up provisions the job and runs its first ``check_steps`` steps one
quantum of one step at a time: that compiles the step, and the readings
the check compares are taken from them. The window then ticks the same
runtime a quantum of ``quantum_steps`` at a time and ends after the first
whole cycle that reaches ``--seconds``; a cycle is one tick here, and
``executor_preempt`` adds a preemption to it. ``train_tokens_per_s``
counts every token of every step in the window over the window.
"""
from __future__ import annotations

import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import harness
import reference
import traffic
from weights import make_params_fn


def make_job(ctx: harness.Context):
    from repro.core.compiler import ArtifactStore, TaskCompiler
    from repro.core.executor import LocalExecutor
    from repro.core.scheduler import Job, JobState
    from repro.core.schema import ResourceSpec, RuntimeEnv, TaskSpec
    mix = ctx.cell.traffic
    spec = TaskSpec(
        name=ctx.cell.name, tenant="bench",
        resources=ResourceSpec(chips=ctx.cell.chips),
        runtime=RuntimeEnv(
            backend="jax_train",
            checkpoint_interval_steps=mix["checkpoint_interval_steps"]),
        entry={"arch": ctx.cell.config["arch"], "smoke": ctx.smoke,
               "global_batch": mix["global_batch"],
               "seq_len": mix["seq_len"], "lr": mix["lr"],
               "warmup": mix["warmup_steps"], "seed": ctx.seeds["data"]},
        total_steps=mix["total_steps"])
    store = ArtifactStore(os.path.join(ctx.workdir, "cas"))
    plan = TaskCompiler(store, os.path.join(ctx.workdir, "work")
                        ).compile(spec)
    job = Job(id="bench-job", plan=plan, submit_time=time.time())
    job.state, job.chips = JobState.RUNNING, ctx.cell.chips
    return LocalExecutor(store, quantum_steps=1), job


def job_settings(ctx: harness.Context, rt) -> Tuple[Dict, Dict]:
    """The optimizer and the data stream the provisioned job runs with, as
    plain numbers for the reference. The mix sets the learning rate, the
    warm-up and the length of the schedule; every other setting is the
    program's own: ``OptConfig`` as ``JaxTrainRuntime`` builds it from the
    job's entry, the loss's z-loss weight from ``TrainConfig``, and the walk
    of the runtime's ``SyntheticLM``."""
    from repro.train import OptConfig, TrainConfig
    mix = ctx.cell.traffic
    ocfg = OptConfig(lr=mix["lr"], warmup_steps=mix["warmup_steps"],
                     total_steps=mix["total_steps"])
    opt = {k: getattr(ocfg, k) for k in (
        "lr", "warmup_steps", "total_steps", "min_lr_ratio", "b1", "b2",
        "eps", "weight_decay", "clip_norm")}
    opt["z_loss"] = TrainConfig().z_loss
    data = {"a": rt.data.a, "b": rt.data.b, "noise": rt.data.noise,
            "seed": rt.data.seed}
    return opt, data


def tick(ex, job, spans) -> Dict[str, float]:
    """One quantum through the executor; its fail-safe requeue would hide
    an error, so a job that is no longer running fails the run."""
    from repro.core.scheduler import JobState
    with spans("bench.tick"):
        out = ex.tick([job])[job.id]
    if "error" in out or job.state != JobState.RUNNING:
        raise RuntimeError(f"job left RUNNING ({job.state}): {out}; log:\n"
                           + "".join(ex.logs(job, tail=20)))
    return out


def _same_layout(a, b) -> bool:
    return (jax.tree.structure(a) == jax.tree.structure(b)
            and all(x.shape == y.shape and x.dtype == y.dtype
                    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))))


def seed_state(rt, params) -> None:
    """Replace the runtime's own initial state by the benchmark's."""
    state = jax.jit(lambda p: {
        "params": p,
        "opt": {"m": jax.tree.map(jnp.zeros_like, p),
                "v": jax.tree.map(jnp.zeros_like, p),
                "step": jnp.zeros((), jnp.int32)}})(params)
    if not _same_layout(state, rt.state):
        raise ValueError("benchmark weights do not have the program's "
                         "train-state layout")
    rt.state = state


def leaf_norms_fn():
    return jax.jit(lambda t: jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)]))


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers the check compares; every one is a relative gap."""
    counted = harness.counted_leaves(ref["grad_leaf"])
    loss_p, loss_r = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    return {
        "loss_gap": float(np.max(np.abs(loss_p - loss_r) / np.abs(loss_r))),
        "grad_norm_gap": abs(prog["grad_norm"] - ref["grad_norm"])
        / ref["grad_norm"],
        "grad_leaf_gap": harness.worst_leaf_gap(prog["m1_leaf"],
                                                ref["m1_leaf"], counted),
        "update_leaf_gap": harness.worst_leaf_gap(prog["delta_leaf"],
                                                  ref["delta_leaf"], counted),
    }


def batches(ctx: harness.Context, data: Dict, n: int,
            rows: Optional[int] = None) -> List:
    mix = ctx.cell.traffic
    out = [traffic.walk_batch(data, step, mix["global_batch"],
                              mix["seq_len"], ctx.sizes["vocab_size"])
           for step in range(n)]
    return [(t[:rows], l[:rows]) for t, l in out] if rows else out


def check(ctx: harness.Context, prog: Dict, make, key,
          settings: Tuple[Dict, Dict]) -> Dict[str, float]:
    mix = ctx.cell.traffic
    opt, data = settings
    n, rows = mix["check_steps"], mix["check_rows_per_block"]
    ref = reference.train(ctx.sizes, opt, make(key), batches(ctx, data, n),
                          rows_per_block=rows)
    checks = compare(prog, ref)
    counted = harness.counted_leaves(ref["grad_leaf"])
    harness.log(f"reference losses {ref['loss']}, grad norm "
                f"{ref['grad_norm']!r}; program {prog['loss']}, "
                f"{prog['grad_norm']!r}; {int(counted.sum())} of "
                f"{counted.size} leaves counted")
    if ctx.control:
        low = reference.train(ctx.sizes, opt, make(key),
                              batches(ctx, data, n), prec="fp8",
                              rows_per_block=rows)
        half = reference.train(ctx.sizes, opt, make(key),
                               batches(ctx, data, n, mix["global_batch"] // 2),
                               rows_per_block=rows)
        checks.update({f"control.{k}": v
                       for k, v in compare(low, ref).items()})
        checks.update({f"half_batch.{k}": v
                       for k, v in compare(half, ref).items()})
    return checks


def run(ctx: harness.Context, devices,
        preempt: Optional[Callable] = None) -> harness.RunRecord:
    mix, sp = ctx.cell.traffic, ctx.spans
    make = make_params_fn(ctx.sizes, ctx.cell.config["weights_dtype"])
    key = jax.random.PRNGKey(ctx.seeds["weights"])
    ex, job = make_job(ctx)
    ex.provision(job)
    rt = ex.runtimes[job.id]
    settings = job_settings(ctx, rt)
    seed_state(rt, make(key))
    norms = leaf_norms_fn()
    prog: Dict = {"loss": []}
    for i in range(mix["check_steps"]):
        m = tick(ex, job, sp)
        prog["loss"].append(m["loss"])
        if i == 0:
            prog["grad_norm"] = m["grad_norm"]
            prog["m1_leaf"] = np.asarray(norms(rt.state["opt"]["m"]))
    delta = jax.jit(lambda p, k: jax.tree.map(jnp.subtract, p, make(k)))
    prog["delta_leaf"] = np.asarray(norms(delta(rt.state["params"], key)))
    harness.log(f"first {mix['check_steps']} steps: losses {prog['loss']}")
    ex.quantum = mix["quantum_steps"]
    if preempt is not None:
        preempt(ex, job, ctx, warm=True)
    per_cycle = max(1, mix["preempt_every_steps"] // mix["quantum_steps"])
    rec = harness.RunRecord()
    steps = cycles = 0
    with ctx.window(rec):
        t0 = time.perf_counter()
        while True:
            if preempt is not None:
                t0 += ctx.trace_poll(time.perf_counter() - t0)
                preempt(ex, job, ctx, rec=rec)
            for _ in range(per_cycle if preempt else 1):
                t0 += ctx.trace_poll(time.perf_counter() - t0)
                start = job.progress
                tick(ex, job, sp)
                steps += int(job.progress - start)
                ctx.count(rec, traced_steps=int(job.progress - start))
            cycles += 1
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        rec.window_s = time.perf_counter() - t0
    tokens = steps * mix["global_batch"] * mix["seq_len"]
    rec.counters.update(steps=steps, tokens=tokens, cycles=cycles)
    rec.attempted = steps
    harness.log(f"{steps} steps in {cycles} cycles, {tokens} tokens in "
                f"{rec.window_s:.3f} s: {tokens / rec.window_s:.1f} tokens/s")
    rec.memory_peak_bytes = harness.memory_peak(devices)
    ex.deprovision(job.id)
    del rt
    rec.checks.update(check(ctx, prog, make, key, settings))
    return rec
