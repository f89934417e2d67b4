"""End-to-end driver: train a ~150M-parameter model THROUGH the full TACC
stack — schema -> compiler (CAS) -> scheduler (priority policy) ->
execution layer (real JAX training) — with a node failure injected after
the first checkpoint (checkpoint restart) and a high-priority serving task
that preempts the training job (checkpoint-then-preempt).

  PYTHONPATH=src python examples/train_cluster.py            # full width
  PYTHONPATH=src python examples/train_cluster.py --smoke    # tiny, fast

:func:`run_cluster` and :func:`check_outcome` are the flow and its check;
``main`` and ``chip_smoke.py`` both call them.
"""
import argparse
import tempfile
import time
from typing import Any, Callable, Dict, Tuple

from repro.core import (JobState, ResourceSpec, RuntimeEnv, TACC, TaskSpec)


def run_cluster(root: str, *, train_entry: Dict[str, Any], steps: int,
                ckpt_every: int, fail_at: int, urgent_at: int,
                urgent_entry: Dict[str, Any],
                log: Callable[[str], None] = print
                ) -> Tuple[TACC, str, str, int]:
    """Submit a training job to a TACC service in ``root``, fail its node
    once at step ``fail_at``, submit an urgent ``jax_serve`` job once it
    reaches ``urgent_at``, and tick until every job has ended.

    Returns (service, training job id, urgent job id, failures injected)."""
    injected = [0]

    def fail_injector(job, step):
        if job.spec.name == "train-main" and not injected[0] \
                and step >= fail_at:
            injected[0] += 1
            log(f"  !! injecting node failure for {job.id} at step {step}")
            return True
        return False

    svc = TACC(root, policy="priority", quantum_steps=10,
               fail_injector=fail_injector)
    train = TaskSpec(
        name="train-main", tenant="lab-a",
        resources=ResourceSpec(chips=8, priority=0),
        runtime=RuntimeEnv(backend="jax_train",
                           checkpoint_interval_steps=ckpt_every),
        entry=dict(train_entry), total_steps=steps,
        estimated_duration_s=1200)
    jid = svc.submit(train)
    log(f"submitted {train.name} -> {jid} (spec hash {train.spec_hash()})")

    uid = None
    while True:
        svc.tick()
        if uid is None and svc.jobs[jid].progress >= urgent_at:
            urgent = TaskSpec(
                name="urgent-eval", tenant="lab-b",
                resources=ResourceSpec(chips=8, priority=10, qos="realtime"),
                runtime=RuntimeEnv(backend="jax_serve"),
                entry=dict(urgent_entry), total_steps=4,
                estimated_duration_s=30)
            uid = svc.submit(urgent)
            log(f"  submitted high-priority {urgent.name} -> {uid}")
        if all(j.state in (JobState.COMPLETED, JobState.FAILED,
                           JobState.KILLED) for j in svc.jobs.values()):
            return svc, jid, uid, injected[0]


def check_outcome(svc: TACC, jid: str, uid: str, injected: int) -> None:
    """Raise unless the run went as planned. The executor catches runtime
    errors and requeues the job (fail-safe switching), so its exit code
    says nothing: read the outcome from the jobs and their logs."""
    job, urgent = svc.jobs[jid], svc.jobs[uid] if uid else None
    problems = []
    if injected != 1:
        problems.append(f"{injected} failures injected, expected 1")
    if job.state != JobState.COMPLETED or int(job.progress) != job.total_steps:
        problems.append(f"training ended {job.state.value} at "
                        f"{int(job.progress)}/{job.total_steps}")
    if job.restarts != injected:        # any extra restart was a real error
        problems.append(f"training restarted {job.restarts} times for "
                        f"{injected} injected failure(s)")
    if not any("restored checkpoint @ step" in line
               for line in svc.logs(jid, tail=10**6)):
        problems.append("training never resumed from a checkpoint")
    if urgent is None or urgent.state != JobState.COMPLETED:
        problems.append("urgent serving job did not complete")
    if job.preemptions < 1:
        problems.append("urgent job did not preempt training")
    failed = [j.id for j in svc.jobs.values() if j.state == JobState.FAILED]
    if failed:
        problems.append(f"jobs FAILED: {failed}")
    if problems:
        raise RuntimeError("cluster run: " + "; ".join(problems))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model (CI); default is the full tacc-100m")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()
    smoke = args.smoke
    steps = args.steps or (60 if smoke else 300)

    with tempfile.TemporaryDirectory() as td:
        t0 = time.time()
        svc, jid, uid, injected = run_cluster(
            td, steps=steps, ckpt_every=25, fail_at=max(steps // 3, 26),
            urgent_at=steps // 2,
            train_entry={"arch": "tacc-100m", "smoke": smoke,
                         "global_batch": 8 if smoke else 16,
                         "seq_len": 64 if smoke else 128, "lr": 3e-4},
            urgent_entry={"arch": "tacc-100m", "smoke": True,
                          "max_batch": 2, "max_new": 4})
        print(f"\nfinished in {time.time()-t0:.0f}s wall")
        for row in svc.status():
            print(" ", row)
        check_outcome(svc, jid, uid, injected)
        job = svc.jobs[jid]
        print(f"\ntraining survived {job.restarts} restart(s) and "
              f"{job.preemptions} preemption(s); last lines of its log:")
        for line in svc.logs(jid, tail=8):
            print("   ", line.rstrip())


if __name__ == "__main__":
    main()
