"""executor_preempt: ``executor_train``'s job, preempted and re-provisioned
at once every ``preempt_every_steps`` steps counted from the window's start.

Each preemption runs in the order ``core/service.py`` handles a
``Preempt`` action: ``LocalExecutor.checkpoint`` (a blocking save of the
whole train state), ``LocalExecutor.deprovision``; the job is then
re-provisioned at once with ``LocalExecutor.provision``, which restores the
checkpoint and builds a new jitted step (traced again and loaded from the
compile cache at its first call, in the next tick). A cycle is that
preemption and the ``preempt_every_steps`` steps after it.

Besides the train check, every round trip in the window is compared bit
for bit: the restored state must equal the live state that was saved, and
the restored job must resume at the saved step, so that it continues as
the live one would.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

import harness

_train = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "executor_train.py"), "driver_executor_train")


def fingerprint_fn():
    """Two exact checksums of every leaf's bits (wrapping uint32 sums)."""
    def one(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32).ravel()
        idx = jnp.arange(bits.size, dtype=jnp.uint32)
        return jnp.stack([jnp.sum(bits, dtype=jnp.uint32),
                          jnp.sum(bits * (idx + 1), dtype=jnp.uint32)])
    return jax.jit(lambda t: jnp.stack([one(x) for x in jax.tree.leaves(t)]))


class Preempt:
    def __init__(self):
        self.fp = fingerprint_fn()
        self.trips = 0
        self.mismatches = 0

    def __call__(self, ex, job, ctx, warm=False, rec=None):
        sp = ctx.spans
        rt = ex.runtimes[job.id]
        if warm:
            np.asarray(self.fp(rt.state))
            return
        with sp("bench.fingerprint"):
            before, step = np.asarray(self.fp(rt.state)), rt.progress()
        with sp("bench.checkpoint"):
            ex.checkpoint(job.id)
        with sp("bench.deprovision"):
            ex.deprovision(job.id)
        with sp("bench.provision"):
            ex.provision(job)
        rt = ex.runtimes[job.id]
        with sp("bench.fingerprint"):
            after = np.asarray(self.fp(rt.state))
        self.trips += 1
        self.mismatches += int(not np.array_equal(before, after)
                               or rt.progress() != step)
        rec.counters["round_trips"] = self.trips
        rec.checks["restore_mismatch"] = self.mismatches


def run(ctx: harness.Context, devices) -> harness.RunRecord:
    return _train.run(ctx, devices, preempt=Preempt())
