"""Mean host time of LocalExecutor.provision per re-provision: restore and a
new jitted step (harness span)."""
import harness


def read(rec, ctx):
    return harness.mean_span(ctx, "bench.provision")
