"""Mean host time of LocalExecutor.checkpoint per save (harness span)."""
import harness


def read(rec, ctx):
    return harness.mean_span(ctx, "bench.checkpoint")
