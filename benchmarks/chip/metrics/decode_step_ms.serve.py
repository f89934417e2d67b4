"""Mean host time of ServeEngine.step (harness span), in ms."""
import harness


def read(rec, ctx):
    s = harness.mean_span(ctx, "bench.step")
    return None if s is None else 1e3 * s
