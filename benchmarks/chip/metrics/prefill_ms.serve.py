"""Mean host time of ServeEngine.add_request per admission (harness span), in
ms."""
import harness


def read(rec, ctx):
    s = harness.mean_span(ctx, "bench.add_request")
    return None if s is None else 1e3 * s
