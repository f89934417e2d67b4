"""90th percentile of every gap between consecutive tokens of one request,
over all requests due in the window: the stalls a stream shows, such as
another request's prefill landing between two of its tokens."""
import harness


def read(rec, ctx):
    gaps = [b - a for r in rec.requests
            for a, b in zip(r["times"], r["times"][1:])]
    return harness.percentile(gaps, 90)
