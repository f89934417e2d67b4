"""90th percentile, over every request due in the window, of the time from
when it was due to its first token. A request that never produced one
counts at the drain limit."""
import harness


def read(rec, ctx):
    if not rec.requests:
        return None
    return harness.percentile(_ttft(rec), 90)


def _ttft(rec):
    return [r["times"][0] - r["due"] if r["times"] else
            rec.counters["served_s"] - r["due"] for r in rec.requests]
