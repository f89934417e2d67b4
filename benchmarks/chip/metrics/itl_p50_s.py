"""Median of every gap between consecutive tokens of one request, over all
requests due in the window: the decode step as a user sees it, since most
gaps hold one step and nothing else."""
import harness


def read(rec, ctx):
    gaps = [b - a for r in rec.requests
            for a, b in zip(r["times"], r["times"][1:])]
    return harness.percentile(gaps, 50)
