"""Every token of every training step completed in the window, over the window
(preemption stalls included)."""


def read(rec, ctx):
    if "tokens" not in rec.counters:
        return None
    return rec.counters["tokens"] / rec.window_s
