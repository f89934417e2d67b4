"""Share of the traced part of the window in which no operation ran on the
device (serving cells)."""
import harness


def read(rec, ctx):
    return harness.idle_pct(rec)
