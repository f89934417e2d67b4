"""Decode's share of the bf16 peak: 2 N_active + unembedding per occupied row
(peaks.py), over the device time of the decode steps in the traced part of
the window."""
import harness


def read(rec, ctx):
    t = harness.device_s(rec, "bench.step")
    if t is None:
        return None
    flops = rec.counters["traced_decode_flops"]
    return 100.0 * flops / t / ctx.peaks["bf16_flops"]
