"""Decode's share of the HBM roofline: the weights in their serving dtype plus
the keys and values of each occupied row up to its length (peaks.py), over
the device time of the decode steps, over the peak bandwidth."""
import harness


def read(rec, ctx):
    t = harness.device_s(rec, "bench.step")
    if t is None:
        return None
    nbytes = rec.counters["traced_decode_bytes"]
    return 100.0 * nbytes / t / ctx.peaks["hbm_bw"]
