"""Prefill's share of the bf16 peak: 2 N_active per real prompt token plus one
unembedding row (peaks.py; the padding not counted), over the device time
of the admissions."""
import harness


def read(rec, ctx):
    t = harness.device_s(rec, "bench.add_request")
    if t is None:
        return None
    flops = rec.counters["traced_prefill_flops"]
    return 100.0 * flops / t / ctx.peaks["bf16_flops"]
