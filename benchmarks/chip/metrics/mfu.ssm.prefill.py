"""The Mamba-2 layers' share of the bf16 peak in prefill: their projections,
conv and chunked SSD per real prompt token (``counts_hybrid``, in
``traced_ssm_prefill_flops``; the padding not counted), over the device time
of the Mamba-2 layers' ops in the admissions (``ssm_prefill_device_s``,
picked by their shapes and folded from the trace by the driver)."""


def read(rec, ctx):
    flops = rec.counters.get("traced_ssm_prefill_flops")
    t = rec.counters.get("ssm_prefill_device_s")
    if rec.trace is None or not flops or not t:
        return None
    return 100.0 * flops / t / ctx.peaks["bf16_flops"]
