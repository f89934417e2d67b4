"""The Mamba-2 layers' share of the HBM roofline in decode: their weights
read by each traced decode step plus each occupied row's conv window and SSM
state read and written (``counts_hybrid``, in ``traced_ssm_decode_bytes``),
over the device time of the Mamba-2 layers' ops in the decode steps
(``ssm_decode_device_s``, picked by their shapes and folded from the trace
by the driver), over the peak bandwidth."""


def read(rec, ctx):
    nbytes = rec.counters.get("traced_ssm_decode_bytes")
    t = rec.counters.get("ssm_decode_device_s")
    if rec.trace is None or not nbytes or not t:
        return None
    return 100.0 * nbytes / t / ctx.peaks["hbm_bw"]
