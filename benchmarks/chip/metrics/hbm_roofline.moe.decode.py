"""The routed experts' share of the HBM roofline in decode: the weights of
the held experts that got a token in each traced decode step (the engine's
``moe_experts_touched``, in ``traced_moe_decode_bytes``), over the device
time of the grouped expert matmuls over the decode program's rows
(max_batch x experts per token), over the peak bandwidth."""
import counts_mla_moe


def read(rec, ctx):
    nbytes = rec.counters.get("traced_moe_decode_bytes")
    moe = ctx.sizes.get("moe")
    if rec.trace is None or not nbytes or moe is None:
        return None
    rows = ctx.cell.traffic["max_batch"] * moe["top_k"]
    t = counts_mla_moe.grouped_expert_s(rec.trace["op_s"], rows)
    return 100.0 * nbytes / t / ctx.peaks["hbm_bw"] if t else None
