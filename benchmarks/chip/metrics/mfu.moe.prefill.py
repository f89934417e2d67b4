"""The routed experts' share of the bf16 peak in prefill: 6 d_model
d_ff_expert FLOPs per real prompt token's assignment to a held expert (the
engine's ``moe_prefill_assignments_here``, in ``traced_moe_prefill_flops``),
over the device time of the grouped expert matmuls over the prefill
program's rows (max_seq x experts per token)."""
import counts_mla_moe


def read(rec, ctx):
    flops = rec.counters.get("traced_moe_prefill_flops")
    moe = ctx.sizes.get("moe")
    if rec.trace is None or not flops or moe is None:
        return None
    rows = ctx.cell.traffic["max_seq"] * moe["top_k"]
    t = counts_mla_moe.grouped_expert_s(rec.trace["op_s"], rows)
    return 100.0 * flops / t / ctx.peaks["bf16_flops"] if t else None
