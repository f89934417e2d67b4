"""Model FLOP/s utilization of the training job: 6 N_active + 3 unembedding
per token (peaks.py) times the window's tokens/s, over chips times the bf16
peak."""
import peaks


def read(rec, ctx):
    if "tokens" not in rec.counters:
        return None
    rate = rec.counters["tokens"] / rec.window_s
    peak = ctx.peaks["bf16_flops"] * ctx.cell.chips
    return 100.0 * rate * peaks.train_flops_per_token(ctx.sizes) / peak
