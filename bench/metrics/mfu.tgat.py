"""Model FLOP utilization of the TGAT window: the matmul FLOPs its eval
batches (forward) and train steps (forward + backward) need at the
cell's shapes (``counts``), per second of the window, over chips times
the chip's bf16 peak."""
from bench import readers


def read(ctx):
    if not ctx.peaks or not ctx.window_stats.get("steps"):
        return None
    window_s = ctx.win[1] - ctx.win[0]
    return 100.0 * readers.train_flops(ctx) / window_s \
        / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
