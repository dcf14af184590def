"""Share of the two-hop sampler's roofline: the least time its bytes
need at the chip's peak HBM bandwidth (``counts.sampler_bytes``: each
target's fanout entries read once and written once, plus the targets)
over the device time of ``jit__khop_impl``. Bandwidth bounds it: the
sampler does no matmul."""
from bench import counts, readers


def read(ctx):
    ms, runs = readers.program_device_ms(ctx, ["jit__khop_impl"])
    if ms is None or not ctx.peaks:
        return None
    m = ctx.cfg["model"]
    least_s = runs * counts.sampler_bytes(m, 3 * m["batch_size"]) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
