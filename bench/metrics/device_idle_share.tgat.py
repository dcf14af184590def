"""Share of the TGAT window in which no operation ran on the device (1
minus the union of op intervals), averaged over the chips used."""
from bench import tracing


def read(ctx):
    if ctx.trace is None or not ctx.used_devices():
        return None
    lo, hi = ctx.window_trace_ns()
    busy = [tracing.busy_ns(d, lo, hi) for d in ctx.used_devices()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
