"""Device time of the fused two-hop sampler (``jit__khop_impl``: the
time-indexed page search and the uniform draws) per batch, from the
profiler trace."""
from bench import readers


def read(ctx):
    ms, runs = readers.program_device_ms(ctx, ["jit__khop_impl"])
    return None if ms is None else ms / runs
