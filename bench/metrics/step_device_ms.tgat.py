"""Device time of the jitted TGAT train step (``jit_train_step``: two
attention layers over 1,800 seeds, 18,000 hop-1 and 180,000 hop-2
neighbours, backward and AdamW) per step, from the profiler trace."""
from bench import readers


def read(ctx):
    ms, runs = readers.program_device_ms(ctx, ["jit_train_step"])
    return None if ms is None else ms / runs
