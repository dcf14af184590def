"""The TGN memory commit after each train step (``memory.commit`` spans:
gather the pending nodes' rows, the GRU update, its read back, the
write), per train step."""
from bench import readers


def read(ctx):
    ms, _ = readers.span_ms(ctx, "memory.commit")
    steps = ctx.window_stats.get("steps", 0)
    return None if ms is None or steps == 0 else ms / steps
