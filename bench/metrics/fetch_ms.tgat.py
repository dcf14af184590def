"""Feature fetch per batch of the TGAT cell: the program's ``fetch``
stage spans (cache-fronted node and edge rows of both hops, about
200,000 node and 198,000 edge rows a batch) in the window."""
from bench import readers


def read(ctx):
    ms, _ = readers.span_ms(ctx, "fetch")
    n = readers.batches(ctx)
    return None if ms is None or n == 0 else ms / n
