"""Host row reads inside the ``fetch`` stage, per batch (train steps and
eval batches): the ``state.gather`` spans whose parent is ``fetch`` (the
cache misses' feature rows, the TGN memory and message-edge rows)."""
from bench import child_spans, readers


def read(ctx):
    spans = child_spans.children(ctx, "state.gather", ("fetch",))
    n = readers.batches(ctx)
    return None if not spans or n == 0 else child_spans.ms(spans) / n
