"""Blocking device-to-host reads inside the ``fetch`` stage, per batch
(train steps and eval batches): the ``xfer.d2h`` spans whose parent is
``fetch`` (the sampled layers, the cache's hit masks and outputs), each
the wait for the device and the copy."""
from bench import child_spans, readers


def read(ctx):
    spans = child_spans.children(ctx, "xfer.d2h", ("fetch",))
    n = readers.batches(ctx)
    return None if not spans or n == 0 else child_spans.ms(spans) / n
