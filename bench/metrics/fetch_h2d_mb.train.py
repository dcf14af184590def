"""Host-to-device bytes uploaded inside the ``fetch`` stage, per batch
(train steps and eval batches): the ``bytes`` of the ``xfer.h2d`` spans
whose parent is ``fetch`` (cache ids and miss buckets, the assembled
hops, the TGN memory blobs), in MB of 10^6 bytes."""
from bench import child_spans, readers


def read(ctx):
    spans = child_spans.children(ctx, "xfer.h2d", ("fetch",))
    n = readers.batches(ctx)
    if not spans or n == 0:
        return None
    return sum(a.get("bytes", 0) for *_, a in spans) / 1e6 / n
