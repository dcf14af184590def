"""Blocking device-to-host reads inside the serving engine's batches,
per batch: the ``xfer.d2h`` spans under ``serve.batch`` (cache
invalidation's state reads, the sampled layers, the cache's hit masks
and outputs, the forward's scores), each the wait for the device and the
copy."""
from bench import child_spans


def read(ctx):
    spans = child_spans.children(ctx, "xfer.d2h", child_spans.SERVE_BATCH)
    batches = len(ctx.span_list("serve.batch"))
    return None if not spans or batches == 0 \
        else child_spans.ms(spans) / batches
