"""Garbage-collector pauses of generation 1 or 2 (``gc`` spans) per
second of the training window."""
from bench import child_spans


def read(ctx):
    return child_spans.gc_pause_ms_per_s(ctx)
