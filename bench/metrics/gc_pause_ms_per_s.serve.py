"""Garbage-collector pauses of generation 1 or 2 (``gc`` spans, on the
serving worker, the ingest thread and the query generator) per second of
the serving window."""
from bench import child_spans


def read(ctx):
    return child_spans.gc_pause_ms_per_s(ctx)
