"""The program's spans by the span that caused them.

The program records on each span, as ``parent``, the kind of the
innermost span open on the same thread when it began; the readers of
``metrics/`` use it to count only the transfers, row reads and waits that
lie inside a given stage. A program that records no ``parent`` anywhere
predates these spans: the readers then find nothing to read and return
None.
"""
from __future__ import annotations

# every span the serving engine opens inside ``serve.batch``, itself
# included (the transfers sit directly under one of these)
SERVE_BATCH = ("serve.batch", "serve.sample", "serve.fetch",
               "serve.forward")


def children(ctx, kind, parents):
    """(start_s, end_s, args) of the window's ``kind`` spans whose parent
    is one of ``parents``."""
    return [(s, e, a) for _, s, e, a in ctx.span_list(kind)
            if a.get("parent") in parents]


def ms(spans):
    return sum(e - s for s, e, _ in spans) * 1e3


def records_parents(ctx) -> bool:
    """Whether the program that ran records ``parent`` on its spans."""
    return any("parent" in a for *_, a in ctx.span_list())


def gc_pause_ms_per_s(ctx):
    """Time in collections of generation 1 or 2 (``gc`` spans, every
    thread) per second of the window; 0 where the program records the
    collections and none ran."""
    spans = ctx.span_list("gc")
    if not spans and not records_parents(ctx):
        return None
    window_s = ctx.win[1] - ctx.win[0]
    return sum(e - s for _, s, e, _ in spans) * 1e3 / window_s
