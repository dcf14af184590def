"""Mean time a query waited in the admission queue, from its submission
until its batch left the queue: the ``queue_wait_ms_sum`` of the
``serve.batch`` spans over their ``queries``."""


def read(ctx):
    spans = [a for *_, a in ctx.span_list("serve.batch")
             if "queue_wait_ms_sum" in a]
    queries = sum(a.get("queries", 0) for a in spans)
    if queries == 0:
        return None
    return sum(a["queue_wait_ms_sum"] for a in spans) / queries
