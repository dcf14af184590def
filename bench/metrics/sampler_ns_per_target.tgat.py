"""Device time of the fused sampler (``jit__khop_impl``) per live target,
summed over hops: the ``targets`` args of the program's ``sampler.hops``
spans (one per sampled batch, one count per hop). The search is
O(k log P) a target, so this stays flat as histories deepen; None where
the program records no ``sampler.hops`` span."""
from bench import readers


def read(ctx):
    targets = sum(sum(a.get("targets", ()))
                  for *_, a in ctx.span_list("sampler.hops"))
    ms, _ = readers.program_device_ms(ctx, ["jit__khop_impl"])
    if ms is None or targets == 0:
        return None
    return ms * 1e6 / targets
