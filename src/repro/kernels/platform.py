"""Where a Pallas kernel runs: compiled by Mosaic, or interpreted.

``pallas_call`` builds both forms of one kernel and lets the platform
the surrounding computation is lowered for choose between them: the
interpreter where the inputs live on the CPU backend (the tests), the
Mosaic compiler everywhere else. There is no user switch and no silent
fallback: a kernel the TPU compiler refuses raises its error there.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kw):
    """``pl.pallas_call(kernel, **kw)``, interpreted only on the CPU."""
    compiled = pl.pallas_call(kernel, **kw)
    interpreted = pl.pallas_call(kernel, interpret=True, **kw)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          default=compiled)
    return call
