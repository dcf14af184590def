"""jit wrapper for the selective_scan kernel (pads L; slices back)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.selective_scan.selective_scan import (
    selective_scan_kernel)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "dtile"))
def selective_scan_pallas(dt, x, A, Bt, Ct, h0, *, chunk: int = 16,
                          dtile: int = 128):
    B, L, Din = x.shape
    pad = (-L) % chunk
    if pad:
        z3 = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        dt, x, Bt, Ct = z3(dt), z3(x), z3(Bt), z3(Ct)
    dtile = min(dtile, Din)
    while Din % dtile:
        dtile //= 2
    y, h_last = selective_scan_kernel(
        dt.astype(jnp.float32), x.astype(jnp.float32),
        A.astype(jnp.float32), Bt.astype(jnp.float32),
        Ct.astype(jnp.float32), h0.astype(jnp.float32),
        chunk=chunk, dtile=dtile)
    return y[:, :L], h_last
