"""Pallas TPU kernel: mamba-1 selective scan (§Perf hillclimb C5).

The pure-JAX paths must round-trip the (Din, N)-wide state through HBM at
some granularity (measured on falcon-mamba-7b train_4k: 92 s memory term
for the associative-scan form, 23.5 s for the chunked sequential form).
The kernel keeps the state in a VMEM scratch across the whole sequence:
HBM traffic collapses to the unavoidable reads of (dt, x, B, C) and the
write of y — d_state x less than any formulation that externalizes h.

Grid: (B, Din/DTILE, L/CHUNK); the L axis is the minor (sequential) grid
dim, so the scratch state persists across chunk steps (flash-attention
loop pattern). Within a chunk the recurrence is unrolled; each iteration
is one VPU multiply-add over the (DTILE, N) state tile.

Backward: the standard selective-scan bwd recomputes h on a reverse sweep
(same traffic shape); we expose forward only and train via jax.checkpoint
recompute — the dry-run roofline for the kernel path is reported
analytically in EXPERIMENTS.md because Pallas TPU kernels cannot compile
on this container's CPU backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call


def _kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref,
            y_ref, hout_ref, h_scratch, *, chunk: int, dtile: int,
            n: int, n_chunks: int):
    j = pl.program_id(2)              # chunk step (sequential minor dim)

    @pl.when(j == 0)
    def _init():
        h_scratch[...] = h0_ref[0]    # (DTILE, N)

    a = a_ref[...]                    # (DTILE, N)
    h = h_scratch[...]
    for t in range(chunk):            # unrolled VPU recurrence
        dtt = dt_ref[0, t, :]         # (DTILE,)
        xt = x_ref[0, t, :]
        bt = b_ref[0, t, :]           # (N,)
        ct = c_ref[0, t, :]
        dA = jnp.exp(dtt[:, None] * a)             # (DTILE, N)
        h = dA * h + (dtt * xt)[:, None] * bt[None, :]
        y_ref[0, t, :] = jnp.sum(h * ct[None, :], axis=1)
    h_scratch[...] = h

    @pl.when(j == n_chunks - 1)
    def _fin():
        hout_ref[0] = h_scratch[...]


def selective_scan_kernel(dt, x, A, Bt, Ct, h0, *, chunk: int = 16,
                          dtile: int = 128):
    """dt, x: (B, L, Din) f32; A: (Din, N); Bt, Ct: (B, L, N);
    h0: (B, Din, N). Returns (y (B, L, Din) f32, h_last)."""
    B, L, Din = x.shape
    N = A.shape[1]
    assert L % chunk == 0, "pad L to a chunk multiple"
    dtile = min(dtile, Din)
    assert Din % dtile == 0
    nD, nL = Din // dtile, L // chunk
    grid = (B, nD, nL)

    dx_spec = pl.BlockSpec((1, chunk, dtile),
                           lambda b, d, l: (b, l, d))
    bc_spec = pl.BlockSpec((1, chunk, N), lambda b, d, l: (b, l, 0))
    a_spec = pl.BlockSpec((dtile, N), lambda b, d, l: (d, 0))
    h_spec = pl.BlockSpec((1, dtile, N), lambda b, d, l: (b, d, 0))

    fn = pallas_call(
        functools.partial(_kernel, chunk=chunk, dtile=dtile, n=N,
                          n_chunks=nL),
        grid=grid,
        in_specs=[dx_spec, dx_spec, bc_spec, bc_spec, a_spec, h_spec],
        out_specs=[dx_spec, h_spec],
        out_shape=[jax.ShapeDtypeStruct((B, L, Din), jnp.float32),
                   jax.ShapeDtypeStruct((B, Din, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dtile, N), jnp.float32)],
    )
    return fn(dt, x, Bt, Ct, A, h0)
