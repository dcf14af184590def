"""Pallas TPU kernel: masked neighborhood attention (GNN aggregation).

The compute hot-spot fed by the temporal sampler: each target attends
over its K sampled neighbors (K = fanout, small) — thousands of tiny
attention problems. The kernel fuses mask + softmax + weighted sum for a
TILE of targets per program, keeping the (TILE, K) score block in VMEM
(the jnp path round-trips scores and normalized weights through HBM).

Layout: q (N, H*Dh); k/v (N, K, H*Dh) with each head's Dh lanes
contiguous; mask (N, K). N is padded to a multiple of TILE by ops.py.
A per-target (1 x Dh) @ (Dh x K) product is far too small for the MXU
(and Mosaic does not lower the 4-D batched einsum), so scores and the
weighted sum are lane-masked multiply-reduce passes on the VPU, one per
head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call


def _kernel(q_ref, k_ref, v_ref, m_ref, o_ref, *, n_heads: int, dh: int):
    q = q_ref[...].astype(jnp.float32)      # (T, H*Dh)
    k = k_ref[...].astype(jnp.float32)      # (T, K, H*Dh)
    v = v_ref[...].astype(jnp.float32)
    m = m_ref[...] != 0                     # (T, K)
    lane = jax.lax.broadcasted_iota(jnp.int32, k.shape, 2)
    qk = q[:, None, :] * k
    out = jnp.zeros(q.shape, jnp.float32)
    for h in range(n_heads):
        in_head = (lane >= h * dh) & (lane < (h + 1) * dh)
        s = jnp.sum(jnp.where(in_head, qk, 0.0), axis=-1) * (dh ** -0.5)
        s = jnp.where(m, s, -1e30)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(m, p, 0.0)
        a = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        out = out + jnp.sum(jnp.where(in_head, a[:, :, None] * v, 0.0),
                            axis=1)
    o_ref[...] = out.astype(o_ref.dtype)


def temporal_attn_kernel(q, k, v, mask, *, n_heads: int, tile: int = 8):
    """q: (N, H*Dh); k, v: (N, K, H*Dh); mask: (N, K) -> (N, H*Dh)."""
    N, HD = q.shape
    K = k.shape[1]
    assert N % tile == 0, "caller pads N to a tile multiple"
    fn = pallas_call(
        functools.partial(_kernel, n_heads=n_heads, dh=HD // n_heads),
        grid=(N // tile,),
        in_specs=[
            pl.BlockSpec((tile, HD), lambda i: (i, 0)),
            pl.BlockSpec((tile, K, HD), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile, K, HD), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile, K), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile, HD), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, HD), q.dtype),
    )
    return fn(q, k, v, mask.astype(jnp.int32))
