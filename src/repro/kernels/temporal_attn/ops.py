"""jit wrapper for the temporal_attn kernel (head-major lane layout, N
padded to a tile multiple)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.temporal_attn.temporal_attn import temporal_attn_kernel


@functools.partial(jax.jit, static_argnames=("tile",))
def temporal_attn_pallas(q, k, v, mask, *, tile: int = 8):
    """q: (N, H, Dh); k, v: (N, K, H, Dh); mask: (N, K) -> (N, H, Dh)."""
    N, H, Dh = q.shape
    K = k.shape[1]
    pad = (-N) % tile
    q = jnp.pad(q.reshape(N, H * Dh), ((0, pad), (0, 0)))
    k = jnp.pad(k.reshape(N, K, H * Dh), ((0, pad), (0, 0), (0, 0)))
    v = jnp.pad(v.reshape(N, K, H * Dh), ((0, pad), (0, 0), (0, 0)))
    mask = jnp.pad(mask, ((0, pad), (0, 0)))
    out = temporal_attn_kernel(q, k, v, mask, n_heads=H, tile=tile)
    return out[:N].reshape(N, H, Dh)
