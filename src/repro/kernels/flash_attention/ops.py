"""jit wrapper for the flash_attention kernel (pads Sq/Skv; slices)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import (
    flash_attention_kernel)


@functools.partial(jax.jit,
                   static_argnames=("causal", "qb", "kb"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, qb: int = 128,
                           kb: int = 128):
    B, Sq, Hq, D = q.shape
    Skv = k.shape[1]
    qb = min(qb, max(8, Sq))
    kb = min(kb, max(8, Skv))
    pq = (-Sq) % qb
    pk = (-Skv) % kb
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        # pad keys BEFORE the valid region would break causal offsets;
        # pad at the end and rely on causal masking / explicit -inf via
        # padded k rows producing scores that the causal mask kills for
        # in-range queries. For non-causal, padded keys must be masked:
        # we instead require Skv % kb == 0 there.
        assert causal or pk == 0, "non-causal needs Skv % kb == 0"
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    out = flash_attention_kernel(q, k, v, causal=causal, qb=qb, kb=kb)
    return out[:, :Sq]
