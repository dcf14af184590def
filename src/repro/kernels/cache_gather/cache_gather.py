"""Pallas TPU kernel: cache feature gather driven by probed slots
(GNNFlow §4.3).

The wrapper (ops.py) probes the cache — slot lookup plus the tag
compare (slot id == requested id) — in one XLA fusion and hands the
kernel one slot per id, -1 for a miss. The slots are scalar-prefetched
into SMEM and drive the BlockSpec index_map, so each grid step DMAs
exactly the one feature row it needs into VMEM; misses write zeros.

Rows move as (1, D) blocks of a (C, 1, D) view: the block then spans
the array's two minor dims, which meets the TPU's tiling rule for any
D. SMEM holds 1 MiB on v5e, so one call takes at most ``MAX_IDS``
slots and the wrapper loops over chunks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

NULL = -1
MAX_IDS = 1 << 16          # 256 KiB of int32 slots in SMEM per call


def _kernel(slots_ref,                 # scalar prefetch: (N,)
            feat_row_ref,              # (1, D) gathered row
            out_ref):                  # (1, D)
    hit = slots_ref[pl.program_id(0)] >= 0
    row = feat_row_ref[...]
    out_ref[...] = jnp.where(hit, row, jnp.zeros_like(row))


def cache_gather_kernel(slots, feats):
    """slots: (N,) probed slot per id (-1 = miss), N <= MAX_IDS;
    feats: (C, D). Returns (N, D), zero rows for misses."""
    N = slots.shape[0]
    C, D = feats.shape
    assert N <= MAX_IDS, "caller chunks the ids"

    def feat_map(i, slots_):
        return (jnp.maximum(slots_[i], 0), 0, 0)

    def out_map(i, slots_):
        return (i, 0, 0)

    row = (pl.Squeezed(), 1, D)
    fn = pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(N,),
            in_specs=[pl.BlockSpec(row, feat_map)],
            out_specs=pl.BlockSpec(row, out_map)),
        out_shape=jax.ShapeDtypeStruct((N, 1, D), feats.dtype),
    )
    return fn(slots, feats.reshape(C, 1, D)).reshape(N, D)
