"""jit wrapper: cache probe (slot lookup + tag compare) in one XLA
fusion, then the Pallas row gather over chunks of probed slots."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.cache_gather.cache_gather import (MAX_IDS,
                                                     cache_gather_kernel)


@jax.jit
def cache_gather_pallas(slot_of, slot_ids, feats, ids):
    safe = jnp.clip(ids, 0, slot_of.shape[0] - 1)
    slot = jnp.where(ids >= 0, slot_of[safe], -1)
    slot_c = jnp.clip(slot, 0, slot_ids.shape[0] - 1)
    hit = (slot >= 0) & (slot_ids[slot_c] == ids)
    slots = jnp.where(hit, slot, -1).astype(jnp.int32)
    out = jnp.concatenate([
        cache_gather_kernel(slots[lo:lo + MAX_IDS], feats)
        for lo in range(0, max(len(ids), 1), MAX_IDS)])
    return out, hit
