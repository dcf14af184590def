"""jit'd wrapper for the temporal_sample Pallas kernel with the same
signature as the vectorized-jnp sampler hop (recent + uniform policies)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.rand import gumbel_noise
from repro.kernels.temporal_sample.temporal_sample import (
    NULL, temporal_sample_kernel)


@functools.partial(jax.jit, static_argnames=("k", "policy"))
def temporal_sample_pallas(page_table_rows, page_tmin, page_tmax,
                           pages_nbr, pages_eid, pages_ts, pages_valid,
                           targets, t_end, t_start, tmask, *, k: int,
                           policy: str = "recent", rng_key=None):
    """Gathers each target's page-table row, drops the pages whose
    t_min/t_max descriptor misses the target's window (the paper's block
    skip), then invokes the kernel.

    page_table_rows: (N_nodes, S) — full table; targets: (N,). For
    policy="uniform", ``rng_key`` drives the per-candidate Gumbel noise.
    Returns (nbr, eid, ts, mask) each (N, k), matching the jnp path.
    """
    in_range = (targets >= 0) & (targets < page_table_rows.shape[0])
    safe_t = jnp.clip(targets, 0, page_table_rows.shape[0] - 1)
    pt = page_table_rows[safe_t].astype(jnp.int32)
    pid = jnp.clip(pt, 0, page_tmin.shape[0] - 1)
    hit = ((pt != NULL) & (tmask & in_range)[:, None]
           & (page_tmin[pid] < t_end[:, None])
           & (page_tmax[pid] >= t_start[:, None]))
    pt = jnp.where(hit, pt, NULL)
    noise = None
    if policy == "uniform":
        if rng_key is None:
            raise ValueError("uniform policy needs an rng key")
        noise = gumbel_noise(rng_key, pt.shape + (pages_ts.shape[1],))
    return temporal_sample_kernel(
        pt, pages_nbr, pages_eid, pages_ts, pages_valid, t_start, t_end,
        k=k, policy=policy, noise=noise)
