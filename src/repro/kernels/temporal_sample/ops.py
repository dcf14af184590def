"""jit'd wrapper for the temporal_sample Pallas kernel with the same
signature as the vectorized-jnp sampler's ``recent`` hop."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.temporal_sample.temporal_sample import (
    NULL, temporal_sample_kernel)


@functools.partial(jax.jit, static_argnames=("k", "scan_pages"))
def temporal_sample_pallas(page_table_rows, page_tmin, page_tmax,
                           pages_nbr, pages_eid, pages_ts, pages_valid,
                           targets, t_end, t_start, tmask, *, k: int,
                           start=None, scan_pages: int = 0):
    """Gathers each target's pages from the page table, drops the pages
    whose t_min/t_max descriptor misses the target's window (the paper's
    block skip), then invokes the kernel.

    page_table_rows: (N_nodes, W) newest-first chains; targets: (N,).
    With ``start`` (N,) the kernel walks the ``scan_pages`` columns from
    ``start[i]`` on (the sampler's located page: the window of
    ``recent``), else each target's whole row.
    Returns (nbr, eid, ts, mask) each (N, k), matching the jnp path.
    """
    in_range = (targets >= 0) & (targets < page_table_rows.shape[0])
    safe_t = jnp.clip(targets, 0, page_table_rows.shape[0] - 1)
    if start is None:
        pt = page_table_rows[safe_t].astype(jnp.int32)
    else:
        width = page_table_rows.shape[1]
        cols = start[:, None] + jnp.arange(scan_pages, dtype=jnp.int32)
        pt = page_table_rows[safe_t[:, None], jnp.clip(cols, 0, width - 1)]
        pt = jnp.where(cols < width, pt, NULL).astype(jnp.int32)
    pid = jnp.clip(pt, 0, page_tmin.shape[0] - 1)
    hit = ((pt != NULL) & (tmask & in_range)[:, None]
           & (page_tmin[pid] < t_end[:, None])
           & (page_tmax[pid] >= t_start[:, None]))
    pt = jnp.where(hit, pt, NULL)
    return temporal_sample_kernel(
        pt, pages_nbr, pages_eid, pages_ts, pages_valid, t_start, t_end,
        k=k)
