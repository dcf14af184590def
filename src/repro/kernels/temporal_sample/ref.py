"""Pure-jnp oracle for the temporal_sample kernel.

For each target i with window [t_start_i, t_end_i), walk its pages
newest-first (pages are given newest-first; lanes within a page are
oldest-first), collect valid in-window edges in newest-first order,
return the first K.
"""
from __future__ import annotations

import jax.numpy as jnp

NULL = -1


def temporal_sample_ref(page_table, page_tmin, page_tmax, pages_nbr,
                        pages_eid, pages_ts, pages_valid, targets, t_end,
                        t_start, tmask, *, k: int):
    """page_table: (N_nodes, S) int32 (newest-first page ids, -1 pad);
    pages_*: (P, C); targets: (N,) int32; t_end/t_start: (N,) f32;
    tmask: (N,) bool. Returns (nbr, eid, ts, mask) each (N, k)."""
    N = targets.shape[0]
    S = page_table.shape[1]
    C = pages_ts.shape[1]
    in_range = (targets >= 0) & (targets < page_table.shape[0])
    safe_t = jnp.clip(targets, 0, page_table.shape[0] - 1)
    pt = page_table[safe_t]                                # (N, S)
    pvalid = (pt != NULL) & (tmask & in_range)[:, None]
    ptc = jnp.clip(pt, 0, pages_ts.shape[0] - 1)
    tmin, tmax = page_tmin[ptc], page_tmax[ptc]
    p_hit = pvalid & (tmin < t_end[:, None]) & (tmax >= t_start[:, None])

    nbr = pages_nbr[ptc][:, :, ::-1].reshape(N, S * C)
    eid = pages_eid[ptc][:, :, ::-1].reshape(N, S * C)
    ts = pages_ts[ptc][:, :, ::-1].reshape(N, S * C)
    val = pages_valid[ptc][:, :, ::-1].reshape(N, S * C)
    in_win = (val & jnp.repeat(p_hit, C, axis=1)
              & (ts >= t_start[:, None]) & (ts < t_end[:, None]))

    order = jnp.argsort(~in_win, axis=-1, stable=True)[:, :k]
    take = jnp.take_along_axis
    m = take(in_win, order, axis=-1)
    return (jnp.where(m, take(nbr, order, axis=-1), NULL),
            jnp.where(m, take(eid, order, axis=-1), NULL),
            jnp.where(m, take(ts, order, axis=-1), 0.0),
            m)
