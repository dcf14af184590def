"""Pallas TPU kernel: paged temporal neighbor sampling (recent + uniform).

GNNFlow Algorithm 1, re-derived for the TPU (DESIGN.md §2):
  * the paper's warp-per-target traversal becomes one grid *program* per
    target; the page loop is the second (minor, sequential) grid dim, so
    per-target state (the output tile) stays resident in VMEM across
    page steps — the same pattern as a flash-attention KV loop;
  * the paper's per-thread binary search inside a block becomes a masked
    VPU compare over the page's lane vector (a lane-parallel "search"
    is one vector op);
  * the paper's register-cached block descriptor check (t_min/t_max
    skip) runs in the wrapper as one XLA pass over the targets' page
    table rows: a page whose window misses becomes a NULL entry, and
    the scalar-prefetched page id then both drives the BlockSpec
    index_map and skips the compute of NULL pages.

Layout: pages_* are viewed as (P, 1, C) so each DMA'd page row is a
(1, C) block spanning the array's two minor dims (the TPU tiling rule);
lanes are oldest-first within a page, pages arrive newest-first via
the page table. SMEM holds 1 MiB on v5e and pads a 2-D array's minor
dim to 128 words, so the page table and windows are prefetched
flattened, for at most ``_SMEM_TABLE_ENTRIES // S`` targets per call;
the wrapper loops over chunks of targets.

Mosaic lowers neither cumsum, flip, top_k nor a 1-D gather, so each
page step works on (C, C) and (C, K) masks instead: a lane's rank is a
masked count over the other lanes, a lane value becomes a column by a
masked reduce over the diagonal, and slot s of the output takes the
one candidate whose rank is s.

Policies:
  * recent  — running fill of the newest-K in-window edges
    (``_kernel_recent``); a candidate's slot is the number of filled
    slots plus the number of newer in-window lanes of its page;
  * uniform — sampling without replacement via Gumbel top-k: i.i.d.
    Gumbel noise (supplied as an input so the kernel is deterministic
    and testable) scores every candidate, and the kernel keeps a
    running K-entry top-k reservoir merged page by page
    (``_kernel_uniform``). The merge is associative, so the result
    equals a global Gumbel top-k over all in-window candidates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

NULL = -1
_SMEM_TABLE_ENTRIES = 1 << 16     # 256 KiB of int32 page ids per call
_IMIN = jnp.iinfo(jnp.int32).min


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _column(row, fill):
    """(1, C) row -> (C, 1) column: a masked reduce over the diagonal."""
    c = row.shape[1]
    diag = _iota((c, c), 0) == _iota((c, c), 1)
    return jnp.max(jnp.where(diag, jnp.broadcast_to(row, (c, c)), fill),
                   axis=1, keepdims=True)


def _count(mask):
    return jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)


def _page_window(i, tq_ref, ts_ref, val_ref):
    """In-window mask (1, C) of the current page for target i."""
    ts = ts_ref[...]
    return ((val_ref[...] != 0) & (ts >= tq_ref[2 * i])
            & (ts < tq_ref[2 * i + 1]))


def _kernel_recent(pt_ref, tq_ref,          # scalar prefetch: (N*S,), (2N,)
                   nbr_ref, eid_ref, ts_ref, val_ref,   # (1, C) page row
                   out_nbr_ref, out_eid_ref, out_ts_ref,  # (1, K)
                   *, k: int, s: int):
    i = pl.program_id(0)             # target index
    j = pl.program_id(1)             # page step (newest-first)

    @pl.when(j == 0)
    def _init():
        out_nbr_ref[...] = jnp.full((1, k), NULL, jnp.int32)
        out_eid_ref[...] = jnp.full((1, k), NULL, jnp.int32)
        out_ts_ref[...] = jnp.zeros((1, k), jnp.float32)

    @pl.when(pt_ref[i * s + j] != NULL)
    def _scan_page():
        in_win = _page_window(i, tq_ref, ts_ref, val_ref)     # (1, C)
        c = in_win.shape[1]
        win_i = in_win.astype(jnp.int32)
        # in-window lanes newer than lane r (lanes are oldest-first)
        newer = jnp.sum(jnp.where(_iota((c, c), 1) > _iota((c, c), 0),
                                  jnp.broadcast_to(win_i, (c, c)), 0),
                        axis=1, keepdims=True)                 # (C, 1)
        filled = _count(out_eid_ref[...] != NULL)              # (1, 1)
        sel = ((_column(win_i, 0) != 0)
               & (filled + newer == _iota((c, k), 1)))         # (C, K)
        got = jnp.max(sel.astype(jnp.int32), axis=0, keepdims=True) != 0

        def pick(ref, row, fill):
            new = jnp.max(jnp.where(sel, _column(row, fill), fill),
                          axis=0, keepdims=True)
            ref[...] = jnp.where(got, new, ref[...])

        pick(out_nbr_ref, nbr_ref[...], _IMIN)
        pick(out_eid_ref, eid_ref[...], _IMIN)
        pick(out_ts_ref, ts_ref[...], -jnp.inf)


def _kernel_uniform(pt_ref, tq_ref,         # scalar prefetch: (N*S,), (2N,)
                    nbr_ref, eid_ref, ts_ref, val_ref,  # (1, C) page row
                    noise_ref,               # (1, C) Gumbel noise
                    out_nbr_ref, out_eid_ref, out_ts_ref,
                    out_score_ref,           # (1, K) running reservoir
                    *, k: int, s: int):
    i = pl.program_id(0)             # target index
    j = pl.program_id(1)             # page step (newest-first)

    @pl.when(j == 0)
    def _init():
        out_nbr_ref[...] = jnp.full((1, k), NULL, jnp.int32)
        out_eid_ref[...] = jnp.full((1, k), NULL, jnp.int32)
        out_ts_ref[...] = jnp.zeros((1, k), jnp.float32)
        out_score_ref[...] = jnp.full((1, k), -jnp.inf, jnp.float32)

    # no early stop: every candidate must get a chance
    @pl.when(pt_ref[i * s + j] != NULL)
    def _merge_page():
        in_win = _page_window(i, tq_ref, ts_ref, val_ref)
        c = in_win.shape[1]
        # top-k of [reservoir | page] in score order, ties to the lower
        # index (reservoir first) as lax.top_k breaks them: each
        # element's rank counts the elements ordered before it
        sc = jnp.where(in_win, noise_ref[...], -jnp.inf)       # (1, C)
        rs = out_score_ref[...]                                # (1, K)
        sc_col = _column(sc, -jnp.inf)                         # (C, 1)
        rs_col = _column(rs, -jnp.inf)                         # (K, 1)
        before_cc = (sc > sc_col) | ((sc == sc_col)
                                     & (_iota((c, c), 1)
                                        < _iota((c, c), 0)))
        before_kk = (rs > rs_col) | ((rs == rs_col)
                                     & (_iota((k, k), 1)
                                        < _iota((k, k), 0)))
        rank_page = _count(before_cc) + _count(rs >= sc_col)   # (C, 1)
        rank_res = _count(before_kk) + _count(sc > rs_col)     # (K, 1)
        sel_p = rank_page == _iota((c, k), 1)                  # (C, K)
        sel_r = rank_res == _iota((k, k), 1)                   # (K, K)

        def merge(ref, page_row, fill):
            res_row = ref[...]
            new = jnp.maximum(
                jnp.max(jnp.where(sel_p, _column(page_row, fill), fill),
                        axis=0, keepdims=True),
                jnp.max(jnp.where(sel_r, _column(res_row, fill), fill),
                        axis=0, keepdims=True))
            ref[...] = new

        merge(out_nbr_ref, nbr_ref[...], _IMIN)
        merge(out_eid_ref, eid_ref[...], _IMIN)
        merge(out_ts_ref, ts_ref[...], -jnp.inf)
        merge(out_score_ref, sc, -jnp.inf)


def _sample_chunk(page_table, tq, pages, noise, *, k: int, policy: str):
    N, S = page_table.shape
    C = pages[0].shape[2]
    page_row = pl.BlockSpec(
        (pl.Squeezed(), 1, C),
        lambda i, j, pt, tq_: (jnp.maximum(pt[i * S + j], 0), 0, 0))
    out_row = pl.BlockSpec((pl.Squeezed(), 1, k),
                           lambda i, j, *_: (i, 0, 0))
    in_specs = [page_row] * 4
    inputs = list(pages)
    n_out = 3
    if policy == "uniform":
        in_specs.append(pl.BlockSpec((pl.Squeezed(), 1, C),
                                     lambda i, j, *_: (i * S + j, 0, 0)))
        inputs.append(noise.reshape(N * S, 1, C))
        n_out = 4
        body = _kernel_uniform
    else:
        body = _kernel_recent
    dtypes = (jnp.int32, jnp.int32, jnp.float32, jnp.float32)[:n_out]
    fn = pallas_call(
        functools.partial(body, k=k, s=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N, S), in_specs=in_specs,
            out_specs=[out_row] * n_out),
        out_shape=[jax.ShapeDtypeStruct((N, 1, k), d) for d in dtypes],
    )
    return [o.reshape(N, k)
            for o in fn(page_table.reshape(-1), tq.reshape(-1), *inputs)]


def temporal_sample_kernel(page_table, pages_nbr, pages_eid, pages_ts,
                           pages_valid, t_start, t_end, *, k: int,
                           policy: str = "recent", noise=None):
    """page_table: (N, S) newest-first page ids, NULL for pages to skip;
    pages_*: (P, C); t_start/t_end: (N,) window; noise: (N, S, C)
    Gumbel scores, required for policy="uniform".
    Returns (nbr, eid, ts, mask) each (N, k)."""
    if policy not in ("recent", "uniform"):
        raise ValueError(f"unknown policy {policy!r}")
    if policy == "uniform" and noise is None:
        raise ValueError("uniform policy needs Gumbel noise")
    N, S = page_table.shape
    P, C = pages_ts.shape
    pages = [pages_nbr.astype(jnp.int32), pages_eid.astype(jnp.int32),
             pages_ts.astype(jnp.float32),
             pages_valid.astype(jnp.int32)]
    pages = [a.reshape(P, 1, C) for a in pages]
    tq = jnp.stack([t_start, t_end], axis=1).astype(jnp.float32)
    step = max(8, _SMEM_TABLE_ENTRIES // S)
    outs = [_sample_chunk(
        page_table[lo:lo + step], tq[lo:lo + step], pages,
        None if noise is None else noise[lo:lo + step].astype(jnp.float32),
        k=k, policy=policy) for lo in range(0, max(N, 1), step)]
    nbr, eid, ts, *score = [jnp.concatenate(o) for o in zip(*outs)]
    mask = (score[0] > -jnp.inf) if score else (eid != NULL)
    return (jnp.where(mask, nbr, NULL), jnp.where(mask, eid, NULL),
            jnp.where(mask, ts, 0.0), mask)
