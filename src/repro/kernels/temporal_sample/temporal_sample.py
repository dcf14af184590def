"""Pallas TPU kernel: paged temporal neighbor sampling (``recent``).

GNNFlow Algorithm 1, re-derived for the TPU (DESIGN.md §2):
  * the paper's warp-per-target traversal becomes one grid *program* per
    target; the page loop is the second (minor, sequential) grid dim, so
    per-target state (the output tile) stays resident in VMEM across
    page steps — the same pattern as a flash-attention KV loop;
  * the paper's time-indexed block search locates, per target, the
    newest page that starts before the query time (``core.sampling``
    binary-searches the chain by page t_min); the wrapper hands the
    kernel the ``scan_pages`` pages from that page back, so the window
    follows the query time however deep in the history it lies;
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

The kernel keeps a running fill of the newest-K in-window edges
(``_kernel_recent``): a candidate's slot is the number of filled slots
plus the number of newer in-window lanes of its page. ``uniform`` is no
kernel's: the sampler draws ranks over the whole history on the jnp path
(``core.sampling._draw``, O(k log P) a target), which no page-by-page
scan matches.
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


def _sample_chunk(page_table, tq, pages, *, k: int):
    N, S = page_table.shape
    C = pages[0].shape[2]
    page_row = pl.BlockSpec(
        (pl.Squeezed(), 1, C),
        lambda i, j, pt, tq_: (jnp.maximum(pt[i * S + j], 0), 0, 0))
    out_row = pl.BlockSpec((pl.Squeezed(), 1, k),
                           lambda i, j, *_: (i, 0, 0))
    dtypes = (jnp.int32, jnp.int32, jnp.float32)
    fn = pallas_call(
        functools.partial(_kernel_recent, k=k, s=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N, S), in_specs=[page_row] * 4,
            out_specs=[out_row] * 3),
        out_shape=[jax.ShapeDtypeStruct((N, 1, k), d) for d in dtypes],
    )
    return [o.reshape(N, k)
            for o in fn(page_table.reshape(-1), tq.reshape(-1), *pages)]


def temporal_sample_kernel(page_table, pages_nbr, pages_eid, pages_ts,
                           pages_valid, t_start, t_end, *, k: int):
    """page_table: (N, S) newest-first page ids, NULL for pages to skip;
    pages_*: (P, C); t_start/t_end: (N,) window.
    Returns (nbr, eid, ts, mask) each (N, k): the newest k in-window
    edges over the given pages."""
    N, S = page_table.shape
    P, C = pages_ts.shape
    pages = [pages_nbr.astype(jnp.int32), pages_eid.astype(jnp.int32),
             pages_ts.astype(jnp.float32),
             pages_valid.astype(jnp.int32)]
    pages = [a.reshape(P, 1, C) for a in pages]
    tq = jnp.stack([t_start, t_end], axis=1).astype(jnp.float32)
    step = max(8, _SMEM_TABLE_ENTRIES // S)
    outs = [_sample_chunk(page_table[lo:lo + step], tq[lo:lo + step], pages,
                          k=k) for lo in range(0, max(N, 1), step)]
    nbr, eid, ts = [jnp.concatenate(o) for o in zip(*outs)]
    mask = eid != NULL
    return (jnp.where(mask, nbr, NULL), jnp.where(mask, eid, NULL),
            jnp.where(mask, ts, 0.0), mask)
