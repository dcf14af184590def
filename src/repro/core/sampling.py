"""Temporal k-hop neighborhood sampling (GNNFlow §4.2, Algorithm 1).

Three interchangeable implementations (tests assert agreement):

  * ``oracle_sample``     — trusted numpy reference walking the dynamic
                            graph's block lists exactly as Algorithm 1.
  * ``TemporalSampler``   — vectorized jnp path over the paged snapshot,
                            the time-indexed block search of Algorithm 1
                            per target: a binary search of the node's
                            newest-first chain by page ``t_min`` locates
                            the page holding its newest edge before the
                            query time; the pages' earlier-edge counts
                            (``page_before``) plus one in-page compare
                            count the candidates. ``recent`` takes the
                            newest k from a ``scan_pages``-page window
                            starting at the located page; ``uniform``
                            draws k distinct ranks among all candidates
                            and maps each to its (page, lane) by a second
                            search over the counts — O(k log P).
  * Pallas kernel         — kernels/temporal_sample, the ``recent``
                            window (``use_pallas=True``), validated in
                            interpret mode against both paths.

Static shapes: every hop pads targets to a fixed budget and returns masked
(N, K) neighbor tiles, so the whole GNN step jits once per shape. The
entire k-hop loop is ONE jitted dispatch (``_sample_khop``): intermediate
targets/times/masks never leave the device, and the paged snapshot itself
is device-resident — ``refresh()`` applies SnapshotDeltas as
copy-on-write row/cell scatters rather than re-uploading (README
"Sampling pipeline").

Bounded work note: no path scans a node's whole history. The mirror
holds every page of every chain (the page table at a power-of-two
width), and each target costs two binary searches of its chain plus,
for ``uniform``, one search per draw; ``recent`` gathers ``scan_pages``
pages from the located one back, which hold its k newest candidates
(a page holds at least ``min_block`` edges) unless deletions emptied
them. ``uniform`` and ``window`` see every earlier edge, however deep.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dgraph import DynamicGraph, NULL
from repro.obs import trace
from repro.core.snapshot import GraphSnapshot, build_snapshot


# ---------------------------------------------------------------------------
# Sampled-subgraph containers (static shapes, mask-padded)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SampledLayer:
    """One hop: for each target i, up to K sampled temporal neighbors."""
    dst_nodes: np.ndarray | jnp.ndarray    # (N,) int32
    dst_times: np.ndarray | jnp.ndarray    # (N,) float32
    dst_mask: np.ndarray | jnp.ndarray     # (N,) bool
    nbr_ids: np.ndarray | jnp.ndarray      # (N, K) int32
    nbr_eids: np.ndarray | jnp.ndarray     # (N, K) int32
    nbr_ts: np.ndarray | jnp.ndarray       # (N, K) float32
    mask: np.ndarray | jnp.ndarray         # (N, K) bool
    # (3,) int32 from the fused dispatch: live targets, their candidates
    # summed, targets with candidates older than their scan_pages newest
    # pages; None where the layer was not sampled on the device
    stats: Optional[np.ndarray | jnp.ndarray] = None

    @property
    def fanout(self) -> int:
        return self.nbr_ids.shape[1]


# ---------------------------------------------------------------------------
# Oracle (numpy, exact Algorithm 1 over the block lists)
# ---------------------------------------------------------------------------


def _oracle_one(g: DynamicGraph, node: int, t_end: float, t_start: float,
                k: int, policy: str, rng: np.random.Generator
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    nbrs, eids, tss = g.neighbors_in_window(node, t_start, t_end)
    if len(nbrs) == 0:
        return nbrs, eids, tss
    if policy == "recent":
        return nbrs[:k], eids[:k], tss[:k]
    # uniform / window: uniform without replacement among candidates
    take = min(k, len(nbrs))
    sel = rng.choice(len(nbrs), size=take, replace=False)
    return nbrs[sel], eids[sel], tss[sel]


def oracle_sample(g: DynamicGraph, seeds: np.ndarray, seed_ts: np.ndarray,
                  fanouts: Sequence[int], policy: str = "recent",
                  window: float = 0.0, seed: int = 0
                  ) -> List[SampledLayer]:
    """Reference temporal k-hop sampling. Layer l's targets are layer
    l-1's sampled neighbors queried at their edge timestamps."""
    rng = np.random.default_rng(seed)
    targets = np.asarray(seeds, np.int64)
    times = np.asarray(seed_ts, np.float64)
    tmask = np.ones(len(targets), bool)
    layers: List[SampledLayer] = []
    for k in fanouts:
        N = len(targets)
        nbr = np.full((N, k), NULL, np.int64)
        eid = np.full((N, k), NULL, np.int64)
        ts = np.zeros((N, k), np.float64)
        msk = np.zeros((N, k), bool)
        for i in range(N):
            if not tmask[i]:
                continue
            t_end = times[i]
            t_start = t_end - window if (policy == "window" and window > 0) \
                else -np.inf
            a, b, c = _oracle_one(g, int(targets[i]), t_end, t_start, k,
                                  policy, rng)
            m = len(a)
            nbr[i, :m], eid[i, :m], ts[i, :m] = a, b, c
            msk[i, :m] = True
        layers.append(SampledLayer(
            dst_nodes=targets.astype(np.int32),
            dst_times=times.astype(np.float32), dst_mask=tmask.copy(),
            nbr_ids=nbr.astype(np.int32), nbr_eids=eid.astype(np.int32),
            nbr_ts=ts.astype(np.float32), mask=msk))
        targets = nbr.reshape(-1)
        times = ts.reshape(-1)
        tmask = msk.reshape(-1)
    return layers


# ---------------------------------------------------------------------------
# Vectorized device path (fused k-hop dispatch)
# ---------------------------------------------------------------------------

# Incremented once per *trace* of the fused k-hop dispatch — steady-state
# sampling must not retrace, so tests use this as a dispatch-count probe.
TRACE_COUNTS: collections.Counter = collections.Counter()


@functools.lru_cache(maxsize=1)
def _zero_key():
    """Constant key threaded through deterministic-policy dispatches (the
    rng argument is dead code there and DCE'd by jit)."""
    return jax.random.PRNGKey(0)


def _first_true(pred, width: int, shape):
    """Per row, the first column in ``[0, width)`` where the monotone
    (False...False True...True) predicate ``pred(col)`` holds, or
    ``width`` where none does: binary lifting, log2(width) + 1 probes.
    ``width`` is a power of two."""
    col = jnp.zeros(shape, jnp.int32)
    step = width // 2
    while step:
        col = jnp.where(pred(col + step - 1), col, col + step)
        step //= 2
    return jnp.where(pred(col), col, width)


def _page_at(dev, rows, col):
    """Page id at (row, col) of the page table, NULL past its width."""
    table = dev["page_table"]
    width = table.shape[1]
    pid = table[rows, jnp.clip(col, 0, width - 1)]
    return jnp.where((col >= 0) & (col < width), pid, NULL)


def _locate(dev, rows, t):
    """Column of each target's newest page that starts before ``t``:
    the page holding its newest edge earlier than ``t`` (chains are
    newest-first and chronological, so ``t_min`` falls along a row).
    Past the chain's end where no page does."""
    tmin = dev["page_tmin"]

    def starts_before(col):
        p = _page_at(dev, rows, col)
        return (p == NULL) | (tmin[jnp.maximum(p, 0)] < t)
    return _first_true(starts_before, dev["page_table"].shape[1],
                       rows.shape)


def _rank_below(dev, rows, t, col):
    """How many valid edges of each target are earlier than ``t``, given
    the located column: the count of the page's older pages plus one
    in-page comparison (lanes are chronological)."""
    p = _page_at(dev, rows, col)
    pc = jnp.maximum(p, 0)
    in_page = jnp.sum(dev["pages_valid"][pc]
                      & (dev["pages_ts"][pc] < t[:, None]), axis=-1)
    return jnp.where(p != NULL, dev["page_before"][pc] + in_page, 0)


def _targets(dev, targets, tmask):
    rows_n = dev["page_table"].shape[0]
    live = tmask & (targets >= 0) & (targets < rows_n)
    return jnp.clip(targets, 0, rows_n - 1), live


def _draw(dev, rows, lo, n, rng_key, k: int):
    """``min(k, n)`` distinct ranks drawn uniformly from ``[lo, lo+n)``
    per target (Floyd's algorithm, k static steps), each mapped to its
    (page, lane) by a search over the chain's earlier-edge counts and
    one in-page search over the valid lanes. O(k log P) per target."""
    N = rows.shape[0]
    m = jnp.minimum(n, k)
    step = jnp.arange(k, dtype=jnp.int32)[None, :]
    top = n[:, None] - m[:, None] + step          # inclusive bound, step i
    u = jax.random.randint(rng_key, (N, k), 0, jnp.maximum(top + 1, 1))
    picks = []
    for i in range(k):
        dup = jnp.zeros((N,), bool)
        for q in picks:
            dup = dup | (q == u[:, i])
        picks.append(jnp.where(dup, top[:, i], u[:, i]))
    on = step < m[:, None]                          # (N, k)
    rank = jnp.where(on, lo[:, None] + jnp.stack(picks, axis=1), 0)

    flat_rows = jnp.repeat(rows, k)
    flat_rank = rank.reshape(-1)
    before = dev["page_before"]

    def holds_rank(col):        # the page's older pages end at or below r
        p = _page_at(dev, flat_rows, col)
        return (p == NULL) | (before[jnp.maximum(p, 0)] <= flat_rank)
    col = _first_true(holds_rank, dev["page_table"].shape[1],
                      flat_rows.shape)
    page = jnp.maximum(_page_at(dev, flat_rows, col), 0)
    q = flat_rank - before[page]
    seen = jnp.cumsum(dev["pages_valid"][page].astype(jnp.int32), axis=-1)
    lane = jnp.sum(seen <= q[:, None], axis=-1)
    lane = jnp.minimum(lane, dev["pages_ts"].shape[1] - 1)
    on_f = on.reshape(-1)

    def pick(name, fill):
        v = dev[name][page, lane]
        return jnp.where(on_f, v, fill).reshape(N, k)
    return (pick("pages_nbr", NULL), pick("pages_eid", NULL),
            pick("pages_ts", 0.0), on)


def _window_recent(dev, rows, live, t_end, t_start, first, *, k: int,
                   scan_pages: int):
    """The k newest valid edges in [t_start, t_end) among the
    ``scan_pages`` pages from the located one back. Exact while those
    pages hold k candidates, which they do unless deletions emptied
    them: the pages older than the located one are full, of at least
    ``min_block`` edges each."""
    pages_ts = dev["pages_ts"]
    N = rows.shape[0]
    page_cap = pages_ts.shape[1]
    cols = first[:, None] + jnp.arange(scan_pages, dtype=jnp.int32)
    pt = _page_at(dev, rows[:, None], cols)                # (N, S)
    pvalid = (pt != NULL) & live[:, None]
    ptc = jnp.maximum(pt, 0)

    # page lanes newest-first (lanes are chronological within a page)
    nbr = dev["pages_nbr"][ptc][:, :, ::-1]               # (N, S, C)
    eid = dev["pages_eid"][ptc][:, :, ::-1]
    ts = pages_ts[ptc][:, :, ::-1]
    val = dev["pages_valid"][ptc][:, :, ::-1]
    in_win = (val & pvalid[:, :, None]
              & (ts >= t_start[:, None, None])
              & (ts < t_end[:, None, None]))              # (N, S, C)

    W = scan_pages * page_cap
    nbr_f = nbr.reshape(N, W)
    eid_f = eid.reshape(N, W)
    ts_f = ts.reshape(N, W)
    m_f = in_win.reshape(N, W)                            # newest-first
    if W < k:   # degenerate tiny snapshot: pad the candidate window
        pad = ((0, 0), (0, k - W))
        nbr_f = jnp.pad(nbr_f, pad, constant_values=NULL)
        eid_f = jnp.pad(eid_f, pad, constant_values=NULL)
        ts_f = jnp.pad(ts_f, pad, constant_values=0.0)
        m_f = jnp.pad(m_f, pad, constant_values=False)
        W = k
    # valid lanes score by newest-first position, invalid strictly
    # below all valid ones; float32 scores (XLA's top-k fast path is
    # float-only; W < 2^24 keeps positions exact)
    idx = jnp.arange(W, dtype=jnp.float32)
    score = jnp.where(m_f, -idx[None, :], -jnp.inf)
    order = jax.lax.top_k(score, k)[1]
    take = jnp.take_along_axis
    out_m = take(m_f, order, axis=-1)
    out_nbr = jnp.where(out_m, take(nbr_f, order, axis=-1), NULL)
    out_eid = jnp.where(out_m, take(eid_f, order, axis=-1), NULL)
    out_ts = jnp.where(out_m, take(ts_f, order, axis=-1), 0.0)
    return out_nbr, out_eid, out_ts, out_m


def _hop(dev, targets, t_end, t_start, tmask, rng_key, *, k: int,
         policy: str, bounded: bool, scan_pages: int, use_pallas: bool):
    """One hop for N targets over the device-resident snapshot; traced
    inside the fused dispatch. Returns (nbr, eid, ts, mask), each (N, k),
    and the hop's (live targets, their candidates summed, targets with
    candidates older than their ``scan_pages`` newest pages).

    ``recent`` takes the k newest candidates from a ``scan_pages``-page
    window that starts at the located page (the Pallas kernel when
    ``use_pallas``); ``uniform`` draws among all candidates of the
    whole history, bounded below by ``t_start`` when ``bounded``."""
    rows, live = _targets(dev, targets, tmask)
    first = _locate(dev, rows, t_end)
    hi = _rank_below(dev, rows, t_end, first)
    lo = (_rank_below(dev, rows, t_start, _locate(dev, rows, t_start))
          if bounded else jnp.zeros_like(hi))
    n = jnp.where(live, jnp.maximum(hi - lo, 0), 0)
    S = min(scan_pages, dev["page_table"].shape[1])
    # ranks below the count of the S-th newest page lie in older pages
    edge = _page_at(dev, rows, jnp.full_like(rows, S - 1))
    deep = (n > 0) & (lo < jnp.where(
        edge != NULL, dev["page_before"][jnp.maximum(edge, 0)], 0))
    stats = jnp.stack([jnp.sum(live), jnp.sum(n), jnp.sum(deep)]
                      ).astype(jnp.int32)
    if policy == "uniform":
        out = _draw(dev, rows, lo, n, rng_key, k)
    elif use_pallas:
        from repro.kernels.temporal_sample.ops import temporal_sample_pallas
        out = temporal_sample_pallas(
            dev["page_table"], dev["page_tmin"], dev["page_tmax"],
            dev["pages_nbr"], dev["pages_eid"], dev["pages_ts"],
            dev["pages_valid"], targets, t_end, t_start, tmask, k=k,
            start=first, scan_pages=S)
    else:
        out = _window_recent(dev, rows, live, t_end, t_start, first, k=k,
                             scan_pages=S)
    return out, stats


def _khop_impl(dev, seeds, seed_ts, tmask0, rng_key, *,
               fanouts: Tuple[int, ...], policy: str, window: float,
               scan_pages: int, use_pallas: bool):
    """The whole k-hop loop as ONE jitted dispatch: intermediate targets/
    times/masks stay on device; per-hop fanouts are static so each hop
    unrolls into the same trace. Returns a tuple of per-hop layer tuples
    (dst_nodes, dst_times, dst_mask, nbr, eid, ts, mask, stats)."""
    TRACE_COUNTS["khop"] += 1        # trace-time side effect (probe)
    targets, times, tmask = seeds, seed_ts, tmask0
    needs_rng = policy in ("uniform", "window")
    bounded = policy == "window" and window > 0
    pol = "uniform" if policy == "window" else policy
    layers = []
    for h, k in enumerate(fanouts):
        sub = jax.random.fold_in(rng_key, h) if needs_rng else rng_key
        t_end = times
        t_start = (times - window if bounded
                   else jnp.full_like(times, -jnp.inf))
        (nbr, eid, ts, m), stats = _hop(
            dev, targets, t_end, t_start, tmask, sub, k=k, policy=pol,
            bounded=bounded, scan_pages=scan_pages, use_pallas=use_pallas)
        layers.append((targets, times, tmask, nbr, eid, ts, m, stats))
        targets, times, tmask = (nbr.reshape(-1), ts.reshape(-1),
                                 m.reshape(-1))
    return tuple(layers)


_sample_khop = jax.jit(
    _khop_impl,
    static_argnames=("fanouts", "policy", "window", "scan_pages",
                     "use_pallas"))


# device-mirror scatters: a steady-state refresh transfers only the
# updated rows/cells, and each scatter writes a fresh output buffer so
# PREVIOUS readers stay valid — the serving wing's versioned read
# handles pin old buffers while ingest publishes new ones
# (repro.serve.handle).
@jax.jit
def _scatter_rows(buf, rows, upd):
    return buf.at[rows].set(upd)


@functools.partial(jax.jit, static_argnums=(1,))
def _widen(table, width: int):
    """The page table at a wider power-of-two width, new columns NULL."""
    return jnp.pad(table, ((0, 0), (0, width - table.shape[1])),
                   constant_values=NULL)


def _group_set(bufs, idx, bits):
    """Scatter one packed delta into several arrays at once: ``idx``
    (n, d) holds each entry's row (and lane), ``bits`` (n, len(bufs))
    its value per array as int32 bit patterns. One dispatch and two
    uploads, whatever the number of arrays."""
    where = tuple(idx[:, i] for i in range(idx.shape[1]))
    out = []
    for j, b in enumerate(bufs):
        v = bits[:, j]
        if b.dtype == jnp.bool_:
            v = v != 0
        elif b.dtype != v.dtype:
            v = jax.lax.bitcast_convert_type(v, b.dtype)
        out.append(b.at[where].set(v))
    return tuple(out)


_scatter_group = jax.jit(_group_set)


def _bits(a: np.ndarray) -> np.ndarray:
    """int32 bit patterns of a 4-byte or bool array (``_group_set``)."""
    return a.view(np.int32) if a.dtype.itemsize == 4 else a.astype(np.int32)


class DeviceMirror:
    """Device-resident mirror of a :class:`GraphSnapshot` — the graph's
    one device copy, owned by the trainer's ``TemporalSampler`` and
    published as-is to the serving wing (``repro.serve.handle``).

    ``sync`` scatters the snapshot's delta when it chains from the
    mirrored version and uploads in full otherwise. Every ``sync`` that
    changes anything returns a FRESH dict whose updated arrays are new
    buffers (copy-on-write at array granularity): a reader holding a
    previously returned dict keeps a complete, immutable view of that
    version, which is exactly what a versioned query handle pins.

    Every array's leading (row) dimension is rounded up to a power of
    two, so the mirrored shapes change O(log n) times as the graph grows
    instead of at every geometric reallocation: the jitted sampler and
    scatters retrace per distinct shape, and on the query path each
    retrace is a multi-hundred-ms stall.
    """

    #: pad fill per device array — uploads extend each array to its
    #: power-of-two shape with entries no sampler ever dereferences
    #: (NULL page ids / +inf timestamps / invalid lanes)
    _FILL = dict(page_table=NULL, pages_nbr=NULL, pages_eid=NULL,
                 pages_ts=np.inf, pages_valid=False,
                 page_tmin=np.inf, page_tmax=-np.inf, page_before=0)
    #: device array -> snapshot attribute; the per-page arrays take the
    #: changed (page, lane) cells or descriptor rows of a delta
    _CELLS = dict(pages_nbr="nbr", pages_eid="eid", pages_ts="ts",
                  pages_valid="valid")
    _DESC = ("page_tmin", "page_tmax", "page_before")

    def __init__(self):
        self.dev: Optional[dict] = None   # current device arrays
        self.version = -1                 # snapshot version mirrored
        self.snap_obj = None              # snapshot object the mirror was
        #                                   built from — deltas chain via
        #                                   in-place mutation, so versions
        #                                   from a DIFFERENT object are
        #                                   unrelated (full upload)
        self.last_refresh_bytes = 0       # H2D payload of the last sync
        self.total_refresh_bytes = 0

    def _target_shape(self, name: str, host: np.ndarray) -> tuple:
        rows = 1 << max(3, int(host.shape[0] - 1).bit_length())
        if name == "page_table":
            return (rows, 1 << max(0, int(host.shape[1] - 1).bit_length()))
        return (rows,) + host.shape[1:]

    def _padded(self, name: str, host: np.ndarray) -> np.ndarray:
        """Host array padded to its device shape, a private copy either
        way: the snapshot arena mutates its host arrays in place."""
        tgt = self._target_shape(name, host)
        if tgt == host.shape:
            return np.array(host, copy=True)
        out = np.full(tgt, self._FILL[name], host.dtype)
        out[tuple(slice(0, s) for s in host.shape)] = host
        return out

    @staticmethod
    def _table_cols(snap: GraphSnapshot) -> int:
        """Device width of the page table: the longest chain, rounded up
        to a power of two. The mirror holds every page of every chain —
        the samplers search a node's whole history by time — and the
        rounding makes the table's shape, and the fused sampler's
        compiles, change O(log P) times as the longest chain grows, and
        gives the binary searches whole lifting steps. A dense (N, width)
        table costs 4 bytes a cell: 5.6 MB for 10,984 nodes at 128
        columns. It passes 1 GB near 2 million nodes at that width, or
        once the longest chain passes 16,384 pages (a million edges of
        one node at threshold 64: 1.4 GB over 10,984 nodes)."""
        longest = int(snap.node_npages[:snap.n_live].max(initial=1))
        return 1 << max(0, longest - 1).bit_length()

    def _arrays(self, snap: GraphSnapshot) -> dict:
        # the snapshot's own table keeps spare columns past the longest
        # chain: they are NULL, and the device width covers the chains
        out = {"page_table": snap.page_table[:, :self._table_cols(snap)]}
        out.update({d: getattr(snap, a) for d, a in self._CELLS.items()})
        out.update({d: getattr(snap, d) for d in self._DESC})
        return out

    def _upload_full(self, snap: GraphSnapshot) -> None:
        host = self._arrays(snap)
        self.dev = {k: jnp.asarray(self._padded(k, a))
                    for k, a in host.items()}
        self.last_refresh_bytes += sum(a.nbytes for a in host.values())

    def _full(self, name: str, host: np.ndarray, n: int,
              of: int) -> bool:
        """Re-upload ``host`` whole where the device buffer no longer has
        its shape (geometric growth, a new table-width bucket) or the
        ``n`` changed entries cover most of its ``of``; True where it
        did, or where nothing changed."""
        dev = self.dev[name]
        tgt = self._target_shape(name, host)
        if dev.shape == tgt and n == 0:
            return True
        if dev.shape != tgt or n * 2 >= of:
            self.dev[name] = jnp.asarray(self._padded(name, host))
            self.last_refresh_bytes += host.nbytes
            return True
        return False

    @staticmethod
    def _pad_pow2(*idx: np.ndarray) -> list:
        """Index arrays padded to a power-of-two length by repeating the
        first entry (idempotent), so the scatters trace O(log P) times."""
        n = len(idx[0])
        pad = (1 << (n - 1).bit_length()) - n
        return [np.concatenate([a, np.full(pad, a[0], a.dtype)])
                for a in idx]

    def _scatter_rows(self, name: str, host: np.ndarray,
                      rows: np.ndarray) -> None:
        """Mirror whole changed rows of a 2-D array (page-table rows,
        validity rows flipped by deletions). A page table that needs a
        wider bucket widens on the device: only chains that grew past
        the old width hold entries there, and their rows are changed."""
        dev = self.dev[name]
        tgt = self._target_shape(name, host)
        if dev.shape[0] == tgt[0] and dev.shape[1] < tgt[1]:
            self.dev[name] = _widen(dev, tgt[1])
        if self._full(name, host, len(rows), host.shape[0]):
            return
        dev = self.dev[name]
        [rows_p] = self._pad_pow2(rows)
        upd = host[rows_p]
        if dev.shape[1] != upd.shape[1]:
            # the page table's device width is a power of two: pad the
            # gathered rows out to it (chains that long do not exist yet)
            wide = np.full((len(rows_p), dev.shape[1]), self._FILL[name],
                           host.dtype)
            wide[:, :upd.shape[1]] = upd
            upd = wide
        self.dev[name] = _scatter_rows(dev, jnp.asarray(rows_p, jnp.int32),
                                       jnp.asarray(upd))
        self.last_refresh_bytes += upd.nbytes + rows_p.size * 4

    def _scatter_entries(self, hosts: dict, *idx: np.ndarray) -> None:
        """Mirror the same changed entries of several arrays — (page,
        lane) cells of the append-only page arrays (only the lanes filled
        since the last refresh move over the wire), or rows of the page
        descriptors — packed into one upload and one dispatch."""
        todo = [k for k, h in hosts.items()
                if not self._full(k, h, len(idx[0]), h.size)]
        if not todo:
            return
        idx_p = self._pad_pow2(*idx)
        at = tuple(idx_p)
        bits = np.stack([_bits(hosts[k][at]) for k in todo], axis=1)
        where = np.stack(idx_p, axis=1).astype(np.int32)
        out = _scatter_group(tuple(self.dev[k] for k in todo),
                             jnp.asarray(where), jnp.asarray(bits))
        self.dev.update(zip(todo, out))
        self.last_refresh_bytes += bits.nbytes + where.nbytes

    def sync(self, snap: GraphSnapshot) -> dict:
        """Bring the mirror to ``snap``'s version; returns the device
        dict reflecting exactly that version."""
        if (self.dev is not None and self.snap_obj is snap
                and self.version == snap.version):
            self.last_refresh_bytes = 0   # in sync: nothing transferred
            return self.dev
        self.last_refresh_bytes = 0
        d = snap.delta
        if (self.dev is None or d is None or d.full
                or self.snap_obj is not snap
                or d.base_version != self.version):
            self._upload_full(snap)
        else:
            # fresh dict per version: readers of the previous dict
            # (pinned query handles) keep the old arrays
            self.dev = dict(self.dev)
            host = self._arrays(snap)
            self._scatter_rows("page_table", host["page_table"],
                               d.table_rows)
            self._scatter_entries({k: host[k] for k in self._CELLS},
                                  d.cell_rows, d.cell_lanes)
            # deletions/offloads flip validity outside the appended
            # cells: those pages re-upload their (small) validity rows
            self._scatter_rows("pages_valid", host["pages_valid"],
                               d.valid_rows)
            self._scatter_entries({k: host[k] for k in self._DESC},
                                  d.page_rows)
        self.version = snap.version
        self.snap_obj = snap
        self.total_refresh_bytes += self.last_refresh_bytes
        return self.dev


def sample_khop(dev: dict, seeds, seed_ts, *, fanouts: Sequence[int],
                policy: str = "recent", window: float = 0.0,
                scan_pages: int = 16, use_pallas: bool = False,
                key=None) -> List[SampledLayer]:
    """Fused k-hop sampling against an explicit device mirror dict.

    The serving read path (``repro.serve``) dispatches through this
    against a *pinned* handle's arrays — same jitted program as
    ``TemporalSampler.sample`` (the jit cache is shared), but the
    caller controls which snapshot version answers."""
    targets = jnp.asarray(seeds, jnp.int32)
    times = jnp.asarray(seed_ts, jnp.float32)
    tmask = jnp.ones(targets.shape, bool)
    if key is None:
        key = _zero_key()
    raw = _sample_khop(dev, targets, times, tmask, key,
                       fanouts=tuple(int(f) for f in fanouts),
                       policy=policy, window=float(window),
                       scan_pages=int(scan_pages), use_pallas=use_pallas)
    return [SampledLayer(*h) for h in raw]


class TemporalSampler:
    """Paper's sampler: recent / uniform / window policies, k-hop.

    Device-resident incremental pipeline: the paged snapshot lives in
    the graph's one device ``mirror`` (:class:`DeviceMirror`);
    ``refresh()`` applies the snapshot's ``SnapshotDelta`` as
    copy-on-write row/cell scatters instead of re-uploading, and
    ``sample()`` runs the whole k-hop loop as a single jitted dispatch.
    The serving wing publishes this same mirror."""

    def __init__(self, g_or_snap, fanouts: Sequence[int],
                 policy: str = "recent", window: float = 0.0,
                 scan_pages: int = 16, use_pallas: bool = False,
                 seed: int = 0, device=None):
        if isinstance(g_or_snap, DynamicGraph):
            self.snap = build_snapshot(g_or_snap)
        else:
            self.snap = g_or_snap
        self.fanouts = tuple(int(f) for f in fanouts)
        assert policy in ("recent", "uniform", "window")
        self.policy = policy
        self.window = float(window)
        self.scan_pages = int(scan_pages)
        self.use_pallas = use_pallas
        # optional device pin for the mirror + all sampling dispatches.
        # The multihost launch serves this sampler to REMOTE trainers
        # from an RPC thread while the local trainer's shard_map step
        # may be blocked in a cross-process collective on the mesh
        # devices; pinning sampling to a spare device keeps served hops
        # from queueing behind that blocked collective (a head-of-line
        # deadlock: the peer can't finish staging without our sampler,
        # and our collective can't finish without the peer's step).
        self.device = device
        self._key = jax.random.PRNGKey(seed)
        # request-keyed derivation base (never advanced): stochastic
        # hops served for a DISTRIBUTED trainer fold (requesting
        # machine, request seq, hop) into this so results are
        # independent of request arrival order across processes
        self.base_key = self._key
        # the graph's one device mirror (the serving wing publishes it)
        self.mirror = DeviceMirror()

    def _on_device(self):
        """Placement scope for mirror uploads + sampling dispatches."""
        return (jax.default_device(self.device)
                if self.device is not None
                else contextlib.nullcontext())

    def refresh(self, snap: GraphSnapshot) -> None:
        """Adopt a refreshed snapshot and sync the device mirror (delta
        scatter when the snapshot's delta chains from our version; full
        upload otherwise)."""
        with trace.span("sampler.refresh") as sp:
            self.snap = snap
            with self._on_device():
                self.mirror.sync(snap)
            sp.set(bytes=self.mirror.last_refresh_bytes)

    # -- sampling ------------------------------------------------------
    def request_key(self, req_machine: int, seq: int, hop: int):
        """Order-independent RNG key for one served stochastic hop:
        ``fold_in`` of (requesting machine, that requester's request
        seq, hop index) on this sampler's base key.  The serving
        sampler is already (machine, rank)-seeded, so the full request
        coordinate (machine, rank, hop, seq) determines the key and
        concurrent requesters cannot perturb each other's draws.
        Returns None for the deterministic ``recent`` policy."""
        if self.policy not in ("uniform", "window"):
            return None
        key = jax.random.fold_in(self.base_key, req_machine)
        key = jax.random.fold_in(key, seq)
        return jax.random.fold_in(key, hop)

    def _dispatch(self, targets, times, tmask,
                  fanouts: Optional[Tuple[int, ...]] = None, key=None):
        dev = self.mirror.sync(self.snap)
        if key is not None:
            sub = key
        elif self.policy in ("uniform", "window"):
            # legacy call-order stream (single-host sampling path)
            self._key, sub = jax.random.split(self._key)
        else:
            # deterministic policy: skip the per-call host-side split
            sub = _zero_key()
        return _sample_khop(
            dev, targets, times, tmask, sub,
            fanouts=self.fanouts if fanouts is None else fanouts,
            policy=self.policy, window=self.window,
            scan_pages=self.scan_pages,
            use_pallas=self.use_pallas)

    def sample_hop(self, targets, times, tmask, k: int, key=None):
        """One hop for (padded) targets; returns (nbr, eid, ts, mask).
        ``key`` overrides the sampler-local RNG stream with a
        request-derived key (see :meth:`request_key`)."""
        with self._on_device():
            targets = jnp.asarray(targets, jnp.int32)
            times = jnp.asarray(times, jnp.float32)
            tmask = jnp.asarray(tmask, bool)
            [(_, _, _, nbr, eid, ts, m, _)] = self._dispatch(
                targets, times, tmask, fanouts=(int(k),), key=key)
        return nbr, eid, ts, m

    def sample(self, seeds, seed_ts) -> List[SampledLayer]:
        """k-hop sampling in ONE jitted dispatch; returns one
        SampledLayer per fanout entry."""
        with trace.span("sampler.sample", seeds=len(seeds)), \
                self._on_device():
            targets = jnp.asarray(seeds, jnp.int32)
            times = jnp.asarray(seed_ts, jnp.float32)
            tmask = jnp.ones(targets.shape, bool)
            return [SampledLayer(*h)
                    for h in self._dispatch(targets, times, tmask)]
