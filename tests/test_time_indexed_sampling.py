"""The time-indexed page search: samplers see a node's whole history.

``uniform`` and ``window`` draw among every earlier edge of a target,
however many pages deep; ``recent`` takes its window from the page that
holds the target's newest earlier edge; the device mirror holds every
page of every chain and stays exact across page-table width buckets.
"""
import numpy as np
import pytest
from scipy import stats

from repro.core.dgraph import DynamicGraph
from repro.core.sampling import DeviceMirror, TemporalSampler, oracle_sample
from repro.core.snapshot import build_snapshot, refresh_snapshot

SCAN = 4


def _hub(n_edges=400, tau=4, deleted=()):
    """Node 0 with ``n_edges`` edges, one a second, in pages of ``tau``:
    a chain many times ``SCAN`` pages deep."""
    g = DynamicGraph(threshold=tau, min_block=tau)
    g.add_edges(np.zeros(n_edges, np.int64), 1 + np.arange(n_edges),
                np.arange(n_edges, dtype=float))
    if len(deleted):
        g.delete_edges(np.asarray(deleted))
    return g


def _sorted_rows(layer):
    out = []
    for i in range(layer.nbr_ids.shape[0]):
        m = np.asarray(layer.mask[i])
        out.append(sorted(zip(np.asarray(layer.nbr_eids[i])[m].tolist(),
                              np.asarray(layer.nbr_ids[i])[m].tolist())))
    return out


@pytest.mark.parametrize("policy,window", [("uniform", 0.0),
                                           ("window", 150.0)])
def test_uniform_draws_span_deep_chains(policy, window):
    """Every draw an earlier, valid, distinct edge of its target, and
    min(k, candidates) of them, at query times whose candidates lie up
    to 100 pages deep, with deleted edges among them."""
    deleted = np.arange(3, 400, 7)
    g = _hub(deleted=deleted)
    assert g.nblocks[0] >= 20 * SCAN
    k = 6
    times = np.array([2.0, 5.0, 37.0, 120.0, 250.0, 399.0, 400.0, 1e4])
    seeds = np.zeros(len(times), np.int64)
    smp = TemporalSampler(g, fanouts=(k,), policy=policy, window=window,
                          scan_pages=SCAN, seed=3)
    [layer] = smp.sample(seeds, times)
    eid = np.asarray(layer.nbr_eids)
    ts = np.asarray(layer.nbr_ts)
    m = np.asarray(layer.mask)
    gone = set(deleted.tolist())
    for i, t in enumerate(times):
        lo = t - window if window else -np.inf
        cand_n, cand_e, _ = g.neighbors_in_window(0, lo, t)
        got = eid[i][m[i]]
        assert m[i].sum() == min(k, len(cand_e))
        assert len(set(got.tolist())) == len(got)
        assert set(got.tolist()) <= set(cand_e.tolist())
        assert not (set(got.tolist()) & gone)
        assert (ts[i][m[i]] < t).all() and (ts[i][m[i]] >= lo).all()
        assert (eid[i][~m[i]] == -1).all()


def test_uniform_is_uniform_over_the_whole_history():
    """Draw positions over a 100-page history pass a chi-square test
    against uniform, and the oldest page is drawn from as often as any."""
    n, k, rows = 400, 5, 4000
    g = _hub(n_edges=n)
    smp = TemporalSampler(g, fanouts=(k,), policy="uniform",
                          scan_pages=SCAN, seed=11)
    [layer] = smp.sample(np.zeros(rows, np.int64), np.full(rows, 1e4))
    m = np.asarray(layer.mask)
    assert m.all()
    eid = np.asarray(layer.nbr_eids)
    srt = np.sort(eid, axis=1)
    assert (srt[:, 1:] != srt[:, :-1]).all()       # distinct per row
    counts = np.bincount(eid.reshape(-1), minlength=n)
    p = stats.chisquare(counts).pvalue
    assert p > 1e-3, p
    oldest_page = counts[:4].sum()
    expect = rows * k * 4 / n
    assert abs(oldest_page - expect) < 5 * np.sqrt(expect)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_recent_equals_oracle_when_later_edges_fill_many_pages(
        use_pallas):
    """A round's later edges fill far more than ``scan_pages`` pages of a
    node: ``recent`` at the round's earlier times still returns the
    oracle's newest earlier edges (the window starts at the located page,
    not at the newest page)."""
    g = DynamicGraph(threshold=4, min_block=4)
    g.add_edges(np.zeros(30, np.int64), 1 + np.arange(30),
                np.arange(30, dtype=float))
    # the later edges of a round: 20 * SCAN pages after t = 30
    g.add_edges(np.zeros(320, np.int64), 100 + np.arange(320),
                30.0 + np.arange(320, dtype=float))
    g.add_edges(np.array([1, 2]), np.array([0, 0]), np.array([500., 501.]))
    seeds = np.array([0, 0, 0, 0, 1, 2, 0], np.int64)
    times = np.array([0.0, 7.5, 30.0, 31.0, 600.0, 600.0, 349.5])
    k = 5
    smp = TemporalSampler(g, fanouts=(k,), policy="recent",
                          scan_pages=SCAN, use_pallas=use_pallas)
    [got] = smp.sample(seeds, times)
    [want] = oracle_sample(g, seeds, times, (k,), policy="recent")
    assert _sorted_rows(got) == _sorted_rows(want)
    assert np.asarray(got.mask)[2].sum() == k


def _dev_host(mirror):
    return {k: np.asarray(v) for k, v in mirror.dev.items()}


def _brute_before(g, snap):
    """Valid edges in the older pages of each page's chain, walking the
    graph's block links."""
    out = np.zeros(snap.n_pages, np.int64)
    for p in range(snap.n_pages):
        b = g.blk_prev[p]
        while b != -1:
            out[p] += snap.valid[b].sum()
            b = g.blk_prev[b]
    return out


def test_mirror_refresh_across_width_buckets_matches_fresh_upload(
        tmp_path):
    """A hub's chain grows through several power-of-two widths while
    other nodes join, edges are deleted and old blocks offloaded; the
    delta-synced mirror equals a fresh upload of the same snapshot in
    every array, ``page_before`` included."""
    rng = np.random.default_rng(5)
    g = DynamicGraph(threshold=4, min_block=2, undirected=True)
    snap = None
    mirror = DeviceMirror()
    widths = set()
    t = 0.0
    for r in range(12):
        n = 24 + 4 * r
        src = np.concatenate([np.zeros(n, np.int64),
                              rng.integers(1, 10 + 5 * r, n)])
        dst = rng.integers(1, 10 + 5 * r, 2 * n)
        ts = np.sort(rng.uniform(t, t + 10, 2 * n))
        t += 10.0
        g.add_edges(src, dst, ts)
        if r in (4, 9):
            live = np.unique(g.eid[:g.arena_used][g.valid[:g.arena_used]])
            g.delete_edges(rng.choice(live, 9, replace=False))
        if r == 7:
            g.offload_older_than(25.0, tmp_path / "off.npz")
        snap = (build_snapshot(g) if snap is None
                else refresh_snapshot(g, snap))
        dev = mirror.sync(snap)
        widths.add(dev["page_table"].shape[1])
        fresh = DeviceMirror()
        fresh.sync(build_snapshot(g, page_cap=snap.page_cap))
        got, want = _dev_host(mirror), _dev_host(fresh)
        nb, nl = snap.n_pages, snap.n_live
        # rows follow the snapshot's capacity, columns the longest chain
        assert got["page_table"].shape[1] == want["page_table"].shape[1]
        np.testing.assert_array_equal(got["page_table"][:nl],
                                      want["page_table"][:nl])
        assert (got["page_table"][nl:] == -1).all()
        for name in ("page_tmin", "page_tmax", "page_before",
                     "pages_valid"):
            np.testing.assert_array_equal(got[name][:nb], want[name][:nb],
                                          err_msg=name)
        v = want["pages_valid"][:nb]
        for name in ("pages_nbr", "pages_eid", "pages_ts"):
            np.testing.assert_array_equal(got[name][:nb][v],
                                          want[name][:nb][v], err_msg=name)
        np.testing.assert_array_equal(snap.page_before[:nb],
                                      _brute_before(g, snap))
    assert len(widths) >= 3
    assert all(w & (w - 1) == 0 for w in widths)


def test_sampler_hops_span_reads_the_dispatch_counts():
    """With tracing on, assembling a sampled batch records one
    ``sampler.hops`` span whose per-hop args are the live targets, their
    candidates summed and the targets with candidates past their
    ``scan_pages`` newest pages, as the oracle counts them."""
    from repro.core.mfg import assemble
    from repro.obs import trace

    g = _hub(n_edges=120)
    g.add_edges(1 + np.arange(5), np.zeros(5, np.int64),
                200.0 + np.arange(5))
    seeds = np.array([0, 0, 3, 77], np.int64)
    times = np.array([150.0, 10.0, 300.0, 300.0])
    smp = TemporalSampler(g, fanouts=(3, 2), policy="uniform",
                          scan_pages=SCAN, seed=1)
    layers = smp.sample(seeds, times)

    def candidates(targets, ts, live):
        return sum(len(g.neighbors_in_window(int(v), -np.inf, float(t))[0])
                   for v, t, ok in zip(targets, ts, live) if ok)

    fetch = lambda ids: np.zeros((len(ids), 2), np.float32)  # noqa: E731
    trace.enable()
    trace.reset()
    try:
        assemble(layers, fetch, fetch)
    finally:
        spans = [e for e in trace.events() if e["kind"] == "sampler.hops"]
        trace.disable()
    [sp] = spans
    args = sp["args"]
    for h, l in enumerate(layers):
        live = np.asarray(l.dst_mask)
        assert args["targets"][h] == int(live.sum())
        assert args["candidates"][h] == candidates(
            np.asarray(l.dst_nodes), np.asarray(l.dst_times), live)
    # node 0 at t = 150: 150 edges behind 30 pages, all past the newest
    # four; at t = 10 its candidates lie in its 16th page and older
    assert args["deep"][0] == 2
