"""Delta-upload protocol: a sampler's device mirror, updated only through
SnapshotDeltas, must stay bit-identical to a from-scratch build_snapshot
upload across arbitrary interleavings of add_edges / delete_edges /
offload_older_than — including the page-table width-growth, node/page
capacity-growth, and tau-change (full rebuild) fallback paths."""
import numpy as np
import pytest

from repro.core.dgraph import NULL, DynamicGraph
from repro.core.sampling import TemporalSampler
from repro.core.snapshot import build_snapshot, refresh_snapshot


def _assert_dev_equals_fresh(smp: TemporalSampler, g: DynamicGraph):
    """Device arrays == from-scratch snapshot on every live row; spare
    capacity rows in the page table must be empty (NULL) because the
    sampler clip-gathers them for out-of-range targets."""
    dev = smp.mirror.sync(smp.snap)
    fresh = build_snapshot(g, page_cap=smp.snap.page_cap)
    nb, n = fresh.n_pages, fresh.n_live
    width = fresh.page_table.shape[1]
    pt = np.asarray(dev["page_table"])
    # the mirror holds only the scan_pages-newest page columns
    w = min(pt.shape[1], width)
    assert pt.shape[0] >= n
    np.testing.assert_array_equal(pt[:n, :w], fresh.page_table[:n, :w])
    assert (pt[:n, w:] == NULL).all()
    assert (pt[n:] == NULL).all()
    # validity must match exactly; payload lanes only matter where valid
    # (offload/delete leave stale payload behind valid=False — samplers
    # never read through an invalid lane)
    v = fresh.valid[:nb]
    d_nbr = np.asarray(dev["pages_nbr"])
    d_eid = np.asarray(dev["pages_eid"])
    d_ts = np.asarray(dev["pages_ts"])
    d_val = np.asarray(dev["pages_valid"])
    if "page_tmin" in dev:                    # pallas-path descriptors
        np.testing.assert_array_equal(np.asarray(dev["page_tmin"])[:nb],
                                      fresh.page_tmin[:nb])
        np.testing.assert_array_equal(np.asarray(dev["page_tmax"])[:nb],
                                      fresh.page_tmax[:nb])
    np.testing.assert_array_equal(d_val[:nb], v)
    for name, got, host in (("nbr", d_nbr, fresh.nbr),
                            ("eid", d_eid, fresh.eid),
                            ("ts", d_ts, fresh.ts)):
        np.testing.assert_array_equal(got[:nb][v], host[:nb][v],
                                      err_msg=name)


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_interleaved_rounds_delta_equals_fresh(tmp_path, seed,
                                               use_pallas):
    rng = np.random.default_rng(seed)
    g = DynamicGraph(threshold=8, min_block=2,
                     undirected=(seed % 2 == 0))
    t = 0.0
    snap = smp = None
    for r in range(8):
        n_ev = int(rng.integers(20, 120))
        nmax = 30 + r * 10        # widening node space: capacity growth
        src = rng.integers(0, nmax, n_ev)
        dst = rng.integers(0, nmax, n_ev)
        ts = np.sort(rng.uniform(t, t + 100, n_ev))
        t += 100.0
        g.add_edges(src, dst, ts)
        if snap is None:
            snap = build_snapshot(g)
            smp = TemporalSampler(snap, (4,), policy="recent",
                                  use_pallas=use_pallas)
        else:
            snap = refresh_snapshot(g, snap)
            smp.refresh(snap)
        if r % 3 == 1:
            live = g.eid[:g.arena_used][g.valid[:g.arena_used]]
            if len(live):
                kill = rng.choice(np.unique(live),
                                  size=min(7, len(np.unique(live))),
                                  replace=False)
                g.delete_edges(kill)
                snap = refresh_snapshot(g, snap)
                smp.refresh(snap)
        if r == 5:
            g.offload_older_than(t - 300.0, tmp_path / f"off{seed}.npz")
            snap = refresh_snapshot(g, snap)
            smp.refresh(snap)
        _assert_dev_equals_fresh(smp, g)


def test_width_growth_path():
    """A hub whose page chain lengthens every round forces page-table
    width growth; the delta path must survive the reallocation."""
    g = DynamicGraph(threshold=4, min_block=4)
    g.add_edges(np.zeros(4, np.int64), np.arange(4), np.arange(4.0))
    snap = build_snapshot(g)
    smp = TemporalSampler(snap, (3,), policy="recent")
    for r in range(1, 8):
        ts = 4.0 * r + np.arange(4.0)
        g.add_edges(np.zeros(4, np.int64), np.arange(4), ts)
        snap = refresh_snapshot(g, snap)
        smp.refresh(snap)
        _assert_dev_equals_fresh(smp, g)
    assert snap.page_table.shape[1] > 1


def test_tau_change_fallback_rebuilds():
    """Adaptive block caps outgrowing the snapshot's page_cap trigger the
    full-rebuild fallback; the sampler must detect delta.full and
    re-upload rather than scattering stale rows."""
    g = DynamicGraph(threshold=64, min_block=4)
    # tiny degrees -> page_cap rounds up to 8
    g.add_edges(np.arange(10), np.arange(10) + 1, np.arange(10.0))
    snap = build_snapshot(g)
    assert snap.page_cap == 8
    smp = TemporalSampler(snap, (4,), policy="recent")
    smp.sample(np.arange(10), np.full(10, 100.0))
    # one node gains enough degree that its next block cap > page_cap
    g.add_edges(np.zeros(40, np.int64), np.arange(40),
                10.0 + np.arange(40.0))
    snap = refresh_snapshot(g, snap)
    assert snap.delta is not None and snap.delta.full
    assert snap.page_cap > 8
    smp.refresh(snap)
    _assert_dev_equals_fresh(smp, g)


def test_append_only_transfer_bytes_sublinear():
    """Steady-state ingest must upload only the arena suffix that
    changed: per-round H2D bytes stay far below (and don't scale with)
    the full snapshot size."""
    rng = np.random.default_rng(7)
    n_nodes, batch = 200, 400
    g = DynamicGraph(threshold=16)
    t = 0.0

    def add_batch():
        nonlocal t
        src = rng.integers(0, n_nodes, batch)
        dst = rng.integers(0, n_nodes, batch)
        ts = np.sort(rng.uniform(t, t + 10, batch))
        t += 10.0
        g.add_edges(src, dst, ts)

    for _ in range(10):           # warm: most growth happens here
        add_batch()
    snap = build_snapshot(g)
    smp = TemporalSampler(snap, (4,), policy="recent")
    smp.mirror.sync(smp.snap)
    per_round = []
    for _ in range(40):
        add_batch()
        snap = refresh_snapshot(g, snap)
        smp.refresh(snap)
        per_round.append(smp.mirror.last_refresh_bytes)
    full_bytes = (snap.page_table.nbytes + snap.page_tmin.nbytes
                  + snap.page_tmax.nbytes + snap.nbr.nbytes
                  + snap.eid.nbytes + snap.ts.nbytes + snap.valid.nbytes)
    early = sorted(per_round[5:15])[5]
    steady = sorted(per_round[-10:])[5]      # median of the last rounds
    # per-round payload is O(batch), not O(graph): it must neither grow
    # with the graph nor stay comparable to a full upload
    assert steady < full_bytes / 4, (steady, full_bytes)
    assert steady < early * 2, (early, steady)
    # and the device mirror is still exact
    _assert_dev_equals_fresh(smp, g)


def test_rebuilt_snapshot_is_not_mistaken_for_in_sync():
    """Version counters only chain within one refresh lineage: a fresh
    build_snapshot (version 0, like the one already mirrored) must
    force a full upload, not be skipped as already-synced — the
    distributed scheduler rebuilds snapshots from scratch per round."""
    g = DynamicGraph(threshold=8)
    g.add_edges(np.zeros(3, np.int64), np.arange(1, 4),
                np.arange(3, dtype=float))
    smp = TemporalSampler(build_snapshot(g), (4,), policy="recent")
    [l0] = smp.sample(np.array([0]), np.array([100.0]))
    assert np.asarray(l0.mask).sum() == 3
    g.add_edges(np.zeros(1, np.int64), np.array([7]), np.array([50.0]))
    smp.refresh(build_snapshot(g))        # unrelated lineage, version 0
    [l1] = smp.sample(np.array([0]), np.array([100.0]))
    assert np.asarray(l1.mask).sum() == 4
    assert 7 in np.asarray(l1.nbr_ids)[0].tolist()
    _assert_dev_equals_fresh(smp, g)


def test_stale_sampler_falls_back_to_full_upload():
    """A sampler that missed intermediate deltas (version gap) must not
    apply a non-chaining delta; it re-uploads and stays correct."""
    g = DynamicGraph(threshold=8)
    g.add_edges(np.arange(20), np.arange(20) + 1, np.arange(20.0))
    snap = build_snapshot(g)
    smp = TemporalSampler(snap, (4,), policy="recent")
    smp.mirror.sync(smp.snap)
    for r in range(3):            # refresh the snapshot WITHOUT syncing
        g.add_edges(np.arange(20), np.arange(20) + 1,
                    20.0 * (r + 1) + np.arange(20.0))
        snap = refresh_snapshot(g, snap)
    smp.refresh(snap)             # delta chains v2->v3 but mirror is v0
    _assert_dev_equals_fresh(smp, g)
