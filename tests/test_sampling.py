"""Temporal k-hop sampling: oracle vs vectorized-jnp vs Pallas kernel."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.dgraph import NULL, DynamicGraph
from repro.core.sampling import TemporalSampler, oracle_sample
from repro.core.snapshot import build_snapshot


def _graph(n_events=600, n_nodes=40, tau=8, seed=0, undirected=False):
    rng = np.random.default_rng(seed)
    # power-law-ish degree: preferential source choice
    src = rng.zipf(1.6, n_events) % n_nodes
    dst = rng.integers(0, n_nodes, n_events)
    ts = np.sort(rng.uniform(0, 1000.0, n_events))
    g = DynamicGraph(threshold=tau, min_block=2, undirected=undirected)
    g.add_edges(src, dst, ts)
    return g, src, dst, ts


def _sorted_rows(layer):
    """Canonical per-row sets (order-insensitive comparison)."""
    out = []
    for i in range(layer.nbr_ids.shape[0]):
        m = np.asarray(layer.mask[i])
        rows = sorted(zip(np.asarray(layer.nbr_eids[i])[m].tolist(),
                          np.asarray(layer.nbr_ids[i])[m].tolist()))
        out.append(rows)
    return out


@pytest.mark.parametrize("tau", [2, 8, 64])
def test_recent_jnp_matches_oracle(tau):
    g, src, dst, ts = _graph(tau=tau, seed=1)
    seeds = np.arange(g.n_nodes, dtype=np.int64)
    seed_ts = np.full(len(seeds), 900.0)
    orc = oracle_sample(g, seeds, seed_ts, fanouts=(5, 3),
                        policy="recent")
    smp = TemporalSampler(g, fanouts=(5, 3), policy="recent",
                          scan_pages=512)
    dev = smp.sample(seeds, seed_ts)
    for lo, ld in zip(orc, dev):
        # recent sampling is deterministic: exact equality (as sets per
        # row; ties in timestamps may reorder equal-ts edges)
        np.testing.assert_array_equal(np.asarray(ld.mask).sum(1),
                                      lo.mask.sum(1))
        assert _sorted_rows(lo) == _sorted_rows(ld)


def test_uniform_covers_candidates_only():
    g, src, dst, ts = _graph(seed=2)
    seeds = np.arange(g.n_nodes, dtype=np.int64)
    seed_ts = np.full(len(seeds), 800.0)
    smp = TemporalSampler(g, fanouts=(7,), policy="uniform",
                          scan_pages=512)
    [layer] = smp.sample(seeds, seed_ts)
    nbr = np.asarray(layer.nbr_ids)
    msk = np.asarray(layer.mask)
    tss = np.asarray(layer.nbr_ts)
    for i, v in enumerate(seeds):
        cand_n, cand_e, cand_t = g.neighbors_in_window(int(v), -np.inf,
                                                       800.0)
        got = set(zip(nbr[i][msk[i]].tolist(),
                      np.round(tss[i][msk[i]].astype(np.float64),
                               2).tolist()))
        allowed = set(zip(cand_n.tolist(),
                          np.round(cand_t.astype(np.float32)
                                   .astype(np.float64), 2).tolist()))
        assert got <= allowed
        assert msk[i].sum() == min(7, len(cand_n))


def test_uniform_is_actually_uniform():
    """Chi-squared-ish sanity: each candidate appears with similar freq."""
    g = DynamicGraph(threshold=8)
    g.add_edges(np.zeros(20, np.int64), np.arange(20),
                np.arange(20, dtype=float))
    counts = np.zeros(20)
    for s in range(200):
        smp = TemporalSampler(g, fanouts=(5,), policy="uniform", seed=s,
                              scan_pages=512)
        [layer] = smp.sample(np.array([0]), np.array([100.0]))
        for x in np.asarray(layer.nbr_ids)[0][np.asarray(layer.mask)[0]]:
            counts[x] += 1
    # every candidate sampled at least once; no candidate hogs
    assert (counts > 0).all()
    assert counts.max() / counts.mean() < 2.5


def test_window_policy_respects_window():
    g, src, dst, ts = _graph(seed=3)
    smp = TemporalSampler(g, fanouts=(8,), policy="window", window=50.0,
                          scan_pages=512)
    seeds = np.arange(g.n_nodes, dtype=np.int64)
    [layer] = smp.sample(seeds, np.full(len(seeds), 600.0))
    tss = np.asarray(layer.nbr_ts)
    msk = np.asarray(layer.mask)
    assert ((tss[msk] >= 550.0) & (tss[msk] < 600.0)).all()


def test_khop_times_propagate():
    """Layer l+1 queries at the edge timestamps of layer l (TGAT rule)."""
    g, *_ = _graph(seed=4)
    smp = TemporalSampler(g, fanouts=(4, 4), policy="recent",
                          scan_pages=512)
    layers = smp.sample(np.arange(10, dtype=np.int64), np.full(10, 700.0))
    l0, l1 = layers
    np.testing.assert_allclose(np.asarray(l1.dst_times),
                               np.asarray(l0.nbr_ts).reshape(-1))
    # sampled edges at hop 2 are strictly older than their query time
    m = np.asarray(l1.mask)
    assert (np.asarray(l1.nbr_ts)[m]
            < np.asarray(l1.dst_times)[:, None].repeat(4, 1)[m]).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([2, 8, 32]),
       st.sampled_from([1, 4, 10]))
def test_property_recent_matches_oracle(seed, tau, k):
    rng = np.random.default_rng(seed)
    n_nodes = int(rng.integers(3, 30))
    n_ev = int(rng.integers(5, 200))
    src = rng.integers(0, n_nodes, n_ev)
    dst = rng.integers(0, n_nodes, n_ev)
    ts = np.sort(rng.uniform(0, 100.0, n_ev))
    # strictly increasing timestamps avoid tie-order ambiguity
    ts = ts + np.arange(n_ev) * 1e-4
    g = DynamicGraph(threshold=tau, min_block=1)
    g.add_edges(src, dst, ts)
    seeds = rng.integers(0, n_nodes, 8)
    seed_ts = rng.uniform(0, 120.0, 8)
    orc = oracle_sample(g, seeds, seed_ts, fanouts=(k,), policy="recent")
    smp = TemporalSampler(g, fanouts=(k,), policy="recent",
                          scan_pages=512)
    dev = smp.sample(seeds, seed_ts)
    assert _sorted_rows(orc[0]) == _sorted_rows(dev[0])


def test_pallas_kernel_matches_ref_and_oracle():
    from repro.kernels.temporal_sample.ref import temporal_sample_ref
    import jax.numpy as jnp

    g, *_ = _graph(n_events=300, n_nodes=25, tau=8, seed=5)
    snap = build_snapshot(g)
    seeds = np.arange(25, dtype=np.int64)
    seed_ts = np.full(25, 700.0)
    k = 6

    smp = TemporalSampler(snap, fanouts=(k,), policy="recent",
                          use_pallas=True)
    [lp] = smp.sample(seeds, seed_ts)

    smp2 = TemporalSampler(snap, fanouts=(k,), policy="recent",
                           use_pallas=False, scan_pages=16)
    [lj] = smp2.sample(seeds, seed_ts)
    assert _sorted_rows(lp) == _sorted_rows(lj)

    # and against the pure-jnp kernel ref
    scan = min(16, snap.page_table.shape[1])
    nbr, eid, ts_, m = temporal_sample_ref(
        jnp.asarray(snap.page_table)[:, :scan],
        jnp.asarray(snap.page_tmin), jnp.asarray(snap.page_tmax),
        jnp.asarray(snap.nbr), jnp.asarray(snap.eid),
        jnp.asarray(snap.ts), jnp.asarray(snap.valid),
        jnp.asarray(seeds, jnp.int32), jnp.asarray(seed_ts, jnp.float32),
        jnp.full(25, -jnp.inf, jnp.float32), jnp.ones(25, bool), k=k)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(lp.mask))
    np.testing.assert_array_equal(np.asarray(eid), np.asarray(lp.nbr_eids))


def test_khop_is_single_fused_dispatch():
    """The whole k-hop sample() is ONE jitted dispatch: the trace-count
    probe must tick once for a 3-hop sampler, and steady-state calls
    must not retrace."""
    from repro.core import sampling as S

    g, *_ = _graph(seed=11)
    smp = TemporalSampler(g, fanouts=(4, 3, 2), policy="recent",
                          scan_pages=8)
    seeds = np.arange(16, dtype=np.int64)
    ts = np.full(16, 900.0)
    base = S.TRACE_COUNTS["khop"]
    layers = smp.sample(seeds, ts)
    assert len(layers) == 3
    assert S.TRACE_COUNTS["khop"] == base + 1
    smp.sample(seeds, ts)
    smp.sample(seeds, ts)
    assert S.TRACE_COUNTS["khop"] == base + 1


def test_rng_only_consumed_by_stochastic_policies():
    """recent is deterministic: no per-call host-side key split."""
    g, *_ = _graph(seed=12)
    seeds = np.arange(10, dtype=np.int64)
    ts = np.full(10, 700.0)
    smp = TemporalSampler(g, fanouts=(4,), policy="recent", scan_pages=8)
    k0 = np.asarray(smp._key).copy()
    smp.sample(seeds, ts)
    np.testing.assert_array_equal(np.asarray(smp._key), k0)
    smp_u = TemporalSampler(g, fanouts=(4,), policy="uniform",
                            scan_pages=8)
    k0 = np.asarray(smp_u._key).copy()
    smp_u.sample(seeds, ts)
    assert not np.array_equal(np.asarray(smp_u._key), k0)


@pytest.mark.parametrize("policy,use_pallas", [
    ("recent", False), ("recent", True),
    ("uniform", False), ("uniform", True),
    ("window", False), ("window", True),
])
def test_fused_sampler_agrees_with_oracle(policy, use_pallas):
    """All three policies, jnp and Pallas (interpret) paths, against the
    numpy oracle: recent matches exactly; stochastic policies must pick
    only oracle candidates and the full min(k, n_candidates) of them."""
    g, *_ = _graph(n_events=400, n_nodes=30, tau=8, seed=6)
    window = 80.0 if policy == "window" else 0.0
    seeds = np.arange(g.n_nodes, dtype=np.int64)
    seed_ts = np.full(len(seeds), 800.0)
    k = 5
    smp = TemporalSampler(g, fanouts=(k,), policy=policy, window=window,
                          scan_pages=64, use_pallas=use_pallas)
    [layer] = smp.sample(seeds, seed_ts)
    if policy == "recent":
        [orc] = oracle_sample(g, seeds, seed_ts, (k,), policy="recent")
        assert _sorted_rows(orc) == _sorted_rows(layer)
        return
    nbr = np.asarray(layer.nbr_ids)
    eidm = np.asarray(layer.nbr_eids)
    msk = np.asarray(layer.mask)
    t_lo = 800.0 - window if policy == "window" else -np.inf
    for i, v in enumerate(seeds):
        cand_n, cand_e, _ = g.neighbors_in_window(int(v), t_lo, 800.0)
        got = set(zip(eidm[i][msk[i]].tolist(), nbr[i][msk[i]].tolist()))
        allowed = set(zip(cand_e.tolist(), cand_n.tolist()))
        assert got <= allowed
        assert msk[i].sum() == min(k, len(cand_n))


def test_pallas_uniform_kernel_matches_gumbel_ref():
    """Given identical Gumbel noise, the kernel's page-by-page reservoir
    merge must equal a global Gumbel top-k (the jnp reference)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.temporal_sample.ref import (
        temporal_sample_uniform_ref)
    from repro.kernels.temporal_sample.temporal_sample import (
        temporal_sample_kernel)

    g, *_ = _graph(n_events=300, n_nodes=25, tau=8, seed=8)
    snap = build_snapshot(g)
    N = 25
    S = snap.page_table.shape[1]
    C = snap.ts.shape[1]
    k = 6
    from repro.core.rand import gumbel_noise

    targets = jnp.arange(N, dtype=jnp.int32)
    t_end = jnp.full(N, 700.0, jnp.float32)
    t_start = jnp.full(N, -jnp.inf, jnp.float32)
    tmask = jnp.ones(N, bool)
    noise = gumbel_noise(jax.random.PRNGKey(3), (N, S, C))
    pt = jnp.asarray(snap.page_table)
    nbr, eid, ts_, mask = temporal_sample_kernel(
        pt, jnp.asarray(snap.nbr), jnp.asarray(snap.eid),
        jnp.asarray(snap.ts), jnp.asarray(snap.valid), t_start, t_end,
        k=k, policy="uniform", noise=noise)
    r_nbr, r_eid, r_ts, r_m = temporal_sample_uniform_ref(
        pt, jnp.asarray(snap.page_tmin), jnp.asarray(snap.page_tmax),
        jnp.asarray(snap.nbr), jnp.asarray(snap.eid),
        jnp.asarray(snap.ts), jnp.asarray(snap.valid), targets,
        t_end, t_start, tmask, noise, k=k)
    mask = np.asarray(mask)
    np.testing.assert_array_equal(mask, np.asarray(r_m))
    np.testing.assert_array_equal(np.asarray(eid)[mask],
                                  np.asarray(r_eid)[mask])
    np.testing.assert_array_equal(np.asarray(nbr)[mask],
                                  np.asarray(r_nbr)[mask])
    np.testing.assert_allclose(np.asarray(ts_)[mask],
                               np.asarray(r_ts)[mask], rtol=1e-6)


def test_pallas_uniform_is_actually_uniform():
    """Distributional sanity for the kernel's Gumbel reservoir."""
    g = DynamicGraph(threshold=8)
    g.add_edges(np.zeros(20, np.int64), np.arange(20),
                np.arange(20, dtype=float))
    snap = build_snapshot(g)
    counts = np.zeros(20)
    for s in range(200):
        smp = TemporalSampler(snap, fanouts=(5,), policy="uniform",
                              seed=s, use_pallas=True, scan_pages=16)
        [layer] = smp.sample(np.array([0]), np.array([100.0]))
        for x in np.asarray(layer.nbr_ids)[0][np.asarray(layer.mask)[0]]:
            counts[x] += 1
    assert (counts > 0).all()
    assert counts.max() / counts.mean() < 2.5


@pytest.mark.parametrize("shape", [(3, 4, 2), (17, 8, 10), (30, 16, 5)])
def test_pallas_kernel_shape_sweep(shape):
    """Kernel vs ref across (nodes, tau, k) shapes (deliverable c)."""
    from repro.kernels.temporal_sample.ref import temporal_sample_ref
    from repro.kernels.temporal_sample.ops import temporal_sample_pallas
    import jax.numpy as jnp

    n_nodes, tau, k = shape
    g, *_ = _graph(n_events=20 * n_nodes, n_nodes=n_nodes, tau=tau,
                   seed=sum(shape))
    snap = build_snapshot(g)
    scan = snap.page_table.shape[1]
    seeds = np.arange(n_nodes, dtype=np.int32)
    t_end = np.random.default_rng(0).uniform(200, 1000, n_nodes) \
        .astype(np.float32)
    t_start = np.full(n_nodes, -np.inf, np.float32)
    tmask = np.ones(n_nodes, bool)
    args = (jnp.asarray(snap.page_table), jnp.asarray(snap.page_tmin),
            jnp.asarray(snap.page_tmax), jnp.asarray(snap.nbr),
            jnp.asarray(snap.eid), jnp.asarray(snap.ts),
            jnp.asarray(snap.valid), jnp.asarray(seeds),
            jnp.asarray(t_end), jnp.asarray(t_start), jnp.asarray(tmask))
    got = temporal_sample_pallas(*args, k=k)
    exp = temporal_sample_ref(args[0], *args[1:7], *args[7:], k=k)
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(exp[3]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(exp[1]))
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(exp[2]),
                               rtol=1e-6)
