"""Online serving wing (repro.serve).

Pins the PR's contracts:

* versioned read handles — a query admitted concurrently with ingest
  observes exactly ONE snapshot version (never a half-applied delta),
  property-tested by interleaving real ingest with live queries and
  replaying every response's neighborhood against the graph rebuilt at
  the response's version;
* copy-on-write handle pinning — old handles keep answering
  bit-identically after arbitrarily many newer deltas publish;
* served scores == an offline forward on the pinned handle (≤ 1e-4,
  exact in practice), including the TGN committed-memory path;
* batched admission (one jit dispatch per admitted batch, all
  responses in a batch share a version) and EdgeBank fallback under
  saturation;
* EdgeBank correctness against a brute-force recency table.
"""
import gc
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.tgn_gdelt import tgat, tgn
from repro.core.continuous import ContinuousTrainer
from repro.core.dgraph import DynamicGraph
from repro.core.sampling import DeviceMirror, oracle_sample, sample_khop
from repro.core.snapshot import build_snapshot, refresh_snapshot
from repro.data.events import synth_ctdg
from repro.obs import trace
from repro.serve import (AdmissionQueue, EdgeBank, HandlePublisher,
                         Query, QueryEngine, QueryFuture)

D = 4  # feature dims for every trainer in this file


def _cfg(**kw):
    base = dict(d_node=D, d_edge=D, d_time=4, d_hidden=8, fanouts=(4,),
                sampling="recent", batch_size=32)
    base.update(kw)
    return tgat(**base)


def _trainer(stream, cfg=None):
    return ContinuousTrainer(cfg or _cfg(), stream, threshold=8,
                             cache_ratio=0.2)


# ---------------------------------------------------------------------------
# EdgeBank vs brute force
# ---------------------------------------------------------------------------


def _brute_predict(src, dst, ts, q_src, q_dst, q_ts, *, window,
                   undirected):
    out = np.zeros(len(q_src), np.float32)
    for i, (u, v, t) in enumerate(zip(q_src, q_dst, q_ts)):
        last = None
        for a, b, et in zip(src, dst, ts):
            hit = (a == u and b == v) or (undirected and a == v and b == u)
            if hit:
                last = et if last is None else max(last, et)
        if last is None:
            continue
        if window > 0 and last < t - window:
            continue
        out[i] = 1.0
    return out


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.booleans(),
       st.sampled_from([0.0, 15.0, 60.0]))
def test_edgebank_matches_bruteforce(seed, undirected, window):
    rng = np.random.default_rng(seed)
    n, q = 80, 40
    src = rng.integers(0, 12, n)
    dst = rng.integers(0, 12, n)
    ts = np.sort(rng.uniform(0, 100, n))
    bank = EdgeBank(window=window, undirected=undirected)
    # fold in over several batches (the ingest shape)
    for lo in range(0, n, 17):
        bank.update(src[lo:lo + 17], dst[lo:lo + 17], ts[lo:lo + 17])
    q_src = rng.integers(0, 14, q)          # some never-seen nodes
    q_dst = rng.integers(0, 14, q)
    q_ts = rng.uniform(50, 150, q)
    got = bank.predict(q_src, q_dst, q_ts)
    want = _brute_predict(src, dst, ts, q_src, q_dst, q_ts,
                          window=window, undirected=undirected)
    np.testing.assert_array_equal(got, want)
    # count signal agrees with a direct tally
    cnt = bank.counts(q_src[:5], q_dst[:5])
    for i in range(5):
        same = (src == q_src[i]) & (dst == q_dst[i])
        if undirected:
            same |= (src == q_dst[i]) & (dst == q_src[i])
        assert cnt[i] == int(same.sum())


# ---------------------------------------------------------------------------
# versioned read handles: copy-on-write pinning
# ---------------------------------------------------------------------------


def test_pinned_handle_survives_later_deltas():
    """Sampling against a pinned handle is bit-identical before and
    after newer versions publish — the mirror's scatters write new
    buffers and leave the pinned ones as they were."""
    stream = synth_ctdg(n_nodes=40, n_events=300, d_node=D, d_edge=D,
                        seed=3)
    g = DynamicGraph(threshold=8, undirected=True)
    g.add_edges(stream.src[:100], stream.dst[:100], stream.ts[:100])
    snap = build_snapshot(g)
    pub = HandlePublisher(DeviceMirror(), scan_pages=16)
    hA = pub.publish(snap, n_events=100)
    seeds = np.arange(12, dtype=np.int64)
    t_hi = np.full(12, float(stream.ts.max()) + 1, np.float32)

    def hop0(handle):
        layers = sample_khop(handle.dev, seeds, t_hi, fanouts=(4,),
                             policy="recent", scan_pages=16)
        l0 = layers[0]
        return (np.asarray(l0.nbr_ids), np.asarray(l0.nbr_ts),
                np.asarray(l0.mask))

    before = hop0(hA)
    # publish several newer versions through the SAME publisher
    for lo in (100, 150, 200, 250):
        g.add_edges(stream.src[lo:lo + 50], stream.dst[lo:lo + 50],
                    stream.ts[lo:lo + 50])
        snap = refresh_snapshot(g, snap)
        pub.publish(snap, n_events=lo + 50)
    after = hop0(hA)                        # same pinned handle
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    assert pub.current().version > hA.version
    # the new handle really sees the new edges: hop counts can only grow
    newest = hop0(pub.current())
    assert newest[2].sum() >= before[2].sum()
    # history retains the pinned version for offline replay
    assert pub.get(hA.version) is hA


def test_trainer_and_engine_share_one_mirror():
    """The serving wing publishes the trainer's own device mirror: the
    published arrays ARE the sampler's, a publish moves no bytes of its
    own, and a handle pinned before a later ingest samples
    bit-identically after it."""
    stream = synth_ctdg(n_nodes=40, n_events=300, d_node=D, d_edge=D,
                        seed=13)
    tr = _trainer(stream)
    eng = QueryEngine.attach(tr, start=False)
    mirror = tr.sampler.mirror
    tr.ingest(stream.slice(0, 150))
    h = eng.publisher.current()
    assert h.dev is mirror.dev and h.version == mirror.version
    assert mirror.total_refresh_bytes == tr._refresh_bytes > 0
    seeds = np.arange(12, dtype=np.int64)
    t_hi = np.full(12, float(stream.ts.max()) + 1, np.float32)

    def hop0(handle):
        [l0] = sample_khop(handle.dev, seeds, t_hi, fanouts=(4,),
                           policy="recent", scan_pages=handle.scan_pages)
        return [np.asarray(a)
                for a in (l0.nbr_ids, l0.nbr_eids, l0.nbr_ts, l0.mask)]

    before = hop0(h)
    tr.ingest(stream.slice(150, 300))
    new = eng.publisher.current()
    assert new.version > h.version and new.dev is mirror.dev
    assert mirror.total_refresh_bytes == tr._refresh_bytes
    for a, b in zip(before, hop0(h)):
        np.testing.assert_array_equal(a, b)
    # ... while the newest handle sees the second ingest's edges
    assert not all(np.array_equal(a, b)
                   for a, b in zip(before, hop0(new)))
    eng.stop()


# ---------------------------------------------------------------------------
# ingest || query: every response consistent with exactly one version
# ---------------------------------------------------------------------------


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10_000))
def test_interleaved_add_delete_query_consistency(seed):
    """Property at the mirror level: a mutator thread applies add AND
    delete batches (each published as a new version) while this thread
    samples pinned handles; every sample must equal the oracle on the
    graph replayed to exactly that version's operation prefix."""
    n_nodes = 40
    stream = synth_ctdg(n_nodes=n_nodes, n_events=240, d_node=D,
                        d_edge=D, seed=seed)
    rng = np.random.default_rng(seed + 1)
    g = DynamicGraph(threshold=8, undirected=True)
    pub = HandlePublisher(DeviceMirror(), scan_pages=16, history=64)
    oplog = []
    version_ops = {}
    vlock = threading.Lock()
    snap = None

    def _replay(ops):
        gg = DynamicGraph(threshold=8, undirected=True)
        for op in ops:
            if op[0] == "add":
                _, lo, hi = op
                gg.add_edges(stream.src[lo:hi], stream.dst[lo:hi],
                             stream.ts[lo:hi])
            else:
                gg.delete_edges(op[1])
        return gg

    def _apply(op):
        nonlocal snap
        if op[0] == "add":
            _, lo, hi = op
            g.add_edges(stream.src[lo:hi], stream.dst[lo:hi],
                        stream.ts[lo:hi])
        else:
            g.delete_edges(op[1])
        oplog.append(op)
        snap = (build_snapshot(g) if snap is None
                else refresh_snapshot(g, snap))
        h = pub.publish(snap)
        with vlock:
            version_ops[h.version] = len(oplog)

    _apply(("add", 0, 40))
    ops = []
    inserted = 40
    for lo in range(40, 240, 40):
        ops.append(("add", lo, lo + 40))
        inserted = lo + 40
        ops.append(("del", rng.integers(0, inserted, 6)))

    t_hi = np.full(3, float(stream.ts.max()) + 1, np.float32)
    seeds0 = np.zeros(3, np.int64)
    sample_khop(pub.current().dev, seeds0, t_hi, fanouts=(4,))  # warm jit

    th = threading.Thread(target=lambda: [_apply(op) for op in ops])
    taken = []
    th.start()
    while th.is_alive():
        h = pub.current()
        seeds = rng.integers(0, n_nodes, 3)
        l0 = sample_khop(h.dev, seeds, t_hi, fanouts=(4,),
                         policy="recent", scan_pages=16)[0]
        taken.append((h.version, seeds, np.asarray(l0.nbr_ids),
                      np.asarray(l0.nbr_ts), np.asarray(l0.mask)))
        time.sleep(0.0003)
    th.join()
    h = pub.current()                       # cover the final version
    seeds = rng.integers(0, n_nodes, 3)
    l0 = sample_khop(h.dev, seeds, t_hi, fanouts=(4,), policy="recent",
                     scan_pages=16)[0]
    taken.append((h.version, seeds, np.asarray(l0.nbr_ids),
                  np.asarray(l0.nbr_ts), np.asarray(l0.mask)))

    assert len({v for v, *_ in taken}) >= 2
    for version, seeds, ids, ts_, mask in taken:
        n_ops = version_ops.get(version)
        assert n_ops is not None, f"unknown version {version} sampled"
        gg = _replay(oplog[:n_ops])
        want = oracle_sample(gg, seeds, t_hi.astype(np.float64),
                             fanouts=(4,), policy="recent")[0]
        w_mask = np.asarray(want.mask)
        np.testing.assert_array_equal(mask, w_mask)
        np.testing.assert_array_equal(ids[w_mask],
                                      np.asarray(want.nbr_ids)[w_mask])
        np.testing.assert_array_equal(ts_[w_mask],
                                      np.asarray(want.nbr_ts)[w_mask])


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10_000))
def test_interleaved_ingest_query_consistency(seed):
    """Property: with ingest running on another thread, every answered
    query's sampled neighborhood equals the oracle's answer on the
    graph REBUILT at exactly the response's version — a torn read
    (mixing deltas from two versions) would match no single prefix."""
    n_nodes, n_events, chunk = 60, 360, 40
    stream = synth_ctdg(n_nodes=n_nodes, n_events=n_events, d_node=D,
                        d_edge=D, seed=seed)
    tr = _trainer(stream)
    eng = QueryEngine.attach(tr, record_neighbors=True, max_batch=4,
                             admit_timeout_s=0.0005)
    version_prefix = {}
    vlock = threading.Lock()

    def _ingest(lo, hi):
        tr.ingest(stream.slice(lo, hi))
        with vlock:
            version_prefix[eng.publisher.current().version] = hi

    _ingest(0, chunk)                       # prime a first version
    rng = np.random.default_rng(seed + 1)
    t_hi = float(stream.ts.max()) + 1.0
    # blocking warm-up query: compiles the jitted sample+forward so the
    # worker keeps pace with the submit loop below
    eng.query_embed(np.zeros(2, np.int64), np.full(2, t_hi, np.float32))

    def _rest():
        for lo in range(chunk, n_events, chunk):
            _ingest(lo, lo + chunk)

    th = threading.Thread(target=_rest)
    pending = []
    th.start()
    while th.is_alive():
        if eng.queue.depth < 64:            # don't outrun the worker
            nodes = rng.integers(0, n_nodes, 2)
            pending.append((nodes, eng.submit_embed(
                nodes, np.full(2, t_hi, np.float32))))
        time.sleep(0.0005)
    th.join()
    nodes = rng.integers(0, n_nodes, 2)     # cover the final version too
    pending.append((nodes, eng.submit_embed(
        nodes, np.full(2, t_hi, np.float32))))
    results = [(nodes, f.result(60)) for nodes, f in pending]
    eng.stop()

    assert len({res.version for _, res in results}) >= 2, \
        "queries never overlapped ingest — no concurrency exercised"
    for nodes, res in results:
        assert res.version in version_prefix, \
            f"response pinned unknown version {res.version}"
        hi = version_prefix[res.version]
        g = DynamicGraph(threshold=8, undirected=True)
        g.add_edges(stream.src[:hi], stream.dst[:hi], stream.ts[:hi])
        want = oracle_sample(g, nodes, np.full(2, t_hi), fanouts=(4,),
                             policy="recent")[0]
        np.testing.assert_array_equal(res.nbrs["mask"],
                                      np.asarray(want.mask))
        m = np.asarray(want.mask)
        np.testing.assert_array_equal(res.nbrs["ids"][m],
                                      np.asarray(want.nbr_ids)[m])
        np.testing.assert_array_equal(res.nbrs["ts"][m],
                                      np.asarray(want.nbr_ts)[m])


# ---------------------------------------------------------------------------
# served scores == offline forward on the pinned handle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", ["tgat", "tgn"])
def test_serving_parity_with_offline_forward(model):
    if model == "tgn":
        cfg = tgn(d_node=D, d_edge=D, d_time=4, d_hidden=8, d_memory=6,
                  fanouts=(4,), sampling="recent", batch_size=32)
    else:
        cfg = _cfg()
    stream = synth_ctdg(n_nodes=50, n_events=300, d_node=D, d_edge=D,
                        seed=5)
    tr = _trainer(stream, cfg)
    eng = QueryEngine.attach(tr, max_batch=8)
    tr.train_round(stream.slice(0, 150), epochs=1)
    tr.train_round(stream.slice(150, 300), epochs=1)

    t_q = float(stream.ts.max()) + 1.0
    src, dst = np.array([1, 2, 3]), np.array([4, 5, 6])
    res = eng.query_link(src, dst, np.full(3, t_q, np.float32))
    assert res.tier == "gnn"
    off = eng.offline_forward(res.version, src, dst,
                              np.full(3, t_q, np.float32))
    np.testing.assert_allclose(res.scores, off, atol=1e-4)

    emb = eng.query_embed(np.array([7, 8]), np.full(2, t_q, np.float32))
    assert emb.emb.shape == (2, cfg.d_hidden)
    off_e = eng.offline_forward(emb.version, np.array([7, 8]),
                                ts=np.full(2, t_q, np.float32))
    np.testing.assert_allclose(emb.emb, off_e, atol=1e-4)
    eng.stop()


def test_params_refresh_after_round_changes_scores():
    """on_params installs the finetuned weights: the same query scores
    differently (same version pinning rules) after a train round."""
    stream = synth_ctdg(n_nodes=50, n_events=300, d_node=D, d_edge=D,
                        seed=9)
    tr = _trainer(stream)
    eng = QueryEngine.attach(tr, max_batch=8)
    tr.ingest(stream.slice(0, 200))
    q = (np.array([1]), np.array([2]),
         np.full(1, float(stream.ts.max()) + 1, np.float32))
    s0 = eng.query_link(*q).scores
    tr.train_round(stream.slice(200, 300), epochs=2)
    s1 = eng.query_link(*q).scores
    assert not np.allclose(s0, s1)
    eng.stop()


# ---------------------------------------------------------------------------
# batched admission + EdgeBank saturation tier
# ---------------------------------------------------------------------------


def test_admission_batches_share_version_and_dispatch():
    stream = synth_ctdg(n_nodes=50, n_events=200, d_node=D, d_edge=D,
                        seed=11)
    tr = _trainer(stream)
    eng = QueryEngine.attach(tr, max_batch=8, admit_timeout_s=0.01,
                             start=False)       # worker not running yet
    tr.ingest(stream.slice(0, 200))
    t_q = np.full(1, float(stream.ts.max()) + 1, np.float32)
    futs = [eng.submit_link([i], [i + 1], t_q) for i in range(6)]
    assert all(isinstance(f, QueryFuture) for f in futs)
    assert eng.queue.depth == 6
    eng.start()                                 # one admission batch
    results = [f.result(60) for f in futs]
    assert len({r.version for r in results}) == 1
    assert eng.metrics.counter("serve.batches").value == 1
    assert eng.metrics.histogram("serve.batch_queries").summary()[
        "max"] == 6
    eng.stop()


def test_edgebank_tier_takes_over_when_saturated():
    stream = synth_ctdg(n_nodes=50, n_events=200, d_node=D, d_edge=D,
                        seed=13)
    tr = _trainer(stream)
    bank = EdgeBank()
    eng = QueryEngine.attach(tr, edgebank=bank, saturate_depth=0,
                             start=False)       # depth >= 0: always
    tr.ingest(stream.slice(0, 200))
    assert len(bank) > 0                        # on_publish fed the bank
    u, v = int(stream.src[0]), int(stream.dst[0])
    res = eng.query_link([u, 49], [v, 48],
                         np.full(2, float(stream.ts.max()), np.float32))
    assert res.tier == "edgebank"
    np.testing.assert_array_equal(
        res.scores, bank.predict([u, 49], [v, 48]))
    assert res.scores[0] == 1.0                 # seen edge
    assert eng.metrics.counter("serve.fallback").value == 1
    eng.stop()


def test_admission_queue_backpressure_and_close():
    q = AdmissionQueue(max_batch=4, timeout_s=0.001, max_depth=2)
    mk = lambda: Query("link", np.array([0]), np.array([1]),
                       np.array([0.0], np.float32), QueryFuture(),
                       time.perf_counter())
    assert q.submit(mk()) and q.submit(mk())
    assert not q.submit(mk())                   # depth bound, fail fast
    batch = q.next_batch()
    assert len(batch) == 2
    q.close()
    assert q.next_batch() is None               # drained + closed
    assert not q.submit(mk())                   # closed rejects


# ---------------------------------------------------------------------------
# tracing of the serving worker
# ---------------------------------------------------------------------------


def _served(n_queries, *, max_batch, pause_s=0.0):
    """Queue ``n_queries`` link queries on an idle engine, wait
    ``pause_s``, then start the worker and collect the answers."""
    stream = synth_ctdg(n_nodes=50, n_events=200, d_node=D, d_edge=D,
                        seed=17)
    tr = _trainer(stream, tgn(d_node=D, d_edge=D, d_time=4, d_hidden=8,
                              d_memory=6, fanouts=(4,), batch_size=32))
    eng = QueryEngine.attach(tr, max_batch=max_batch, admit_timeout_s=0.01,
                             start=False)
    tr.ingest(stream.slice(0, 200))
    t_q = np.full(1, float(stream.ts.max()) + 1, np.float32)
    futs = [eng.submit_link([i], [i + 1], t_q) for i in range(n_queries)]
    time.sleep(pause_s)
    eng.start()
    try:
        return [f.result(60) for f in futs]
    finally:
        eng.stop()


def test_untraced_served_batch_records_nothing():
    trace.disable()
    trace.reset()
    callbacks = list(gc.callbacks)
    results = _served(3, max_batch=8)
    gc.collect()
    assert len(results) == 3
    assert trace.events() == []
    assert gc.callbacks == callbacks


def test_serve_batch_records_queue_wait_and_depth():
    trace.disable()
    trace.reset()
    trace.enable()
    try:
        results = _served(6, max_batch=4, pause_s=0.05)
    finally:
        trace.disable()
    evs = trace.events()
    trace.reset()
    assert len(results) == 6
    batches = sorted((e for e in evs if e["kind"] == "serve.batch"),
                     key=lambda e: e["ts_us"])
    assert [b["args"]["queries"] for b in batches] == [4, 2]
    # the first batch left two queries behind it; all waited the pause
    assert [b["args"]["depth"] for b in batches] == [2, 0]
    for b in batches:
        a = b["args"]
        assert a["queue_wait_ms_max"] >= 50.0
        assert a["queries"] * 50.0 <= a["queue_wait_ms_sum"] \
            <= a["queries"] * a["queue_wait_ms_max"] + 1e-9
    # the engine's device reads sit under its batch spans
    worker = {b["tid"] for b in batches}
    d2h = [e for e in evs if e["kind"] == "xfer.d2h" and e["tid"] in worker]
    assert d2h and {e["args"]["parent"] for e in d2h} <= {
        "serve.batch", "serve.fetch", "serve.forward"}
    assert "serve.forward" in {e["args"]["parent"] for e in d2h}
    # the TGN memory rows are read from the host state inside the fetch
    gathers = [e for e in evs if e["kind"] == "state.gather"
               and e["tid"] in worker]
    assert "serve.fetch" in {e["args"]["parent"] for e in gathers}
