"""Pipeline engine (repro.core.pipeline): stage ordering under double
buffering, the prefetch/finalize split of FeatureAssembler (TGN memory
blobs must observe the previous step's commit), ragged padding helpers,
and the headline numerics guarantee: pipelined == serial execution,
step for step."""
import gc

import numpy as np


from repro.configs.tgn_gdelt import tgat, tgn
from repro.core.continuous import ContinuousTrainer
from repro.core.pipeline import (FeatureAssembler, PipelineEngine,
                                 pad_tail, pow2_pad_len)
from repro.core.sampling import SampledLayer
from repro.data.events import synth_ctdg
from repro.obs import trace

STREAM = synth_ctdg(n_nodes=160, n_events=1200, t_span=15_000,
                    d_node=8, d_edge=8, seed=9)
WARM, ROUND = 384, 192


# ---------------------------------------------------------------------------
# engine scheduling semantics
# ---------------------------------------------------------------------------


def _traced_engine(overlap):
    calls = []
    eng = PipelineEngine(overlap=overlap)
    out = eng.run(
        [1, 2, 3],
        prefetch=lambda it: (calls.append(("prefetch", it)), it)[1],
        launch=lambda it, st: (calls.append(("launch", it)), it)[1],
        complete=lambda h, it: (calls.append(("complete", it)), h)[1])
    return calls, out


def test_overlap_schedule_order():
    """Double buffering: batch t+1's prefetch runs BEFORE batch t's
    completion (that's the overlap), but launch t+1 runs after it (the
    TGN memory dependency)."""
    calls, out = _traced_engine(overlap=True)
    assert out == [1, 2, 3]
    assert calls == [
        ("prefetch", 1), ("launch", 1),
        ("prefetch", 2), ("complete", 1), ("launch", 2),
        ("prefetch", 3), ("complete", 2), ("launch", 3),
        ("complete", 3)]


def test_serial_schedule_order():
    """overlap=False reproduces the strictly serial pre-pipeline loop."""
    calls, out = _traced_engine(overlap=False)
    assert out == [1, 2, 3]
    assert calls == [
        ("prefetch", 1), ("launch", 1), ("complete", 1),
        ("prefetch", 2), ("launch", 2), ("complete", 2),
        ("prefetch", 3), ("launch", 3), ("complete", 3)]


def test_engine_drains_on_empty_and_single():
    eng = PipelineEngine(overlap=True)
    assert eng.run([], prefetch=lambda i: i, launch=lambda i, s: i,
                   complete=lambda h, i: h) == []
    assert eng.run([7], prefetch=lambda i: i, launch=lambda i, s: i,
                   complete=lambda h, i: h) == [7]


# ---------------------------------------------------------------------------
# FeatureAssembler: prefetch/finalize split
# ---------------------------------------------------------------------------


class _StubMemory:
    """Stands in for TGNMemory: gather() returns the CURRENT version so
    the test can detect when blobs were actually assembled."""

    def __init__(self):
        self.version = 0

    def gather(self, ids, edge_feat_fn):
        return {"v": np.full(len(np.asarray(ids)), self.version)}


def _one_layer_sample(seeds, ts):
    n = len(seeds)
    return [SampledLayer(
        dst_nodes=np.asarray(seeds, np.int32),
        dst_times=np.asarray(ts, np.float32),
        dst_mask=np.ones(n, bool),
        nbr_ids=np.zeros((n, 2), np.int32),
        nbr_eids=np.zeros((n, 2), np.int32),
        nbr_ts=np.zeros((n, 2), np.float32),
        mask=np.ones((n, 2), bool))]


def test_assembler_memory_blobs_are_late_bound():
    """Memory blobs must reflect state at finalize() time (after the
    previous step's commit), not at prefetch() time."""
    cfg = tgat(d_node=4, d_edge=4, d_time=4, d_hidden=8, fanouts=(2,))
    mem = _StubMemory()
    asm = FeatureAssembler(
        cfg, fetch_node=lambda ids: np.zeros((len(ids), 4), np.float32),
        fetch_edge=lambda ids: np.zeros((len(ids), 4), np.float32),
        edge_feat_fn=None, memory=mem)
    assert asm.needs_finalize
    seeds = np.arange(6, dtype=np.int64)
    staged = asm.prefetch(seeds, np.zeros(6, np.float32),
                          _one_layer_sample)
    assert "mem_blobs" not in staged["batch"]
    mem.version = 42                      # the "previous step's commit"
    batch = asm.finalize(staged)
    dstb, nbrb = batch["mem_blobs"][0]
    assert (dstb["v"] == 42).all() and (nbrb["v"] == 42).all()


def test_assembler_passthrough_without_memory():
    cfg = tgat(d_node=4, d_edge=4, d_time=4, d_hidden=8, fanouts=(2,))
    asm = FeatureAssembler(
        cfg, fetch_node=lambda ids: np.zeros((len(ids), 4), np.float32),
        fetch_edge=lambda ids: np.zeros((len(ids), 4), np.float32))
    assert not asm.needs_finalize
    staged = asm.prefetch(np.arange(6, dtype=np.int64),
                          np.zeros(6, np.float32), _one_layer_sample)
    batch = asm.finalize(staged)
    assert "hops" in batch and "seed_mask" in batch
    np.testing.assert_array_equal(np.asarray(batch["seed_mask"]),
                                  np.ones(2, np.float32))


# ---------------------------------------------------------------------------
# padding helpers
# ---------------------------------------------------------------------------


def test_pow2_pad_len():
    assert pow2_pad_len(64, 64) == 64      # full batch: untouched
    assert pow2_pad_len(51, 64) == 64      # ragged: next pow2
    assert pow2_pad_len(16, 60) == 16      # already pow2: no padding
    assert pow2_pad_len(3, 64) == 8        # floor bucket
    assert pow2_pad_len(513, 600) == 600   # pow2 overshoot: cap at full


def test_pad_tail_fills_with_last_real():
    src = np.array([5, 6, 7], np.int64)
    ts = np.array([1.0, 2.0, 3.0], np.float32)
    (ps, pt) = pad_tail((src, ts), 3, 8)
    np.testing.assert_array_equal(ps[:3], src)
    assert (ps[3:] == 7).all()
    assert (pt[3:] == 3.0).all()


# ---------------------------------------------------------------------------
# numerics: pipelined == serial, step for step
# ---------------------------------------------------------------------------


def _run(cfg, overlap, n_rounds=2):
    tr = ContinuousTrainer(cfg, STREAM, threshold=16, cache_ratio=0.2,
                           lr=5e-4, seed=0, overlap=overlap)
    tr.ingest(STREAM.slice(0, WARM))
    out = []
    for i in range(n_rounds):
        sl = STREAM.slice(WARM + i * ROUND, WARM + (i + 1) * ROUND)
        out.append(tr.train_round(sl, epochs=2,
                                  replay_ratio=0.2 if i else 0.0))
    return out


def test_pipelined_matches_serial_tgat():
    cfg = tgat(sampling="recent", d_node=8, d_edge=8, d_time=8,
               d_hidden=16, fanouts=(4, 4), batch_size=64)
    serial = _run(cfg, overlap=False)
    piped = _run(cfg, overlap=True)
    for a, b in zip(serial, piped):
        assert abs(a.loss - b.loss) <= 1e-6, (a.loss, b.loss)
        assert abs(a.ap - b.ap) <= 1e-6, (a.ap, b.ap)


def test_pipelined_matches_serial_tgn_memory():
    """The TGN raw-message path is the one cross-batch dependency the
    pipeline reorders around: commits must land before the next batch's
    blob gather, so pipelined and serial runs stay in lockstep."""
    cfg = tgn(d_node=8, d_edge=8, d_time=8, d_hidden=16, d_memory=12,
              fanouts=(4,), batch_size=64)
    serial = _run(cfg, overlap=False)
    piped = _run(cfg, overlap=True)
    for a, b in zip(serial, piped):
        assert abs(a.loss - b.loss) <= 1e-6, (a.loss, b.loss)
        assert abs(a.ap - b.ap) <= 1e-6, (a.ap, b.ap)


def test_ragged_tail_padded_not_recompiled():
    """Ragged tails pad to a pow2 bucket with loss-masked lanes: the
    reported loss must equal the unpadded batch's loss (masked mean),
    and metrics stay finite."""
    cfg = tgat(sampling="recent", d_node=8, d_edge=8, d_time=8,
               d_hidden=16, fanouts=(4, 4), batch_size=80)
    # 192-event rounds -> per-epoch batches of 80, 80, 32: the tail
    # pads 32 -> 32 (pow2) and a replay round makes a 38 -> 64 pad
    out = _run(cfg, overlap=True)
    for m in out:
        assert np.isfinite(m.loss) and 0.0 <= m.ap <= 1.0


# ---------------------------------------------------------------------------
# failure modes: a stage raising mid-round must surface, not hang, and
# leave the trainer resumable
# ---------------------------------------------------------------------------

import pytest  # noqa: E402


class _Boom(RuntimeError):
    pass


def _failing_engine(overlap, fail_stage, fail_item):
    calls = []
    eng = PipelineEngine(overlap=overlap)

    def stage(name, it):
        calls.append((name, it))
        if name == fail_stage and it == fail_item:
            raise _Boom(f"{name}({it})")
        return it

    with pytest.raises(_Boom):
        eng.run([1, 2, 3],
                prefetch=lambda it: stage("prefetch", it),
                launch=lambda it, st: stage("launch", it),
                complete=lambda h, it: stage("complete", it))
    return calls, eng


@pytest.mark.parametrize("overlap", [True, False])
@pytest.mark.parametrize("fail_stage", ["prefetch", "launch"])
def test_engine_surfaces_stage_error_and_drains_inflight(overlap,
                                                         fail_stage):
    """prefetch/launch raising on batch 2: the exception propagates
    (no hang), and every LAUNCHED batch was completed — the in-flight
    step's host side effects (TGN memory commit) are not silently
    dropped."""
    calls, _ = _failing_engine(overlap, fail_stage, 2)
    # batch 1 launched successfully -> completed exactly once; the
    # failed attempt itself launched nothing that needs draining
    ok_launched = [i for (n, i) in calls if n == "launch"
                   and not (fail_stage == "launch" and i == 2)]
    completed = [i for (n, i) in calls if n == "complete"]
    assert completed == ok_launched == [1]
    # and the round stopped: batch 3 never entered the pipeline
    assert ("prefetch", 3) not in calls and ("launch", 3) not in calls


@pytest.mark.parametrize("overlap", [True, False])
def test_engine_complete_error_not_doubled(overlap):
    """complete itself raising must surface without being re-invoked
    for the same batch by the drain path (double side effects)."""
    calls, _ = _failing_engine(overlap, "complete", 1)
    assert [i for (n, i) in calls if n == "complete"] == [1]


@pytest.mark.parametrize("overlap", [True, False])
def test_trainer_resumes_after_mid_round_failure(overlap):
    """A step blowing up mid-round leaves the trainer usable: the
    exception surfaces out of train_round, and the next round runs
    clean with finite metrics (overlap and serial schedules)."""
    cfg = tgat(sampling="recent", d_node=8, d_edge=8, d_time=8,
               d_hidden=16, fanouts=(4, 4), batch_size=64)
    tr = ContinuousTrainer(cfg, STREAM, threshold=16, cache_ratio=0.2,
                           lr=5e-4, seed=0, overlap=overlap)
    tr.ingest(STREAM.slice(0, WARM))

    real = tr._launch_train
    count = {"n": 0}

    def flaky(item, staged):
        count["n"] += 1
        if count["n"] == 2:
            raise _Boom("mid-round failure")
        return real(item, staged)

    tr._launch_train = flaky
    with pytest.raises(_Boom):
        tr.train_round(STREAM.slice(WARM, WARM + ROUND), epochs=1)
    tr._launch_train = real

    m = tr.train_round(STREAM.slice(WARM + ROUND, WARM + 2 * ROUND),
                       epochs=1)
    assert np.isfinite(m.loss) and 0.0 <= m.ap <= 1.0


# ---------------------------------------------------------------------------
# transfer, row-read and commit spans (repro.obs.xfer)
# ---------------------------------------------------------------------------

TGN_TINY = tgn(d_node=8, d_edge=8, d_time=8, d_hidden=16, d_memory=12,
               fanouts=(4,), batch_size=64)


def _tgn_trainer():
    tr = ContinuousTrainer(TGN_TINY, STREAM, threshold=16, cache_ratio=0.2,
                           lr=5e-4, seed=0)
    tr.ingest(STREAM.slice(0, WARM))
    return tr


def _traced(fn):
    trace.disable()
    trace.reset()
    trace.enable()
    try:
        fn()
    finally:
        trace.disable()
    evs = trace.events()
    trace.reset()
    return evs


def test_untraced_round_records_nothing_and_keeps_gc_callbacks():
    trace.disable()
    trace.reset()
    callbacks = list(gc.callbacks)
    tr = _tgn_trainer()
    tr.train_round(STREAM.slice(WARM, WARM + ROUND), epochs=1)
    gc.collect()
    assert trace.events() == []
    assert gc.callbacks == callbacks


def test_fetch_h2d_bytes_equal_the_uploaded_arrays():
    """The ``bytes`` of the ``xfer.h2d`` spans under ``fetch`` add up to
    what one batch uploads: per cache fetch its padded ids and its miss
    bucket (twice: the merged output and the cache update), then the
    assembled hops and the TGN memory blobs."""
    tr = _tgn_trainer()
    tr.train_round(STREAM.slice(WARM, WARM + ROUND), epochs=1)  # pending
    B = TGN_TINY.batch_size
    lo = WARM + ROUND
    out = {}

    def one_batch():
        staged = tr._stage_batch(STREAM.src[lo:lo + B],
                                 STREAM.dst[lo:lo + B], STREAM.ts[lo:lo + B])
        out["batch"] = tr.assembler.finalize(staged)

    evs = _traced(one_batch)
    batch = out["batch"]
    h2d = [e for e in evs if e["kind"] == "xfer.h2d"]
    assert {e["args"]["parent"] for e in h2d} == {"fetch"}

    def bucket(n):
        return max(8, 1 << int(np.ceil(np.log2(n))))
    n_dst, k = 3 * B, TGN_TINY.fanouts[0]
    cache = sum(bucket(n) * 4 + 2 * bucket(n) * d * 4
                for n, d in ((n_dst, TGN_TINY.d_node),
                             (n_dst * k, TGN_TINY.d_node),
                             (n_dst * k, TGN_TINY.d_edge)))
    hops = sum(v.nbytes for hop in batch["hops"] for v in hop.values())
    blobs = sum(v.nbytes for pair in batch["mem_blobs"] for blob in pair
                for v in blob.values())
    assert sum(e["args"]["bytes"] for e in h2d) == cache + hops + blobs


def test_transfer_spans_lie_inside_fetch_commit_or_ingest():
    """On a traced round every ``xfer.*`` and ``state.gather`` span lies
    inside a ``fetch`` or ``memory.commit`` span of its own thread, or in
    ``ingest`` (the caches' invalidation), and names that span as its
    parent."""
    tr = _tgn_trainer()
    evs = _traced(lambda: tr.train_round(
        STREAM.slice(WARM, WARM + ROUND), epochs=1))
    outer = [e for e in evs
             if e["kind"] in ("fetch", "memory.commit", "ingest")]
    inner = [e for e in evs
             if e["kind"].startswith("xfer.") or e["kind"] == "state.gather"]
    assert {e["kind"] for e in inner} == {"xfer.h2d", "xfer.d2h",
                                          "state.gather"}
    assert {e["kind"] for e in outer} == {"fetch", "memory.commit",
                                          "ingest"}
    for e in inner:
        holders = [o for o in outer if o["tid"] == e["tid"]
                   and o["ts_us"] <= e["ts_us"]
                   and e["ts_us"] + e["dur_us"] <= o["ts_us"] + o["dur_us"]]
        assert holders, e
        assert e["args"]["parent"] in {o["kind"] for o in holders}, e
    commits = [e for e in evs if e["kind"] == "memory.commit"]
    assert sum(e["args"]["nodes"] for e in commits) > 0
