"""repro.obs: span tracer sharp edges, metric registry semantics,
report round-trip, canonical transport-stats schema, logger routing,
and trace-vs-metrics agreement on a real trainer."""
import gc
import json
import threading

import numpy as np
import pytest
import hypothesis
from hypothesis import given, settings, strategies as st

# real hypothesis flags the (intentionally) function-scoped autouse
# trace-reset fixture; the in-container fallback has no HealthCheck
_HC = getattr(hypothesis, "HealthCheck", None)
_SETTINGS_KW = ({"suppress_health_check":
                 [_HC.function_scoped_fixture]} if _HC else {})

from repro.obs import Counter, Gauge, Histogram, MetricRegistry, trace
from repro.obs import report as obs_report
from repro.obs.log import LOG_ENV, get_logger
from repro.dist.transport import (STATS_KEYS, LocalTransport,
                                  RpcTransport, transport_stats)


@pytest.fixture(autouse=True)
def _clean_trace():
    # an automatic collection would add a ``gc`` span to the exact event
    # counts below; the gc test collects by hand
    gc.disable()
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()
    gc.enable()


# ---------------------------------------------------------------------------
# tracer sharp edges
# ---------------------------------------------------------------------------


def test_disabled_emits_nothing():
    assert not trace.enabled()
    with trace.span("x", a=1) as sp:
        sp.set(b=2)                      # no-op .set must exist
    h = trace.begin_async("y", lane="device")
    trace.end_async(h)
    assert h is None
    assert trace.events() == []
    # disabled span() returns one shared singleton (no per-call alloc)
    assert trace.span("a") is trace.span("b")


def test_stage_times_even_when_disabled():
    reg = MetricRegistry()
    timers = reg.timers("sample")
    with trace.stage(timers, "sample"):
        pass
    assert timers["sample"] > 0.0
    assert trace.events() == []          # but no span recorded


def test_span_recorded_when_block_raises():
    trace.enable()
    with pytest.raises(ValueError):
        with trace.span("boom", k=3):
            raise ValueError("inner")
    evs = trace.events()
    assert len(evs) == 1
    assert evs[0]["kind"] == "boom"
    assert evs[0]["dur_us"] >= 0
    assert evs[0]["args"] == {"k": 3}


def test_stage_span_and_timer_cover_same_interval():
    trace.enable()
    reg = MetricRegistry()
    timers = reg.timers("fetch")
    with trace.stage(timers, "fetch", phase="assemble"):
        x = sum(range(20_000))
    assert x > 0
    (ev,) = trace.events()
    # the span is emitted over the exact interval added to the timer
    assert abs(ev["dur_us"] * 1e-6 - timers["fetch"]) <= 1e-4


def test_async_lane_and_abandoned_handle():
    trace.enable()
    h = trace.begin_async("device.step", lane="device")
    trace.end_async(h, bytes=128)
    abandoned = trace.begin_async("device.step", lane="device")
    assert abandoned is not None         # never ended -> never recorded
    evs = trace.events()
    assert len(evs) == 1
    assert evs[0]["lane"] == "device"
    assert evs[0]["args"]["bytes"] == 128


def test_ring_buffer_drops_oldest_and_counts():
    trace.enable(capacity=8)
    for i in range(20):
        with trace.span(f"s{i}"):
            pass
    evs = trace.events()
    assert len(evs) == 8
    assert {e["kind"] for e in evs} == {f"s{i}" for i in range(12, 20)}
    assert trace.dropped() == 12


@settings(max_examples=8, deadline=None, **_SETTINGS_KW)
@given(st.integers(min_value=2, max_value=4),
       st.integers(min_value=3, max_value=25))
def test_concurrent_threads_do_not_corrupt(n_threads, per_thread):
    """Pipeline + prefetch threads trace concurrently: every span must
    land exactly once, in its own thread's lane, durations sane."""
    trace.disable()
    trace.reset()
    trace.enable()
    barrier = threading.Barrier(n_threads)

    def work(i):
        barrier.wait()
        for j in range(per_thread):
            with trace.span(f"thread{i}", j=j):
                pass

    threads = [threading.Thread(target=work, args=(i,),
                                name=f"obs-worker-{i}")
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = [e for e in trace.events() if e["kind"].startswith("thread")]
    assert len(evs) == n_threads * per_thread
    by_kind = {}
    for e in evs:
        assert e["dur_us"] >= 0 and e["ts_us"] > 0
        by_kind.setdefault(e["kind"], []).append(e)
    for i in range(n_threads):
        mine = by_kind[f"thread{i}"]
        assert len(mine) == per_thread
        # one producer thread -> one tid, all its span args intact
        assert len({e["tid"] for e in mine}) == 1
        assert sorted(e["args"]["j"] for e in mine) == list(
            range(per_thread))
    trace.reset()
    trace.disable()


def test_enable_installs_one_gc_callback_and_records_collections():
    before = list(gc.callbacks)
    trace.enable()
    trace.enable()                       # twice: still one callback
    assert len(gc.callbacks) == len(before) + 1
    with trace.span("round"):
        gc.collect()                     # one collection, generation 2
    trace.disable()
    assert gc.callbacks == before
    gc.collect()                         # off: not recorded
    evs = [e for e in trace.events() if e["kind"] == "gc"]
    assert len(evs) == 1
    (ev,) = evs
    assert ev["args"]["generation"] == 2
    assert ev["args"]["collected"] >= 0
    assert ev["args"]["parent"] == "round"
    assert ev["tid"] == threading.get_ident()


def test_parent_is_innermost_open_span_per_thread():
    trace.enable()
    timers = MetricRegistry().timers("fetch")
    seen = {}

    def other_thread():
        with trace.span("worker"):
            pass
        seen["done"] = True

    with trace.span("round"):
        with trace.stage(timers, "fetch", phase="assemble"):
            with trace.span("xfer.h2d", bytes=4):
                pass
            # a thread started inside a span does not inherit it
            t = threading.Thread(target=other_thread)
            t.start()
            t.join(timeout=30)
            h = trace.begin_async("device.step", lane="device")
        trace.end_async(h)
        with trace.span("after"):
            pass
    with trace.span("top"):
        pass
    assert not t.is_alive() and seen["done"]
    parent = {e["kind"]: e["args"].get("parent") for e in trace.events()}
    assert parent == {"round": None, "fetch": "round", "xfer.h2d": "fetch",
                      "worker": None, "device.step": "fetch",
                      "after": "round", "top": None}


def test_xfer_helpers_span_only_when_tracing():
    import jax
    import jax.numpy as jnp
    from repro.obs import xfer

    host = np.arange(12, dtype=np.float64).reshape(3, 4)
    rows = lambda ids: host[ids]                       # noqa: E731
    for on in (False, True):
        trace.reset()
        if on:
            trace.enable()
        dev = xfer.h2d(host, jnp.float32)
        assert isinstance(dev, jax.Array) and dev.dtype == jnp.float32
        xfer.h2d(dev)                                  # on the device
        back = xfer.d2h(dev, np.float64)
        np.testing.assert_array_equal(back, host)
        assert xfer.d2h(host) is host                  # no copy, no span
        np.testing.assert_array_equal(xfer.gather(rows, [2, 0]),
                                      host[[2, 0]])
        evs = trace.events()
        if not on:
            assert evs == []
            continue
        got = sorted((e["kind"], e["args"].get("bytes", e["args"].get(
            "rows"))) for e in evs)
        # the upload moves float32 (4 bytes a value); arrays already on
        # the device, or already on the host, move nothing
        assert got == [("state.gather", 2), ("xfer.d2h", 48),
                       ("xfer.h2d", 48)]


# ---------------------------------------------------------------------------
# export / merge / report round-trip
# ---------------------------------------------------------------------------


def test_export_chrome_lanes_and_clock_shift(tmp_path):
    trace.enable()
    with trace.span("sample", seeds=4):
        pass
    h = trace.begin_async("device.step", lane="device")
    trace.end_async(h)
    sync = trace.now_us()
    out = trace.export_chrome(str(tmp_path / "t.json"), pid=2,
                              process_name="worker2",
                              clock_sync_us=sync,
                              metadata={"metrics": {"cache.node.hits": 1}})
    xs = [e for e in out["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in out["traceEvents"] if e["ph"] == "M"]
    assert all(e["pid"] == 2 for e in xs + ms)
    # spans recorded BEFORE the sync point export with negative ts
    assert all(e["ts"] <= 0 for e in xs)
    names = {e["args"]["name"] for e in ms if e["name"] == "thread_name"}
    assert "device" in names             # virtual lane materialized
    tid_by_lane = {e["args"]["name"]: e["tid"] for e in ms
                   if e["name"] == "thread_name"}
    step = [e for e in xs if e["name"] == "device.step"]
    assert step[0]["tid"] == tid_by_lane["device"]
    assert out["metadata"]["clock_sync_us"] == sync
    assert out["metadata"]["metrics"] == {"cache.node.hits": 1}
    # written file loads back identically
    assert trace.load_trace(str(tmp_path / "t.json")) == json.loads(
        json.dumps(out))


def test_merge_rebases_and_collects_worker_metadata(tmp_path):
    def part(pid, ts):
        return ({"traceEvents": [
            {"ph": "X", "name": "round", "ts": ts, "dur": 10,
             "pid": 0, "tid": 1, "args": {}}],
            "metadata": {"pid": pid,
                         "metrics": {f"w{pid}": pid}}}, pid)

    p0, p1 = part(0, 150), part(1, -50)
    paths = []
    for tr, pid in (p0, p1):
        p = tmp_path / f"w{pid}.json"
        p.write_text(json.dumps(tr))
        paths.append((str(p), pid))
    merged = trace.merge_chrome_files(paths,
                                      path=str(tmp_path / "m.json"))
    xs = sorted((e for e in merged["traceEvents"] if e["ph"] == "X"),
                key=lambda e: e["pid"])
    assert [e["pid"] for e in xs] == [0, 1]
    # fleet minimum (-50) rebased to 0
    assert [e["ts"] for e in xs] == [200, 0]
    assert set(merged["metadata"]["workers"]) == {"0", "1"}


def test_report_cli_round_trip(tmp_path, capsys):
    trace.enable()
    for i in range(5):
        with trace.span("sample", seeds=8):
            pass
        with trace.span("rpc.call", op="sample_hop", machine=1,
                        bytes=100 + i):
            pass
    path = str(tmp_path / "trace.json")
    trace.export_chrome(path, pid=0, metadata={
        "metrics": {"cache.node.hits": 30, "cache.node.accesses": 40}})
    assert obs_report.main([path]) == 0
    text = capsys.readouterr().out
    assert "== spans ==" in text and "sample" in text
    assert "rpc.call:sample_hop" in text
    assert "w0:cache.node" in text
    # --json emits machine-readable summary with the same numbers
    assert obs_report.main([path, "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["spans"]["sample"]["count"] == 5
    wire = summary["wire"]["rpc.call:sample_hop"]
    assert wire["calls"] == 5
    assert wire["bytes"] == sum(100 + i for i in range(5))
    assert summary["caches"]["w0:cache.node"]["hit_rate"] == 0.75


# ---------------------------------------------------------------------------
# metric registry
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    reg = MetricRegistry()
    c = reg.counter("rpc.calls")
    c.add(3)
    assert reg.counter("rpc.calls") is c         # get-or-create
    g = reg.gauge("staleness")
    g.set(2.5)
    h = reg.histogram("lat")
    for v in (1.0, 2.0, 10.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["rpc.calls"] == 3
    assert snap["staleness"] == 2.5
    assert snap["lat"]["count"] == 3 and snap["lat"]["max"] == 10.0
    c.add(2)
    h.observe(5.0)
    d = reg.delta(snap)
    assert d["rpc.calls"] == 2
    assert d["lat"]["count"] == 1 and d["lat"]["sum"] == 5.0
    with pytest.raises(TypeError):
        reg.gauge("rpc.calls")                   # type conflict


def test_registry_timers_adapter_keeps_dict_idiom():
    reg = MetricRegistry()
    timers = reg.timers("sample", "fetch")
    timers["sample"] += 0.5
    timers["fetch"] += 0.25
    assert timers["sample"] == 0.5
    assert reg.snapshot()["time.sample"] == 0.5
    for k in timers:                             # the zeroing loop
        timers[k] = 0.0
    assert timers["sample"] == 0.0 and timers["fetch"] == 0.0


# ---------------------------------------------------------------------------
# canonical transport-stats schema (satellite: one schema, both wires)
# ---------------------------------------------------------------------------


def test_transport_stats_schema_shared():
    base = transport_stats()
    assert tuple(base.keys()) == STATS_KEYS
    assert tuple(LocalTransport().stats().keys()) == STATS_KEYS
    rpc = RpcTransport(0, 1, [0])                # no connect: lazy wire
    assert tuple(rpc.stats().keys()) == STATS_KEYS
    assert rpc.stats()["calls"] == 0


# ---------------------------------------------------------------------------
# structured logger (satellite: no bare prints in launcher/bench)
# ---------------------------------------------------------------------------


def test_logger_levels_and_worker_prefix(monkeypatch, capsys):
    lg = get_logger("launch.multihost")
    monkeypatch.setenv(LOG_ENV, "warn")
    lg.info("hidden")
    lg.warn("shown", rounds=3)
    out = capsys.readouterr()
    assert out.out == ""                         # stdout stays clean
    assert "hidden" not in out.err
    assert "shown" in out.err and "rounds=3" in out.err
    monkeypatch.setenv("REPRO_MH_PROCESS_ID", "1")
    monkeypatch.setenv(LOG_ENV, "info")
    lg.info("tagged")
    assert "[w1|launch.multihost] tagged" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# trace totals vs round metrics on a real trainer (10% criterion)
# ---------------------------------------------------------------------------


def test_trace_agrees_with_round_metrics():
    from repro.configs.tgn_gdelt import tgat
    from repro.core.continuous import ContinuousTrainer
    from repro.data.events import synth_ctdg

    stream = synth_ctdg(n_nodes=200, n_events=2_000, t_span=20_000,
                        d_node=12, d_edge=8, seed=3)
    cfg = tgat(d_node=12, d_edge=8, d_time=8, d_hidden=16,
               fanouts=(4,), batch_size=128)
    tr = ContinuousTrainer(cfg, stream, threshold=16, cache_ratio=0.2,
                           lr=3e-3, seed=0, overlap=True)
    trace.enable()
    tr.ingest(stream.slice(0, 1_000))
    metrics = [tr.train_round(stream.slice(1_000, 1_500), epochs=2),
               tr.train_round(stream.slice(1_500, 2_000), epochs=2)]
    summary = obs_report.summarize(trace.export_chrome())
    for kind, field in (("sample", "sample_s"), ("fetch", "fetch_s"),
                        ("step", "step_s")):
        want = sum(getattr(m, field) for m in metrics)
        got = summary["spans"].get(kind, {}).get("total_s", 0.0)
        assert abs(got - want) <= max(0.10 * want, 0.05), (
            f"{kind}: trace {got:.4f}s vs metrics {want:.4f}s")
    # cache accounting flows from the same registry the report reads
    snap = tr.metrics.snapshot()
    assert snap["cache.node.accesses"] == tr.node_cache.accesses
    assert tr.node_cache.accesses > 0
