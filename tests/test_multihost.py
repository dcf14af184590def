"""Real multi-process multi-host launch (repro.launch.multihost):

* cross-process PARITY — the 2-process launch (one OS process per
  machine, jax.distributed + gloo CPU collectives, RPC sampling
  servers) reproduces the in-process ``DistributedContinuousTrainer``
  to <= 1e-4 train/eval loss over 3 rounds, TGN memory path included,
  and all worker processes report identical metrics;
* transport-level equivalence — routing hops through a real
  ``RpcTransport``/``RpcSamplingServer`` pair returns bit-identical
  samples to the all-local system (fast, no subprocesses);
* the in-process mode is the degenerate 1-process case of the injected
  transport interface.

The subprocess tests are marked ``slow`` and run in their own CI lane
(multihost-smoke); ``pytest -x -q`` skips them via the default
``-m "not slow"`` addopts.
"""
import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs.tgn_gdelt import GNN_MODELS, DistConfig
from repro.core.partition import Dispatcher, GraphPartition
from repro.core.scheduler import DistributedSamplerSystem
from repro.data.events import synth_ctdg
from repro.dist.continuous import DistributedContinuousTrainer
from repro.dist.transport import LocalTransport, RpcTransport
from repro.launch import multihost
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace

WORKER = Path(__file__).resolve().parent / "_multihost_worker.py"
P_, G_ = 2, 2          # 2 machines x 2 trainer ranks = 4 workers


def _run_cfg(model: str) -> dict:
    """One config dict shared VERBATIM by the in-process reference and
    the spawned workers — same stream, same model, same schedule."""
    model_kw = dict(d_node=8, d_edge=8, d_time=8, d_hidden=16,
                    batch_size=64)
    if model == "tgn":
        model_kw.update(fanouts=(4,), d_memory=12)
    else:
        model_kw.update(fanouts=(4, 4), sampling="recent")
    return {
        "model": model,
        "model_kw": model_kw,
        "stream": dict(n_nodes=192, n_events=1800, t_span=20_000,
                       d_node=8, d_edge=8, seed=7),
        "dist": {"collective": "bucketed"},
        "trainer": dict(threshold=16, cache_ratio=0.2, lr=5e-4,
                        seed=0, overlap=True),
        "warm": 512, "round_size": 256, "rounds": 3, "epochs": 2,
        "replay_ratio": 0.2, "replay_round": 2,
    }


def _reference_rounds(run_cfg: dict):
    """The in-process trainer on the SAME schedule (drive_rounds is the
    single source of truth for it)."""
    stream = synth_ctdg(**run_cfg["stream"])
    cfg = GNN_MODELS[run_cfg["model"]](**run_cfg["model_kw"])
    dist = DistConfig(n_machines=P_, n_gpus=G_, **run_cfg["dist"])
    tr = DistributedContinuousTrainer(cfg, stream, dist,
                                      **run_cfg["trainer"])
    rounds = multihost.drive_rounds(
        tr, stream, warm=run_cfg["warm"],
        round_size=run_cfg["round_size"], rounds=run_cfg["rounds"],
        epochs=run_cfg["epochs"],
        replay_ratio=run_cfg["replay_ratio"],
        replay_round=run_cfg["replay_round"])
    return tr, rounds


def _launch_workers(run_cfg: dict, subprocess_env: dict, **env):
    # let the workers share CI's persistent XLA compile cache
    extra = {k: os.environ[k] for k in (
        "JAX_COMPILATION_CACHE_DIR",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES") if k in os.environ}
    extra.update(env)
    outs = multihost.launch(
        [sys.executable, str(WORKER), json.dumps(run_cfg)],
        n_processes=P_, n_local_devices=G_,
        base_env=subprocess_env, extra_env=extra, timeout_s=1500.0)
    return multihost.parse_results(outs)


def _assert_parity(run_cfg, results, ref_rounds):
    # every worker ran all rounds and they agree with EACH OTHER
    # exactly (params are replicated through the collectives)
    assert len(results) == P_
    for r in results:
        assert len(r["rounds"]) == run_cfg["rounds"]
    for a, b in zip(*[r["rounds"] for r in results]):
        assert abs(a["loss"] - b["loss"]) <= 1e-6
        assert abs(a["eval_loss"] - b["eval_loss"]) <= 1e-6
    # ... and with the in-process trainer within the collective band
    for ref, got in zip(ref_rounds, results[0]["rounds"]):
        assert abs(ref.loss - got["loss"]) <= 1e-4, \
            (ref.loss, got["loss"])
        assert abs(ref.eval_loss - got["eval_loss"]) <= 1e-4, \
            (ref.eval_loss, got["eval_loss"])
        assert abs(ref.ap - got["ap"]) <= 1e-3, (ref.ap, got["ap"])
    # the launch actually crossed process boundaries: real RPC traffic
    # from every worker, every round
    for r in results:
        assert r["rpc"]["calls"] > 0
        assert r["rpc"]["bytes_out"] > 0 and r["rpc"]["bytes_in"] > 0
        for rd in r["rounds"]:
            assert rd["rpc_calls"] > 0
            assert rd["rpc_wire_bytes"] > 0
            assert rd["request_bytes"] > 0       # modeled payloads too
    # partitioned ingest: dispatch bytes accounted on every process
    assert all(rd["dispatch_bytes"] > 0
               for r in results for rd in r["rounds"])


def _assert_fleet_trace(results, out_dir):
    """The traced fleet's merged timeline: every worker on one clock,
    the in-flight step and the state prefetch on lanes of their own,
    the step concurrent with the host's prefetch of the next batch, and
    span totals agreeing with the round metrics the workers computed
    from the same intervals (``trace.stage`` feeds both)."""
    path = multihost.collect_fleet_trace(results,
                                         str(out_dir / "fleet.json"))
    assert path, "traced fleet produced no worker trace files"
    merged = obs_trace.load_trace(path)
    xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert {e["pid"] for e in xs} == set(range(P_))
    w0 = [e for e in xs if e["pid"] == 0]
    steps, prefetch, state_pf = (
        [e for e in w0 if e["name"] == n]
        for n in ("device.step", "pipeline.prefetch", "state.prefetch"))
    assert steps and state_pf
    main_tids = {e["tid"] for e in prefetch}
    assert main_tids.isdisjoint(e["tid"] for e in steps + state_pf)
    assert any(a["ts"] < b["ts"] + b["dur"] and b["ts"] < a["ts"] + a["dur"]
               for a in steps for b in prefetch)
    summary = obs_report.summarize(merged, pid=0)
    rounds = next(r for r in results if r["process_id"] == 0)["rounds"]
    for kind, field in (("sample", "sample_s"), ("fetch", "fetch_s"),
                        ("step", "step_s"), ("state.wait", "state_wait_s")):
        metric = sum(m[field] for m in rounds)
        span = summary["spans"].get(kind, {}).get("total_s", 0.0)
        assert abs(span - metric) <= max(0.10 * metric, 0.05), \
            (kind, span, metric)


@pytest.mark.slow
def test_two_process_parity_tgat(subprocess_env):
    """2-process launch == in-process trainer, <= 1e-4 train/eval loss
    over 3 rounds (replay-thinned round included)."""
    run_cfg = _run_cfg("tgat")
    _, ref = _reference_rounds(run_cfg)
    results = _launch_workers(run_cfg, subprocess_env)
    _assert_parity(run_cfg, results, ref)


@pytest.mark.slow
def test_two_process_parity_tgn_memory(subprocess_env):
    """The TGN node-memory path (raw messages with explicit eids,
    in-graph GRU, commit after each step) stays in lockstep across
    REAL process boundaries: each process maintains a replica of the
    memory store from the replicated step, and the replicas never
    diverge."""
    run_cfg = _run_cfg("tgn")
    tr, ref = _reference_rounds(run_cfg)
    # memory actually engaged on the reference side
    stream = synth_ctdg(**run_cfg["stream"])
    active = np.unique(stream.src[:run_cfg["warm"]
                                  + 3 * run_cfg["round_size"]])
    assert np.abs(tr.state.get_memory(active)[0]).sum() > 0
    results = _launch_workers(run_cfg, subprocess_env)
    _assert_parity(run_cfg, results, ref)


@pytest.mark.slow
def test_two_process_sharded_state_parity_tgn(subprocess_env):
    """Owner-sharded StateService across REAL process boundaries: each
    worker holds only its owned feature/memory partitions, remote rows
    (TGN memory included) travel over the transport's state ops — and
    the run still matches the replicated in-process trainer to <= 1e-4
    train/eval loss over 3 rounds."""
    run_cfg = _run_cfg("tgn")
    ref_tr, ref = _reference_rounds(run_cfg)   # replicated reference
    run_cfg["trainer"] = dict(run_cfg["trainer"], state="sharded")
    results = _launch_workers(run_cfg, subprocess_env)
    _assert_parity(run_cfg, results, ref)
    ref_resident = ref_tr.state.resident_bytes()
    for r in results:
        ss = r["state"]
        assert ss["mode"] == "sharded"
        # remote rows really crossed the wire, and this process served
        # its peers' requests for the rows it owns
        assert ss["wire_calls"] > 0 and ss["wire_bytes"] > 0
        assert ss["served_calls"] > 0
        assert ss["wait_s"] > 0.0
        # each process holds ~1/P of the replicated per-process tables
        assert ss["resident_bytes"] <= 0.7 * ref_resident, \
            (ss["resident_bytes"], ref_resident)
        # state-RPC traffic surfaces per round in DistRoundMetrics
        for rd in r["rounds"]:
            assert rd["state_calls"] > 0
            assert rd["state_bytes"] > 0
            assert rd["state_resident_bytes"] > 0
            # coalesced-read surface: real trips stay below what the
            # per-table path would have issued, repeats were deduped
            # before the wire, and the async prefetch actually served
            assert rd["state_round_trips"] > 0
            assert rd["state_baseline_trips"] >= rd["state_round_trips"]
            assert rd["state_trips_per_batch"] > 0
            assert rd["state_dedup_saved_bytes"] > 0
            assert rd["state_pf_hits"] > 0
            # fenced default: nothing ever served stale
            assert rd["state_stale_served"] == 0
            assert sum(rd["state_wire_bytes_per_part"]) > 0


@pytest.mark.slow
def test_two_process_sharded_state_coalesced_tgat(subprocess_env, tmp_path):
    """The owner-sharded TGAT fleet reads all of a global batch's remote
    rows in one ``state_batch`` frame per foreign peer: at most P-1
    wire round trips per batch, at least 3x fewer than the per-table
    path would take, with the losses of the replicated in-process
    trainer. (TGN reads its memory again after every commit, so the
    budget is TGAT's.) The fleet runs traced, and its merged timeline
    is checked too."""
    run_cfg = _run_cfg("tgat")
    _, ref = _reference_rounds(run_cfg)        # replicated reference
    run_cfg["trainer"] = dict(run_cfg["trainer"], state="sharded")
    results = _launch_workers(
        run_cfg, subprocess_env, REPRO_TRACE="1",
        **{multihost._ENV["trace_dir"]: str(tmp_path)})
    _assert_parity(run_cfg, results, ref)
    for r in results:
        assert r["state"]["mode"] == "sharded"
        for rd in r["rounds"]:
            assert 0 < rd["state_trips_per_batch"] <= P_ - 1, rd
            assert rd["state_stale_served"] == 0
    base = sum(r["state"]["baseline_trips"] for r in results)
    trips = sum(r["state"]["round_trips"] for r in results)
    assert base >= 3 * trips, (base, trips)
    _assert_fleet_trace(results, tmp_path)


@pytest.mark.slow
def test_two_process_sharded_memory_staleness_bounded(subprocess_env):
    """``memory_staleness=1``: remote TGN memory reads may serve the
    prefetched copy one commit stale and the mem-read/mem-commit fleet
    barriers disappear.  The contract is BOUNDED deviation, not
    equality: losses stay within a loose band of the fenced replicated
    reference, stale rows really were served, and the fleet still
    agrees with itself (the collectives keep params replicated)."""
    run_cfg = _run_cfg("tgn")
    _, ref = _reference_rounds(run_cfg)        # fenced reference
    run_cfg["trainer"] = dict(run_cfg["trainer"], state="sharded",
                              memory_staleness=1)
    results = _launch_workers(run_cfg, subprocess_env)
    assert len(results) == P_
    for a, b in zip(*[r["rounds"] for r in results]):
        assert abs(a["loss"] - b["loss"]) <= 1e-6
    for want, got in zip(ref, results[0]["rounds"]):
        assert abs(want.loss - got["loss"]) <= 0.1, \
            (want.loss, got["loss"])
        assert abs(want.eval_loss - got["eval_loss"]) <= 0.1
    assert sum(rd["state_stale_served"] for r in results
               for rd in r["rounds"]) > 0
    assert all(rd["state_pf_hits"] > 0
               for r in results for rd in r["rounds"])


# ---------------------------------------------------------------------------
# fast, in-process: transport interface + RPC scheduler equivalence
# ---------------------------------------------------------------------------


def test_default_transport_is_the_degenerate_local_case():
    """No transport argument == LocalTransport: all machines hosted in
    this process, nothing listens, barriers are no-ops."""
    stream = synth_ctdg(n_nodes=64, n_events=400, d_node=4, d_edge=4,
                        seed=1)
    cfg = GNN_MODELS["tgat"](d_node=4, d_edge=4, d_time=4, d_hidden=8,
                             fanouts=(2,), sampling="recent",
                             batch_size=32)
    tr = DistributedContinuousTrainer(
        cfg, stream, DistConfig(2, 1, "bucketed"), threshold=16,
        cache_ratio=0.2, lr=1e-3, seed=0)
    assert isinstance(tr.transport, LocalTransport)
    assert not tr.multihost
    assert tr.transport.local_machines(2) == (0, 1)
    assert sorted(tr.samplers.samplers) == [0, 1]   # hosts both
    tr.transport.barrier("noop")                    # must not block


def test_rpc_transport_matches_local_sampling():
    """Two single-machine sampler systems wired through REAL
    RpcTransport servers return bit-identical k-hop samples to the
    all-local system (recent policy: arrival order cannot matter)."""
    P = 2
    stream = synth_ctdg(n_nodes=300, n_events=4000, seed=3)

    def build_parts():
        parts = [GraphPartition(p, P, threshold=16) for p in range(P)]
        disp = Dispatcher(parts, undirected=True)
        disp.add_edges(stream.src, stream.dst, stream.ts)
        return parts

    ref_parts = build_parts()
    full = DistributedSamplerSystem(ref_parts, 1, (4, 4),
                                    scan_pages=16)

    # one "process" per machine, same partition contents, RPC between
    a_parts, b_parts = build_parts(), build_parts()
    ports = multihost.free_ports(2)
    ta = RpcTransport(0, P, ports)
    tb = RpcTransport(1, P, ports)
    sys_a = DistributedSamplerSystem([a_parts[0]], 1, (4, 4),
                                     scan_pages=16, n_machines=P,
                                     transport=ta)
    sys_b = DistributedSamplerSystem([b_parts[1]], 1, (4, 4),
                                     scan_pages=16, n_machines=P,
                                     transport=tb)
    try:
        ta.bind(sys_a)
        tb.bind(sys_b)
        ta.connect()
        tb.connect()
        seeds = np.arange(64, dtype=np.int64)
        ts = np.full(64, float(stream.ts[-1]), np.float32)
        for system, machine in ((sys_a, 0), (sys_b, 1)):
            got = system.sample(machine, 0, seeds, ts)
            want = full.sample(machine, 0, seeds, ts)
            for la, lb in zip(got, want):
                np.testing.assert_array_equal(np.asarray(la.nbr_ids),
                                              np.asarray(lb.nbr_ids))
                np.testing.assert_array_equal(np.asarray(la.nbr_eids),
                                              np.asarray(lb.nbr_eids))
                np.testing.assert_array_equal(np.asarray(la.mask),
                                              np.asarray(lb.mask))
        # the equivalence went over the wire, both directions
        assert ta.calls > 0 and tb.calls > 0
        assert ta.bytes_out > 0 and ta.bytes_in > 0
        # a crashing remote surfaces as an error, not a hang
        with pytest.raises(RuntimeError, match="sampling server"):
            ta._call(1, "hop", 5, 0, seeds, ts, np.ones(64, bool), 4)
    finally:
        ta.close()
        tb.close()


def test_rpc_server_rejects_unknown_ops():
    parts = [GraphPartition(0, 1, threshold=16)]
    system = DistributedSamplerSystem(parts, 1, (4,), scan_pages=16)
    ports = multihost.free_ports(2)
    t0 = RpcTransport(0, 2, ports)
    t1 = RpcTransport(1, 2, ports)
    try:
        t0.bind(system)
        t1.connect()
        assert t1._call(0, "ping") == "pong"
        # unknown ops are rejected CLIENT-side (the shared op table is
        # the contract — nothing unregistered ever hits the wire)
        with pytest.raises(ValueError, match="unknown rpc op"):
            t1._call(0, "bogus")
        # registered state ops reach the server, which refuses them
        # while no state service is bound (sampling-only server)
        with pytest.raises(RuntimeError, match="no state service"):
            t1._call(0, "feat_get", "node", np.arange(4))
    finally:
        t1.close()
        t0.close()
