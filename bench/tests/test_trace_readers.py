"""Checks of the readers of the program's child spans, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_trace_readers.py

The readers are checked by hand on made-up spans (what they count, and
None where the program records no such span, as a program from before
these spans does), then on traced runs of the tiny cells, where each
must read a number.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import run  # noqa: E402
from bench.tests.test_bench import SERVE, TRAIN, tiny  # noqa: E402

NEW = {
    "t": ["fetch_h2d_mb.train", "fetch_d2h_wait_ms.train",
          "fetch_gather_ms.train", "memory_commit_ms.train",
          "gc_pause_ms_per_s.train"],
    "s": ["serve_queue_wait_ms", "serve_d2h_wait_ms",
          "gc_pause_ms_per_s.serve"],
}


def reader(name):
    return run.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                           "reader_" + name.replace(".", "_"))


class Ctx:
    """What a reader sees of a run: spans as (kind, start_s, end_s,
    args) on the host clock, the window and the driver's counts."""

    def __init__(self, spans, win=(0.0, 10.0), **stats):
        self.spans = spans
        self.win = win
        self.window_stats = stats

    def span_list(self, kind=None):
        return [s for s in self.spans
                if (kind is None or s[0] == kind)
                and self.win[0] <= s[1] <= self.win[1]]


# -- by hand -------------------------------------------------------------


def test_readers_return_none_without_their_spans():
    """A program from before these spans: ``fetch`` and ``serve.batch``
    without ``parent``, transfers, commits, queue waits or collections."""
    old = Ctx([("fetch", 1.0, 2.0, {"phase": "assemble"}),
               ("serve.batch", 2.0, 2.1, {"queries": 3})],
              steps=2, eval_batches=2)
    for name in NEW["t"] + NEW["s"]:
        assert reader(name).read(old) is None, name


def test_fetch_readers_count_only_children_of_fetch():
    spans = [
        ("fetch", 1.0, 3.0, {}),
        ("xfer.h2d", 1.1, 1.2, {"bytes": 3_000_000, "parent": "fetch"}),
        ("xfer.h2d", 1.2, 1.3, {"bytes": 1_000_000, "parent": "fetch"}),
        ("xfer.h2d", 4.0, 4.1, {"bytes": 9_000_000, "parent": "ingest"}),
        ("xfer.d2h", 1.3, 1.5, {"bytes": 8, "parent": "fetch"}),
        ("xfer.d2h", 5.0, 6.0, {"bytes": 8, "parent": "memory.commit"}),
        ("state.gather", 1.5, 1.9, {"rows": 4, "parent": "fetch"}),
        ("state.gather", 5.0, 5.5, {"rows": 4, "parent": "memory.commit"}),
        ("xfer.h2d", 20.0, 20.1, {"bytes": 7, "parent": "fetch"}),  # late
    ]
    ctx = Ctx(spans, steps=1, eval_batches=1)
    assert reader("fetch_h2d_mb.train").read(ctx) == pytest.approx(2.0)
    assert reader("fetch_d2h_wait_ms.train").read(ctx) == \
        pytest.approx(100.0)
    assert reader("fetch_gather_ms.train").read(ctx) == pytest.approx(200.0)


def test_memory_commit_per_step():
    ctx = Ctx([("memory.commit", 1.0, 1.03, {"nodes": 5,
                                              "parent": "pipeline.complete"}),
               ("memory.commit", 2.0, 2.01, {"nodes": 5})],
              steps=2, eval_batches=2)
    assert reader("memory_commit_ms.train").read(ctx) == pytest.approx(20.0)


def test_gc_pause_per_second_of_window():
    ctx = Ctx([("gc", 1.0, 1.02, {"generation": 1, "collected": 0}),
               ("gc", 3.0, 3.03, {"generation": 2, "collected": 9,
                                  "parent": "fetch"})], win=(0.0, 5.0))
    for name in ("gc_pause_ms_per_s.train", "gc_pause_ms_per_s.serve"):
        assert reader(name).read(ctx) == pytest.approx(10.0)
    # the program records parents but no collection ran: zero, not None
    quiet = Ctx([("fetch", 1.0, 2.0, {}),
                 ("xfer.d2h", 1.0, 1.1, {"parent": "fetch"})])
    assert reader("gc_pause_ms_per_s.train").read(quiet) == 0.0


def test_serve_readers():
    spans = [
        ("serve.batch", 1.0, 1.05, {"queries": 3, "queue_wait_ms_sum": 30.0,
                                    "queue_wait_ms_max": 12.0, "depth": 0}),
        ("serve.batch", 2.0, 2.05, {"queries": 1, "queue_wait_ms_sum": 10.0,
                                    "queue_wait_ms_max": 10.0, "depth": 4}),
        ("xfer.d2h", 1.00, 1.01, {"bytes": 8, "parent": "serve.batch"}),
        ("xfer.d2h", 1.01, 1.02, {"bytes": 8, "parent": "serve.fetch"}),
        ("xfer.d2h", 1.02, 1.04, {"bytes": 8, "parent": "serve.forward"}),
        ("xfer.d2h", 3.00, 3.50, {"bytes": 8, "parent": "ingest"}),
    ]
    ctx = Ctx(spans)
    assert reader("serve_queue_wait_ms").read(ctx) == pytest.approx(10.0)
    assert reader("serve_d2h_wait_ms").read(ctx) == pytest.approx(20.0)


# -- traced tiny runs ----------------------------------------------------


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.mark.parametrize("config,traffic,cell,e2e", [
    ("tgn-tiny", TRAIN, "t", "events_per_s"),
    ("tgn-tiny", SERVE, "s", "query_p50_ms"),
])
def test_traced_tiny_run_reads_every_new_metric(config, traffic, cell, e2e):
    from repro.obs import trace
    bench = {"end_to_end": [
        {"name": e2e, "unit": "x", "workloads": [cell]},
        {"name": "setup_s", "unit": "s"}],
        "per_layer": [dict(m, workloads=[cell]) for m in run.load_json(
            ROOT / "BENCHMARK.json")["per_layer"] if m["name"] in NEW[cell]]}
    res = run.run_cell(bench, {"name": cell, "chips": 1}, tiny(config),
                       traffic, seed=11, seconds=1.5, trace=True,
                       require_chip=False, t_start=time.perf_counter())
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == set(NEW[cell]), res["metrics"]
    for name, m in res["metrics"].items():
        assert m["value"] >= 0.0, (name, m)
    assert trace.dropped() == 0
