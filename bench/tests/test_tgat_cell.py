"""Checks of the TGAT cell, on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_tgat_cell.py

A tiny TGAT run with pages of 8 edges (chains many times deeper than the
samplers' 16-page window) comes out correct; with the device mirror cut
to each chain's newest pages, the uniform sampler leaves candidates out
and the run does not. The cell's readers are checked by hand.
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
from bench.tests.test_bench import BENCH, TRAIN, tiny  # noqa: E402

ROUNDS_OF_4 = dict(TRAIN, batches_per_round=4)
TGAT = ["sampler_device_ms.tgat", "sampler_roofline.tgat",
        "sampler_ns_per_target.tgat", "step_device_ms.tgat", "mfu.tgat",
        "fetch_ms.tgat", "device_idle_share.tgat"]


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


def run_tgat(seed=7):
    cfg = tiny("tgat-tiny")
    cfg["trainer"]["threshold"] = 8
    return run.run_cell(BENCH, {"name": "t", "chips": 1}, cfg, ROUNDS_OF_4,
                        seed=seed, seconds=1.0, trace=False,
                        require_chip=False, t_start=time.perf_counter())


@pytest.mark.parametrize("seed", [7, 4_000_000_011])
def test_tgat_tiny_is_correct_at_threshold_8(seed):
    res = run_tgat(seed)
    assert res["correct"], res["checks"]
    assert res["checks"]["sampler_faults"]["value"] == 0
    assert res["failed"] == 0 and res["attempted"] > 0


def test_newest_pages_only_is_not_correct(monkeypatch):
    """The mirror holds each chain's two newest pages only: a uniform
    draw can no longer reach a target's older edges."""
    from repro.core.sampling import DeviceMirror
    monkeypatch.setattr(DeviceMirror, "_table_cols",
                        staticmethod(lambda snap: 2))
    res = run_tgat()
    assert not res["correct"], res["checks"]
    assert res["checks"]["sampler_faults"]["value"] > 0


class Ctx:
    def __init__(self, spans, peaks=None):
        self.spans, self.trace, self.peaks = spans, None, peaks
        self.win, self.window_stats = (0.0, 10.0), {"steps": 0}

    def span_list(self, kind=None):
        return [s for s in self.spans if kind is None or s[0] == kind]


def reader(name):
    return run.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                           "reader_" + name.replace(".", "_"))


def test_readers_return_none_without_a_trace_or_spans():
    old = Ctx([("fetch", 1.0, 2.0, {"phase": "assemble"})])
    for name in TGAT:
        if name != "fetch_ms.tgat":
            assert reader(name).read(old) is None, name


def test_ns_per_target_sums_every_hop(monkeypatch):
    from bench import readers
    monkeypatch.setattr(readers, "program_device_ms",
                        lambda ctx, names: (3.0, 2))
    ctx = Ctx([("sampler.hops", 1.0, 1.0, {"targets": [100, 900],
                                           "candidates": [5, 7],
                                           "deep": [0, 4]}),
               ("sampler.hops", 2.0, 2.0, {"targets": [200, 1800]})])
    # 3 ms over 3,000 targets
    assert reader("sampler_ns_per_target.tgat").read(ctx) == \
        pytest.approx(1000.0)
