"""TGAT on the normal trainer path against the benchmark's plain reference.

``ContinuousTrainer`` is built as the benchmark's training driver builds
it (no memory, two hops, ``uniform``, seeded random weights) and runs
one pipelined round; the plain reference (``bench/reference.py``, which
imports nothing of the program) then recomputes the first step at the
neighbourhoods the program recorded. Pages of 8 edges make the chains
many times deeper than the samplers' 16-page window.
"""
import json
import sys
import types
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import checks, reference as R  # noqa: E402
from bench.drivers import train_rounds  # noqa: E402
from bench.stream import generate  # noqa: E402

WARM, PER_ROUND = 1000, 4


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 977])
def test_tgat_first_step_matches_reference(seed):
    with open(ROOT / "bench" / "tests" / "data" / "tgat-tiny.json") as f:
        cfg = json.load(f)
    cfg["trainer"]["threshold"] = 8
    m, limits = cfg["model"], cfg["limits"]
    stream = generate(cfg["stream"], m["d_node"], m["d_edge"], seed)
    ctx = types.SimpleNamespace(cfg=cfg, seed=seed, stream=stream)
    with jax.default_matmul_precision("highest"):
        tr, w0 = train_rounds.build_trainer(ctx)
        rec = train_rounds.Recorder(tr, stream.n_nodes)
        tr.ingest(tr.stream.slice(0, WARM))
        B = m["batch_size"]
        tr.train_round(tr.stream.slice(WARM, WARM + PER_ROUND * B),
                       epochs=1)
        rec.detach()
        assert tr.sampler.snap.node_npages.max() > 16
        [step] = train_rounds.check_steps(
            stream, m, PER_ROUND, WARM, tr.builder.draws, rec.samples)[:1]
        adj = R.Adjacency(stream)
        assert checks.sampler_faults(stream, adj, step, m,
                                     step["recorded"]) == 0
        ref = checks.reference_train(stream, adj, m, cfg["optimizer"], w0,
                                     [step], precision="highest")
    assert abs(rec.losses[0] - ref["losses"][0]) <= limits["loss1_gap"]
    g1 = jax.tree.map(lambda x: np.asarray(x) / (1 - cfg["optimizer"]["b1"]),
                      rec.mu1)
    gaps = checks.leaf_gaps(checks.leaf_norms(g1),
                            checks.leaf_norms(ref["g1"]))
    assert max(gaps.values()) <= limits["grad1_gap"], gaps
