"""Checkpointing, elastic policy, gradient compression, LM trainer loop."""
import os

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
import numpy as np
import pytest

from repro.configs import get_arch
from repro.dist.collectives import (bucketed_psum, quantized_psum_grads,
                                    topk_psum_grads)
from repro.models import lm_zoo
from repro.train.checkpoint import CheckpointManager
from repro.train.elastic import ElasticCoordinator, StragglerPolicy
from repro.train.optimizer import adamw, sgd, warmup_cosine_schedule
from repro.train.trainer import LMTrainer, TrainerConfig


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_converges_quadratic():
    opt = adamw(0.1, weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    target = jnp.array([1.0, 2.0])
    loss = lambda p: jnp.sum((p["w"] - target) ** 2)
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state = opt.update(g, state, params)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_schedule_shapes():
    s = warmup_cosine_schedule(1.0, 10, 100)
    assert float(s(0)) == 0.0
    assert abs(float(s(10)) - 1.0) < 1e-6
    assert float(s(100)) <= 0.2


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_and_keep(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_save=False)
    state = {"a": jnp.arange(10, dtype=jnp.float32),
             "b": {"c": jnp.ones((3, 4), jnp.bfloat16)}}
    for s in (10, 20, 30):
        st = jax.tree.map(lambda x: x + s, state)
        mgr.save(s, st, extra={"cursor": s * 2})
    assert mgr.all_steps() == [20, 30]   # keep=2 retention
    step, restored, extra = mgr.restore(state)
    assert step == 30 and extra["cursor"] == 60
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.arange(10) + 30)
    assert restored["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_atomic_no_partial(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3, async_save=False)
    mgr.save(1, {"x": jnp.zeros(4)})
    # a stale tmp dir from a "crashed" save must not break anything
    (tmp_path / ".tmp-99").mkdir()
    mgr.save(2, {"x": jnp.ones(4)})
    step, st, _ = mgr.restore({"x": jnp.zeros(4)})
    assert step == 2


def test_checkpoint_async_writer_joined_on_close(tmp_path):
    """Regression: the async writer used to be a daemon thread with no
    join on teardown — interpreter exit could truncate a checkpoint
    mid-write.  The writer is now non-daemon and ``close()`` joins it,
    so after close the newest checkpoint is fully durable on disk."""
    import json as _json
    import threading as _threading
    with CheckpointManager(tmp_path, keep=3, async_save=True) as mgr:
        mgr.save(7, {"x": jnp.arange(64, dtype=jnp.float32)})
        th = mgr._thread
        assert th is not None and not th.daemon
    # context exit == close(): writer joined, thread slot cleared
    assert mgr._thread is None
    assert not any(t.name == "ckpt-writer" and t.is_alive()
                   for t in _threading.enumerate())
    d = tmp_path / "step-0000000007"
    assert d.is_dir()
    manifest = _json.loads((d / "MANIFEST.json").read_text())
    assert manifest["step"] == 7
    assert not list(tmp_path.glob(".tmp-*"))        # no stragglers
    assert not list(d.glob(".MANIFEST.json.tmp"))   # manifest atomic
    step, st, _ = mgr.restore({"x": jnp.zeros(64)})
    assert step == 7
    np.testing.assert_allclose(np.asarray(st["x"]), np.arange(64))
    mgr.close()                                     # idempotent


def test_trainer_resume_exact(tmp_path):
    cfg = get_arch("yi-6b").reduced()
    tcfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=5,
                         log_every=100, max_steps=8)
    rng = np.random.default_rng(0)
    mk = lambda: {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (2, 32)), jnp.int32)}

    tr = LMTrainer(cfg, tcfg, seed=0)
    tr.init_or_restore()
    tr.train(iter([mk() for _ in range(8)]), max_steps=8)
    assert tr.step == 8

    tr2 = LMTrainer(cfg, tcfg, seed=0)
    tr2.init_or_restore()
    assert tr2.step == 8                 # resumed from the final save
    p1 = jax.tree.leaves(tr.state["params"])
    p2 = jax.tree.leaves(tr2.state["params"])
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# elastic policy
# ---------------------------------------------------------------------------


def test_elastic_failure_and_replan():
    co = ElasticCoordinator(hosts=range(8), devices_per_host=8,
                            heartbeat_timeout=10.0, model_parallel=16)
    assert co.plan().n_devices == 64     # 64 devices: dp=4 x mp=16
    now = 1000.0
    for h in range(8):
        co.heartbeat(h, now)
    failed = co.sweep(now + 11.0)        # nobody re-heartbeated
    assert failed == list(range(8))
    for h in range(6):                   # 6 survivors come back
        co.join(h, now + 12.0)
    plan = co.reform()
    assert plan.n_hosts == 6
    assert plan.data_parallel * plan.model_parallel <= 6 * 8
    assert (plan.data_parallel & (plan.data_parallel - 1)) == 0  # pow2


def test_straggler_policy():
    sp = StragglerPolicy(deadline_factor=2.0, tolerance=2)
    for _ in range(10):
        assert not sp.observe(0, 1.0)
    assert not sp.observe(1, 5.0)        # first strike
    assert sp.observe(1, 5.0)            # second strike -> report


# ---------------------------------------------------------------------------
# gradient compression (multi-device via fake XLA devices in a subprocess
# is heavy; on 1 device psum over a size-1 axis must be exact identity,
# and error-feedback must make quantization lossless over steps)
# ---------------------------------------------------------------------------


def _mesh1():
    return jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))


def test_quantized_psum_error_feedback():
    mesh = _mesh1()
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(64,)), jnp.float32)}
    err = None
    acc_true = np.zeros(64)
    acc_q = np.zeros(64)
    for _ in range(50):
        red, err = quantized_psum_grads(g, err, mesh)
        acc_q += np.asarray(red["w"])
        acc_true += np.asarray(g["w"])
    # error feedback: accumulated quantized sum tracks the true sum
    rel = np.abs(acc_q - acc_true) / (np.abs(acc_true) + 1e-6)
    assert np.median(rel) < 0.05, np.median(rel)


def test_topk_psum_error_feedback():
    mesh = _mesh1()
    rng = np.random.default_rng(1)
    g = {"w": jnp.asarray(rng.normal(size=(128,)), jnp.float32)}
    err = None
    acc = np.zeros(128)
    for _ in range(40):
        red, err = topk_psum_grads(g, err, mesh, frac=0.1)
        acc += np.asarray(red["w"])
    # every coordinate eventually transmitted via error feedback
    true = np.asarray(g["w"]) * 40
    assert np.corrcoef(acc, true)[0, 1] > 0.99


def test_bucketed_psum_identity_on_one_device():
    mesh = _mesh1()
    rng = np.random.default_rng(2)
    g = {"a": jnp.asarray(rng.normal(size=(1000,)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(37, 5)), jnp.float32),
         "c": jnp.asarray(rng.normal(size=(3,)), jnp.float32)}
    red = bucketed_psum(g, mesh, bucket_bytes=2048)
    for k in g:
        np.testing.assert_allclose(np.asarray(red[k]), np.asarray(g[k]),
                                   rtol=1e-6)
