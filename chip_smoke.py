#!/usr/bin/env python3
"""Smoke run of the continuous trainer and serving wing on a TPU.

    python chip_smoke.py [--seed 0]       # one chip: every phase below
    python chip_smoke.py --chips 4        # the distributed trainer only

One process drives the chip(s); nothing here starts a child process.

One chip, at the published widths of ``configs/tgn_gdelt.py`` on a
stream shaped like JODIE Wikipedia (the d_e = 172 dataset of the
TGN/TGL evaluations):

* tgn   — ``ContinuousTrainer`` with the ``tgn()`` defaults: ingest a
          warm prefix, then two ``train_round`` calls (8 optimizer
          steps). Checks: every loss finite; params and the sampler
          mirror on the TPU; one batch's sampled ``recent``
          neighbourhood equal to ``oracle_sample``; that batch's eval
          loss on the TPU within ``CPU_LOSS_TOL`` of the same forward
          on the host CPU.
* serve — ``QueryEngine.attach`` on that trainer answers link queries;
          each answer matches ``offline_forward`` on its pinned version
          within ``SERVE_TOL`` at "highest" matmul precision (the
          default-precision difference is reported). The engine serves
          with the trainer's
          policy, and only a deterministic one (``recent``, TGN's) can
          be replayed: under ``uniform`` the replay draws new
          neighbours.
* tgat  — ``ContinuousTrainer`` with the ``tgat()`` defaults (fanouts
          (10, 10), ``uniform``, batch 600) for one round; losses
          finite, params on the TPU.
* kernels — each GNN-path Pallas kernel, compiled by Mosaic (the
          lowered program must hold a ``tpu_custom_call``), against its
          ``ref.py`` on the tgn batch: exact for ``temporal_sample``
          and ``cache_gather``, within ``ATTN_TOL`` for
          ``temporal_attn``.

Four chips (``--chips 4``): ``DistributedContinuousTrainer`` in-process
(``LocalTransport``, 2 machines x 2 ranks on a 4-device ``dp`` mesh)
against a one-chip ``ContinuousTrainer`` on the same stream and seed;
per-round losses within ``DIST_TOL``; prints which chip holds each mesh
shard and each sampler mirror.

Printed timings are wall-clock smoke facts of this run (compilation
included), not benchmark numbers. The last line of stdout is one JSON
object naming the device. Any failed check raises; with no TPU the
script exits non-zero before it prints a result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# JODIE Wikipedia: 8,227 users and 1,000 pages (synth_ctdg splits the
# bipartite id space in half), 157,474 edits over one month, timestamps
# in whole seconds.
WIKI = dict(n_nodes=9_227, n_events=157_474, t_span=2_678_400.0,
            bipartite=True)

CPU_LOSS_TOL = 1e-2   # |TPU - CPU| eval loss: the TPU runs f32 matmuls
#                       in bf16 passes at default precision
SERVE_TOL = 1e-4      # |served - offline| link logit, the engine's bar
ATTN_TOL = 1e-4       # temporal_attn kernel vs f32 reference
DIST_TOL = 1e-2       # |distributed - single chip| per-round loss


class CheckFailed(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    fact("check", passed=what)


def fact(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def wiki_stream(seed: int, d_node: int, d_edge: int, **shape):
    from repro.data.events import synth_ctdg
    s = synth_ctdg(**{**WIKI, **shape}, d_node=d_node, d_edge=d_edge,
                   seed=seed)
    # whole-second timestamps: exact in float32, so the device's window
    # test and the float64 oracle agree on every edge
    return dataclasses.replace(s, ts=np.floor(s.ts))


def platforms(tree) -> set:
    import jax
    return {d.platform for leaf in jax.tree.leaves(tree)
            for d in leaf.devices()}


def record_losses(trainer) -> list:
    """Every optimizer step's loss, read at the step's stage boundary."""
    losses = []
    complete = trainer._complete_train

    def spy(loss, item):
        out = complete(loss, item)
        losses.append(out)
        return out
    trainer._complete_train = spy
    return losses


def run_rounds(phase, trainer, stream, *, warm, round_events, rounds):
    losses = record_losses(trainer)
    t0 = time.perf_counter()
    trainer.ingest(stream.slice(0, warm))
    fact(phase, warm_events=warm, ingest_wall_s=time.perf_counter() - t0)
    for r in range(rounds):
        lo = warm + r * round_events
        n_before = len(losses)
        t0 = time.perf_counter()
        m = trainer.train_round(stream.slice(lo, lo + round_events),
                                epochs=1)
        fact(phase, round=r, events=round_events,
             steps=len(losses) - n_before,
             losses=[round(x, 6) for x in losses[n_before:]],
             eval_loss=m.eval_loss, round_wall_s=time.perf_counter() - t0)
    return losses


def phase_tgn(cfg, stream, *, warm, round_events, platform):
    import jax
    from repro.core.continuous import ContinuousTrainer
    from repro.core.sampling import oracle_sample

    tr = ContinuousTrainer(cfg, stream, seed=stream.seed)
    losses = run_rounds("tgn", tr, stream, warm=warm,
                        round_events=round_events, rounds=2)
    check(len(losses) >= 8, f"tgn took {len(losses)} >= 8 steps")
    check(np.isfinite(losses).all(), "tgn losses finite")
    check(platforms(tr.params) == {platform}, f"tgn params on {platform}")
    check(platforms(tr.sampler.mirror.dev) == {platform},
          f"tgn sampler mirror on {platform}")

    # one batch of the NEXT events, sampled against the trained graph
    lo = warm + 2 * round_events
    nxt = stream.slice(lo, lo + cfg.batch_size)
    staged = tr._stage_batch(nxt.src, nxt.dst, nxt.ts)
    [layer] = staged["layers"]
    [orc] = oracle_sample(tr.graph, np.asarray(layer.dst_nodes),
                          np.asarray(layer.dst_times), cfg.fanouts,
                          policy=cfg.sampling)
    for f in ("nbr_ids", "nbr_eids", "nbr_ts", "mask"):
        check(np.array_equal(np.asarray(getattr(layer, f)),
                             getattr(orc, f)),
              f"tgn sampled {f} == oracle_sample "
              f"({len(orc.mask)} targets)")

    batch = tr.assembler.finalize(staged)
    loss_dev = float(tr._eval_step(tr.params, batch)[0])
    cpu = jax.devices("cpu")[0]
    loss_cpu = float(tr._eval_step(jax.device_put(tr.params, cpu),
                                   jax.device_put(batch, cpu))[0])
    fact("tgn", eval_loss_device=loss_dev, eval_loss_cpu=loss_cpu,
         abs_diff=abs(loss_dev - loss_cpu))
    check(abs(loss_dev - loss_cpu) <= CPU_LOSS_TOL,
          f"tgn eval loss device vs cpu within {CPU_LOSS_TOL}")
    return tr, layer, stream.slice(lo, len(stream))


def phase_serve(tr, upcoming, *, n_queries):
    """The queries are answered twice: at the default matmul precision,
    which users get and whose difference is reported, and at "highest",
    where the served batch and its one-query replay must agree to the
    engine's own parity bar. At default precision the TPU rounds f32
    matmul operands to bf16, and the batched and the replayed program
    need not round an intermediate alike."""
    import jax
    from repro.serve.engine import QueryEngine, QueryResult

    eng = QueryEngine.attach(tr)
    q = upcoming.slice(0, n_queries)
    default = jax.config.jax_default_matmul_precision
    try:
        for precision in (default, "highest"):
            # global, not the thread-local context: the engine answers
            # on its own worker thread
            jax.config.update("jax_default_matmul_precision", precision)
            t0 = time.perf_counter()
            futs = [eng.submit_link(s, d, t)
                    for s, d, t in zip(q.src, q.dst, q.ts)]
            res = [f if isinstance(f, QueryResult) else f.result(600)
                   for f in futs]
            wall = time.perf_counter() - t0
            check(all(r.tier == "gnn" for r in res),
                  "serve answered by GNN")
            diff = max(float(np.max(np.abs(
                r.scores - eng.offline_forward(r.version, s, d, t))))
                for r, s, d, t in zip(res, q.src, q.dst, q.ts))
            fact("serve", matmul_precision=precision, queries=len(res),
                 answer_wall_s=wall,
                 versions=sorted({r.version for r in res}),
                 max_abs_diff_vs_offline=diff)
            check(np.isfinite(diff), "served scores finite")
        check(diff <= SERVE_TOL,
              f"serve {len(res)} answers == offline_forward within "
              f"{SERVE_TOL} at highest matmul precision")
    finally:
        jax.config.update("jax_default_matmul_precision", default)
        eng.stop()


def phase_tgat(cfg, stream, *, warm, round_events, platform):
    from repro.core.continuous import ContinuousTrainer

    tr = ContinuousTrainer(cfg, stream, seed=stream.seed)
    losses = run_rounds("tgat", tr, stream, warm=warm,
                        round_events=round_events, rounds=1)
    check(len(losses) >= 1, f"tgat took {len(losses)} steps")
    check(np.isfinite(losses).all(), "tgat losses finite")
    check(platforms(tr.params) == {platform}, f"tgat params on {platform}")


def _compiled_kernel(fn, *args, **kw) -> bool:
    return "tpu_custom_call" in fn.lower(*args, **kw).as_text()


def _timed(fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def phase_kernels(tr, layer, *, seed, expect_mosaic):
    import jax
    import jax.numpy as jnp
    from repro.kernels.cache_gather.ops import cache_gather_pallas
    from repro.kernels.cache_gather.ref import cache_gather_ref
    from repro.kernels.temporal_attn.ops import temporal_attn_pallas
    from repro.kernels.temporal_attn.ref import temporal_attn_ref
    from repro.kernels.temporal_sample.ops import temporal_sample_pallas
    from repro.kernels.temporal_sample.ref import temporal_sample_ref

    mosaic = ("to a Mosaic kernel" if expect_mosaic
              else "to the interpreter")
    dev, snap = tr.sampler.mirror.dev, tr._snap
    targets = jnp.asarray(layer.dst_nodes)
    n = targets.shape[0]
    k = layer.nbr_ids.shape[1]
    args = (dev["page_table"], jnp.asarray(snap.page_tmin),
            jnp.asarray(snap.page_tmax), dev["pages_nbr"],
            dev["pages_eid"], dev["pages_ts"], dev["pages_valid"],
            targets, jnp.asarray(layer.dst_times),
            jnp.full((n,), -jnp.inf, jnp.float32), jnp.ones((n,), bool))
    S, C = dev["page_table"].shape[1], dev["pages_ts"].shape[1]
    check(_compiled_kernel(temporal_sample_pallas, *args, k=k)
          == expect_mosaic, f"temporal_sample recent lowers {mosaic}")
    got, wall = _timed(temporal_sample_pallas, *args, k=k)
    want = temporal_sample_ref(*args, k=k)
    fact("kernels", kernel="temporal_sample/recent", targets=n, pages=S,
         lanes=C, first_call_wall_s=wall,
         sampled=int(np.asarray(want[3]).sum()))
    check(all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(got, want)),
          "temporal_sample recent == ref exactly")

    cache = tr.edge_cache.state
    ids = jnp.asarray(np.asarray(layer.nbr_eids).reshape(-1))
    cargs = (cache.slot_of, cache.ids, cache.feats, ids)
    check(_compiled_kernel(cache_gather_pallas, *cargs) == expect_mosaic,
          f"cache_gather lowers {mosaic}")
    got, wall = _timed(cache_gather_pallas, *cargs)
    want = cache_gather_ref(*cargs)
    fact("kernels", kernel="cache_gather", ids=ids.shape[0],
         dim=cache.feats.shape[1], first_call_wall_s=wall,
         hits=int(np.asarray(want[1]).sum()))
    check(all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(got, want)), "cache_gather == ref exactly")

    cfg = tr.cfg
    H, dh = cfg.n_heads, cfg.d_hidden // cfg.n_heads
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(n, H, dh)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(n, k, H, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n, k, H, dh)), jnp.float32)
    aargs = (q, kk, v, jnp.asarray(layer.mask))
    check(_compiled_kernel(temporal_attn_pallas, *aargs) == expect_mosaic,
          f"temporal_attn lowers {mosaic}")
    got, wall = _timed(temporal_attn_pallas, *aargs)
    with jax.default_matmul_precision("float32"):
        want = temporal_attn_ref(*aargs)
    err = float(jnp.max(jnp.abs(got - want)))
    fact("kernels", kernel="temporal_attn", targets=n, heads=H,
         head_dim=dh, first_call_wall_s=wall, max_abs_diff=err)
    check(err <= ATTN_TOL, f"temporal_attn == ref within {ATTN_TOL}")


def run_one_chip(seed: int, platform: str, *, tgn_cfg, tgat_cfg, stream,
                 tgn_warm, tgn_round, tgat_warm, tgat_round, n_queries):
    tr, layer, upcoming = phase_tgn(tgn_cfg, stream, warm=tgn_warm,
                                    round_events=tgn_round,
                                    platform=platform)
    phase_serve(tr, upcoming, n_queries=n_queries)
    phase_kernels(tr, layer, seed=seed,
                  expect_mosaic=platform == "tpu")
    phase_tgat(tgat_cfg, stream, warm=tgat_warm, round_events=tgat_round,
               platform=platform)


def run_four_chips(cfg, stream, *, warm, round_events, rounds):
    import jax
    from repro.configs.tgn_gdelt import DistConfig
    from repro.core.continuous import ContinuousTrainer
    from repro.dist.continuous import DistributedContinuousTrainer

    single = ContinuousTrainer(cfg, stream, seed=stream.seed)
    multi = DistributedContinuousTrainer(
        cfg, stream, DistConfig(n_machines=2, n_gpus=2), seed=stream.seed)
    for tr in (single, multi):
        tr.ingest(stream.slice(0, warm))
    for r in range(rounds):
        lo = warm + r * round_events
        batch = stream.slice(lo, lo + round_events)
        t0 = time.perf_counter()
        a = single.train_round(batch, epochs=1)
        t1 = time.perf_counter()
        b = multi.train_round(batch, epochs=1)
        t2 = time.perf_counter()
        fact("dist", round=r, events=round_events,
             single_loss=a.loss, dist_loss=b.loss,
             single_eval_loss=a.eval_loss, dist_eval_loss=b.eval_loss,
             steps=b.collective_steps, single_wall_s=t1 - t0,
             dist_wall_s=t2 - t1)
        for what, x, y in (("train", a.loss, b.loss),
                           ("eval", a.eval_loss, b.eval_loss)):
            check(np.isfinite([x, y]).all()
                  and abs(x - y) <= DIST_TOL,
                  f"dist round {r} {what} loss == single chip within "
                  f"{DIST_TOL}")
    for w, d in enumerate(multi.mesh.devices.flat):
        fact("dist", mesh_shard=w, device_id=d.id,
             coords=getattr(d, "coords", None))
    step_in = multi.params["head"]["w1"]
    fact("dist", params_on=sorted(d.id for d in step_in.devices()))
    for m, ranks in multi.samplers.samplers.items():
        for r, s in enumerate(ranks):
            fact("dist", sampler_mirror=f"machine{m}/rank{r}",
                 device_ids=sorted({d.id for a in s.mirror.dev.values()
                                    for d in a.devices()}))
    check(platforms(multi.params) == {jax.devices()[0].platform},
          "dist params on the accelerator")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{d0.platform!r} ({len(devices)} device(s))")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU chips, found {len(devices)}")
    fact("device", platform=d0.platform, kind=d0.device_kind,
         count=len(devices))

    from repro.configs.tgn_gdelt import tgat, tgn
    from repro.launch.compile_cache import enable_compile_cache
    fact("device", compile_cache=enable_compile_cache())

    tgn_cfg, tgat_cfg = tgn(), tgat()
    stream = wiki_stream(args.seed, tgn_cfg.d_node, tgn_cfg.d_edge)
    if args.chips == 4:
        run_four_chips(tgn_cfg, stream, warm=100_000, round_events=8_000,
                       rounds=2)
    else:
        run_one_chip(args.seed, "tpu", tgn_cfg=tgn_cfg, tgat_cfg=tgat_cfg,
                     stream=stream, tgn_warm=100_000, tgn_round=16_000,
                     tgat_warm=100_000, tgat_round=3_000, n_queries=40)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
