"""Distributed continuous temporal-GNN training (GNNFlow §4.4–§5).

The full paper loop across P simulated machines × G trainer ranks on the
(fake) multi-device host mesh, run through the staged pipeline engine
(``repro.core.pipeline``):

  ingest   — ``Dispatcher`` splits each incremental event batch by owner
             into per-machine ``GraphPartition``s and hash-co-located
             feature shards; each partition then chains ONE
             ``SnapshotDelta`` into all of its rank samplers' device
             mirrors (``DistributedSamplerSystem.refresh`` — no
             snapshot rebuild, O(batch) H2D bytes).
  sample   — the static load-balancing schedule routes every worker's
             k-hop requests to the owner machine's same-rank sampler
             (byte/CV-accounted; the paper measures CV < 0.06).
  fetch    — per-worker shards assemble through the FeatureCache in
             front of the partitioned feature store.  Sample + fetch of
             batch *t+1* (including the partition-remote requests) run
             on the host while batch *t*'s shard_map step executes —
             the paper's fetch/train overlap.
  train    — hand-rolled data parallelism: the global batch is split
             into P*G shards, every worker computes gradients under one
             ``shard_map`` over the 'dp' mesh axis, and gradients are
             summed with ``repro.dist.collectives`` (exact
             ``bucketed_psum`` by default; int8/fp16-quantized or
             top-k-sparsified with error feedback selectable via
             ``DistConfig.collective``), with optional gradient
             accumulation over micro-batches.  One replicated optimizer
             step applies the worker-average.

Per-lane loss masking makes sharding exact for ANY batch size: shards
carry a ``seed_mask``, each worker contributes ``W * masked_sum /
total`` to the psum, and the combined gradient is exactly the
global-batch mean over real events.  Ragged stream tails are therefore
padded (pow2, masked lanes) and take the SAME shard_map collective path
as full batches — there is no replicated single-worker fallback — while
reproducing the single-host ``ContinuousTrainer`` step for step with
the exact collective (tests assert ≤ 1e-4 loss parity over multiple
rounds); the lossy collectives track it within an error-feedback band.

The machine topology is a *transport* concern
(``repro.dist.transport``): with the default ``LocalTransport`` every
machine is an in-process object and "RPC" is byte-accounted in-process
calls (DESIGN.md §2) — the degenerate 1-process case.  Injecting an
``RpcTransport`` (as ``repro.launch.multihost`` does) turns the same
trainer into one machine of a REAL multi-process launch: this process
hosts one graph partition + its rank samplers, serves them to peers
over an RPC sampling server, fetches remote hops over the wire, and
the shard_map collectives run across processes on the global
``jax.distributed`` mesh (gloo CPU collectives in-container).  Graph
state is genuinely partitioned; features and TGN memories go through
the ``StateService`` API (``repro.core.feature_store``): with
``state="replicated"`` (the default) every process derives identical
replicas from the deterministic ingest + the replicated step, which
keeps the numerics bit-comparable to the in-process run; with
``state="sharded"`` each process holds ONLY its owned feature/memory
partitions (``repro.dist.state.ShardedStateService``) and remote rows
travel over the transport in ONE coalesced ``state_batch`` round trip
per peer per global batch: staging samples every local shard first,
unions the remote node/edge/memory ids, and ships them on a
background thread while the previous jitted step runs — assembly then
drains the prefetch buffer through the placement-aware FeatureCache
(remote rows only) instead of issuing per-table ``feat_get`` calls.
Ingest is bracketed by coordination-service barriers: remote samplers
read the partition state it mutates; the sharded-memory commit adds
read/commit fences so no owner overwrites step t-1's memory while a
peer still reads it — unless ``memory_staleness > 0``, which lets
remote memory reads serve a buffered copy up to k commits stale and
drops both fences off the critical path (bounded loss deviation,
exact at 0).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.tgn_gdelt import DistConfig, GNNConfig
from repro.core.continuous import ContinuousTrainer, RoundMetrics
from repro.core.partition import Dispatcher, GraphPartition
from repro.core.scheduler import DistributedSamplerSystem
from repro.data.events import EventStream
from repro.dist import collectives as C
from repro.dist.transport import LocalTransport, SamplingTransport
from repro.obs import trace


@dataclasses.dataclass
class DistRoundMetrics(RoundMetrics):
    dispatch_bytes: int = 0     # ingest RPC payload (owner dispatch)
    request_bytes: int = 0      # sampling RPC request payload (modeled)
    response_bytes: int = 0     # sampling RPC response payload (modeled)
    reduce_bytes: int = 0       # per-worker gradient wire payload
    load_cv: float = 0.0        # worker-load CV of the static schedule
    collective_steps: int = 0   # optimizer steps (ALL via shard_map)
    node_hit_per_part: Tuple[float, ...] = ()
    edge_hit_per_part: Tuple[float, ...] = ()
    # real cross-process RPC traffic (zero for the in-process mode,
    # whose request/response bytes above are the modeled payloads)
    rpc_calls: int = 0
    rpc_wire_bytes: int = 0     # pickled request+response bytes
    rpc_wait_s: float = 0.0     # client-side blocking on remote hops
    # state-service traffic (feature/memory get/put through the
    # StateService API): modeled calls for the replicated service,
    # modeled + real wire for the sharded one
    state_calls: int = 0
    state_bytes: int = 0
    state_wait_s: float = 0.0   # client-side blocking on state RPCs
    state_resident_bytes: int = 0   # per-process resident table bytes
    # coalesced-read surface (PR 7): real wire round trips vs what the
    # per-table path would have issued, dedup savings, prefetch overlap
    # (wire time hidden behind the in-flight step) and the staleness
    # counter; per-partition wire bytes pair with the per-partition
    # cache hit rates above for the hit-rate-vs-wire-bytes tradeoff
    state_round_trips: int = 0
    state_trips_per_batch: float = 0.0
    state_staged_batches: int = 0
    state_baseline_trips: int = 0
    state_dedup_saved_bytes: int = 0
    state_pf_overlap_s: float = 0.0
    state_pf_hits: int = 0
    state_pf_misses: int = 0
    state_stale_served: int = 0
    state_wire_bytes_per_part: Tuple[int, ...] = ()


def _unstack(tree):
    """Drop the leading (per-device / micro) axis of every leaf."""
    return jax.tree.map(lambda x: x[0], tree)


class DistributedContinuousTrainer(ContinuousTrainer):
    """P×G data-parallel continuous trainer over partitioned graph,
    feature and sampler state — the paper's full distributed loop.
    Subclasses the single-host trainer: only topology, the shard_map
    steps and the sharded batch staging differ; the round driver, cache
    lifecycle and pipeline overlap are inherited."""

    def __init__(self, cfg: GNNConfig, stream: EventStream,
                 dist: Optional[DistConfig] = None, *,
                 threshold: int = 64, cache_ratio: float = 0.03,
                 cache_policy: str = "lru", lam: float = 0.2,
                 use_pallas: bool = False, lr: float = 1e-3,
                 seed: int = 0, overlap: bool = True,
                 transport: Optional[SamplingTransport] = None,
                 state: str = "replicated", memory_staleness: int = 0):
        if state not in ("replicated", "sharded"):
            raise ValueError(f"unknown state mode {state!r}")
        if memory_staleness < 0:
            raise ValueError("memory_staleness must be >= 0")
        self.memory_staleness = int(memory_staleness)
        self.dist = dist if dist is not None else DistConfig()
        self.transport = transport if transport is not None \
            else LocalTransport()
        self.multihost = self.transport.n_processes > 1
        self.state_mode = state
        super().__init__(cfg, stream, threshold=threshold,
                         cache_ratio=cache_ratio,
                         cache_policy=cache_policy, lam=lam,
                         use_pallas=use_pallas, lr=lr, seed=seed,
                         overlap=overlap)

    # -- topology hooks ----------------------------------------------------
    def _init_sampling(self, threshold: int, seed: int) -> None:
        dist = self.dist
        W = dist.n_workers
        G = dist.n_gpus
        sample_device = None
        if self.multihost:
            # every process contributes G mesh devices PLUS one spare
            # that hosts its sampler mirrors: served hops must never
            # queue behind a peer-blocked collective on the mesh
            # devices (head-of-line deadlock — see transport.py)
            if len(jax.local_devices()) != G + 1:
                raise RuntimeError(
                    f"multihost worker {self.transport.process_id} has "
                    f"{len(jax.local_devices())} local devices, wants "
                    f"G+1={G + 1} (G trainer ranks + 1 sampling "
                    f"device); set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={G + 1}")
            taken: Dict[int, int] = {}
            mesh_devs = []
            for d in jax.devices():     # process-major id order
                if taken.get(d.process_index, 0) < G:
                    mesh_devs.append(d)
                    taken[d.process_index] = \
                        taken.get(d.process_index, 0) + 1
            sample_device = jax.local_devices()[G]
        else:
            devs = jax.devices()
            if len(devs) < W:
                raise RuntimeError(
                    f"need {W} devices for P={dist.n_machines} x "
                    f"G={G}, got {len(devs)}; set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={W}")
            mesh_devs = devs[:W]
        self.mesh = Mesh(np.asarray(mesh_devs), ("dp",))
        self.n_partitions = dist.n_machines

        # this process hosts every machine (in-process mode) or exactly
        # its own (one machine per process under repro.launch.multihost)
        local = self.transport.local_machines(dist.n_machines)
        parts = [GraphPartition(p, dist.n_machines, threshold=threshold)
                 for p in local]
        self.dispatcher = Dispatcher(parts, undirected=True,
                                     n_parts=dist.n_machines)
        self.samplers = DistributedSamplerSystem(
            parts, G, self.cfg.fanouts, policy=self.cfg.sampling,
            window=self.cfg.window, scan_pages=dist.scan_pages, seed=seed,
            n_machines=dist.n_machines, transport=self.transport,
            sample_device=sample_device)
        # multihost: expose the local samplers to peers, dial theirs,
        # and only proceed once the whole fleet is serving
        self.transport.bind(self.samplers)
        self.transport.connect()
        self.transport.barrier("rpc-up")

    def _make_state(self):
        if self.state_mode == "replicated":
            return super()._make_state()
        from repro.dist.state import ShardedStateService
        cfg = self.cfg
        svc = ShardedStateService(
            self.dist.n_machines, d_node=cfg.d_node, d_edge=cfg.d_edge,
            d_memory=cfg.d_memory if cfg.use_memory else 0,
            hosted=self.transport.local_machines(self.dist.n_machines),
            transport=self.transport,
            local_rank=self.transport.process_id,
            memory_staleness=self.memory_staleness)
        # expose the hosted shards to peer processes; the first remote
        # state access happens after the pre-ingest barrier, long after
        # every fleet member has bound its state here
        self.transport.bind_state(svc)
        return svc

    def _init_dist_state(self) -> None:
        dist = self.dist
        W = dist.n_workers
        if self.multihost:
            # the jitted steps span processes: every input must be a
            # global array on the distributed mesh. Params/opt state are
            # replicated (identical on all processes — same init seed),
            # the error-feedback residual is dp-sharded like the batch.
            self.state.local_rank = self.transport.process_id
            self.params = self._replicated(self.params)
            self.opt_state = self._replicated(self.opt_state)
        # per-worker error-feedback residual, only for the lossy
        # collectives (an empty pytree otherwise — the exact path would
        # carry W dead parameter copies through every step)
        if dist.collective == "bucketed":
            self.err = {}
        elif self.multihost:
            G = dist.n_gpus
            self.err = jax.tree.map(
                lambda p: self._dp_global(
                    np.zeros((G,) + np.shape(p), np.float32)),
                self.params)
        else:
            self.err = jax.tree.map(
                lambda p: jnp.zeros((W,) + p.shape, jnp.float32),
                self.params)
        self.reduce_bytes_per_step = C.grad_payload_bytes(
            self.params, dist.collective, bits=dist.quant_bits,
            frac=dist.topk_frac)
        # registry-backed round counters (see the properties below —
        # the `_x += n` call sites read like plain ints)
        self._c_reduce_bytes = self.metrics.counter("reduce_bytes")
        self._c_collective_steps = self.metrics.counter("collective_steps")
        self._c_staged_batches = self.metrics.counter("staged_batches")
        # per-partition cache accounting: (node=0 | edge=1, partition)
        Pm = dist.n_machines
        self._part_hits = np.zeros((2, Pm), np.int64)
        self._part_accesses = np.zeros((2, Pm), np.int64)

    @property
    def _reduce_bytes(self) -> int:
        return int(self._c_reduce_bytes.value)

    @_reduce_bytes.setter
    def _reduce_bytes(self, value: int) -> None:
        self._c_reduce_bytes.reset(value)

    @property
    def _collective_steps(self) -> int:
        return int(self._c_collective_steps.value)

    @_collective_steps.setter
    def _collective_steps(self, value: int) -> None:
        self._c_collective_steps.reset(value)

    @property
    def _staged_batches(self) -> int:
        return int(self._c_staged_batches.value)

    @_staged_batches.setter
    def _staged_batches(self, value: int) -> None:
        self._c_staged_batches.reset(value)

    # -- multihost global-array staging ------------------------------------
    def _replicated(self, tree):
        """Host tree -> mesh-replicated global arrays (every local
        device holds the full value; all processes pass identical
        data, which the deterministic init/ingest guarantees)."""
        sh = NamedSharding(self.mesh, P())
        devs = self.mesh.local_devices

        def one(x):
            x = np.asarray(x)
            return jax.make_array_from_single_device_arrays(
                x.shape, sh, [jax.device_put(x, d) for d in devs])
        return jax.tree.map(one, tree)

    def _dp_global(self, x):
        """Local (G, ...) host leaf -> global (W, ...) dp-sharded array:
        local shard i lands on local device i == global worker
        process_id * G + i (device order is process-major)."""
        x = np.asarray(x)
        devs = self.mesh.local_devices
        shape = (self.dist.n_workers,) + x.shape[1:]
        parts = [jax.device_put(x[i:i + 1], d)
                 for i, d in enumerate(devs)]
        return jax.make_array_from_single_device_arrays(
            shape, NamedSharding(self.mesh, P("dp")), parts)

    def _worker_ids(self) -> range:
        """Global worker ids this process stages batches for."""
        if not self.multihost:
            return range(self.dist.n_workers)
        G = self.dist.n_gpus
        return range(self.transport.process_id * G,
                     (self.transport.process_id + 1) * G)

    def _memory_params(self):
        # host copies for the eager TGN commit (replicated global
        # arrays are fully addressable, so np.asarray is local)
        if not self.multihost:
            return self.params["memory"]
        return jax.tree.map(np.asarray, self.params["memory"])

    # -- jitted steps -----------------------------------------------------
    def _build_steps(self) -> None:
        from repro.core.continuous import make_forward
        dist = self.dist
        W, A = dist.n_workers, dist.grad_accum
        mode = dist.collective
        if mode not in ("bucketed", "quantized", "topk"):
            raise ValueError(f"unknown collective mode {mode!r}")
        forward = make_forward(self.cfg, self.use_pallas)
        optimizer = self.optimizer

        def micro_grads(params, mb, scale):
            """Gradients of `W * masked_sum / total` for one micro shard
            (`scale` = W/total): psum over workers / scan over micros of
            these, divided by W, is exactly the global-batch mean
            gradient — for padded ragged tails as well as full
            batches."""
            def f(p):
                loss, aux = forward(p, mb)
                cnt = 2.0 * jnp.sum(mb["seed_mask"])  # pos + neg lanes
                return loss * cnt * scale, (loss * cnt, aux)
            (_, (wsum, aux)), g = jax.value_and_grad(
                f, has_aux=True)(params)
            return g, wsum, aux

        def local_grads(params, batch, scale):
            """This worker's gradient/loss-sum. Batch leaves are the
            plain shard when A == 1, or (A, ...) micro-stacks."""
            if A == 1:
                g, wsum, (scores, labels, w) = micro_grads(
                    params, batch, scale)
                return g, wsum, (scores, labels, w)

            def one(carry, mb):
                gc, wc = carry
                g, wsum, aux = micro_grads(params, mb, scale)
                return (jax.tree.map(jnp.add, gc, g), wc + wsum), aux

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, wsum), (scores, labels, w) = lax.scan(
                one, (zero, jnp.zeros(())), batch)
            return gsum, wsum, (scores.reshape(-1), labels.reshape(-1),
                                w.reshape(-1))

        def train_shard(params, batch, err):
            # under shard_map: leaves carry a leading length-1 device dim
            batch = _unstack(batch)
            err = _unstack(err)
            cnt = 2.0 * jnp.sum(batch["seed_mask"])   # over micros too
            total = jnp.maximum(lax.psum(cnt, "dp"), 1.0)
            g, wsum, (scores, labels, w) = local_grads(
                params, batch, W / total)
            if mode == "bucketed":
                red = C.bucketed_psum(g, "dp",
                                      bucket_bytes=dist.bucket_bytes)
                new_err = err
            elif mode == "quantized":
                red, new_err = C.quantized_psum_grads(
                    g, err, "dp", bits=dist.quant_bits)
            else:
                red, new_err = C.topk_psum_grads(
                    g, err, "dp", frac=dist.topk_frac)
            grads = jax.tree.map(lambda x: x / W, red)
            loss = lax.psum(wsum, "dp") / total
            new_err = jax.tree.map(lambda x: x[None], new_err)
            return grads, loss, (scores, labels, w), new_err

        smap_train = jax.shard_map(
            train_shard, mesh=self.mesh,
            in_specs=(P(), P("dp"), P("dp")),
            out_specs=(P(), P(), (P("dp"), P("dp"), P("dp")), P("dp")),
            check_vma=False)

        def dist_step(params, opt_state, batch, err):
            grads, loss, aux, new_err = smap_train(params, batch, err)
            new_params, new_opt = optimizer.update(grads, opt_state,
                                                   params)
            return new_params, new_opt, loss, aux, new_err

        def eval_shard(params, batch):
            loss, (scores, labels, w) = forward(params, _unstack(batch))
            cnt = 2.0 * jnp.sum(_unstack(batch)["seed_mask"])
            total = jnp.maximum(lax.psum(cnt, "dp"), 1.0)
            # all_gather the per-shard scores so the outputs come back
            # REPLICATED: under a multi-process mesh every process can
            # then read the full eval arrays locally (a P("dp") output
            # would leave each process holding only its shard); the
            # concatenation order equals the old sharded output's.
            g = lambda x: lax.all_gather(x, "dp", tiled=True)
            return (lax.psum(loss * cnt, "dp") / total,
                    g(scores), g(labels), g(w))

        smap_eval = jax.shard_map(
            eval_shard, mesh=self.mesh,
            in_specs=(P(), P("dp")),
            out_specs=(P(), P(), P(), P()),
            check_vma=False)

        self._dist_step = jax.jit(dist_step)
        self._dist_eval = jax.jit(smap_eval)

    # -- feature fetch (device cache in front of the sharded store) -------
    # With sharded state the device cache is placement-aware: only rows
    # whose owner is a different machine than this process's rank are
    # cacheable (and hit/miss-counted), so the hit rate measures
    # avoided (real or modeled) wire traffic, not re-reads of the local
    # shard.  The in-process sharded run hosts every machine in one
    # process but keeps the same owner != local_rank mask — its cost
    # model matches the real multi-process launch.  Replicated state
    # has no remote rows by construction and keeps the unmasked cache.
    def _cacheable(self, table: str, ids) -> Optional[np.ndarray]:
        if self.state_mode != "sharded":
            return None
        return self.state.remote_mask(table, ids)

    def _fetch_node(self, ids):
        out = self.node_cache.fetch(
            ids, lambda miss: self.state.get_node_feats(miss),
            cacheable=self._cacheable("node", ids))
        self._account_cache(0, ids, self.node_cache.last_hit)
        return out

    def _fetch_edge(self, eids):
        out = self.edge_cache.fetch(
            eids, lambda miss: self.state.get_edge_feats(miss),
            cacheable=self._cacheable("edge", eids))
        self._account_cache(1, eids, self.edge_cache.last_hit)
        return out

    def _account_cache(self, kind: int, ids, hit: np.ndarray) -> None:
        """Per-partition hit accounting: cache traffic bucketed by the
        owner machine that a miss would have had to RPC to."""
        ids = np.asarray(ids, np.int64)
        own = self.state.owners("node" if kind == 0 else "edge", ids)
        valid = own >= 0
        if not valid.any():
            return
        np.add.at(self._part_accesses[kind], own[valid], 1)
        np.add.at(self._part_hits[kind], own[valid],
                  np.asarray(hit)[valid].astype(np.int64))

    def hit_rate_per_partition(self, kind: str) -> Tuple[float, ...]:
        k = 0 if kind == "node" else 1
        acc = np.maximum(self._part_accesses[k], 1)
        return tuple((self._part_hits[k] / acc).round(4).tolist())

    # -- sampling routes ---------------------------------------------------
    def _sample_fn(self, worker: int):
        m, r = divmod(worker, self.dist.n_gpus)
        return lambda seeds, ts: self.samplers.sample(
            m, r, np.asarray(seeds, np.int64),
            np.asarray(ts, np.float32))

    # -- sharded batch staging ---------------------------------------------
    def _stage_shards(self, src, dst, ts, *, micros: int,
                      for_train: bool = True) -> Dict[str, Any]:
        """Prefetch the stacked (W[, A], ...) device batch for one
        global batch: each worker's shard is sampled through the static
        schedule from that worker's (machine, rank) perspective.  The
        negatives are drawn ONCE for the global batch (same RNG
        consumption as the single-host trainer).  Batches that do not
        split evenly are padded per shard (pow2 lanes, loss-masked) so
        EVERY step takes the shard_map collective path.

        Staging is two-phase so remote state reads coalesce: first
        every local shard is SAMPLED, then ONE async ``state_batch``
        prefetch per remote peer ships the union of all remote rows
        the batch will touch (overlapping the in-flight device step),
        and only then does cache-fronted assembly run — it drains the
        prefetch buffer instead of issuing per-table round trips."""
        W = self.dist.n_workers
        n = len(src)
        neg = self.builder.negatives(n)         # full-batch draw: the
        # RNG stream stays in lockstep with the single-host trainer —
        # and across multihost processes, which each stage only their
        # own workers' shards out of the SAME global batch
        chunks = W * micros
        s = -(-n // chunks)                     # ceil
        if n % chunks:
            # ragged: pow2 shard so the tail's compilation is reused
            s = max(1, 1 << (s - 1).bit_length()) if s > 1 else 1
        sampled: List[List[Dict[str, Any]]] = []
        for w in self._worker_ids():
            fn = self._sample_fn(w)
            parts = []
            for a in range(micros):
                i = w * micros + a
                lo, hi = min(i * s, n), min(i * s + s, n)
                v = hi - lo
                sc, dc, nc, tc = (
                    np.asarray(src[lo:hi]), np.asarray(dst[lo:hi]),
                    np.asarray(neg[lo:hi]), np.asarray(ts[lo:hi]))
                if v < s:
                    # pad with the batch's last real event (valid ids)
                    sc, dc, nc, tc = (
                        np.concatenate([x, np.full(s - v, fill, x.dtype)])
                        for x, fill in ((sc, src[n - 1]), (dc, dst[n - 1]),
                                        (nc, neg[n - 1]), (tc, ts[n - 1])))
                mask = np.zeros(s, np.float32)
                mask[:v] = 1.0
                seeds = np.concatenate([sc, dc, nc]).astype(np.int64)
                seed_ts = np.concatenate([tc, tc, tc]).astype(np.float32)
                parts.append(self.assembler.sample(seeds, seed_ts, fn,
                                                   mask))
            sampled.append(parts)
        self._state_prefetch([p for parts in sampled for p in parts],
                             for_train)
        self._staged_batches += 1
        stageds = [[self.assembler.assemble_batch(p) for p in parts]
                   for parts in sampled]
        if not self.assembler.needs_finalize:
            # memory-less models: batches are complete — stack during
            # prefetch so the host work overlaps the in-flight step
            return {"batch": self._stack(stageds), "parts": None}
        return {"batch": None, "parts": stageds}

    def _state_prefetch(self, sampled_parts: List[Dict[str, Any]],
                        for_train: bool) -> None:
        """Union the ids every local shard of this global batch will
        read and ship the REMOTE subset in one background
        ``state_batch`` round trip per peer.  Rows the prefetch buffer
        already staged are filtered out host-side before the wire."""
        svc = self.state
        if not callable(getattr(svc, "prefetch_async", None)):
            return
        nodes, eids, mems = [], [], []
        for p in sampled_parts:
            n_, e_, m_ = self.assembler.collect_ids(p)
            nodes.append(n_)
            eids.append(e_)
            if m_ is not None:
                mems.append(m_)
        nodes = (np.unique(np.concatenate(nodes)) if nodes
                 else np.zeros(0, np.int64))
        eids = (np.unique(np.concatenate(eids)) if eids
                else np.zeros(0, np.int64))
        # staged-buffer filter only — deliberately NOT a device-cache
        # probe: this batch's own assemblies evict probed rows under
        # LRU churn, and every such race is a wire fallback that blows
        # the <= P-1 trips/batch budget.  Features are immutable within
        # a round, so the buffer ships each remote row at most once
        # between ingests (pf_reset) regardless.
        nodes = svc.pf_filter_new("node",
                                  nodes[svc.remote_mask("node", nodes)])
        eids = svc.pf_filter_new("edge",
                                 eids[svc.remote_mask("edge", eids)])
        mem_ids = None
        if mems and (self.memory_staleness > 0 or not for_train):
            # staleness 0 + the commit between prefetch and finalize
            # would version-reject every buffered row — skip the wasted
            # bytes; eval rounds never commit, so the buffered copy
            # serves EXACTLY, and staleness > 0 serves within bound
            m = np.unique(np.concatenate(mems))
            mem_ids = m[svc.remote_mask("memory", m)]
        svc.prefetch_async(node_ids=nodes, eids=eids, mem_ids=mem_ids)

    def _stack(self, stageds):
        # multihost stacks on the HOST: the global dp-sharded batch is
        # then built with one device_put per local shard (_dp_global)
        # instead of a throwaway device stack + D2H readback per step
        stk = ((lambda *xs: np.stack([np.asarray(x) for x in xs]))
               if self.multihost else (lambda *xs: jnp.stack(xs)))
        shards = []
        for parts in stageds:
            done = [self.assembler.finalize(p) for p in parts]
            shards.append(done[0] if len(done) == 1
                          else jax.tree.map(stk, *done))
        stacked = jax.tree.map(stk, *shards)
        if not self.multihost:
            return stacked
        # this process stacked its G local shards; assemble the global
        # (W, ...) dp-sharded batch the cross-process step consumes
        return jax.tree.map(self._dp_global, stacked)

    def _sharded_batch(self, staged):
        return staged["batch"] if staged["batch"] is not None \
            else self._stack(staged["parts"])

    # -- pipeline stage overrides ------------------------------------------
    def _stage_train(self, item) -> Dict[str, Any]:
        src, dst, ts, _ = item
        return self._stage_shards(src, dst, ts,
                                  micros=self.dist.grad_accum)

    def _stage_eval(self, item) -> Dict[str, Any]:
        src, dst, ts, _ = item
        return self._stage_shards(src, dst, ts, micros=1,
                                  for_train=False)

    def _launch_train(self, item, staged):
        batch = self._sharded_batch(staged)
        with trace.stage(self.timers, "step", phase="dispatch"):
            (self.params, self.opt_state, loss, _,
             self.err) = self._dist_step(
                self.params, self.opt_state, batch, self.err)
        self._reduce_bytes += self.reduce_bytes_per_step
        self._collective_steps += 1
        return loss

    def _launch_eval(self, item, staged):
        batch = self._sharded_batch(staged)
        return self._dist_eval(self.params, batch)

    # -- TGN memory fences (sharded multihost only) ------------------------
    def _cross_process_memory(self) -> bool:
        return (self.multihost and self.state_mode == "sharded"
                and self.cfg.use_memory)

    def _memory_fence(self):
        # commit_and_stage READS step t-1's memory for the pending set
        # then WRITES step t's values; with cross-process shards every
        # process must finish the read before any owner overwrites its
        # rows.  The pending set derives from replicated host state, so
        # every process reaches the fence the same number of times.
        if not self._cross_process_memory():
            return None
        if self.memory_staleness > 0:
            # bounded-stale reads: peers may serve memory up to k
            # commits old, so the read fence (and the commit fence
            # below) come off the critical path entirely
            return None
        return lambda: self.transport.barrier("mem-read")

    def _complete_train(self, loss, item) -> float:
        loss = super()._complete_train(loss, item)
        if self._cross_process_memory() and self.memory_staleness == 0:
            # nobody gathers batch t+1's memory until every owner has
            # committed batch t's writes into its shard
            self.transport.barrier("mem-commit")
        return loss

    # -- public API --------------------------------------------------------
    def ingest(self, batch: EventStream) -> float:
        """Dispatch the incremental batch to owner partitions + feature
        shards, then publish per-partition deltas to all rank samplers.

        Under multihost the two barriers fence the one mutation point
        remote samplers can observe: nobody rewrites partition state
        while a peer still samples the old round (pre), and nobody
        samples the new round until every peer finished writing
        (post)."""
        with trace.span("ingest", events=len(batch.src)):
            return self._ingest_body(batch)

    def _ingest_body(self, batch: EventStream) -> float:
        t0 = time.perf_counter()
        if callable(getattr(self.state, "pf_reset", None)):
            # quiesce the prefetch thread and drop buffered rows BEFORE
            # the fleet fence: no in-flight state_batch may race the
            # feature rewrites, and nothing pre-ingest survives them
            self.state.pf_reset()
        self.transport.barrier("pre-ingest")
        eids = self.dispatcher.ingest(batch, self.state)
        self.events.append(batch.ts, eids)
        self._last_eids = eids
        # write coherence (mirrors the single-host ingest): rows cached
        # before this batch's features landed must not serve stale zeros
        self.node_cache.invalidate(
            np.unique(np.concatenate([batch.src, batch.dst])))
        self.edge_cache.invalidate(np.unique(eids))
        self._refresh_bytes += self.samplers.refresh()
        self.transport.barrier("post-ingest")
        dt = time.perf_counter() - t0
        self.timers["ingest"] += dt
        return dt

    # -- round bookkeeping -------------------------------------------------
    def _reset_round_stats(self) -> None:
        super()._reset_round_stats()
        self._reduce_bytes = 0
        self._collective_steps = 0
        self.samplers.reset_stats()
        self._dispatch_base = self.dispatcher.bytes_dispatched
        self._part_hits[:] = 0
        self._part_accesses[:] = 0
        self._staged_batches = 0
        self._rpc_base = self.transport.stats()
        self._state_base = self.state.stats()

    def _round_metrics(self, ev, last_loss, train_s) -> DistRoundMetrics:
        st = self.samplers.load_stats()
        rt = self.transport.stats()
        base = getattr(self, "_rpc_base", None) or {}
        ss = self.state.stats()
        sbase = getattr(self, "_state_base", None) or {}
        trips = ss.get("round_trips", 0) - sbase.get("round_trips", 0)
        per_part = [int(a - b) for a, b in zip(
            ss.get("wire_bytes_per_part", []),
            sbase.get("wire_bytes_per_part", []))]
        return DistRoundMetrics(
            rpc_calls=rt["calls"] - base.get("calls", 0),
            rpc_wire_bytes=(rt["bytes_out"] + rt["bytes_in"]
                            - base.get("bytes_out", 0)
                            - base.get("bytes_in", 0)),
            rpc_wait_s=rt["wait_s"] - base.get("wait_s", 0.0),
            state_calls=ss["calls"] - sbase.get("calls", 0),
            state_bytes=ss["bytes"] - sbase.get("bytes", 0),
            state_wait_s=ss["wait_s"] - sbase.get("wait_s", 0.0),
            state_resident_bytes=ss["resident_bytes"],
            state_round_trips=trips,
            state_trips_per_batch=round(
                trips / max(self._staged_batches, 1), 4),
            state_staged_batches=self._staged_batches,
            state_baseline_trips=(ss.get("baseline_trips", 0)
                                  - sbase.get("baseline_trips", 0)),
            state_dedup_saved_bytes=(ss.get("dedup_saved_bytes", 0)
                                     - sbase.get("dedup_saved_bytes", 0)),
            state_pf_overlap_s=round(
                ss.get("pf_overlap_s", 0.0)
                - sbase.get("pf_overlap_s", 0.0), 6),
            state_pf_hits=ss.get("pf_hits", 0) - sbase.get("pf_hits", 0),
            state_pf_misses=(ss.get("pf_misses", 0)
                             - sbase.get("pf_misses", 0)),
            state_stale_served=(ss.get("stale_served", 0)
                                - sbase.get("stale_served", 0)),
            state_wire_bytes_per_part=tuple(per_part),
            ap=ev["ap"], auc_like=ev["acc"], loss=last_loss,
            eval_loss=ev["loss"],
            ingest_s=self.timers["ingest"],
            sample_s=self.timers["sample"],
            fetch_s=self.timers["fetch"], train_s=train_s,
            node_hit_rate=self.node_cache.hit_rate,
            edge_hit_rate=self.edge_cache.hit_rate,
            refresh_bytes=self._refresh_bytes,
            step_s=self.timers["step"],
            dispatch_bytes=(self.dispatcher.bytes_dispatched
                            - self._dispatch_base),
            request_bytes=st.request_bytes,
            response_bytes=st.response_bytes,
            reduce_bytes=self._reduce_bytes,
            load_cv=st.cv,
            collective_steps=self._collective_steps,
            node_hit_per_part=self.hit_rate_per_partition("node"),
            edge_hit_per_part=self.hit_rate_per_partition("edge"))

    # -- introspection -----------------------------------------------------
    def full_upload_bytes(self) -> int:
        """What ONE full snapshot re-upload across every hosted rank
        sampler would cost right now — the delta protocol's baseline."""
        total = 0
        for snap in self.samplers.snaps.values():
            per_rank = snap.edge_data_bytes() + snap.metadata_bytes()
            total += per_rank * self.dist.n_gpus
        return total
