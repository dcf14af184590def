"""QueryEngine: sample → state-fetch → forward on a pinned handle.

One worker thread drains the admission queue; each admitted batch pins
the newest :class:`SnapshotHandle` ONCE and answers every query in the
batch against exactly that snapshot version and parameter set — the
version travels on each response so callers (and the bench/test
harnesses) can assert consistency.  The sampling dispatch is the SAME
jitted ``_sample_khop`` program the trainer compiled (shapes are padded
to powers of two, so the jit cache is shared), and features come
through the same ``StateService`` — the paper's read path, reused.

Tiering: when the GNN queue is saturated (depth ≥ ``saturate_depth``)
or full, link queries fall back to the :class:`EdgeBank` table —
always fresh (updated synchronously at ingest), answered inline in
microseconds, tier-tagged ``"edgebank"`` on the response.

Thread-safety notes:

* the engine's ``FeatureCache`` instances are touched ONLY by the
  worker thread; the ingest thread queues invalidations
  (:meth:`invalidate`) which the worker drains at batch start, so a
  batch never reads a row the pinned version's features superseded;
* node/edge feature reads against a live ``StateService`` are safe
  because ingested features are deterministic per id (rewrites are
  idempotent); TGN memory reads return the last COMMITTED memory and
  are documented bounded-stale (pending raw messages are a training
  construct);
* the handle swap in ``HandlePublisher`` is the only synchronization
  with ingest — no locks on the query hot path.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.feature_cache import FeatureCache
from repro.core.mfg import assemble
from repro.core.sampling import sample_khop
from repro.models import gnn as G
from repro.obs import trace, xfer
from repro.obs.log import get_logger
from repro.obs.metrics import MetricRegistry
from repro.serve.admission import AdmissionQueue, Query, QueryFuture
from repro.serve.edgebank import EdgeBank
from repro.serve.handle import HandlePublisher, SnapshotHandle

log = get_logger("serve")


def _pow2_lanes(n: int) -> int:
    """Pad a query batch's lane count to a power of two (min 8) so the
    number of distinct jit shapes stays O(log max_batch)."""
    if n <= 8:
        return 8
    return 1 << (n - 1).bit_length()


def _pad(arrs, n: int, m: int):
    """Pad 1-D arrays from n to m lanes repeating the last real entry
    (a valid id/ts — padded lanes are sliced off before reply)."""
    if m == n:
        return tuple(arrs)
    out = []
    for x in arrs:
        p = np.full(m, x[n - 1] if n else 0, x.dtype)
        p[:n] = x[:n]
        out.append(p)
    return tuple(out)


@dataclasses.dataclass
class QueryResult:
    """One answered query.  ``version`` is the snapshot version the
    answer was computed against (EdgeBank tier: the bank's update
    counter); ``nbrs`` carries the hop-0 sampled neighborhood when the
    engine runs with ``record_neighbors=True`` (consistency tests)."""
    kind: str
    tier: str
    version: int
    scores: Optional[np.ndarray] = None
    emb: Optional[np.ndarray] = None
    nbrs: Optional[Dict[str, Any]] = None


class QueryEngine:
    """Versioned online query engine over the live graph.

    Wire-up (see :meth:`attach` for the one-liner)::

        pub = HandlePublisher(trainer.sampler.mirror, scan_pages=...)
        eng = QueryEngine(pub, cfg=trainer.cfg, state=trainer.state, ...)
        trainer.register_serving(eng)   # publishes on every ingest
        eng.start()
        res = eng.query_link([u], [v], [t])   # res.version, res.scores
    """

    def __init__(self, publisher: HandlePublisher, *, cfg,
                 state, use_pallas: bool = False,
                 edgebank: Optional[EdgeBank] = None,
                 max_batch: int = 64, admit_timeout_s: float = 0.002,
                 max_depth: int = 1024, saturate_depth: Optional[int] = None,
                 cache_nodes: int = 256, cache_edges: int = 256,
                 id_space_nodes: int = 1 << 20,
                 id_space_edges: int = 1 << 20,
                 metrics: Optional[MetricRegistry] = None,
                 record_neighbors: bool = False, seed: int = 0):
        if cfg.model == "dysat":
            raise NotImplementedError(
                "serving covers the single-neighborhood models "
                "(tgn/tgat/graphsage/gat); dysat's snapshot stack is a "
                "training-eval construct")
        self.publisher = publisher
        self.cfg = cfg
        self.state = state
        self.use_pallas = use_pallas
        self.edgebank = edgebank
        self.record_neighbors = record_neighbors
        self.queue = AdmissionQueue(max_batch=max_batch,
                                    timeout_s=admit_timeout_s,
                                    max_depth=max_depth)
        self.saturate_depth = (int(saturate_depth) if saturate_depth
                               is not None else 4 * max_batch)
        self.metrics = metrics if metrics is not None else MetricRegistry()
        self._h_batch = self.metrics.histogram("serve.batch_queries")
        self._c_queries = self.metrics.counter("serve.queries")
        self._c_fallback = self.metrics.counter("serve.fallback")
        self._c_batches = self.metrics.counter("serve.batches")
        self._g_version = self.metrics.gauge("serve.version")
        # worker-thread-only caches (invalidations arrive via the
        # pending queue below, drained at batch start)
        self.node_cache = FeatureCache(
            cache_nodes, cfg.d_node, id_space=id_space_nodes,
            metrics=self.metrics, name="serve.cache.node")
        self.edge_cache = FeatureCache(
            cache_edges, cfg.d_edge, id_space=id_space_edges,
            metrics=self.metrics, name="serve.cache.edge")
        self._inval_lock = threading.Lock()
        self._pend_nodes: List[np.ndarray] = []
        self._pend_eids: List[np.ndarray] = []
        self._n_events = 0
        self._t_max = 0.0
        self._base_key = jax.random.PRNGKey(seed)
        self._seq = 0
        self._build_forwards()
        self._thread: Optional[threading.Thread] = None

    # -- wiring ----------------------------------------------------------
    @classmethod
    def attach(cls, trainer, *, edgebank: Optional[EdgeBank] = None,
               history: int = 8, start: bool = True, **kw) -> "QueryEngine":
        """Build a publisher of the trainer's device mirror + an engine
        for ``trainer``, register the serving hooks, and start the
        worker."""
        pub = HandlePublisher(
            trainer.sampler.mirror, scan_pages=trainer.sampler.scan_pages,
            use_pallas=trainer.use_pallas, history=history)
        kw.setdefault("id_space_nodes", trainer.stream.n_nodes + 1)
        kw.setdefault("id_space_edges", len(trainer.stream) + 1)
        eng = cls(pub, cfg=trainer.cfg, state=trainer.state,
                  use_pallas=trainer.use_pallas, edgebank=edgebank, **kw)
        trainer.register_serving(eng)
        if start:
            eng.start()
        return eng

    # -- trainer listener protocol --------------------------------------
    def on_publish(self, trainer, snap, batch, nodes, eids) -> None:
        """Ingest-thread hook: fold the batch into the EdgeBank tier,
        queue cache invalidations for the rewritten rows, and publish
        the new snapshot version."""
        if batch is not None:
            if self.edgebank is not None:
                self.edgebank.update(batch.src, batch.dst, batch.ts)
            self._n_events += len(batch.src)
            if len(batch.ts):
                self._t_max = max(self._t_max, float(np.max(batch.ts)))
        self.invalidate(nodes, eids)
        h = self.publisher.publish(
            snap, params=trainer.params, t_max=self._t_max,
            n_events=self._n_events)
        self._g_version.set(h.version)

    def on_params(self, params) -> None:
        """Train-thread hook: swap refreshed model params into the
        current handle (version unchanged)."""
        self.publisher.set_params(params)

    def invalidate(self, nodes, eids) -> None:
        """Queue cache invalidations (any thread); applied by the
        worker at the next batch start."""
        with self._inval_lock:
            if nodes is not None and len(nodes):
                self._pend_nodes.append(np.asarray(nodes, np.int64))
            if eids is not None and len(eids):
                self._pend_eids.append(np.asarray(eids, np.int64))

    def _drain_invalidations(self) -> None:
        with self._inval_lock:
            nodes, self._pend_nodes = self._pend_nodes, []
            eids, self._pend_eids = self._pend_eids, []
        if nodes:
            self.node_cache.invalidate(np.unique(np.concatenate(nodes)))
        if eids:
            self.edge_cache.invalidate(np.unique(np.concatenate(eids)))

    # -- public query API ------------------------------------------------
    def query_link(self, src, dst, ts, *, timeout: Optional[float] = 30.0
                   ) -> QueryResult:
        out = self.submit_link(src, dst, ts)
        if isinstance(out, QueryResult):
            return out
        return out.result(timeout)

    def submit_link(self, src, dst, ts):
        """Admit a link query; returns a :class:`QueryFuture`, or an
        immediate EdgeBank-tier :class:`QueryResult` when the GNN queue
        is saturated/full."""
        src = np.atleast_1d(np.asarray(src, np.int64))
        dst = np.atleast_1d(np.asarray(dst, np.int64))
        ts = np.atleast_1d(np.asarray(ts, np.float32))
        self._c_queries.add()
        if (self.edgebank is not None
                and self.queue.depth >= self.saturate_depth):
            return self._edgebank_answer(src, dst, ts)
        q = Query("link", src, dst, ts, QueryFuture(), time.perf_counter())
        if not self.queue.submit(q):
            if self.edgebank is not None:
                return self._edgebank_answer(src, dst, ts)
            raise RuntimeError("serving queue full and no fallback tier")
        return q.future

    def query_embed(self, nodes, ts, *, timeout: Optional[float] = 30.0
                    ) -> QueryResult:
        out = self.submit_embed(nodes, ts)
        return out.result(timeout)

    def submit_embed(self, nodes, ts) -> QueryFuture:
        nodes = np.atleast_1d(np.asarray(nodes, np.int64))
        ts = np.atleast_1d(np.asarray(ts, np.float32))
        self._c_queries.add()
        q = Query("embed", nodes, None, ts, QueryFuture(),
                  time.perf_counter())
        if not self.queue.submit(q):
            raise RuntimeError("serving queue full (embed has no "
                               "non-parametric fallback tier)")
        return q.future

    def _edgebank_answer(self, src, dst, ts) -> QueryResult:
        with trace.span("serve.fallback", pairs=len(src)):
            scores = self.edgebank.predict(src, dst, ts)
        self._c_fallback.add()
        return QueryResult(kind="link", tier="edgebank",
                           version=self.edgebank.version, scores=scores)

    # -- worker ----------------------------------------------------------
    def start(self) -> "QueryEngine":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._worker, name="serve-worker", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self.queue.close()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def __enter__(self) -> "QueryEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _worker(self) -> None:
        while True:
            batch = self.queue.next_batch()
            if batch is None:
                return
            try:
                self._process(batch)
            except Exception as e:     # noqa: BLE001 — fail the batch,
                log.error("serve batch failed", op="serve.batch",
                          error=repr(e), queries=len(batch))
                for q in batch:        # not the engine
                    if not q.future.done():
                        q.future.set_exception(e)

    def _process(self, batch: List[Query]) -> None:
        with trace.span("serve.batch", queries=len(batch)) as sp:
            if trace.enabled():
                # admission: the batch has just left the queue; ``depth``
                # counts the queries still waiting behind it
                now = time.perf_counter()
                waits = [now - q.t_submit for q in batch]
                sp.set(queue_wait_ms_sum=sum(waits) * 1e3,
                       queue_wait_ms_max=max(waits) * 1e3,
                       depth=self.queue.depth)
            self._drain_invalidations()
            handle = self.publisher.current()
            if handle is None:
                raise RuntimeError("no snapshot published yet")
            self._c_batches.add()
            self._h_batch.observe(len(batch))
            links = [q for q in batch if q.kind == "link"]
            embeds = [q for q in batch if q.kind == "embed"]
            if links:
                self._answer(handle, links, link=True)
            if embeds:
                self._answer(handle, embeds, link=False)
            sp.set(version=handle.version)

    def _next_key(self):
        """Per-batch RNG key for the stochastic sampling policies (the
        deterministic ``recent`` policy dispatches keyless so serving
        and offline replays agree bit-for-bit)."""
        if self.cfg.sampling not in ("uniform", "window"):
            return None
        self._seq += 1
        return jax.random.fold_in(self._base_key, self._seq)

    def _fetch_node(self, ids):
        return self.node_cache.fetch(
            ids, lambda miss: self.state.get_node_feats(miss))

    def _fetch_edge(self, eids):
        return self.edge_cache.fetch(
            eids, lambda miss: self.state.get_edge_feats(miss))

    def _fetch_memory(self):
        if not self.cfg.use_memory:
            return None
        return lambda ids: xfer.gather(self.state.get_memory, ids)[0]

    def _build_forwards(self) -> None:
        cfg = self.cfg
        use_pallas = self.use_pallas

        def embed_fwd(params, hops):
            return G.gnn_embed(params["gnn"], cfg, hops,
                               use_pallas=use_pallas)

        def link_fwd(params, hops):
            h = G.gnn_embed(params["gnn"], cfg, hops,
                            use_pallas=use_pallas)
            n = h.shape[0] // 2            # seeds = [src | dst], static
            return G.link_score(params["head"], h[:n], h[n:])

        self._embed_fwd = jax.jit(embed_fwd)
        self._link_fwd = jax.jit(link_fwd)

    def _sample_assemble(self, handle: SnapshotHandle, seeds, seed_ts,
                         *, use_cache: bool = True):
        """Shared sample+fetch path (worker hot path AND the offline
        parity replay — ``use_cache=False`` bypasses the worker-only
        caches so any thread may call it)."""
        with trace.span("serve.sample", lanes=len(seeds)):
            layers = sample_khop(
                handle.dev, seeds, seed_ts, fanouts=self.cfg.fanouts,
                policy=self.cfg.sampling, window=self.cfg.window,
                scan_pages=handle.scan_pages,
                use_pallas=handle.use_pallas, key=self._next_key())
        fn = self._fetch_node if use_cache else self.state.get_node_feats
        fe = self._fetch_edge if use_cache else self.state.get_edge_feats
        with trace.span("serve.fetch"):
            hops = assemble(layers, fn, fe, self._fetch_memory())
        return layers, hops

    def _answer(self, handle: SnapshotHandle, queries: List[Query],
                *, link: bool) -> None:
        ns = [q.n for q in queries]
        n = sum(ns)
        m = _pow2_lanes(n)
        u = np.concatenate([q.src for q in queries])
        t = np.concatenate([q.ts for q in queries])
        if link:
            v = np.concatenate([q.dst for q in queries])
            u, v, t = _pad((u, v, t), n, m)
            seeds = np.concatenate([u, v])
            seed_ts = np.concatenate([t, t])
        else:
            u, t = _pad((u, t), n, m)
            seeds, seed_ts = u, t
        layers, hops = self._sample_assemble(handle, seeds, seed_ts)
        with trace.span("serve.forward", lanes=len(seeds)):
            if link:
                out = xfer.d2h(self._link_fwd(handle.params, hops))
            else:
                out = xfer.d2h(self._embed_fwd(handle.params, hops))
        l0 = layers[0]
        nbr_ids = np.asarray(l0.nbr_ids)
        nbr_ts = np.asarray(l0.nbr_ts)
        nbr_mask = np.asarray(l0.mask)
        off = 0
        for q, k in zip(queries, ns):
            nbrs = None
            if self.record_neighbors:
                nbrs = {"ids": nbr_ids[off:off + k],
                        "ts": nbr_ts[off:off + k],
                        "mask": nbr_mask[off:off + k]}
                if link:
                    nbrs["dst_ids"] = nbr_ids[m + off:m + off + k]
                    nbrs["dst_mask"] = nbr_mask[m + off:m + off + k]
            res = QueryResult(
                kind=q.kind, tier="gnn", version=handle.version, nbrs=nbrs,
                scores=out[off:off + k].copy() if link else None,
                emb=None if link else out[off:off + k].copy())
            q.future.set_result(res)
            off += k

    # -- offline replay (parity harnesses) -------------------------------
    def offline_forward(self, version: int, src, dst=None, ts=None):
        """Recompute a query on the RETAINED handle for ``version`` —
        the parity oracle: a served response must match this ≤ 1e-4
        at float32 matmul precision (a TPU at its default precision
        rounds matmul operands to bf16, and the batched and the replayed
        program may round an intermediate differently). Bypasses
        admission, batching and the caches; safe from any thread."""
        handle = self.publisher.get(version)
        if handle is None:
            raise KeyError(f"version {version} not in publisher history")
        src = np.atleast_1d(np.asarray(src, np.int64))
        ts = np.atleast_1d(np.asarray(ts, np.float32))
        if dst is not None:
            dst = np.atleast_1d(np.asarray(dst, np.int64))
            seeds = np.concatenate([src, dst])
            seed_ts = np.concatenate([ts, ts])
        else:
            seeds, seed_ts = src, ts
        _, hops = self._sample_assemble(handle, seeds, seed_ts,
                                        use_cache=False)
        if dst is not None:
            return np.asarray(self._link_fwd(handle.params, hops))
        return np.asarray(self._embed_fwd(handle.params, hops))
