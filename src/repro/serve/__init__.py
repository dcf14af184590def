"""Online serving wing: low-latency temporal-embedding and
link-prediction queries against the live graph (ROADMAP direction 1).

The trainer keeps learning while queries are answered from the SAME
device-resident snapshot mirror and fused k-hop sampler — through a
versioned read handle so a query admitted mid-ingest never observes a
half-applied ``SnapshotDelta``:

* :class:`~repro.serve.handle.HandlePublisher` — publishes the
  trainer's copy-on-write device mirror (``TemporalSampler.mirror``):
  each ingest installs an immutable
  :class:`~repro.serve.handle.SnapshotHandle` (snapshot version + device
  arrays + model params), and the atomic handle swap is the ONLY
  synchronization between ingest and query threads.
* :class:`~repro.serve.admission.AdmissionQueue` — batched admission:
  requests collect up to a size/timeout budget and pad to a power of
  two, so serving reuses the trainer's jit cache.
* :class:`~repro.serve.engine.QueryEngine` — sample → state-fetch →
  forward on a worker thread, pinned to one handle per batch; plugs
  into the trainer via ``trainer.register_serving(engine)``.
* :class:`~repro.serve.edgebank.EdgeBank` — non-parametric
  recency/frequency tier answering link queries instantly when the GNN
  queue is saturated (always fresh: updated synchronously at ingest).
"""
from repro.serve.admission import AdmissionQueue, Query, QueryFuture
from repro.serve.edgebank import EdgeBank
from repro.serve.engine import QueryEngine, QueryResult
from repro.serve.handle import HandlePublisher, SnapshotHandle

__all__ = [
    "AdmissionQueue", "EdgeBank", "HandlePublisher", "Query",
    "QueryEngine", "QueryFuture", "QueryResult", "SnapshotHandle",
]
